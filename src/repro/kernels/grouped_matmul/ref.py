"""Oracle grouped matmul: each row times its group's matrix, in float32;
rows past the last group are zero."""

import jax.numpy as jnp


def grouped_matmul_ref(lhs: jnp.ndarray, rhs: jnp.ndarray,
                       group_sizes: jnp.ndarray) -> jnp.ndarray:
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(m), side="right")
    w = rhs[jnp.minimum(gid, rhs.shape[0] - 1)].astype(jnp.float32)
    out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32), w)
    return jnp.where((gid < rhs.shape[0])[:, None], out, 0.0)
