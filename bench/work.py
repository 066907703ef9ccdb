"""Operations and bytes of a step or a kernel, from the lengths it
actually computed (never from padded shapes).

``m`` is a configuration's model dimensions (``spec.model_dims``).  A
multiply-add counts as two operations.  Bytes are the least a step must
move through HBM: every weight once, the K/V it reads and writes, and
for a kernel its inputs and outputs once.  Elements are 2 bytes (bf16
weights and K/V, as the configurations serve).
"""

from __future__ import annotations

from typing import Iterable, Tuple

BYTES = 2


def layer_weights(m) -> int:
    """Matrix weights of one layer."""
    d, hq, hkv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["d_head"], m["d_ff"])
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f


def weight_bytes(m) -> int:
    """Every parameter the forward pass reads: layers (with biases and
    norms), the head, the final norm."""
    d, hq, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    per_layer = layer_weights(m) + (hq + 2 * hkv) * dh + 2 * d
    head = d * m["vocab"]
    return BYTES * (m["n_layers"] * per_layer + head + d)


def kv_token_bytes(m) -> int:
    """K and V of one token over all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"] * BYTES


def _attn(m, keys: float) -> float:
    """Score and value products over all layers, for query rows that see
    ``keys`` keys in all."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * keys


def _causal_keys(n: int, start: int = 0) -> float:
    """Keys seen by ``n`` causal rows after ``start`` cached tokens."""
    return n * start + n * (n + 1) / 2.0


def decode_step(m, ctx: Iterable[int]) -> Tuple[float, float]:
    """One decode token for each live row; ``ctx`` = keys each row
    attends (its position + 1)."""
    ctx = list(ctx)
    flops = (2.0 * (m["n_layers"] * layer_weights(m)
                    + m["d_model"] * m["vocab"]) * len(ctx)
             + _attn(m, sum(ctx)))
    nbytes = weight_bytes(m) + kv_token_bytes(m) * (sum(ctx) + len(ctx))
    return flops, float(nbytes)


def decode_kernel(m, ctx: Iterable[int]) -> Tuple[float, float]:
    """Paged decode attention over all layers of one step: reads each
    row's K/V once, reads q and writes o."""
    ctx = list(ctx)
    qo = 2 * m["n_layers"] * m["n_heads"] * m["d_head"] * BYTES * len(ctx)
    return (_attn(m, sum(ctx)),
            float(kv_token_bytes(m) * sum(ctx) + qo))


def prefill(m, n: int, start: int = 0) -> Tuple[float, float]:
    """A prompt's ``n`` uncached tokens after ``start`` cached ones: all
    layers, causal attention over what each row sees, the head on the
    last token only."""
    flops = (2.0 * m["n_layers"] * layer_weights(m) * n
             + _attn(m, _causal_keys(n, start))
             + 2.0 * m["d_model"] * m["vocab"])
    nbytes = (weight_bytes(m) + kv_token_bytes(m) * (start + 2 * n))
    return flops, float(nbytes)


def flash_kernel(m, n: int) -> Tuple[float, float]:
    """Causal flash attention over one ``n``-token prompt, all layers:
    q, k, v read once, o written once."""
    rows = (2 * m["n_heads"] + 2 * m["n_kv_heads"]) * m["d_head"]
    return (_attn(m, _causal_keys(n)),
            float(m["n_layers"] * n * rows * BYTES))


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
