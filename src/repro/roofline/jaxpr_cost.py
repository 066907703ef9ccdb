"""Trip-count-aware FLOP/byte accounting from the jaxpr.

XLA's ``HloCostAnalysis`` visits every while body exactly once, so a model
that scans over 80 layers under-reports FLOPs by ~80x.  The jaxpr still has
the structure XLA lost: ``scan`` carries an explicit ``length``, and nested
call primitives (pjit / remat / custom_vjp) can be recursed.  This walker
produces:

  flops  — 2*M*N*K for every dot_general (batch dims included), 1/elem for
           elementwise work, input-size for reductions; scan bodies are
           multiplied by their trip count.  Exact for the matmuls that
           dominate every assigned architecture.
  bytes  — HBM traffic estimated at *fusion boundaries* only: XLA fuses
           elementwise chains, so counting every equation's operands
           overestimates traffic ~10x on attention softmax.  We charge
           operand+result bytes for ops that genuinely stream (dot_general,
           conv, gather/scatter/dynamic-update), input+output for
           reductions (their producer chain is fused, but the reduced
           operand must be resident), result bytes for materializing
           data movement (slice/concat/pad), and zero for elementwise /
           layout ops.  Chains that end in a dot are charged by the dot's
           operand read, balancing the uncounted final write.

Both are global (mesh-independent); divide by the device count for the
per-chip roofline terms.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import numpy as np

_CALL_PARAM_NAMES = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape) * np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0.0


def _aval_size(aval) -> float:
    try:
        return float(np.prod(aval.shape))
    except Exception:
        return 0.0


def _dot_flops(eqn) -> float:
    (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = np.prod([lhs.shape[i] for i in lhs_b]) if lhs_b else 1.0
    k = np.prod([lhs.shape[i] for i in lhs_c]) if lhs_c else 1.0
    m = np.prod([d for i, d in enumerate(lhs.shape)
                 if i not in lhs_c and i not in lhs_b]) or 1.0
    n = np.prod([d for i, d in enumerate(rhs.shape)
                 if i not in rhs_c and i not in rhs_b]) or 1.0
    return 2.0 * float(batch) * float(m) * float(n) * float(k)


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    # 2 * output_elements * (kernel elements per output channel)
    per_out = np.prod(rhs.shape) / max(rhs.shape[-1], 1)
    return 2.0 * _aval_size(out) * float(per_out)


# layout/elementwise: fused by XLA -> no HBM traffic charged.  ``rev`` is
# here because a static flip along a minor axis is a register permute (the
# butterfly exchange) — the whole point of the paper's HW path.
_FREE_BYTES = {
    "broadcast_in_dim", "reshape", "squeeze", "transpose",
    "convert_element_type", "iota", "stop_gradient", "copy",
    "device_put", "select_n", "split", "rev",
}
# materializing data movement: charge the result write
_MOVE_OUT = {"slice", "concatenate", "pad"}
# true streaming ops: charge operands + result
_STREAM = {"sort", "cumsum", "cumlogsumexp", "cummax", "cumprod"}
# pointer ops: traffic is the slice moved, not the full operand (XLA
# aliases the buffer in place inside loops; real HW touches the element)
_POINTER = {"gather", "dynamic_slice"}
_POINTER_UPDATE = {"scatter", "scatter-add", "scatter_add",
                   "dynamic_update_slice"}

# cap on grid points we are willing to walk when replaying Pallas block
# index maps; a larger grid is an error, not a coarser number
_PALLAS_MAX_STEPS = 1 << 16
# stand-in for scalar-prefetch operands (valid lengths, positions, block
# tables) when replaying index maps at trace time.  Values are
# ``_PALLAS_SCALAR_FILL + arange``: every element is large enough that
# length clamps stay inactive (conservative full-length traffic) AND
# distinct, so an index map that *gathers* through a scalar operand — the
# paged decode kernel's block table — yields a different block index at
# every grid step and is charged one block transfer per table entry
# visited.  A constant fill would alias all table lookups to one page and
# report the paged gather as a single fetch.
_PALLAS_SCALAR_FILL = 1 << 30


def _pallas_block_traffic(eqn) -> float:
    """HBM bytes for a pallas_call: replay each operand's block index map
    over the grid and charge one block transfer per *change* of block
    index — the Pallas pipeline only streams a block when its index moves,
    so an index map that clamps at the causal diagonal (flash attention's
    kv block-skip) genuinely saves the traffic this counter reports.

    The speculative k-token verify kernel rides the same replay: its
    block-table gather is charged one page transfer per visited table
    entry (the arange fill keeps entries distinct) while its widened
    (T*G)-row query block is fetched once per (batch, head) — so the
    verify dispatch's traffic is ~constant in k and the per-accepted-token
    bytes fall ~k-fold, which is exactly the k-for-1 dispatch amortization
    ``benchmarks/spec_decode.py`` reports."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    steps = int(np.prod(grid)) if grid else 1
    if steps > _PALLAS_MAX_STEPS:
        raise ValueError(f"pallas grid {grid} has {steps} steps, more than "
                         f"the replay bound {_PALLAS_MAX_STEPS}")
    n_idx = int(getattr(gm, "num_index_operands", 0))
    scalar_args = []
    for v in eqn.invars[:n_idx]:
        dt = (np.dtype(v.aval.dtype)
              if np.issubdtype(np.dtype(v.aval.dtype), np.integer)
              else np.dtype(np.int32))
        size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
        arr = (_PALLAS_SCALAR_FILL
               + np.arange(size, dtype=np.int64)).astype(dt)
        scalar_args.append(arr.reshape(v.aval.shape))
    # row-major grid walk, last axis innermost — the TPU iteration order
    points = [()]
    for g in grid:
        points = [p + (i,) for p in points for i in range(g)]
    total = 0.0
    for bm in gm.block_mappings:
        if _in_place(bm):
            continue
        # ref_aval is the block as the kernel sees it (squeezed dims
        # dropped): same element count as the transferred block
        block_bytes = float(np.prod(bm.ref_aval.shape)
                            * np.dtype(bm.array_aval.dtype).itemsize)
        im = bm.index_map_jaxpr
        run = _index_map_runner(im)
        prev = None
        fetches = 0
        for pt in points:
            idx = tuple(int(np.asarray(x))
                        for x in run(*pt, *scalar_args))
            if idx != prev:
                fetches += 1
                prev = idx
        total += fetches * block_bytes
    return total


def _in_place(bm) -> bool:
    """An operand left where it lives (``memory_space=pl.ANY``): the
    pipeline moves none of it; the kernel's own copies are charged by
    :func:`_dma_bytes_in`."""
    return "any" in str(getattr(bm.block_aval, "memory_space", "")).lower()


def _dma_bytes(eqn) -> float:
    """Bytes one ``dma_start`` moves: the slice its source indexer
    selects."""
    from jax._src.pallas.mosaic.primitives import _dma_unflatten

    src, src_transforms, *_ = _dma_unflatten(
        eqn.params["tree"], [v.aval for v in eqn.invars])
    shape = (src_transforms[-1].get_indexer_shape() if src_transforms
             else src.shape)
    return float(np.prod(shape)) * np.dtype(src.dtype).itemsize


def _dma_bytes_in(jaxpr) -> float:
    """HBM bytes a kernel body's own async copies move in one grid step:
    loop bodies count once per trip, a ``cond`` (``pl.when``) at its
    costliest branch — so a copy the kernel skips for a short row is
    still charged, the conservative full-length traffic of the replay."""
    total = 0.0
    for eqn in jaxpr.eqns:
        prim, params = eqn.primitive.name, eqn.params
        if prim == "dma_start":
            total += _dma_bytes(eqn)
        elif prim == "scan":
            total += params["length"] * _dma_bytes_in(params["jaxpr"].jaxpr)
        elif prim == "cond":
            total += max(_dma_bytes_in(b.jaxpr) for b in params["branches"])
        else:
            for name in _CALL_PARAM_NAMES + ("body_jaxpr",):
                if name in params:
                    sub = params[name]
                    total += _dma_bytes_in(getattr(sub, "jaxpr", sub))
    return total


def _index_map_runner(im):
    """Evaluator for a BlockSpec index-map jaxpr.

    Scalar-prefetch operands appear as *Ref* invars (the SMEM view the
    TPU pipeline reads), so ``eval_jaxpr`` on plain arrays trips over the
    ``get`` primitive.  Discharging the state effects first rewrites refs
    into pure indexing, after which the map evaluates on numpy fills —
    this is what lets the replay follow ``pos``-clamped *and*
    block-table-gathered index maps instead of falling back to coarse
    operand accounting."""
    from jax._src.state.discharge import discharge_state

    n_out = len(im.jaxpr.outvars)
    d_jaxpr, d_consts = discharge_state(im.jaxpr, im.consts)

    def run(*args):
        return jax.core.eval_jaxpr(d_jaxpr, d_consts, *args)[:n_out]

    return run


def _pallas_cost(eqn) -> Tuple[float, float]:
    """(flops, bytes) for a pallas_call equation.

    Compute: the kernel body jaxpr replayed once per grid step (``cond``
    branches — ``pl.when`` — are charged at the max branch, so skipped
    blocks still count; the *traffic* savings of block-skip are what the
    index-map replay captures).  Bytes: block transfers only — everything
    inside the kernel body is VMEM/register-resident, which is exactly the
    HW-path property the proxy exists to measure.
    """
    body = eqn.params["jaxpr"]
    body_f, _ = jaxpr_cost(body)
    grid = tuple(int(g) for g in eqn.params["grid_mapping"].grid)
    steps = float(np.prod(grid)) if grid else 1.0
    dma = _dma_bytes_in(getattr(body, "jaxpr", body))
    return steps * body_f, _pallas_block_traffic(eqn) + steps * dma


def jaxpr_cost(jaxpr) -> Tuple[float, float]:
    """(flops, bytes) for a (closed) jaxpr, trip-count aware."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    flops = 0.0
    mem = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        sub = None
        for name in _CALL_PARAM_NAMES:
            if name in eqn.params:
                sub = eqn.params[name]
                break
        if prim == "scan":
            body_f, body_b = jaxpr_cost(eqn.params["jaxpr"])
            n = float(eqn.params.get("length", 1))
            flops += n * body_f
            mem += n * body_b
            continue
        if prim == "while":
            # bare while: unknown trip count -> count once (we never emit
            # unbounded whiles in the model stack; scans carry lengths)
            cf, cb = jaxpr_cost(eqn.params["body_jaxpr"])
            flops += cf
            mem += cb
            continue
        if prim == "cond":
            branch_costs = [jaxpr_cost(b) for b in eqn.params["branches"]]
            flops += max(c[0] for c in branch_costs)
            mem += max(c[1] for c in branch_costs)
            continue
        if prim == "pallas_call":
            pf, pb = _pallas_cost(eqn)
            flops += pf
            mem += pb
            continue
        if sub is not None:
            cf, cb = jaxpr_cost(sub)
            flops += cf
            mem += cb
            continue
        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        in_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                       if hasattr(v, "aval"))
        out_size = sum(_aval_size(v.aval) for v in eqn.outvars)
        if prim == "dot_general":
            flops += _dot_flops(eqn)
            mem += in_bytes + out_bytes
        elif prim == "conv_general_dilated":
            flops += _conv_flops(eqn)
            mem += in_bytes + out_bytes
        elif prim in _FREE_BYTES:
            pass
        elif prim in _MOVE_OUT:
            mem += out_bytes
        elif prim in _POINTER:
            # read the extracted slice, write it out
            mem += 2 * out_bytes
        elif prim in _POINTER_UPDATE:
            # read + write the update slice (operand aliased in place)
            upd = (_aval_bytes(eqn.invars[1].aval)
                   if len(eqn.invars) > 1 and hasattr(eqn.invars[1], "aval")
                   else out_bytes)
            mem += 2 * upd
        elif prim in _STREAM:
            flops += out_size
            mem += in_bytes + out_bytes
        elif prim.startswith("reduce_") or prim in ("argmax", "argmin"):
            flops += sum(_aval_size(v.aval) for v in eqn.invars
                         if hasattr(v, "aval"))
            mem += in_bytes + out_bytes
        else:  # elementwise: 1 flop per output element, traffic fused away
            flops += out_size
    return flops, mem


def trace_cost(fn, *args) -> Dict[str, float]:
    """Trace ``fn`` with ShapeDtypeStruct args and return global flops/bytes."""
    closed = jax.make_jaxpr(fn)(*args)
    f, b = jaxpr_cost(closed)
    return {"flops_total": f, "bytes_total": b}
