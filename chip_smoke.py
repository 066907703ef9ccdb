"""Chip smoke run: the serving main path at qwen2-1.5b's published widths.

  python chip_smoke.py              # one TPU chip: the whole main path
  python chip_smoke.py --chips 4    # four chips: 4-replica fleet vs 1 replica

Builds qwen2-1.5b (28 layers, d_model 1536, 12/2 heads, vocab 151936,
tied embeddings) in bf16 from ``--seed`` and drives it through the entry
points a user calls.  On one chip:

  1. ``ServeEngine.serve`` on a paged pool holding ``KV_BYTES`` of KV:
     closed-loop requests with prompts of 128-2048 tokens, served twice
     (the first call compiles, the second is warm);
  2. one speculative pass (``spec_k=4``, self-draft) so the verify
     kernel runs;
  3. the Pallas kernel lowerings against the ``jnp`` lowerings on the
     logits of one prefill, a few paged decode steps and one verify
     window, within ``LOGIT_TOL`` (relative to the largest reference
     logit).

With ``--chips 4`` it runs only the 4-replica cluster, one worker per
chip, and the 1-replica serve its greedy tokens must equal.

It exits non-zero, without the result line, when JAX finds no TPU, when
a kernel would run in interpret mode, when an engine degraded to the
``jnp`` path or recovered from an error, when any request ends other
than ``ok``, or when a comparison fails.  The last line of its output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Every time it prints names the device it was measured on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-1.5b"
KV_BYTES = 2 << 30          # paged pool size on one chip: 2 GiB of K/V
PAGE_SIZE = 16
MAX_NEW = 32
PROMPT_LENS = (128, 256, 512, 768, 1024, 1280, 1536, 2048)
# kernel vs jnp: max |logit difference| over the largest |reference
# logit|.  Both lowerings accumulate attention in float32 and differ in
# where bf16 rounding lands, which 28 layers amplify.
LOGIT_TOL = 5e-2


class SmokeFailure(SystemExit):
    """A failed check: exit code 1, the message on stderr."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke FAILED: {msg}")


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def build_model(cfg, seed: int, **backends):
    """Model + params in bf16, made on the device from ``seed``."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import Model

    model = Model(cfg, param_dtype=jnp.bfloat16,
                  compute_dtype=jnp.bfloat16, **backends)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return model, jax.block_until_ready(params)


def make_requests(lens, vocab: int, seed: int, max_new: int):
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, vocab, n).tolist(),
                    max_new_tokens=max_new) for i, n in enumerate(lens)]


def check_engine(engine, label: str):
    """Every request ok, no recovery, no fallback to the jnp path."""
    stats = {u: s for u, s in engine.last_stats.items()
             if isinstance(u, int)}
    bad = {u: s.get("status") for u, s in stats.items()
           if s.get("status") != "ok"}
    print(f"{label}: {len(stats)} requests, statuses "
          f"{sorted({s['status'] for s in stats.values()})}, "
          f"backend_degraded={engine.backend_degraded}, "
          f"recoveries={engine.recoveries}")
    check(not bad, f"{label}: requests not ok: {bad}")
    check(not engine.backend_degraded, f"{label}: engine degraded to jnp")
    check(engine.recoveries == 0, f"{label}: {engine.recoveries} recoveries")


def serve_phase(model, params, cfg, seed: int, kind: str):
    """Closed-loop serve on a paged pool of ``KV_BYTES``; returns the
    greedy tokens and the engine kwargs."""
    import jax

    from repro.serve.engine import ServeEngine

    kv_per_token = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.d_head * 2
    num_pages = KV_BYTES // (PAGE_SIZE * kv_per_token) + 1
    max_seq = max(PROMPT_LENS) + 2 * MAX_NEW
    kw = dict(max_seq=max_seq, batch_slots=len(PROMPT_LENS),
              cache_layout="paged", page_size=PAGE_SIZE,
              num_pages=num_pages, attend_block=max_seq, prompt_block=128)
    print(f"pool: {num_pages} pages x {PAGE_SIZE} tokens, "
          f"{kv_per_token} B/token, "
          f"{num_pages * PAGE_SIZE * kv_per_token / 2**30:.3f} GiB of KV")
    engine = ServeEngine(model, params, **kw)
    outs, times = [], []
    for label in ("cold", "warm"):
        reqs = make_requests(PROMPT_LENS, cfg.vocab, seed, MAX_NEW)
        t0 = time.perf_counter()
        outs.append(engine.serve(reqs))
        times.append(time.perf_counter() - t0)
        check_engine(engine, f"serve ({label})")
    n_tok = sum(len(v) for v in outs[1].values())
    print(f"serve on {kind}: {len(PROMPT_LENS)} requests, prompts "
          f"{min(PROMPT_LENS)}-{max(PROMPT_LENS)} tokens, {n_tok} tokens "
          f"served; cold call {times[0]:.1f} s (includes compile), warm "
          f"call {times[1]:.2f} s, so compile ~{times[0] - times[1]:.1f} s")
    print(f"serve: warm call repeats the cold call's tokens: "
          f"{outs[0] == outs[1]}")
    check(n_tok == len(PROMPT_LENS) * MAX_NEW, f"served {n_tok} tokens")
    return outs[1], kw


def spec_phase(model, params, cfg, seed: int, kind: str, kw, plain):
    """One speculative pass: draft proposes, the verify kernel scores."""
    from repro.serve.engine import ServeEngine

    engine = ServeEngine(model, params, spec_k=4, draft="self:4", **kw)
    reqs = make_requests(PROMPT_LENS, cfg.vocab, seed, MAX_NEW)
    t0 = time.perf_counter()
    out = engine.serve(reqs)
    dt = time.perf_counter() - t0
    check_engine(engine, "spec serve")
    acc = [s["accept_rate"] for u, s in engine.last_stats.items()
           if isinstance(u, int) and "accept_rate" in s]
    same = sum(out.get(u) == v for u, v in plain.items())
    print(f"spec serve on {kind}: spec_k=4, self-draft 4 layers, "
          f"{sum(len(v) for v in out.values())} tokens in {dt:.1f} s "
          f"(includes compile), mean accept rate "
          f"{sum(acc) / max(len(acc), 1):.3f}, {same}/{len(plain)} "
          f"requests token-identical to non-speculative decode")


def logits_phase(cfg, params, seed: int, prompt_len: int = 256,
                 steps: int = 4, window: int = 4):
    """Kernel vs jnp lowerings on the same params: one prefill, ``steps``
    teacher-forced paged decode steps, one ``window``-token verify.
    Returns the largest relative logit error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.common import cdiv, default_interpret
    from repro.models.lm import Model
    from repro.serve.kv_cache import scatter_prefill

    b = 2
    max_seq = prompt_len + steps + window + PAGE_SIZE
    nb = cdiv(max_seq, PAGE_SIZE)
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, prompt_len)), jnp.int32)
    forced = jnp.asarray(rng.integers(0, cfg.vocab, (b, steps + window)),
                         jnp.int32)
    tables = jnp.arange(1, b * nb + 1, dtype=jnp.int32).reshape(b, nb)

    def run(backend):
        model = Model(cfg, param_dtype=jnp.bfloat16,
                      compute_dtype=jnp.bfloat16, attn_backend=backend,
                      decode_backend=backend)
        prefill = jax.jit(model.prefill, static_argnums=2)
        decode = jax.jit(model.decode_step, static_argnums=4)
        verify = jax.jit(model.decode_verify_step, static_argnums=(4, 5))
        logits, pc = prefill(params, {"tokens": toks}, prompt_len)
        out = [logits]
        pool = model.init_cache(b, max_seq, layout="paged",
                                page_size=PAGE_SIZE, num_pages=b * nb + 1)
        pool = scatter_prefill(
            {"k_pages": pool["k_pages"], "v_pages": pool["v_pages"]},
            {"k": pc["k"], "v": pc["v"]},
            tables[:, :cdiv(prompt_len, PAGE_SIZE)])
        pool["block_tables"] = tables
        pos = jnp.full((b,), prompt_len, jnp.int32)
        if backend == "kernel" and not default_interpret():
            text = (prefill.lower(params, {"tokens": toks},
                                  prompt_len).as_text()
                    + decode.lower(params, pool, forced[:, 0], pos,
                                   max_seq).as_text())
            check("tpu_custom_call" in text,
                  "the kernel lowering has no Pallas kernel in its program")
        for i in range(steps):
            logits, pool = decode(params, pool, forced[:, i], pos + i,
                                  max_seq)
            out.append(logits)
        logits, _ = verify(params, pool, forced[:, steps:], pos + steps,
                           max_seq, backend)
        out.extend(logits[:, t] for t in range(window))
        return [np.asarray(x, np.float32) for x in out]

    got, want = run("kernel"), run("jnp")
    names = (["prefill"] + [f"decode {i}" for i in range(steps)]
             + [f"verify row {t}" for t in range(window)])
    worst = 0.0
    for name, g, w in zip(names, got, want):
        check(np.isfinite(g).all(), f"{name}: non-finite kernel logits")
        err = float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
        top = float(np.mean(g.argmax(-1) == w.argmax(-1)))
        print(f"logits {name}: max|kernel-jnp|/max|jnp| = {err:.3e}, "
              f"argmax agreement {top:.2f}")
        worst = max(worst, err)
    print(f"logits: max relative error {worst:.3e} (tolerance "
          f"{LOGIT_TOL:.0e}) over prefill + {steps} decode steps + "
          f"{window}-token verify, batch {b}, prompt {prompt_len}")
    check(worst <= LOGIT_TOL, f"kernel vs jnp logits differ by {worst:.3e}")
    return worst


def fleet_phase(model, params, cfg, seed: int, devices):
    """4 replicas, one per chip, against 1 replica on the same requests."""
    import jax

    from repro.serve.cluster import make_cluster
    from repro.serve.engine import ServeEngine

    lens = (256, 320, 384, 448, 512, 288, 352, 416)
    kw = dict(max_seq=512 + 2 * MAX_NEW, batch_slots=2,
              cache_layout="paged", page_size=PAGE_SIZE,
              attend_block=512 + 2 * MAX_NEW, prompt_block=512)
    single = ServeEngine(model, jax.device_put(params, devices[0]), **kw)
    t0 = time.perf_counter()
    base = single.serve(make_requests(lens, cfg.vocab, seed, MAX_NEW))
    print(f"1 replica on {devices[0]}: {len(base)} requests in "
          f"{time.perf_counter() - t0:.1f} s (includes compile)")
    check_engine(single, "1 replica")
    cluster = make_cluster(model, params, replicas=len(devices),
                           router_policy="round-robin", **kw)
    t0 = time.perf_counter()
    out = cluster.serve(make_requests(lens, cfg.vocab, seed, MAX_NEW))
    print(f"{len(devices)} replicas: {len(out)} requests in "
          f"{time.perf_counter() - t0:.1f} s (includes compile)")
    placed = []
    for wid, w in sorted(cluster.workers.items()):
        pool_dev = sorted(str(d) for d in w._st.pool["k_pages"].devices())
        print(f"worker {wid}: engine device {w.engine.device}, pool on "
              f"{pool_dev}, {w.rounds} rounds")
        placed.append(w.engine.device)
        check(not w.engine.backend_degraded and w.engine.recoveries == 0,
              f"worker {wid} degraded or recovered")
    check(len(set(placed)) == len(devices),
          f"workers share devices: {placed}")
    statuses = {e["status"] for u, e in cluster.fleet.items()
                if isinstance(u, int)}
    check(statuses == {"ok"}, f"fleet statuses {statuses}")
    same = sum(out.get(u) == v for u, v in base.items())
    print(f"fleet greedy tokens identical to 1 replica: {same}/{len(base)}")
    check(out == base, "fleet tokens differ from the 1-replica run")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    from repro.configs.registry import get_config
    from repro.kernels.common import default_interpret
    from repro.models.attention import (default_attention_backend,
                                        default_decode_backend)
    from repro.models.layers import _resolve_reduction_backend

    devices = jax.devices()
    platform = devices[0].platform
    check(platform == "tpu", f"no TPU: JAX found {platform!r} devices; "
          "this script never runs on the CPU")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} devices")
    devices = devices[:args.chips]
    kind = devices[0].device_kind
    print(f"device: {platform} {kind} x{len(devices)}; compile cache "
          f"{cache_dir}")
    lowerings = {"prefill attention": default_attention_backend(),
                 "decode attention": default_decode_backend(),
                 "verify attention": default_decode_backend(),
                 "rmsnorm": _resolve_reduction_backend(None)}
    print("lowerings: " + ", ".join(f"{k}={v}" for k, v in
                                    lowerings.items())
          + f"; Pallas interpret mode={default_interpret()}")
    check(not default_interpret(), "Pallas kernels would run interpreted")
    check(set(lowerings.values()) <= {"kernel", "pallas"},
          f"a main-path lowering is not a kernel: {lowerings}")

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    model, params = build_model(cfg, args.seed)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_head={cfg.d_head} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"tie_embeddings={cfg.tie_embeddings} dtype=bfloat16; params "
          f"{n_bytes / 2**30:.3f} GiB, built in "
          f"{time.perf_counter() - t0:.1f} s on {kind}")

    if args.chips == 4:
        fleet_phase(model, params, cfg, args.seed, devices)
    else:
        plain, kw = serve_phase(model, params, cfg, args.seed, kind)
        spec_phase(model, params, cfg, args.seed, kind, kw, plain)
        logits_phase(cfg, params, args.seed)

    stats = devices[0].memory_stats() or {}
    print(f"memory on {kind}: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use')} bytes_limit="
          f"{stats.get('bytes_limit')}")
    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    print(f"compile cache: {entries} entries in {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
