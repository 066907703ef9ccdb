"""Serving launcher: continuous-batching engine with either cache layout.

By default the model is built at its published widths with bf16 params
and compute, as a deployment holds it — that is the chip path
(``chip_smoke.py`` drives the same engine).  ``--reduced`` selects the
laptop-scale config (d_model 256, vocab 512, float32 compute) that the
CPU examples below and the tests use; off the TPU the Pallas kernels run
in interpret mode and ``auto`` lowerings pick ``jnp``.

Runs the fused zero-copy decode fast path by default; ``--no-fused``
selects the seed per-token-dispatch loop for comparison, and
``--cache-layout paged`` swaps the dense slot pool for the paged block
pool (``--page-size`` / ``--num-pages`` size it; the default pool
matches dense capacity, a smaller one exercises preempt-and-requeue).
``--spec-k`` turns on speculative decoding over the paged cache
(``--draft self:N`` for an N-layer self-speculative prefix or an arch
name for an independent draft; ``--verify-backend`` picks the fused
Pallas verify kernel or the chunked-jnp SW baseline).
``--prefix-sharing`` turns on prompt-prefix sharing: requests whose
prompts start with the same ``--shared-prefix`` tokens map the same
physical pages (refcounted, copy-on-write) and prefill only their
suffix — the per-request ``cached`` column shows how many prompt
tokens came from the radix index instead of compute.

Tiered KV memory (paged layout): ``--kv-dtype int8`` stores the page
pool as int8 values + per-row float32 scales (half the bytes, ~2x the
resident tokens per pool; dequant fused into the attention gather),
``--preempt swap|auto`` pages preemption victims to host buffers and
restores them with no recompute instead of requeue-and-recompute, and
``--evict-policy`` / ``--min-cached-tokens`` tune the prefix index's
eviction order and admission threshold.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \\
      --cache-layout paged --kv-dtype int8 --num-pages 12 --preempt swap

Fault tolerance: ``--deadline-ms`` / ``--ttft-deadline-ms`` attach
per-request deadlines (expired requests end TIMEOUT), ``--max-queue``
bounds the waiting queue with ``--shed-policy`` picking the victim
(overflow ends SHED), ``--max-retries`` caps requeues after a recovered
mid-step failure, ``--audit`` sweeps the allocator/index invariants
every scheduler round, and ``--inject-faults SEED`` runs a seeded
random fault schedule (OOM, NaN, kernel failure, stragglers, spec
collapse, cancels, page corruption) against the batch — the status
column then shows each request's terminal state.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --requests 6 --prompt-len 16 --max-new 12
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --cache-layout paged --page-size 16 --num-pages 24
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --cache-layout paged --spec-k 4 --draft self:2
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --cache-layout paged --prefix-sharing --shared-prefix 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --cache-layout paged --inject-faults 0 --audit --deadline-ms 5000

Open-loop traffic: ``--workload poisson|bursty`` replays a deterministic
arrival process (``--arrival-rate`` req/s, ``--burst-factor`` for the
MMPP-2 burst state) through the async streaming server instead of
handing the engine one closed batch; ``--clock round`` makes the replay
fully deterministic in scheduler rounds.  ``--queue-watermark`` /
``--shed-priority`` shed best-effort work under backlog,
``--free-page-watermark`` holds back admission near pool exhaustion,
and ``--prefill-budget`` caps prompt tokens prefilled per round
(chunked prefill).  Every run ends with the SLA block — TTFT/TBT
p50/p95/p99, goodput, and the terminal-status census.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --cache-layout paged --workload poisson --arrival-rate 16 \
      --requests 12 --queue-watermark 4 --shed-priority 2

Multi-replica serving: ``--replicas N`` runs the batch through N engine
workers behind a router (``--router round-robin|least-loaded|
cache-aware``; cache-aware scores content-addressed prompt-prefix
overlap against load), and ``--disaggregate`` splits roles — the first
replica only prefills, the rest only decode, joined by cross-replica KV
handoff on swap handles.  Outputs are bit-identical to ``--replicas 1``
for any topology; the run ends with the fleet SLA, per-replica census,
and router decision counts.  With ``--inject-faults`` each worker runs
its own deterministically derived fault schedule.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --cache-layout paged --replicas 3 --router cache-aware \
      --prefix-sharing --shared-prefix 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --cache-layout paged --replicas 3 --disaggregate
"""

from __future__ import annotations

import argparse
import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm import Model
from repro.serve.async_engine import serve_open_loop
from repro.serve.cluster import ROUTER_POLICIES, make_cluster
from repro.serve.engine import Request, ServeEngine
from repro.serve.faults import FaultSchedule
from repro.serve.sla import format_summary
from repro.serve.workload import WORKLOAD_KINDS, describe, make_workload


def _serve_cluster(args, model, params, cfg, engine_kw, open_loop,
                   timed, reqs):
    """Fleet path: N engine workers behind the router; ends with the
    fleet SLA, per-replica census, and router decision counts."""
    if args.inject_faults is not None:
        print(f"injecting: per-worker schedules derived from seed "
              f"{args.inject_faults}")
    roles = (f"1 prefill + {args.replicas - 1} decode"
             if args.disaggregate else f"{args.replicas} mixed")
    print(f"cluster: {roles}, router={args.router}")
    cluster = make_cluster(model, params, replicas=args.replicas,
                           router_policy=args.router,
                           disaggregate=args.disaggregate,
                           faults_seed=args.inject_faults, **engine_kw)
    t0 = time.perf_counter()
    if open_loop:
        results = cluster.run_workload(timed)
        cluster.close()
    else:
        results = cluster.serve(reqs)
    dt = time.perf_counter() - t0
    n_tok = sum(len(v) for v in results.values())
    fleet = {u: e for u, e in cluster.fleet.items() if isinstance(u, int)}
    print(f"{'req':>4s} {'status':>9s} {'tokens':>7s} {'replica':>8s} "
          f"{'handoffs':>9s} {'reroutes':>9s} {'first_tok@':>11s}")
    for uid in sorted(fleet):
        e = fleet[uid]
        first = (f"round {e['first_token_round']}"
                 if "first_token_round" in e else "—")
        print(f"{uid:4d} {e['status']:>9s} {e['tokens']:7d} "
              f"{str(e['worker']):>8s} {e['handoffs']:9d} "
              f"{e['reroutes']:9d} {first:>11s}")
    router = cluster.last_stats["router"]
    print(f"\n{n_tok} tokens in {dt:.2f}s = {n_tok / dt:.1f} tok/s "
          f"({args.replicas} replicas x {args.slots} slots, "
          f"{router['rounds']} fleet rounds, {cfg.name})")
    print(f"router: decisions={router['decisions']} "
          f"affinity_hits={router['affinity_hits']} "
          f"handoffs={router['handoffs']} reroutes={router['reroutes']}")
    sla = cluster.last_stats["sla"]
    print("fleet SLA:")
    print(format_summary(sla))
    for wid, census in sorted(sla["replicas"].items()):
        statuses = " ".join(f"{k}={v}" for k, v in
                            sorted(census["statuses"].items()))
        pool = cluster.last_pool_stats.get(int(wid))
        pool_s = (f", pool peak {pool.peak_used_pages}/{pool.num_pages} "
                  f"pages, {pool.allocs} allocs"
                  if pool is not None else "")
        print(f"  replica {wid}: {census['requests']} requests "
              f"({statuses or 'idle'}){pool_s}")
    rep = cluster.audit_report
    print(f"fleet audit: {'clean' if rep.ok else rep.errors}")
    for uid in sorted(results):
        print(f"req {uid}: {results[uid]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="laptop-scale config with float32 compute (CPU "
                         "runs and tests); default: published widths, "
                         "bf16")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-fused", action="store_true",
                    help="seed per-token loop instead of the fused "
                         "zero-copy fast path")
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "kernel", "jnp"],
                    help="prefill/admission attention lowering (auto: "
                         "flash Pallas kernel on TPU, jnp elsewhere)")
    ap.add_argument("--cache-layout", default="dense",
                    choices=["dense", "paged"],
                    help="KV cache layout: dense slot pool (HW-contiguous "
                         "reads) or paged block pool (SW block-table "
                         "indirection, memory-bound admission)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool pages incl. the trash page (default: "
                         "dense-capacity parity)")
    ap.add_argument("--kv-dtype", default="auto",
                    choices=["auto", "bf16", "int8"],
                    help="paged pool storage: bf16 values, or int8 values "
                         "with per-row float32 scales dequantized inside "
                         "the attention gather — half the pool bytes, so "
                         "the same pages hold ~2x the tokens (auto: the "
                         "model's cache dtype)")
    ap.add_argument("--preempt", default="requeue",
                    choices=["requeue", "swap", "auto"],
                    help="pool-exhaustion preemption: requeue recomputes "
                         "the victim's cache at re-admission; swap pages "
                         "it to host buffers and restores it with no "
                         "recompute; auto compares the two costs per "
                         "token (paged layout)")
    ap.add_argument("--evict-policy", default="lru",
                    choices=["lru", "lfu", "deepest"],
                    help="prefix-index eviction under allocation "
                         "pressure: least-recently-used, least-frequently-"
                         "used, or deepest-subtree-first (longest cached "
                         "prefixes go first)")
    ap.add_argument("--min-cached-tokens", type=int, default=0,
                    help="admission threshold for the prefix index: "
                         "prompts shorter than this are not published as "
                         "cached prefix (keeps tiny prefixes from "
                         "polluting the radix cache)")
    ap.add_argument("--prefix-sharing", default=False,
                    action=argparse.BooleanOptionalAction,
                    help="share page-aligned prompt prefixes: identical "
                         "prefixes map the same refcounted physical pages "
                         "(copy-on-write), prefill computes only the "
                         "suffix (requires --cache-layout paged)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="make all request prompts share their first N "
                         "tokens (the prefix-sharing demo workload; 0 = "
                         "fully random prompts)")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="speculative window: draft proposes k-1 tokens, "
                         "the target verifies all k in one dispatch "
                         "(requires --cache-layout paged); 1 disables")
    ap.add_argument("--draft", default=None,
                    help="draft model for --spec-k > 1: 'self' "
                         "(half-depth self-speculation, the default), "
                         "'self:N' (N-layer prefix), or a registry arch "
                         "name (independent reduced-shape draft)")
    ap.add_argument("--verify-backend", default="auto",
                    choices=["auto", "kernel", "jnp"],
                    help="k-token verify lowering: fused Pallas verify "
                         "kernel vs chunked-jnp SW baseline (auto: kernel "
                         "on TPU, jnp elsewhere)")
    ap.add_argument("--attend-block", type=int, default=64,
                    help="attention-length bucket: decode scores the live "
                         "prefix rounded up to this many positions")
    ap.add_argument("--prompt-block", type=int, default=16,
                    help="admission bucket: prompts right-pad to a "
                         "multiple of this for the batched prefill")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request end-to-end deadline: requests that "
                         "overrun end TIMEOUT instead of finishing")
    ap.add_argument("--ttft-deadline-ms", type=float, default=None,
                    help="per-request time-to-first-token deadline "
                         "(expires only before the first token)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="requeues allowed per request after a recovered "
                         "mid-step failure before it ends FAILED")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound on the waiting queue: overflow is shed "
                         "per --shed-policy (default: unbounded)")
    ap.add_argument("--shed-policy", default="reject-newest",
                    choices=["reject-newest", "reject-largest"],
                    help="overflow victim selection for --max-queue")
    ap.add_argument("--workload", default="closed",
                    choices=list(WORKLOAD_KINDS),
                    help="traffic shape: closed = one batch at t=0 "
                         "(legacy synchronous path); poisson / bursty "
                         "replay an open-loop arrival process through "
                         "the async streaming server")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="mean arrival rate in req/s for open-loop "
                         "workloads")
    ap.add_argument("--burst-factor", type=float, default=4.0,
                    help="MMPP-2 burst intensity for --workload bursty "
                         "(calm rate/f, burst rate*f)")
    ap.add_argument("--clock", default="wall",
                    choices=["wall", "round"],
                    help="open-loop arrival clock: wall = real sleeps "
                         "(honest latency), round = deterministic "
                         "scheduler rounds (reproducible)")
    ap.add_argument("--queue-watermark", type=int, default=None,
                    help="soft queue depth: beyond it, queued requests "
                         "with priority >= --shed-priority are shed")
    ap.add_argument("--shed-priority", type=int, default=2,
                    help="lowest priority class the watermark may shed "
                         "(lower number = more important)")
    ap.add_argument("--free-page-watermark", type=float, default=0.0,
                    help="fraction of the page pool held in reserve: "
                         "admission defers while free pages would drop "
                         "below it (paged layout)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prompt tokens prefilled per scheduler "
                         "round (chunked prefill; paged layout, "
                         "spec-k 1, no prefix sharing)")
    ap.add_argument("--audit", action="store_true",
                    help="sweep allocator/index invariants every "
                         "scheduler round (always swept once at the end)")
    ap.add_argument("--pipeline", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="overlap host scheduling with the in-flight "
                         "decode step (dispatch/commit round pipeline); "
                         "--no-pipeline keeps the serial round — outputs "
                         "are bit-identical either way")
    ap.add_argument("--preempt-calibrate", action="store_true",
                    help="microbenchmark the D2H/H2D page-copy bandwidth "
                         "and decode throughput at engine construction "
                         "and drive preempt=auto from the measured "
                         "figures instead of the fixed defaults")
    ap.add_argument("--inject-faults", type=int, default=None,
                    metavar="SEED",
                    help="run a seeded random fault schedule against the "
                         "batch (OOM, NaN, kernel failure, stragglers, "
                         "spec collapse, cancels, page corruption); with "
                         "--replicas, each worker derives its own "
                         "schedule from this seed")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine workers behind the router; outputs stay "
                         "bit-identical to --replicas 1 (requires "
                         "--cache-layout paged)")
    ap.add_argument("--router", default="cache-aware",
                    choices=list(ROUTER_POLICIES),
                    help="replica placement policy: classic rotation, "
                         "min queue+slots, or prefix-affinity scoring "
                         "over content-addressed prompt hashes")
    ap.add_argument("--disaggregate", action="store_true",
                    help="split roles: replica 0 only prefills, the rest "
                         "only decode, joined by cross-replica KV "
                         "handoff (requires --replicas >= 2)")
    args = ap.parse_args()
    cluster_mode = args.replicas > 1 or args.disaggregate
    if cluster_mode and args.cache_layout != "paged":
        ap.error("--replicas > 1 / --disaggregate move KV as pages; "
                 "pass --cache-layout paged")
    if args.disaggregate and args.replicas < 2:
        ap.error("--disaggregate needs --replicas >= 2 (at least one "
                 "prefill and one decode worker)")

    enable_compile_cache()
    if args.reduced:
        cfg, dtype = reduced_config(args.arch), jnp.float32
    else:
        cfg, dtype = get_config(args.arch), jnp.bfloat16
    model = Model(cfg, param_dtype=dtype, compute_dtype=dtype,
                  attn_backend=None if args.attn_backend == "auto"
                  else args.attn_backend)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine_kw = dict(max_seq=args.max_seq,
                     batch_slots=args.slots,
                     temperature=args.temperature, seed=args.seed,
                     fused=not args.no_fused,
                     attend_block=args.attend_block,
                     prompt_block=args.prompt_block,
                     cache_layout=args.cache_layout,
                     page_size=args.page_size,
                     num_pages=args.num_pages,
                     kv_dtype=None if args.kv_dtype == "auto"
                     else args.kv_dtype,
                     preempt=args.preempt,
                     prefix_sharing=args.prefix_sharing,
                     evict_policy=args.evict_policy,
                     min_cached_tokens=args.min_cached_tokens,
                     spec_k=args.spec_k, draft=args.draft,
                     verify_backend=None if args.verify_backend == "auto"
                     else args.verify_backend,
                     max_queue=args.max_queue,
                     shed_policy=args.shed_policy,
                     queue_watermark=args.queue_watermark,
                     shed_priority=args.shed_priority,
                     free_page_watermark=args.free_page_watermark,
                     prefill_budget=args.prefill_budget,
                     audit=args.audit,
                     pipeline=args.pipeline,
                     preempt_calibrate=args.preempt_calibrate)
    engine = (None if cluster_mode
              else ServeEngine(model, params, **engine_kw))
    if args.preempt_calibrate and engine is not None:
        cm = engine.cost_model
        print(f"calibrated cost model: swap {cm.swap_gbps / 1e9:.2f} GB/s, "
              f"decode {cm.decode_flops_s / 1e9:.1f} GFLOP/s "
              f"({cm.source})")

    rng = np.random.default_rng(args.seed)
    open_loop = args.workload != "closed"
    if open_loop:
        timed = make_workload(
            args.workload, args.requests, vocab=cfg.vocab,
            seed=args.seed, rate=args.arrival_rate,
            burst_factor=args.burst_factor,
            prompt_median=args.prompt_len, prompt_max=2 * args.prompt_len,
            out_median=args.max_new, out_max=2 * args.max_new,
            shared_prefix_frac=0.5 if args.shared_prefix else 0.0,
            prefix_len=args.shared_prefix,
            deadline_ms=args.deadline_ms,
            ttft_deadline_ms=args.ttft_deadline_ms)
        reqs = [t.request for t in timed]
        d = describe(timed)
        print(f"workload: {args.workload} n={d['n']} "
              f"span={d['span_s']:.2f}s rate={d['mean_rate']:.1f} req/s "
              f"prompts~{d['prompt_mean']:.0f} (max {d['prompt_max']}), "
              f"{args.clock} clock")
    else:
        shared = rng.integers(
            0, cfg.vocab, min(args.shared_prefix, args.prompt_len)).tolist()
        reqs = [Request(uid=i,
                        prompt=shared + rng.integers(
                            0, cfg.vocab,
                            args.prompt_len - len(shared)).tolist(),
                        max_new_tokens=args.max_new,
                        deadline_ms=args.deadline_ms,
                        ttft_deadline_ms=args.ttft_deadline_ms,
                        max_retries=args.max_retries)
                for i in range(args.requests)]
    if cluster_mode:
        _serve_cluster(args, model, params, cfg, engine_kw, open_loop,
                       timed if open_loop else None, reqs)
        return
    faults = None
    if args.inject_faults is not None:
        faults = FaultSchedule.random(
            args.inject_faults, uids=tuple(r.uid for r in reqs))
        print(f"injecting (seed {args.inject_faults}): "
              + ", ".join(f.kind + (f"@{f.step}" if f.span == 1
                                    else f"@{f.step}+{f.span}")
                          for f in faults.faults))
    t0 = time.perf_counter()
    if open_loop:
        results = asyncio.run(serve_open_loop(
            engine, timed, faults=faults, clock=args.clock))
    else:
        results = engine.serve(reqs, faults=faults)
    dt = time.perf_counter() - t0
    n_tok = sum(len(v) for v in results.values())
    per_req = {u: s for u, s in engine.last_stats.items()
               if isinstance(u, int)}
    print(f"{'req':>4s} {'status':>9s} {'tokens':>7s} {'cached':>7s} "
          f"{'admit->first(ms)':>17s} "
          f"{'decode tok/s':>13s} {'e2e tok/s':>10s} {'accept':>7s} "
          f"{'preempts':>9s}")
    for uid in sorted(per_req):
        s = per_req[uid]
        if uid not in results:          # shed/timeout/cancelled/failed
            reason = s.get("reason", "")
            print(f"{uid:4d} {s['status']:>9s} {'—':>7s} {'—':>7s} "
                  f"{reason:>17s}")
            continue
        acc = (f"{s['accept_rate']:7.2f}" if "accept_rate" in s
               else f"{'—':>7s}")
        print(f"{uid:4d} {s['status']:>9s} {len(results[uid]):7d} "
              f"{int(s.get('cached_prefix_tokens', 0)):7d} "
              f"{1e3 * s['admit_to_first_s']:17.1f} {s['tok_s']:13.1f} "
              f"{s['e2e_tok_s']:10.1f} {acc} "
              f"{int(s['preemptions']):9d}")
    spec = f", spec-k={args.spec_k}" if args.spec_k > 1 else ""
    loop = f", {args.workload} open-loop" if open_loop else ""
    print(f"\n{n_tok} tokens in {dt:.2f}s = {n_tok / dt:.1f} tok/s "
          f"({args.slots} slots, {args.cache_layout} cache{spec}{loop}, "
          f"{cfg.name})")
    print("SLA:")
    print(format_summary(engine.last_stats["sla"]))
    counts = {}
    for s in per_req.values():
        counts[s["status"]] = counts.get(s["status"], 0) + 1
    lifecycle = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    stragglers = engine.last_stats["stragglers"]
    print(f"lifecycle: {lifecycle}, {engine.recoveries} recoveries, "
          f"{len(stragglers)} straggler events"
          + (", backend degraded to SW" if engine.backend_degraded else ""))
    if engine.last_pool_stats is not None and args.audit:
        p = engine.last_pool_stats
        print(f"audit: {'clean' if p.audit_ok else p.audit_errors} "
              f"(per-round sweep enabled)")
    if engine.last_pool_stats is not None:
        p = engine.last_pool_stats
        print(f"pool: {p.num_pages} pages x {p.page_size} tok, peak "
              f"{p.peak_used_pages} pages / {p.peak_tokens} tok "
              f"({100 * p.peak_utilization:.0f}% util high-water), "
              f"{p.allocs} allocs / {p.frees} frees / {p.retracts} "
              f"retracts, {engine.preemptions} preemptions")
        if p.kv_dtype is not None or p.swap_outs or p.swap_ins:
            print(f"tiered: kv_dtype={p.kv_dtype or 'auto'}, "
                  f"{p.swap_outs} swap-outs / {p.swap_ins} swap-ins "
                  f"({p.swapped_out_bytes / 1e6:.2f} MB out, "
                  f"{p.swapped_in_bytes / 1e6:.2f} MB in)")
        if args.prefix_sharing:
            print(f"sharing: {p.peak_logical_pages} logical pages peak vs "
                  f"{p.peak_used_pages} physical "
                  f"({p.sharing_ratio:.2f}x high-water), "
                  f"{p.cached_prefix_tokens} prompt tokens served from "
                  f"cache, {p.shares} shares / {p.cow_forks} CoW forks / "
                  f"{p.evictions} evictions, {p.index_pages} pages left "
                  f"in the index")
    for uid in sorted(results):
        print(f"req {uid}: {results[uid]}")


if __name__ == "__main__":
    main()
