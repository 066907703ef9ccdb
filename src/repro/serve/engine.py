"""Serving engine: continuous-batching scheduler over a dense or paged cache.

The engine keeps a fixed pool of batch slots (the static shape pjit needs)
and a waiting queue of requests.  Admission happens at step boundaries;
every decode step advances all live slots together; finished slots free
immediately.  Two cache layouts sit behind one scheduler:

  dense   one (L, slots, max_seq, H, D) pool; admission is gated on a
          free *slot* — each slot reserves ``max_seq`` positions whether
          it uses them or not (slot-bound capacity, the HW-contiguous
          read path).
  paged   a shared (L, num_pages, page_size, H, D) block pool
          (``repro.serve.kv_cache``); admission is gated on free *pages*,
          pages are allocated on demand at step boundaries as sequences
          grow, and when the pool exhausts the newest live request is
          preempted and requeued (recompute-style: its generated tokens
          are folded into its prompt, so greedy outputs are unchanged).
          Capacity is memory-bound — the pool holds the tokens that
          exist, not ``slots x max_seq``.

Fast path (default, ``fused=True``) — one jitted dispatch per token with
the HW-path discipline from the paper applied end to end: decode + sample
+ position/remaining advance + done-mask fuse into a single dispatch;
``donate_argnums`` on the cache lets XLA alias the KV buffers in place;
attention reads are bounded to the live prefix via a bucketed static
``attend_len``; the only host transfer per token is the (tokens, done,
bad) triple.  The paged step additionally reads its block tables,
uploaded only when the allocator changed them — never per token.

Sampling is reproducible under continuous batching: the key for the
token at absolute position P of request ``uid`` is
``fold_in(fold_in(PRNGKey(seed), uid), P)`` — derived from *what* is
being sampled, not from how many keys the engine consumed before, so
outputs are independent of admission order, slot assignment, and
preemption.

Speculative decoding (``spec_k > 1``, paged layout) replaces the
one-token step with a propose+verify window: a draft model proposes k-1
tokens, the target scores all k positions in one fused dispatch
(``repro.serve.spec_decode``), and the longest prefix matching the
target's own ``(uid, position)``-keyed samples commits — 1..k tokens per
dispatch, bit-identical output to non-speculative decode.  Requests with
``spec=False`` ride the same batch committing one token per step.  The
window's page span is mapped before the step and blocks holding only
rejected rows are retracted afterwards (allocator table edit, no copies).

Prefix sharing (``prefix_sharing=True``, paged layout) admits a prompt
by resolving its longest cached page-granular prefix in a radix index
(``repro.serve.prefix_index``) and mapping those *physical* pages into
the new slot's block table — zero copies, refcount++ in the allocator.
Prefill then computes only the un-cached suffix through the paged cache
(:meth:`Model.prefill_suffix`), admission charges only the private
suffix pages against the free-pool gate, and released requests' prefixes
linger in the index as reclaimable cache (LRU-evicted under allocation
pressure).  Greedy outputs are bit-identical to sharing-disabled paged
serving — sharing is invisible below the block tables.

Tiered KV memory (paged layout).  ``kv_dtype='int8'`` stores the page
pools quantized: int8 values plus a float32 per-row (per cached
position) symmetric scale, quantized on every cache write and
dequantized inside the fused attention gathers — kernel and chunked-jnp
SW path alike, so the HW-vs-SW parity gates extend to the quantized
axis unchanged.  Half the pool bytes means the same physical pages hold
~2x the resident tokens, which is admission capacity, not just memory.
``preempt='swap'`` replaces preempt-and-recompute with a host-swap
tier: the victim's pages are snapshotted to host buffers *before* its
slot releases, and re-admission restores them into fresh private pages
with zero recompute — the resume is bit-identical to the requeue-
recompute resume, because per-row quantization makes the stored page
bytes a pure function of the cached values.  ``preempt='auto'`` picks
per configuration by comparing transfer cost against recompute cost per
resident token.  The prefix index additionally takes an eviction policy
(``evict_policy``: lru / lfu / deepest-subtree-first) and a
``min_cached_tokens`` admission threshold for short prompts.

Fault tolerance — every request leaves ``serve()`` with exactly one
terminal status in ``last_stats[uid]["status"]``:

  ok          completed; its tokens are in the returned dict
  shed        rejected at enqueue by the bounded waiting queue
              (``max_queue`` + ``shed_policy``: reject-newest or
              reject-largest)
  timeout     its ``deadline_ms`` (enqueue->finish) or
              ``ttft_deadline_ms`` (enqueue->first token) expired
  cancelled   :meth:`cancel`\\ led (or fault-injected cancel)
  failed      quarantined (non-finite logits poison only the offending
              row — the NaN guard rides inside the fused step, so the
              rest of the batch commits normally), or its capped
              retry-with-requeue budget ran out across step-restart
              recoveries

Recovery is step-restart: an injected, non-fatal mid-step fault
(allocator OOM, kernel-backend failure) releases every live slot,
requeues each request with its generated tokens folded into its prompt
(charging one retry), and rebuilds the manager + device pool from
scratch — the ``(uid, position)`` sampling keys make the replay
bit-identical, the same property preemption rides on.  Any other
exception escapes the session (``_abort`` releases everything on the
way out).  An injected kernel-backend failure additionally degrades the
engine onto the chunked-``jnp`` SW path (``backend_degraded``) — the
paper's HW-vs-SW interchangeability as a runtime policy.  Speculative
decoding auto-disables per request when its acceptance collapses
(window of 1-token commits) and re-enables after a cooldown.
``repro.serve.faults`` injects all of these
deterministically; ``repro.serve.audit`` sweeps the allocator / block
table / prefix index invariants per round under ``audit=True`` and
always after ``serve()`` (via ``last_pool_stats``).

The seed per-token-dispatch loop is preserved under ``fused=False`` as
the benchmark baseline (``benchmarks/serve_decode.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention.decode_attention import pages_per_block
from repro.serve import calibrate, sla, spec_decode
from repro.serve.audit import audit_pool
from repro.serve.faults import InjectedFault, KernelBackendError, poison_pages
from repro.serve.kv_cache import (
    CACHE_LAYOUTS,
    AdmitPlan,
    PagedCacheManager,
    SwapHandle,
    blocks_for,
    cdiv,
    copy_pages,
    resolve_kv_dtype,
    scatter_prefill,
    swap_in_pages,
    write_slot,
    write_slots,
)
from repro.serve.prefix_index import EVICT_POLICIES, PrefixIndex
from repro.serve.trace import SPANS, column, new_phases, span

# terminal request statuses (last_stats[uid]["status"]) — every request
# handed to serve() ends in exactly one of these
STATUS_OK = "ok"
STATUS_SHED = "shed"
STATUS_TIMEOUT = "timeout"
STATUS_CANCELLED = "cancelled"
STATUS_FAILED = "failed"
TERMINAL_STATUSES = (STATUS_OK, STATUS_SHED, STATUS_TIMEOUT,
                     STATUS_CANCELLED, STATUS_FAILED)

# work counters, counted where the engine chooses the shapes; exported as
# last_stats["counters"], mid-session as ServeEngine.counters(st), and per
# round (cumulative) in the timeseries
COUNTERS = (
    "decode_steps",
    "decode_ctx_tokens",    # sum over steps of live rows' slot_pos + 1
    "decode_grid_tokens",   # keys the attention kernel visits, over steps
    "prefill_tokens",       # uncached prompt tokens computed
    "prefill_causal_keys",  # sum of n * start + n (n + 1) / 2
    "admissions",           # requests whose first token a prefill sampled
)

# bounded-queue shed policies: who gets rejected when the waiting queue
# overflows max_queue
SHED_POLICIES = ("reject-newest", "reject-largest")

# preemption-resume policies: requeue recomputes the victim's cache from
# its folded prompt at re-admission; swap pages it to host buffers and
# restores it with no recompute; auto picks per configuration by
# comparing the two per-token costs (both linear in resident tokens)
PREEMPT_POLICIES = ("requeue", "swap", "auto")

# auto-preempt cost model defaults live in repro.serve.calibrate; these
# aliases keep the old import path working.  ``preempt_calibrate=True``
# (or an explicit ``cost_model=``) replaces them with measured figures.
_SWAP_GBPS = calibrate.DEFAULT_SWAP_GBPS
_RECOMPUTE_FLOPS_S = calibrate.DEFAULT_DECODE_FLOPS_S


def _round_up(x: int, block: int) -> int:
    """x rounded up to a positive multiple of block (shape bucketing)."""
    return max(block, -(-x // block) * block)


def sample_token(logits: jnp.ndarray, key, temperature: float = 0.0):
    """logits (B, V) -> tokens (B,).  temperature 0 = greedy."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / temperature, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    generated: Optional[List[int]] = None
    # participate in speculative windows when the engine runs spec_k > 1;
    # spec=False requests share the batch committing one token per step
    spec: bool = True
    # ---- lifecycle (all optional; None = unbounded) ----
    # wall-clock budget from enqueue to completion; expiry -> TIMEOUT
    deadline_ms: Optional[float] = None
    # wall-clock budget from enqueue to the first token; expiry -> TIMEOUT
    ttft_deadline_ms: Optional[float] = None
    # step-restart recoveries this request may ride before FAILED
    max_retries: int = 2
    # SLA priority class: lower admits (and survives shedding /
    # preemption) first; equal-priority traffic keeps strict FIFO order,
    # so the default (every request at 1) reproduces the legacy scheduler
    # exactly
    priority: int = 1
    # internal resume bookkeeping: how many ``generated`` tokens are
    # already folded into ``prompt``.  A preemption/recovery resume rides
    # a copy whose prompt absorbs the generated-so-far suffix; folding
    # the *full* list again on a second preemption would duplicate the
    # earlier tokens (generated is the whole-output accumulator).
    folded: int = 0


@dataclasses.dataclass
class PendingRound:
    """A decode step in flight: the jitted dispatch has been issued and
    its results — device arrays — have not been fetched yet.

    ``arrays`` holds the step's host-relevant outputs ((tok, done, bad),
    plus the candidate window and commit counts for a speculative step);
    :meth:`ServeEngine.commit_round` performs the single blocking
    ``jax.device_get`` on them.  ``live`` snapshots the dispatch-time
    slot -> request map, so the commit accounts tokens to exactly the
    requests the step computed them for, even though queue-side
    scheduling for the next round may run before the commit.  Everything
    else is watchdog bookkeeping."""
    arrays: tuple
    live: Dict[int, Request]
    spec: bool = False
    t_start: float = 0.0        # watchdog clock start (at dispatch)
    live_before: int = 0


# families for which right-padded prefill is exact: cache purely positional
# (mask-protected) AND no cross-token compute beyond causal attention.
# Recurrent state (ssm/hybrid) advances through padding; MoE expert
# capacity / GShard grouping depend on the padded length, so both admit
# sequentially at batch 1 instead.
_PADDED_PREFILL_FAMILIES = ("dense",)


def _home_device(params):
    """The single device every parameter is committed to, else None."""
    devices = {d for x in jax.tree.leaves(params)
               if getattr(x, "committed", False) for d in x.devices()}
    return devices.pop() if len(devices) == 1 else None


class ServeEngine:
    def __init__(self, model, params, *, max_seq: int, batch_slots: int,
                 temperature: float = 0.0, seed: int = 0,
                 cache_shardings=None, fused: bool = True,
                 attend_block: int = 64, prompt_block: int = 16,
                 cache_layout: str = "dense", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 preempt: str = "requeue",
                 prefix_sharing: bool = False,
                 evict_policy: str = "lru",
                 min_cached_tokens: int = 0,
                 spec_k: int = 1, draft=None,
                 verify_backend: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject-newest",
                 queue_watermark: Optional[int] = None,
                 shed_priority: int = 2,
                 free_page_watermark: float = 0.0,
                 prefill_budget: Optional[int] = None,
                 audit: bool = False, faults=None,
                 max_recoveries: int = 2,
                 straggler_factor: float = 3.0,
                 straggler_window: int = 20,
                 spec_disable_window: int = 8,
                 spec_cooldown: int = 16,
                 pipeline: bool = True,
                 cost_model: Optional[calibrate.CostModel] = None,
                 preempt_calibrate: bool = False):
        if cache_layout not in CACHE_LAYOUTS:
            raise ValueError(f"cache_layout must be one of {CACHE_LAYOUTS}; "
                             f"got {cache_layout!r}")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1; got {spec_k}")
        if spec_k > 1 and cache_layout != "paged":
            raise ValueError("speculative decoding (spec_k > 1) verifies "
                             "against the paged cache; pass "
                             "cache_layout='paged'")
        if spec_k > 1 and not fused:
            raise ValueError("speculative decoding requires fused=True")
        if shed_policy not in SHED_POLICIES:
            raise ValueError(f"shed_policy must be one of {SHED_POLICIES}; "
                             f"got {shed_policy!r}")
        resolve_kv_dtype(kv_dtype, jnp.bfloat16)  # validate the flag early
        if kv_dtype not in (None, "auto") and cache_layout != "paged":
            raise ValueError("kv_dtype selects the paged pool's storage "
                             "format; pass cache_layout='paged'")
        if preempt not in PREEMPT_POLICIES:
            raise ValueError(f"preempt must be one of {PREEMPT_POLICIES}; "
                             f"got {preempt!r}")
        if preempt != "requeue" and cache_layout != "paged":
            raise ValueError("swap-tier preemption pages the paged pool "
                             "to host; pass cache_layout='paged'")
        if evict_policy not in EVICT_POLICIES:
            raise ValueError(f"evict_policy must be one of {EVICT_POLICIES}; "
                             f"got {evict_policy!r}")
        if min_cached_tokens < 0:
            raise ValueError(f"min_cached_tokens must be >= 0; "
                             f"got {min_cached_tokens}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 (or None for "
                             f"unbounded); got {max_queue}")
        if queue_watermark is not None and queue_watermark < 0:
            raise ValueError(f"queue_watermark must be >= 0 (or None to "
                             f"disable soft shedding); got {queue_watermark}")
        if not 0.0 <= free_page_watermark < 1.0:
            raise ValueError(f"free_page_watermark must be in [0, 1); "
                             f"got {free_page_watermark}")
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(f"prefill_budget must be >= 1 (or None for "
                             f"unbounded prefill per round); got "
                             f"{prefill_budget}")
        self.model = model
        self.params = params
        # params committed to one device (a cluster worker's chip) pin the
        # engine there: pools, slot state and draft cache are built on it
        self.device = _home_device(params)
        self.max_seq = max_seq
        self.slots = batch_slots
        self.temperature = temperature
        self.fused = fused
        self.attend_block = attend_block
        self.prompt_block = prompt_block
        self.cache_layout = cache_layout
        self.page_size = page_size
        self.kv_dtype = kv_dtype
        self.preempt = preempt
        self.prefix_sharing = prefix_sharing
        self.evict_policy = evict_policy
        self.min_cached_tokens = min_cached_tokens
        # auto-preempt cost model input: recompute cost per token is
        # ~2 * params FLOPs (one forward pass)
        self._n_params = sum(int(x.size) for x in jax.tree.leaves(params))
        # overlapped round pipeline: dispatch round N+1's host scheduling
        # while round N's device step is in flight.  pipeline=False keeps
        # the serial path (one blocking fetch inside every round) —
        # outputs are bit-identical either way.
        self.pipeline = pipeline
        # preempt='auto' cost model: fixed defaults, or a one-shot
        # microbenchmark of this process's actual D2H bandwidth and
        # decode throughput (preempt_calibrate=True); an explicit
        # cost_model always wins (sweeps inject their own figures)
        if cost_model is not None:
            self.cost_model = cost_model
        elif preempt_calibrate:
            self.cost_model = calibrate.calibrate(model, params,
                                                  max_seq=max_seq)
        else:
            self.cost_model = calibrate.DEFAULT_COST_MODEL
        self.spec_k = spec_k
        self.verify_backend = verify_backend
        # ---- lifecycle / fault-tolerance policy
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        # ---- SLA-aware scheduling (admission control + TBT bounding)
        # soft queue bound: depth above it sheds best-effort classes
        # (priority >= shed_priority) instead of everything, every round
        self.queue_watermark = queue_watermark
        self.shed_priority = shed_priority
        # fraction of the page pool kept free by the admission gate while
        # anything is running (decode growth headroom under bursts)
        self.free_page_watermark = free_page_watermark
        # prompt tokens prefilled per scheduler round: long prompts admit
        # in chunks interleaved with decode steps, bounding the
        # time-between-tokens stall a monster prompt inflicts on live
        # requests.  The chunk is a whole number of prompt_block buckets
        # so mid-chunks re-use one jit specialization with no padding.
        self.prefill_budget = prefill_budget
        self._chunk_tokens = (
            max(prompt_block, prefill_budget // prompt_block * prompt_block)
            if prefill_budget is not None else None)
        self.audit = audit
        self.faults = faults            # default FaultSchedule (or None)
        self.max_recoveries = max_recoveries
        self.straggler_factor = straggler_factor
        self.straggler_window = straggler_window
        self.spec_disable_window = spec_disable_window
        self.spec_cooldown = spec_cooldown
        self._draft_spec = draft
        self._seed = seed
        self._cache_shardings = cache_shardings
        self._cancel_uids: set = set()
        self.backend_degraded = False   # kernel -> SW fallback engaged
        self.recoveries = 0             # step restarts, cumulative
        if prefix_sharing:
            if cache_layout != "paged":
                raise ValueError("prefix sharing maps prompt prefixes "
                                 "through the paged block tables; pass "
                                 "cache_layout='paged'")
            if model.cfg.family != "dense":
                raise ValueError(
                    "prefix sharing resolves prompts by token ids and "
                    "prefills only the un-cached suffix; family "
                    f"{model.cfg.family!r} prefills with non-positional "
                    "state (frontend embeddings / length-dependent expert "
                    "capacity), so cached K/V would not be exact — "
                    "supported family: 'dense'")
        if num_pages is None:
            # capacity parity with the dense pool (+1 for the trash page)
            num_pages = batch_slots * cdiv(max_seq, page_size) + 1
        self.num_pages = num_pages
        if cache_layout == "paged":
            if not model.supports_paged():
                raise ValueError(
                    "paged cache layout needs a plain stacked K/V cache "
                    f"(families {model.PAGED_FAMILIES}, non-MLA); "
                    f"got {model.cfg.family}/{model.cfg.attn_type}")
            if not fused:
                raise ValueError("cache_layout='paged' requires fused=True "
                                 "(the seed loop is the dense baseline)")
            if cache_shardings is not None:
                raise ValueError(
                    "cache_shardings describes the dense (L, B, S, H, D) "
                    "pool and cannot shard the paged page pool; sharded "
                    "paged caches are a ROADMAP item")
        # observability, refreshed by every serve() call
        self.last_stats: Dict[Any, Any] = {}
        self.last_pool_stats = None
        self.preemptions = 0

        # sampling keys derive from (uid, position) — see module docstring.
        # Built once: it never touches the model, so it survives the
        # kernel->SW degradation rebuild unchanged (bit-parity across the
        # fallback rides on this).
        sample_base = jax.random.PRNGKey(seed)
        temperature_ = temperature

        def sample_at(logits, token_pos, uids):
            """Per-row reproducible sampling: row i's key is
            fold(fold(base, uids[i]), token_pos[i])."""
            if temperature_ <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            keys = jax.vmap(lambda u, p: jax.random.fold_in(
                jax.random.fold_in(sample_base, u), p))(uids, token_pos)
            return jax.vmap(lambda kk, lg: jax.random.categorical(
                kk, lg.astype(jnp.float32) / temperature_))(
                    keys, logits).astype(jnp.int32)

        self._sample_at = sample_at
        self.draft_model = self.draft_params = None
        self._build_steps()

    # ---------------------------------------------------------- step build
    def _build_steps(self):
        """(Re)build every jitted step function from ``self.model``.

        Called at construction and again by :meth:`_degrade_to_sw`, which
        swaps the model onto the chunked-``jnp`` backends and must re-jit
        everything that closed over the old one.  Keeping all model
        closures here is what makes the degradation a rebuild instead of
        a special case threaded through the scheduler.
        """
        model = self.model
        max_seq = self.max_seq
        sample_at = self._sample_at

        def prefill_fn(params, batch):
            return model.prefill(params, batch, max_seq)

        def prefill_padded_fn(params, batch, last_pos):
            return model.prefill(params, batch, max_seq, last_pos)

        def prefill_bucket_fn(params, batch, last_pos):
            # paged admission: the cache is scattered into pages, so pad
            # only to the prompt bucket instead of all of max_seq
            return model.prefill(params, batch, batch["tokens"].shape[1],
                                 last_pos)

        def decode_fn(params, cache, tokens, pos):
            logits, cache = model.decode_step(params, cache, tokens, pos)
            return logits, cache

        def fused_step_fn(params, cache, tok, pos, remaining, uids,
                          nan_mask, attend_len):
            """One decode token for every slot, single dispatch.

            Returns (cache, next_tok, pos, remaining, done, bad); the
            cache argument is donated — XLA writes the new K/V row through
            the existing buffers instead of copying the pool.  The sampled
            token sits at position pos+1, hence its key position.
            ``nan_mask`` rows get their logits poisoned (fault injection
            riding the real guard); ``bad`` flags rows whose logits are
            non-finite for any reason — the scheduler quarantines those
            requests instead of committing garbage.
            """
            logits, cache = model.decode_step(params, cache, tok, pos,
                                              attend_len, unroll=True)
            logits = jnp.where(nan_mask[:, None],
                               jnp.asarray(jnp.nan, logits.dtype), logits)
            bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
            nxt = sample_at(logits, pos + 1, uids)
            pos = pos + 1
            remaining = remaining - 1
            done = (remaining <= 0) | (pos >= max_seq - 1)
            return cache, nxt, pos, remaining, done, bad

        def paged_step_fn(params, pool, block_tables, tok, pos, remaining,
                          uids, nan_mask, attend_len):
            """Paged twin of fused_step_fn: the page pool is donated, the
            block tables are a read-only input (uploaded at allocator
            boundaries, reused across steps)."""
            cache = dict(pool, block_tables=block_tables)
            logits, cache = model.decode_step(params, cache, tok, pos,
                                              attend_len)
            # rebuild generically: quantized pools carry k_scales/v_scales
            # alongside the value leaves, and the donated step must hand
            # all of them back
            pool = {name: cache[name] for name in pool}
            logits = jnp.where(nan_mask[:, None],
                               jnp.asarray(jnp.nan, logits.dtype), logits)
            bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
            nxt = sample_at(logits, pos + 1, uids)
            pos = pos + 1
            remaining = remaining - 1
            done = (remaining <= 0) | (pos >= max_seq - 1)
            return pool, nxt, pos, remaining, done, bad

        kw: Dict[str, Any] = {}
        fkw: Dict[str, Any] = {}
        if self._cache_shardings is not None:
            kw["out_shardings"] = (None, self._cache_shardings)
            fkw["out_shardings"] = (self._cache_shardings, None, None, None,
                                    None, None)
        self._prefill = jax.jit(prefill_fn)
        self._prefill_padded = jax.jit(prefill_padded_fn)
        self._prefill_bucket = jax.jit(prefill_bucket_fn)
        self._decode = jax.jit(decode_fn, **kw)
        # donate cache/pos/remaining; tok is retained by callers
        # (generate stacks the per-step tokens), so it stays undonated
        self._fused_step = jax.jit(fused_step_fn, static_argnums=(7,),
                                   donate_argnums=(1, 3, 4), **fkw)
        self._paged_step = jax.jit(paged_step_fn, static_argnums=(8,),
                                   donate_argnums=(1, 4, 5))

        # ---- speculative decoding: draft + fused propose/verify/accept
        if self.spec_k > 1:
            self.draft_model, self.draft_params = spec_decode.resolve_draft(
                model, self.params, self._draft_spec, seed=self._seed)
            self._spec_step = spec_decode.build_spec_step(
                model, self.draft_model, sample_at, max_seq=max_seq,
                spec_k=self.spec_k, verify_backend=self.verify_backend)
            draft_model = self.draft_model

            def draft_prefill_fn(dparams, batch, last_pos):
                # pad to max_seq: the draft cache is a dense slot pool
                return draft_model.prefill(dparams, batch, max_seq,
                                           last_pos)

            self._draft_prefill = jax.jit(draft_prefill_fn)

        # ---- prefix sharing / chunked prefill: suffix prefill through
        # the paged cache (chunked admission writes each prompt chunk as
        # the "suffix" of the chunks already resident)
        if self.prefix_sharing or self._chunked_capable():
            vb = self.verify_backend

            def suffix_prefill_fn(params, pool, block_tables, toks,
                                  start_pos, last_idx, attend_len):
                """Prefill only the un-cached suffix: the shared prefix is
                reached through the block tables, the suffix K/V rows are
                written through them, and only the last real token's
                logits come back.  The pool is donated — the suffix lands
                in place like every other cache write."""
                cache = dict(pool, block_tables=block_tables)
                logits, cache = model.prefill_suffix(
                    params, cache, toks, start_pos, last_idx, attend_len,
                    vb)
                return logits, {name: cache[name] for name in pool}

            self._suffix_prefill = jax.jit(suffix_prefill_fn,
                                           static_argnums=(6,),
                                           donate_argnums=(1,))

    # ----------------------------------------------------------- primitives
    def prefill(self, batch: Dict[str, jnp.ndarray]):
        """Equal-length prompt batch -> (last_logits, cache)."""
        return self._prefill(self.params, batch)

    def decode_step(self, cache, tokens, pos):
        return self._decode(self.params, cache, tokens, pos)

    def fused_step(self, cache, tok, pos, remaining, uids, attend_len: int):
        """Public fused step (no injection): a zero nan_mask rides along
        so the NaN guard is always armed."""
        mask = jnp.zeros(tok.shape, jnp.bool_)
        return self._fused_step(self.params, cache, tok, pos, remaining,
                                uids, mask, attend_len)

    def _chunked_capable(self) -> bool:
        """Chunked prefill needs the paged suffix-prefill path: pages for
        the whole prompt are mapped at admission, then written one
        bucketed chunk per round.  Spec decoding and prefix sharing drive
        their own admission prefills, so they opt out (the budget still
        throttles how many whole prompts admit per round)."""
        return (self.prefill_budget is not None
                and self.cache_layout == "paged"
                and self.spec_k == 1
                and not self.prefix_sharing
                and self.model.cfg.family in _PADDED_PREFILL_FAMILIES)

    def cancel(self, uid: int):
        """Request cancellation of ``uid``: queued -> CANCELLED at the
        next round; live -> slot released, partial output discarded.
        Unknown uids are remembered until a serve() sees them."""
        self._cancel_uids.add(uid)

    def _attend_len(self, needed: int) -> int:
        """Static attention bound: ``needed`` rounded up to the bucket."""
        return min(self.max_seq, _round_up(needed, self.attend_block))

    # ------------------------------------------------------------ generation
    def generate(self, prompts: jnp.ndarray, n_tokens: int,
                 frontend_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """prompts: (B, S) equal-length batch.  Returns (B, n_tokens).

        Always runs on the dense layout (one fixed batch, no scheduling —
        paging buys nothing here).  Row i samples with uid=i keys.
        """
        b, s = prompts.shape
        batch = {"tokens": prompts}
        offset = 0
        if frontend_embeds is not None:
            batch["frontend_embeds"] = frontend_embeds
            if self.model.cfg.family == "vlm":
                offset = frontend_embeds.shape[1]
        logits, cache = self.prefill(batch)
        pos = jnp.full((b,), s + offset, jnp.int32)
        uids = jnp.arange(b, dtype=jnp.int32)
        out = []
        tok = self._sample_at(logits, pos, uids)
        out.append(tok)
        if not self.fused:
            for _ in range(n_tokens - 1):
                logits, cache = self.decode_step(cache, tok, pos)
                tok = self._sample_at(logits, pos + 1, uids)
                out.append(tok)
                pos = pos + 1
            return jnp.stack(out, axis=1)

        remaining = jnp.full((b,), n_tokens - 1, jnp.int32)
        for i in range(n_tokens - 1):
            attend = self._attend_len(s + offset + i + 1)
            cache, tok, pos, remaining, _done, _bad = self.fused_step(
                cache, tok, pos, remaining, uids, attend)
            out.append(tok)
        return jnp.stack(out, axis=1)

    # ------------------------------------------------- continuous batching
    def serve(self, requests: List[Request], faults=None) -> Dict[int, List[int]]:
        """Scheduler: waiting queue -> admission -> joint decode.

        Admission is gated on a free slot (dense) or a free slot *and*
        enough free pages for the prompt (paged); paged sequences grow
        page-by-page at step boundaries and preempt-and-requeue when the
        pool exhausts.  Returns {uid: generated tokens} for requests that
        finished OK; every request — OK or not — gets a terminal
        ``status`` (one of :data:`TERMINAL_STATUSES`) plus latency
        figures in ``self.last_stats[uid]``, watchdog events in
        ``self.last_stats["stragglers"]``, and pool accounting (with the
        invariant-audit verdict) in ``self.last_pool_stats``.

        ``faults`` overrides the engine's default
        :class:`~repro.serve.faults.FaultSchedule` for this call only —
        the jit caches are per-engine, so sweeping many schedules through
        one engine never recompiles.
        """
        st = self._open_session(requests, faults)
        try:
            with self.on_device():
                if self.pipeline:
                    # overlapped rounds: each iteration commits the
                    # previous round's in-flight step after the queue-side
                    # sweeps, so host scheduling runs while the device
                    # computes.  A live slot pins the loop until its step
                    # commits, so the loop always exits with nothing
                    # pending.
                    while st.queue or st.live or st.prefilling \
                            or st.pending is not None:
                        self.dispatch_round(st)
                else:
                    while st.queue or st.live or st.prefilling:
                        self._round(st)
        except BaseException as exc:
            # exception safety: whatever escapes, no slot or page stays
            # held and every in-flight request gets a terminal status —
            # the next serve() on this engine starts clean
            self._abort(st, exc)
            raise
        return self._finalize_session(st)

    # --------------------------------------------------- session primitives
    # serve() is the closed-loop driver over three session primitives —
    # _open_session / _round / _finalize_session — which the async engine
    # (repro.serve.async_engine) drives open-loop instead: requests join
    # mid-session via _submit_open and rounds interleave with the event
    # loop.  Both drivers share every scheduling decision below, which is
    # what makes streamed output bit-identical to the batch call.
    def _open_session(self, requests: List[Request],
                      faults=None) -> "_SchedState":
        """Register a (possibly empty) request batch and build fresh
        manager + device state; returns the session state that _round
        advances."""
        st = _SchedState(queue=deque(), mgr=None, t0=time.perf_counter())
        st.faults = faults if faults is not None else self.faults
        self.last_stats = st.stats
        self.preemptions = 0
        for req in requests:
            self._register(st, req)
            st.queue.append(req)
        self._shed_overflow(st)
        self._init_mgr(st)
        if st.mgr is not None:
            # fail fast, before any device work: a request that can never
            # fit the pool must not abort a half-served batch later (or,
            # worse, spin in the admission gate forever)
            for req in st.queue:
                self._check_fits(st, req)
        self._init_device(st)
        return st

    def _register(self, st: "_SchedState", req: Request, now: float = 0.0):
        """Status-ledger entry + arrival stamp (one per request, ever)."""
        if req.uid in st.stats:
            raise ValueError(f"duplicate request uid {req.uid}: the "
                             "status ledger and sampling keys are "
                             "keyed by uid")
        st.arrival[req.uid] = st.seq_arrival
        st.seq_arrival += 1
        st.stats[req.uid] = {"enqueued_s": now, "preemptions": 0,
                             "retries": 0, "status": None,
                             "priority": req.priority}
        if req.deadline_ms is not None or req.ttft_deadline_ms is not None:
            st.has_deadlines = True

    def _check_fits(self, st: "_SchedState", req: Request):
        """Raise unless ``req`` could complete alone in the paged pool."""
        if len(req.prompt) >= self.max_seq:
            raise ValueError(
                f"request {req.uid}: prompt of {len(req.prompt)} "
                f"tokens leaves no decode room in max_seq="
                f"{self.max_seq}")
        # a speculative window transiently maps up to spec_k - 1
        # positions past the final token; charge them so the
        # grow-span can always be granted to a lone request
        if not st.mgr.fits_worst_case(
                len(req.prompt),
                req.max_new_tokens + self.spec_k - 1,
                self.max_seq):
            longest = min(
                len(req.prompt) + req.max_new_tokens
                + self.spec_k - 2, self.max_seq)
            raise ValueError(
                f"request {req.uid} can never fit: needs "
                f"{blocks_for(longest, self.page_size)} pages "
                + (f"(incl. the spec_k={self.spec_k} window "
                   f"overhang) " if self.spec_k > 1 else "")
                + f", pool has {st.mgr.allocator.usable}")

    def _submit_open(self, st: "_SchedState", req: Request,
                     now: float = 0.0):
        """Open-loop arrival: register + enqueue mid-session.  A request
        that could never fit fails terminally instead of raising — the
        server must keep serving everyone else."""
        self._register(st, req, now=now)
        if st.mgr is not None:
            try:
                self._check_fits(st, req)
            except ValueError as exc:
                self._terminal(st, req, STATUS_FAILED,
                               reason=f"never-fits: {exc}")
                return
        st.queue.append(req)

    # ------------------------------------------------- cross-replica handoff
    # The cluster layer (repro.serve.cluster) moves a mid-flight request
    # between two sessions — usually on two different engines — with
    # these two primitives.  Bit-parity with an uninterrupted run falls
    # out of the same invariants preemption relies on: sampling keys are
    # (uid, position), a folded prompt re-creates the exact cache, and a
    # SwapHandle restores page contents placement-free.
    def _migrate_out(self, st: "_SchedState", uid: int):
        """Detach a live request from this session for handoff: swap its
        pages out to a placement-free host handle, release the slot, and
        remove it from this session's ledger (the destination session
        re-registers it — a migrated request must not trip this
        session's terminal-status partition check).

        Returns ``(resume_request, handle, carry)``: the folded resume
        copy (sharing the accumulating ``generated`` list), the
        :class:`~repro.serve.kv_cache.SwapHandle`, and the ledger entry
        whose counters the destination should inherit."""
        # a step in flight may still commit tokens (or free) this slot —
        # drain it before detaching so the handle snapshots final state
        self.commit_round(st)
        slot = next(s for s, r in st.live.items() if r.uid == uid)
        req = st.live.pop(slot)
        handle = st.mgr.swap_out(slot, st.pool, st.slot_pos[slot],
                                 async_copy=True)
        st.pending_swaps.append(handle)
        self._drain_swaps(st)
        resume = dataclasses.replace(
            req, prompt=list(req.prompt) + req.generated[req.folded:],
            folded=len(req.generated))
        carry = st.stats.pop(req.uid)
        st.arrival.pop(req.uid, None)
        st.last_emit.pop(req.uid, None)
        st.spec_hist.pop(req.uid, None)
        return resume, handle, carry

    def _submit_resume(self, st: "_SchedState", req: Request, *,
                       handle=None, carry=None, now: float = 0.0):
        """Accept a mid-flight request handed off from another session:
        register it (inheriting ``carry``'s lifecycle counters), mark it
        resumed so admission keeps its ``generated`` prefix, and either
        stage its :class:`SwapHandle` for a page restore (no prefill) or
        let the folded prompt re-prefill from scratch (the worker-death
        retry path, where the pages died with the replica)."""
        self._register(st, req, now=now)
        s = st.stats[req.uid]
        if carry is not None:
            for k in ("preemptions", "retries", "swap_outs", "swap_ins",
                      "handoffs", "cached_prefix_tokens"):
                if k in carry:
                    s[k] = carry[k]
        s["handoffs"] = s.get("handoffs", 0) + 1
        if st.mgr is not None:
            # _check_fits would double-charge a folded resume (the folded
            # generated tokens sit in both the prompt and max_new_tokens);
            # gate on the true remaining footprint instead so a resume
            # that fit its source replica is not falsely rejected here
            longest = min(len(req.prompt)
                          + max(req.max_new_tokens - req.folded, 1)
                          + self.spec_k - 2, self.max_seq)
            if blocks_for(longest, self.page_size) > st.mgr.allocator.usable:
                self._terminal(
                    st, req, STATUS_FAILED,
                    reason=f"never-fits: resume needs "
                           f"{blocks_for(longest, self.page_size)} pages, "
                           f"pool has {st.mgr.allocator.usable}")
                return
        st.resumed.add(id(req))
        if handle is not None:
            st.swaps[req.uid] = handle
        st.queue.append(req)

    def _round(self, st: "_SchedState"):
        """One scheduler round: fault clock, lifecycle sweeps, admission
        control, admission, growth, one decode step.  Safe to call with
        nothing to do (the round/fault clock still ticks — the async
        driver relies on that to reach scheduled arrivals)."""
        st.rnd += 1
        with span(st, "serve.round"):
            with span(st, "serve.sweep"):
                self._apply_round_faults(st)
                self._expire_and_cancel(st)
                self._admission_control(st)
            if st.queue or st.live or st.prefilling:
                try:
                    self._admit_queued(st)
                    # a prefill-role cluster worker stops at admission:
                    # its live slots (prompt prefilled, first token
                    # sampled) are migrated out by the worker right after
                    # the round, so growth and decode would be wasted work
                    if st.live and not st.prefill_only:
                        if st.mgr is not None:
                            self._grow_or_preempt(st)
                        if st.live:
                            self._timed_step(st)
                except Exception as exc:
                    self._recover_or_raise(st, exc)
                if self.audit and st.mgr is not None:
                    st.mgr.audit().raise_if_failed()
                    if st.pool is not None:
                        # structural only: injected page corruption must
                        # surface as NaN logits, not as an audit failure
                        audit_pool(st.mgr, st.pool).raise_if_failed()
        self._sample_timeseries(st)

    def dispatch_round(self, st: "_SchedState"):
        """Overlapped twin of :meth:`_round`: one scheduler round whose
        decode step is *dispatched* but not committed — the fetch happens
        at the top of the *next* round, after the host work that cannot
        depend on it.

        The ordering is chosen so every scheduling decision lands on
        exactly the inputs the serial round would have given it:

        1. round/fault clock tick;
        2. the **overlap gap** — host work that commit(N-1) provably
           cannot influence runs while the device computes: queue-side
           fault sweeps (cancel/expiry set building), queued-request
           expire/cancel, and admission control (shed/watermark), none
           of which read live-slot flags or allocator state that only
           the commit can change;
        3. ``commit_round`` — the one blocking fetch, token/terminal
           accounting, deferred swap-out materialization;
        4. post-commit host work that *does* read commit products:
           page-corruption injection (targets the post-release owned
           set), live/prefilling expire sweeps, admission (needs the
           freed slots), growth/preemption (needs advanced slot_pos and
           spec retraction) — then the next dispatch.

        Outputs are bit-identical to the serial path; the only visible
        difference is that a session runs one extra (otherwise empty)
        trailing round to commit the last step."""
        st.rnd += 1
        with span(st, "serve.round"):
            st.overlapped = st.pending is not None
            with span(st, "serve.sweep"):
                self._apply_round_faults(st, poison=False)
                self._expire_and_cancel(st, scope="queued")
                self._admission_control(st)
            try:
                self.commit_round(st)
            except Exception as exc:
                self._recover_or_raise(st, exc)
            self._apply_poison_faults(st)
            self._expire_and_cancel(st, scope="held")
            if st.queue or st.live or st.prefilling:
                try:
                    self._admit_queued(st)
                    if st.live and not st.prefill_only:
                        if st.mgr is not None:
                            self._grow_or_preempt(st)
                        if st.live:
                            st.pending = self._timed_dispatch(st)
                except Exception as exc:
                    self._recover_or_raise(st, exc)
                if self.audit and st.mgr is not None:
                    # audit is a debug mode: force the in-flight step to
                    # commit so the auditor sees a quiescent pool (spec
                    # retraction applied, donated buffers settled) —
                    # costs this round's overlap, keeps per-round coverage
                    try:
                        self.commit_round(st)
                    except Exception as exc:
                        self._recover_or_raise(st, exc)
                    st.mgr.audit().raise_if_failed()
                    if st.pool is not None:
                        audit_pool(st.mgr, st.pool).raise_if_failed()
        self._sample_timeseries(st)

    def _admit_queued(self, st: "_SchedState"):
        """Admission phase of a round, with either admission path."""
        with span(st, "serve.admit"):
            if self.prefix_sharing:
                self._admit_shared(st)
            else:
                self._admit(st)

    def commit_round(self, st: "_SchedState"):
        """Commit the in-flight step, if any.  The pending round is
        popped *before* the blocking fetch so an exception discards it
        atomically — recovery rebuilds the pool from scratch, and a
        stale pending round must never commit into the rebuilt state.
        Also materializes any swap-out copies issued since the last
        commit boundary (their device slices are only now guaranteed
        cheap to read)."""
        pending, st.pending = st.pending, None
        if pending is not None:
            self._timed_commit(st, pending)
        self._drain_swaps(st)

    def _drain_swaps(self, st: "_SchedState"):
        """Materialize asynchronously-issued swap-out snapshots (device
        slices -> host arrays).  Idempotent; runs at every commit
        boundary and before anything that hands a handle across
        sessions."""
        if st.pending_swaps:
            with span(st, "serve.swap_fetch"):
                for handle in st.pending_swaps:
                    handle.materialize()
            st.pending_swaps.clear()

    def _recover_or_raise(self, st: "_SchedState", exc: Exception):
        """Shared recovery gate for both round drivers.  Only a
        non-fatal injected fault within the recovery budget takes
        step-restart recovery; everything else escapes — a real error
        from a dispatch (a kernel the compiler refused, a device fault,
        an audit failure) must surface, not be replayed on the same
        device or hidden behind a backend switch."""
        if (not isinstance(exc, InjectedFault) or exc.fatal
                or st.recoveries >= self.max_recoveries):
            raise exc
        self._recover(st, exc)

    def _finalize_session(self, st: "_SchedState") -> Dict[int, List[int]]:
        # safety barrier: the pipelined drivers exit with nothing pending,
        # but a direct caller may not — never finalize over an in-flight
        # step or unmaterialized swap snapshots
        self.commit_round(st)
        missing = [uid for uid, s in st.stats.items()
                   if s.get("status") not in TERMINAL_STATUSES]
        if missing:  # the statuses partition the request set, always
            raise RuntimeError(
                f"requests left without a terminal status: {missing}")
        self._cancel_uids -= set(st.stats)
        st.stats["stragglers"] = st.stragglers
        self._attach_observability(st)
        if st.mgr is not None:
            self.last_pool_stats = st.mgr.stats()
        return st.results

    def _attach_observability(self, st: "_SchedState"):
        """SLA percentile summary + per-round time series under the
        string keys of ``last_stats`` (per-request entries stay keyed by
        int uid)."""
        st.stats["sla"] = sla.summarize(
            st.stats, tbt_s=st.tbt,
            wall_s=time.perf_counter() - st.t0,
            timeseries=st.timeseries)
        st.stats["timeseries"] = st.timeseries
        st.stats["counters"] = self.counters(st)

    def counters(self, st: "_SchedState") -> Dict[str, int]:
        """The session's work counters so far (see :data:`COUNTERS`)."""
        return dict(st.counters)

    def _sample_timeseries(self, st: "_SchedState"):
        """One row per round: queue and slot state, the round's phase
        sums from the span helper (host seconds, one ``<phase>_s``
        column per span, then zeroed so that work between rounds counts
        in the next row), and the cumulative counters."""
        ts = st.timeseries
        ts["t_s"].append(time.perf_counter() - st.t0)
        ts["round"].append(st.rnd)
        ts["queue_depth"].append(self._queue_depth(st))
        busy = len(st.live) + len(st.prefilling)
        ts["live_slots"].append(busy)
        ts["utilization"].append(busy / max(1, self.slots))
        for name, secs in st.phase_s.items():
            ts[column(name)].append(secs)
        ts["overlap_s"].append(st.phase_s["serve.sweep"] if st.overlapped
                               else 0.0)
        for name, n in st.counters.items():
            ts[name].append(n)
        st.phase_s = new_phases()
        st.overlapped = False
        if st.mgr is not None:
            ts["free_pages"].append(st.mgr.allocator.free)

    # ----------------------------------------------------- lifecycle setup
    def _queue_depth(self, st: "_SchedState") -> int:
        """Waiting-queue depth as the admission-control loop sees it:
        preemption / retry requeues are exempt (the bound applies at
        enqueue, not during recovery)."""
        return sum(1 for r in st.queue if id(r) not in st.resumed)

    def _shed_overflow(self, st: "_SchedState"):
        """Bounded waiting queue: reject down to ``max_queue``.
        reject-newest drops the latest arrivals of the least-important
        priority class (FIFO fairness within a class); reject-largest
        drops the biggest worst-case footprint (prompt + budget — protect
        many small requests over one huge one), newest-first among ties.
        Requeues (preemption / retry) are exempt."""
        if self.max_queue is None:
            return
        while self._queue_depth(st) > self.max_queue:
            cands = [r for r in st.queue if id(r) not in st.resumed]
            if self.shed_policy == "reject-newest":
                victim = max(cands, key=lambda r: (r.priority,
                                                   st.arrival[r.uid]))
            else:
                victim = max(cands,
                             key=lambda r: (r.priority,
                                            len(r.prompt) + r.max_new_tokens,
                                            st.arrival[r.uid]))
            st.queue.remove(victim)
            self._terminal(
                st, victim, STATUS_SHED,
                reason=f"queue overflow (max_queue={self.max_queue}, "
                       f"policy={self.shed_policy})")

    def _admission_control(self, st: "_SchedState"):
        """Closed admission-control loop, every round: the hard
        ``max_queue`` bound first (open-loop arrivals can overflow it
        mid-session — in the closed-loop serve() it already ran at
        enqueue and is a no-op), then the soft ``queue_watermark``: depth
        above it sheds only best-effort classes (priority >=
        ``shed_priority``), most-slack then newest first, so
        latency-sensitive traffic keeps its queue position while bulk
        traffic absorbs the overload (deadline-less requests — +inf
        slack — shed before any request racing a deadline)."""
        self._shed_overflow(st)
        if self.queue_watermark is None:
            return
        now_ms = (time.perf_counter() - st.t0) * 1e3
        while self._queue_depth(st) > self.queue_watermark:
            cands = [r for r in st.queue if id(r) not in st.resumed
                     and r.priority >= self.shed_priority]
            if not cands:
                break
            victim = max(cands,
                         key=lambda r: (self._slack_ms(st, r, now_ms),
                                        r.priority, st.arrival[r.uid]))
            st.queue.remove(victim)
            self._terminal(
                st, victim, STATUS_SHED,
                reason=f"queue watermark (depth > {self.queue_watermark}, "
                       f"priority >= {self.shed_priority})")

    def _init_mgr(self, st: "_SchedState"):
        """Fresh paged-cache manager (+ prefix index) with the OOM fault
        hook installed; recovery calls this again — a rebuilt pool must
        never be reachable from a stale index."""
        if self.cache_layout != "paged":
            st.mgr = None
            return
        st.mgr = PagedCacheManager(
            self.num_pages, self.page_size, self.slots, self.max_seq,
            prefix_index=PrefixIndex(
                self.page_size, policy=self.evict_policy,
                min_cached_tokens=self.min_cached_tokens)
            if self.prefix_sharing else None,
            kv_dtype=self.kv_dtype)
        if st.faults is not None:
            fs = st.faults

            def oom_hook(n, _st=st, _fs=fs):
                f = _fs.oom_raise(_st.rnd)
                if f is not None:
                    raise InjectedFault(
                        f"injected allocator OOM (hard) at round {_st.rnd}",
                        fatal=f.fatal)
                return _fs.oom_denied(_st.rnd)

            st.mgr.allocator.fault_hook = oom_hook

    def on_device(self):
        """Context in which new arrays land on this engine's device
        (a no-op for an engine whose params are not committed to one)."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def _init_device(self, st: "_SchedState"):
        """Fresh device-side pool + slot state (used at serve() start and
        again by step-restart recovery)."""
        with self.on_device():
            if st.mgr is not None:
                st.pool = self.model.init_cache(
                    self.slots, self.max_seq, layout="paged",
                    page_size=self.page_size, num_pages=self.num_pages,
                    kv_dtype=self.kv_dtype)
                st.pool.pop("block_tables")  # the manager owns the mapping
                st.bt_dev = st.mgr.device_tables()
                st.cache = None
            else:
                st.cache = self.model.init_cache(self.slots, self.max_seq)
            st.pos = jnp.zeros((self.slots,), jnp.int32)
            st.tok = jnp.zeros((self.slots,), jnp.int32)
            st.remaining = jnp.zeros((self.slots,), jnp.int32)
            st.uids = jnp.zeros((self.slots,), jnp.int32)
            st.zero_mask = jnp.zeros((self.slots,), jnp.bool_)
            st.slot_pos = [0] * self.slots    # host mirror (no device sync)
            st.plans.clear()
            st.prefilling.clear()
            st.gate_block = None
            if self.spec_k > 1:
                st.draft_cache = self.draft_model.init_cache(self.slots,
                                                             self.max_seq)
                st.spec_mask = jnp.zeros((self.slots,), jnp.bool_)

    # ------------------------------------------------------- fault plumbing
    def _apply_round_faults(self, st: "_SchedState", poison: bool = True):
        """Injections that land at round boundaries: cancels, forced
        deadline expiries, and page corruption (NaN-poisoning a live
        physical page — the corruption then surfaces as non-finite logits
        in whichever slot reads it, driving the same quarantine real
        corruption would).  The cancel/expiry halves only build uid sets
        — commit-invariant, safe in the overlap gap; page poison reads
        the manager's owned set, which a commit changes via release, so
        the pipelined round defers it (``poison=False``) to
        :meth:`_apply_poison_faults` after the commit barrier."""
        fs = st.faults
        if fs is None:
            return
        for uid in fs.cancels_at(st.rnd):
            self._cancel_uids.add(uid)
        for uid in fs.deadline_expiries_at(st.rnd):
            st.forced_expired.add(uid)
        if poison:
            self._apply_poison_faults(st)

    def _apply_poison_faults(self, st: "_SchedState"):
        """Page-corruption injections for this round (the commit-
        dependent half of :meth:`_apply_round_faults`)."""
        fs = st.faults
        if fs is None:
            return
        for f in fs.corruptions_at(st.rnd):
            if st.mgr is None or st.pool is None:
                continue
            mapped = sorted({p for owned in st.mgr.owned for p in owned})
            page = fs.corruption_target(f, st.rnd, mapped)
            if page is None or not 0 < page < self.num_pages:
                continue
            st.pool = poison_pages(st.pool,
                                   jnp.asarray([page], jnp.int32))

    def _expired(self, st: "_SchedState", req: Request,
                 now_ms: float) -> Optional[str]:
        """Why this request's deadline is up (None if it is not).
        Deadlines run from the request's own enqueue time — zero for the
        closed-loop serve(), the arrival timestamp for open-loop
        submissions."""
        if req.uid in st.forced_expired:
            return "deadline"
        age_ms = now_ms - st.stats[req.uid]["enqueued_s"] * 1e3
        if req.deadline_ms is not None and age_ms > req.deadline_ms:
            return "deadline"
        if (req.ttft_deadline_ms is not None and age_ms > req.ttft_deadline_ms
                and "first_token_s" not in st.stats[req.uid]):
            return "ttft_deadline"
        return None

    def _expire_and_cancel(self, st: "_SchedState", scope: str = "all"):
        """Terminal-ize cancelled and deadline-expired requests, queued
        and live alike; a live victim's slot frees immediately.  The
        pipelined round splits the sweep: ``scope="queued"`` (the
        waiting queue — commit-invariant, runs in the overlap gap) and
        ``scope="held"`` (live + mid-prefill slots — a commit can free
        or fail them, so this half runs after the commit barrier)."""
        if not (self._cancel_uids or st.forced_expired or st.has_deadlines):
            return
        now_ms = (time.perf_counter() - st.t0) * 1e3
        if scope in ("all", "queued"):
            keep: deque = deque()
            while st.queue:
                req = st.queue.popleft()
                why = self._expired(st, req, now_ms)
                if req.uid in self._cancel_uids:
                    self._terminal(st, req, STATUS_CANCELLED,
                                   reason="cancelled")
                elif why is not None:
                    self._terminal(st, req, STATUS_TIMEOUT, reason=why)
                else:
                    keep.append(req)
            st.queue = keep
        if scope == "queued":
            return
        for slot in list(st.live):
            req = st.live[slot]
            why = self._expired(st, req, now_ms)
            if req.uid in self._cancel_uids:
                self._terminal(st, req, STATUS_CANCELLED, slot=slot,
                               reason="cancelled")
            elif why is not None:
                self._terminal(st, req, STATUS_TIMEOUT, slot=slot,
                               reason=why)
        for slot in list(st.prefilling):
            req = st.prefilling[slot].req
            why = self._expired(st, req, now_ms)
            if req.uid in self._cancel_uids:
                self._terminal(st, req, STATUS_CANCELLED, slot=slot,
                               reason="cancelled")
            elif why is not None:
                self._terminal(st, req, STATUS_TIMEOUT, slot=slot,
                               reason=why)

    def _fault_mask(self, st: "_SchedState", uids: List[Optional[int]]):
        """(slots,) bool device mask over live rows matching the targeted
        uids (None targets every live row)."""
        if not uids:
            return st.zero_mask
        mask = np.zeros((self.slots,), bool)
        for slot, req in st.live.items():
            if any(u is None or u == req.uid for u in uids):
                mask[slot] = True
        return jnp.asarray(mask)

    def _nan_mask(self, st: "_SchedState"):
        fs = st.faults
        if fs is None:
            return st.zero_mask
        return self._fault_mask(st, fs.nan_uids(st.rnd))

    def _collapse_mask(self, st: "_SchedState"):
        fs = st.faults
        if fs is None:
            return st.zero_mask
        return self._fault_mask(st, fs.collapse_uids(st.rnd))

    # ------------------------------------------------------------ recovery
    def _recover(self, st: "_SchedState", exc: Exception):
        """Step-restart recovery: release everything, requeue every live
        request with its generated prefix folded into its prompt (one
        retry charged; budget exhausted -> FAILED), and rebuild the
        manager + device pool from scratch.  The wholesale rebuild is
        deliberate: after an arbitrary mid-step exception the pool, the
        donated device buffers, and the prefix index cannot be trusted to
        agree, and a stale index pointing into a reinitialized pool would
        serve zeroed K/V as if it were cached prefix.  An injected
        kernel-backend failure additionally degrades the engine onto the
        chunked-jnp SW path before the replay; other injected faults (hard
        OOM) restart on the same backends."""
        st.recoveries += 1
        self.recoveries += 1
        if isinstance(exc, KernelBackendError):
            self._degrade_to_sw()
        now = time.perf_counter() - st.t0
        held = {**st.live, **{s: cs.req for s, cs in st.prefilling.items()}}
        st.live.clear()
        st.prefilling.clear()
        for slot in sorted(held, key=lambda s: st.admit_seq[s],
                           reverse=True):
            req = held[slot]
            s = st.stats[req.uid]
            if s["retries"] >= req.max_retries:
                s["status"] = STATUS_FAILED
                s["reason"] = (f"retries exhausted after "
                               f"{type(exc).__name__}: {exc}")
                s["finished_s"] = now
                s["tokens"] = len(req.generated or [])
                st.spec_hist.pop(req.uid, None)
                continue
            s["retries"] += 1
            resume = dataclasses.replace(
                req, prompt=list(req.prompt) + req.generated[req.folded:],
                folded=len(req.generated))
            st.resumed.add(id(resume))
            st.queue.appendleft(resume)
        self._init_mgr(st)
        self._init_device(st)

    def _degrade_to_sw(self):
        """Kernel -> SW fallback: rebuild the model on the chunked-jnp
        decode/attention backends and re-jit every step function.  The
        params are untouched and sampling keys are model-independent, so
        outputs stay bit-identical where both paths are exact — the
        paper's HW/SW interchangeability exercised as a runtime policy."""
        if self.backend_degraded:
            return
        from repro.models.lm import Model

        m = self.model
        self.model = Model(m.cfg, wf=m.wf, chunk_q=m.chunk_q, remat=m.remat,
                           param_dtype=m.param_dtype,
                           compute_dtype=m.compute_dtype,
                           act_sharding=m.act_sharding,
                           remat_policy=m.remat_policy,
                           decode_backend="jnp", attn_backend="jnp")
        self.verify_backend = "jnp"
        self._build_steps()
        self.backend_degraded = True

    def _abort(self, st: "_SchedState", exc: BaseException):
        """Unwind on an escaping exception: release every live slot, mark
        everything still in flight FAILED, and leave last_stats /
        last_pool_stats consistent (the allocator must audit clean — the
        regression tests assert it)."""
        # discard, don't commit: the exception may be a device fault and
        # the fetch could raise again — the session is over either way
        st.pending = None
        st.pending_swaps.clear()
        for slot in list(st.live):
            self._terminal(st, st.live[slot], STATUS_FAILED, slot=slot,
                           reason=f"aborted: {type(exc).__name__}: {exc}")
        for slot in list(st.prefilling):
            self._terminal(st, st.prefilling[slot].req, STATUS_FAILED,
                           slot=slot,
                           reason=f"aborted: {type(exc).__name__}: {exc}")
        while st.queue:
            self._terminal(st, st.queue.popleft(), STATUS_FAILED,
                           reason=f"aborted: {type(exc).__name__}: {exc}")
        st.stats["stragglers"] = st.stragglers
        self._attach_observability(st)
        if st.mgr is not None:
            st.mgr.allocator.fault_hook = None  # audit/stats must not trip
            self.last_pool_stats = st.mgr.stats()

    # --------------------------------------------------------------- steps
    def _timed_step(self, st: "_SchedState"):
        """One decode step under the watchdog, dispatch and commit
        back-to-back — the serial path.  The pipelined driver calls the
        same two halves with a round of host work in between."""
        pending = self._timed_dispatch(st)
        self._timed_commit(st, pending)

    def _timed_dispatch(self, st: "_SchedState") -> PendingRound:
        """Issue one decode step: injected kernel faults and straggler
        stalls land here (keyed to the dispatching round, exactly like
        the serial path).  Returns the in-flight round; the watchdog
        clock starts now and stops at commit."""
        fs = st.faults
        sleep = 0.0
        if fs is not None:
            f = fs.kernel_at(st.rnd)
            if f is not None:
                raise KernelBackendError(
                    f"injected kernel-backend failure at round {st.rnd}",
                    fatal=f.fatal)
            sleep = fs.straggler_sleep(st.rnd)
        with span(st, "serve.dispatch"):
            t_start = time.perf_counter()
            if sleep:
                time.sleep(sleep)
            pending = (self._dispatch_spec(st) if self.spec_k > 1
                       else self._dispatch_step(st))
        pending.t_start = t_start
        pending.live_before = len(pending.live)
        return pending

    def _timed_commit(self, st: "_SchedState", pending: PendingRound):
        """Fetch + account one in-flight step.  Any step whose
        dispatch-to-commit wall time blows past ``straggler_factor`` x
        the recent median is recorded in ``last_stats['stragglers']``
        (the trainer's watchdog ported to the serve loop)."""
        self._commit_step(st, pending)
        dt = time.perf_counter() - pending.t_start
        window = st.durations[-self.straggler_window:]
        if len(window) >= 5:
            med = statistics.median(window)
            if dt > self.straggler_factor * med:
                st.stragglers.append({
                    "step": st.step_no, "duration_s": dt, "median_s": med,
                    "live_slots": pending.live_before})
        st.durations.append(dt)
        st.step_no += 1

    def _dispatch_step(self, st: "_SchedState") -> PendingRound:
        """Launch one non-speculative decode step; every branch ends
        with the same device-side ``(tok, done, bad)`` triple and no
        host transfer — the single fetch site is :meth:`_commit_step`
        (the non-fused fallback used to fetch the tuple piecewise)."""
        needed = max(st.slot_pos[s] for s in st.live) + 1
        attend = self._attend_len(needed)
        self._count_decode(st, 1, attend if self.fused else self.max_seq)
        nan_mask = self._nan_mask(st)
        if self.fused and st.mgr is not None:
            self._upload_tables(st)
            (st.pool, st.tok, st.pos, st.remaining, done,
             bad) = self._paged_step(
                self.params, st.pool, st.bt_dev, st.tok, st.pos,
                st.remaining, st.uids, nan_mask, attend)
        elif self.fused:
            (st.cache, st.tok, st.pos, st.remaining, done,
             bad) = self._fused_step(
                self.params, st.cache, st.tok, st.pos, st.remaining,
                st.uids, nan_mask, attend)
        else:
            logits, st.cache = self.decode_step(st.cache, st.tok, st.pos)
            logits = jnp.where(nan_mask[:, None],
                               jnp.asarray(jnp.nan, logits.dtype), logits)
            bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
            nxt = self._sample_at(logits, st.pos + 1, st.uids)
            st.pos = st.pos + 1
            st.remaining = st.remaining - 1
            st.tok = nxt
            done = (st.remaining <= 0) | (st.pos >= self.max_seq - 1)
        return PendingRound(arrays=(st.tok, done, bad), live=dict(st.live))

    def _dispatch_spec(self, st: "_SchedState") -> PendingRound:
        """Speculative twin of :meth:`_dispatch_step`: one dispatch
        proposes, verifies, and scores a 1..spec_k token window per live
        slot; the committed-prefix accounting and page retraction happen
        at commit."""
        t_w = self.spec_k
        needed = max(st.slot_pos[s] for s in st.live) + t_w
        attend = self._attend_len(needed)
        self._count_decode(st, t_w, attend)
        self._upload_tables(st)
        (st.pool, st.draft_cache, targets, commit, st.tok, st.pos,
         st.remaining, done, bad) = self._spec_step(
            self.params, self.draft_params, st.pool, st.draft_cache,
            st.bt_dev, st.tok, st.pos, st.remaining, st.uids, st.spec_mask,
            self._nan_mask(st), self._collapse_mask(st), attend)
        return PendingRound(arrays=(targets, commit, done, bad),
                            live=dict(st.live), spec=True)

    def _count_decode(self, st: "_SchedState", window: int, grid: int):
        """Decode counters, where the step's shapes are chosen: the keys
        each live row needs (``slot_pos + window``) and the keys the
        attention kernel's grid visits at the ``grid``-token bucket
        (:meth:`_grid_tokens`)."""
        c = st.counters
        c["decode_steps"] += 1
        c["decode_ctx_tokens"] += sum(st.slot_pos[s] + window
                                      for s in st.live)
        c["decode_grid_tokens"] += self._grid_tokens(st, window, grid)

    def _grid_tokens(self, st: "_SchedState", window: int, grid: int):
        """Keys the attention kernel visits in one step.  Dense cache:
        every slot over the bucket.  Paged pool: each row's keys rounded
        up to the kernel's compute block of ``pages_per_block`` pages —
        a live row ``slot_pos + window``, a prefilling row (parked at the
        end of the sequence) the whole bucket, a free row nothing."""
        if st.mgr is None:
            return self.slots * grid
        cfg = self.model.cfg
        nb = cdiv(grid, self.page_size)
        block = self.page_size * pages_per_block(
            nb, self.page_size, window * (cfg.n_heads // cfg.n_kv_heads),
            cfg.n_kv_heads, cfg.d_head, st.pool["k_pages"].dtype.itemsize)
        keys = [st.slot_pos[s] + window for s in st.live]
        keys += [grid] * len(st.prefilling)
        return sum(_round_up(min(k, nb * self.page_size), block)
                   for k in keys)

    def _count_prefill(self, st: "_SchedState", n: int, start: int = 0):
        """Prefill counters: ``n`` uncached prompt tokens computed after
        ``start`` cached ones, and the keys their causal rows attend."""
        c = st.counters
        c["prefill_tokens"] += n
        c["prefill_causal_keys"] += n * start + n * (n + 1) // 2

    def _upload_tables(self, st: "_SchedState"):
        """Upload the block tables if the allocator changed them."""
        if st.mgr.dirty:
            with span(st, "serve.tables"):
                st.bt_dev = st.mgr.device_tables()

    def _step(self, st: "_SchedState"):
        """Serial dispatch + commit in one call (kept for direct
        callers; the round drivers go through the timed halves)."""
        pending = (self._dispatch_spec(st) if self.spec_k > 1
                   else self._dispatch_step(st))
        self._commit_step(st, pending)

    def _commit_step(self, st: "_SchedState", pending: PendingRound):
        """The one host transfer per step — slot-count ints + flags (a
        candidate window per slot when speculative) — then per-slot
        token/terminal accounting over the slots that were live at
        dispatch."""
        if pending.spec:
            return self._commit_spec(st, pending)
        with span(st, "serve.fetch"):
            nxt_h, done_h, bad_h = jax.device_get(pending.arrays)
        with span(st, "serve.commit"):
            now = time.perf_counter() - st.t0
            for slot, req in list(pending.live.items()):
                if bool(bad_h[slot]):
                    # NaN quarantine: fail the offending request only —
                    # no token appended, the rest of the batch commits
                    self._terminal(st, req, STATUS_FAILED, slot=slot,
                                   reason="nan-logits")
                    continue
                req.generated.append(int(nxt_h[slot]))
                st.slot_pos[slot] += 1
                self._record_tbt(st, req.uid, now, 1)
                if bool(done_h[slot]):
                    self._finish(st, slot, now)

    def _commit_spec(self, st: "_SchedState", pending: PendingRound):
        """Commit half of a speculative window: append the committed
        prefix, then retract pages holding only rejected rows (table
        edit)."""
        with span(st, "serve.fetch"):
            targets_h, commit_h, done_h, bad_h = jax.device_get(
                pending.arrays)
        with span(st, "serve.commit"):
            now = time.perf_counter() - st.t0
            for slot, req in list(pending.live.items()):
                if bool(bad_h[slot]):
                    self._terminal(st, req, STATUS_FAILED, slot=slot,
                                   reason="nan-logits")
                    continue
                c = int(commit_h[slot])
                req.generated.extend(int(x) for x in targets_h[slot, :c])
                st.slot_pos[slot] += c
                self._record_tbt(st, req.uid, now, c)
                s = st.stats[req.uid]
                s["spec_steps"] = s.get("spec_steps", 0) + 1
                s["spec_tokens"] = s.get("spec_tokens", 0) + c
                self._spec_governor(st, slot, req, c)
                if bool(done_h[slot]):
                    self._finish(st, slot, now)
                else:
                    # write-then-retract: pages mapped for the window
                    # whose rows were all rejected go back to the
                    # allocator
                    st.mgr.retract_above(slot, st.slot_pos[slot])
            self._spec_cooldown_tick(st)

    def _spec_governor(self, st: "_SchedState", slot: int, req: Request,
                       committed: int):
        """Per-request acceptance governor: when a spec-active request's
        last ``spec_disable_window`` windows averaged <= 1 committed
        token, its draft is wasted work — disable speculation for that
        request (it rides the batch committing 1 token/step, exactly like
        spec=False) and re-enable after ``spec_cooldown`` windows."""
        if not (req.spec and req.uid not in st.spec_disabled):
            return
        hist = st.spec_hist.setdefault(
            req.uid, deque(maxlen=self.spec_disable_window))
        hist.append(committed)
        if len(hist) == self.spec_disable_window and sum(hist) <= len(hist):
            st.spec_mask = st.spec_mask.at[slot].set(False)
            st.spec_disabled[req.uid] = self.spec_cooldown
            s = st.stats[req.uid]
            s["spec_auto_disables"] = s.get("spec_auto_disables", 0) + 1
            hist.clear()

    def _spec_cooldown_tick(self, st: "_SchedState"):
        """Advance auto-disable cooldowns; expired ones re-arm their
        request's speculative flag (if it is still live)."""
        for uid in list(st.spec_disabled):
            st.spec_disabled[uid] -= 1
            if st.spec_disabled[uid] <= 0:
                del st.spec_disabled[uid]
                for slot, req in st.live.items():
                    if req.uid == uid and req.spec:
                        st.spec_mask = st.spec_mask.at[slot].set(True)

    def _record_tbt(self, st: "_SchedState", uid: int, now: float,
                    committed: int):
        """Time-between-tokens samples for ``committed`` tokens delivered
        at ``now``: one real gap since the last emission, plus a zero per
        extra token — a speculative window lands its whole burst at once,
        and the samples should say so."""
        if committed <= 0:
            return
        last = st.last_emit.get(uid)
        if last is not None:
            st.tbt.append(now - last)
            if committed > 1:
                st.tbt.extend([0.0] * (committed - 1))
        st.last_emit[uid] = now

    def _finish(self, st: "_SchedState", slot: int, now: float):
        req = st.live.pop(slot)
        st.results[req.uid] = req.generated
        if st.mgr is not None:
            st.mgr.release(slot)
        s = st.stats[req.uid]
        s["status"] = STATUS_OK
        s["finished_s"] = now
        s["tokens"] = len(req.generated)
        st.spec_hist.pop(req.uid, None)
        st.last_emit.pop(req.uid, None)
        n = len(req.generated)
        # steady-state decode rate: tokens after the first over the decode
        # interval only — admit->first-token (queueing + prefill) is
        # reported separately so a long prompt cannot masquerade as slow
        # decode.  e2e_tok_s keeps the old conflated number.
        decode_wall = max(now - s["first_token_s"], 1e-9)
        s["tok_s"] = (n - 1) / decode_wall if n > 1 else 0.0
        s["e2e_tok_s"] = n / max(now - s["admitted_s"], 1e-9)
        if s.get("spec_steps"):
            # mean committed tokens per window (1..spec_k); spec_k amortizes
            # dispatch overhead by exactly this factor
            s["accept_rate"] = s["spec_tokens"] / s["spec_steps"]

    def _terminal(self, st: "_SchedState", req: Request, status: str, *,
                  slot: Optional[int] = None, reason: Optional[str] = None):
        """Non-OK terminal transition (idempotent): record status/reason,
        free the slot if the request was live.  Partial output is
        discarded — only OK requests appear in the returned dict."""
        s = st.stats[req.uid]
        if s.get("status") is not None:
            return
        s["status"] = status
        if reason:
            s["reason"] = reason
        s["finished_s"] = time.perf_counter() - st.t0
        s["tokens"] = len(req.generated or [])
        st.spec_hist.pop(req.uid, None)
        st.last_emit.pop(req.uid, None)
        st.swaps.pop(req.uid, None)  # host snapshot of a dead request
        if slot is not None:
            st.live.pop(slot, None)
            st.prefilling.pop(slot, None)
            if st.mgr is not None:
                st.mgr.release(slot)

    # ------------------------------------------------------------ admission
    def _bookkeep_admit(self, st: "_SchedState", slot: int, req: Request,
                        t_admit: float):
        """Per-request admission bookkeeping, shared by both admission
        paths — they must stay behaviorally identical (the sharing-on ==
        sharing-off parity guarantee rides on it)."""
        # only a preemption/recovery resume (this serve) keeps its
        # generated prefix; re-serving the same Request objects starts
        # fresh
        if id(req) not in st.resumed:
            req.generated = []
        st.live[slot] = req
        st.admit_seq[slot] = st.next_seq
        st.next_seq += 1
        st.slot_pos[slot] = len(req.prompt)
        # first admission only — a resume keeps its original timestamp
        st.stats[req.uid].setdefault("admitted_s", t_admit)

    def _finish_admission(self, st: "_SchedState", slot: int, req: Request):
        """First-token timing + immediate completion of budgets the
        admission sample already exhausted (a decode step would overrun
        them).  No-op when prefill already quarantined the request."""
        if st.stats[req.uid].get("status") is not None:
            return
        now = time.perf_counter() - st.t0
        s = st.stats[req.uid]
        s.setdefault("first_token_s", now)
        s["admit_to_first_s"] = s["first_token_s"] - s["admitted_s"]
        # TBT clock starts at the first token; a resume keeps its last
        # emission so the preemption stall shows up as one honest gap
        st.last_emit.setdefault(req.uid, s["first_token_s"])
        if req.max_new_tokens - len(req.generated) <= 0:
            self._finish(st, slot, now)

    def _next_candidate(self, st: "_SchedState") -> Request:
        """Admission order: lowest priority class first, then arrival —
        equal-priority traffic keeps the legacy FIFO order exactly
        (head-of-line blocking keeps admission deterministic)."""
        return min(st.queue, key=lambda r: (r.priority, st.arrival[r.uid]))

    def _headroom(self, st: "_SchedState", extra: int) -> int:
        """Pages the admission gate must leave free: one growth page per
        running (and just-taken) slot so admission never hands out the
        pages an older sequence needs at the next boundary, plus the
        ``free_page_watermark`` reserve whenever anything is running —
        never when the pool is idle, so a lone request always admits."""
        n = len(st.live) + len(st.prefilling) + extra
        if n and self.free_page_watermark > 0.0:
            n += int(np.ceil(self.free_page_watermark
                             * st.mgr.allocator.usable))
        return n

    def _admit(self, st: "_SchedState"):
        """Admit queued requests into free slots, priority-then-FIFO.
        Dense gating: a free slot.  Paged gating: a free slot and enough
        free pages for the prompt.  Under a ``prefill_budget``, at most
        that many prompt tokens prefill per round (in-flight chunked
        prompts advance first), and prompts longer than one chunk admit
        through the chunked path."""
        used = self._advance_prefilling(st)
        budget = self.prefill_budget
        taken: List[tuple] = []
        for slot in range(self.slots):
            if slot in st.live or slot in st.prefilling or not st.queue:
                continue
            if budget is not None and used >= budget and (
                    st.live or st.prefilling or taken):
                break  # budget spent; progress guaranteed when idle
            req = self._next_candidate(st)
            if st.mgr is not None and req.uid in st.swaps:
                # a host-swapped resume restores its pages instead of
                # prefilling; blocked exactly like a too-big prompt
                if not self._admit_swapped_row(st, slot, req):
                    break
                continue
            if st.mgr is not None:
                if not st.mgr.can_admit(len(req.prompt),
                                        headroom=self._headroom(
                                            st, len(taken))):
                    break
                if self._chunkable(req):
                    # map the whole prompt now; write it one chunk per
                    # round, interleaved with everyone else's decode
                    if st.mgr.admit(slot, len(req.prompt)) is None:
                        break
                    st.queue.remove(req)
                    self._bookkeep_chunked(st, slot, req)
                    used += self._prefill_chunk(st, slot)
                    continue
                if st.mgr.admit(slot, len(req.prompt)) is None:
                    break  # denied at alloc (injected OOM) despite the gate
            st.queue.remove(req)
            taken.append((slot, req))
            used += len(req.prompt)
        if not taken:
            self._park_prefilling(st)
            return
        t_admit = time.perf_counter() - st.t0
        for slot, req in taken:
            self._bookkeep_admit(st, slot, req, t_admit)
        batched = (self.fused and
                   self.model.cfg.family in _PADDED_PREFILL_FAMILIES)
        if batched:
            groups = [taken]
        else:
            groups = [[t] for t in taken]
        for group in groups:
            self._prefill_group(st, group)
        for slot, req in taken:
            self._finish_admission(st, slot, req)
        self._park_prefilling(st)

    # ----------------------------------------------------- chunked prefill
    def _chunkable(self, req: Request) -> bool:
        return (self._chunked_capable()
                and len(req.prompt) > self._chunk_tokens)

    def _bookkeep_chunked(self, st: "_SchedState", slot: int, req: Request):
        """Admission bookkeeping for a chunked prompt: the slot is
        reserved (pages mapped, admit_seq assigned) but not live — it
        joins the decode batch when its last chunk commits."""
        if id(req) not in st.resumed:
            req.generated = []
        st.prefilling[slot] = _ChunkState(req)
        st.admit_seq[slot] = st.next_seq
        st.next_seq += 1
        st.slot_pos[slot] = len(req.prompt)
        st.stats[req.uid].setdefault("admitted_s",
                                     time.perf_counter() - st.t0)

    def _advance_prefilling(self, st: "_SchedState") -> int:
        """One chunk per in-flight chunked prompt, slot order, until the
        round's budget is spent (the first always advances — a budget
        smaller than a chunk must not stall the pipeline).  Returns
        prompt tokens written."""
        used = 0
        for slot in sorted(st.prefilling):
            if self.prefill_budget is not None and used >= self.prefill_budget:
                break
            used += self._prefill_chunk(st, slot)
        return used

    def _prefill_chunk(self, st: "_SchedState", slot: int) -> int:
        """Write the next prompt chunk through the slot's block tables
        (the chunk is the 'suffix' of the chunks already resident — the
        prefix-sharing suffix path re-aimed at admission).  The final
        chunk's logits sample the first token, exactly like a one-shot
        prefill; mid-chunks are whole prompt_block buckets, so their
        logits are discarded and no padding is computed."""
        cs = st.prefilling[slot]
        req = cs.req
        chunk = req.prompt[cs.done:cs.done + self._chunk_tokens]
        final = cs.done + len(chunk) >= len(req.prompt)
        t_b = _round_up(len(chunk), self.prompt_block)
        toks = np.zeros((1, t_b), np.int32)
        toks[0, :len(chunk)] = chunk
        attend = self._attend_len(cs.done + t_b)
        self._upload_tables(st)
        self._count_prefill(st, len(chunk), cs.done)
        with span(st, "serve.prefill", uid=req.uid, tokens=len(chunk),
                  bucket=t_b):
            logits, st.pool = self._suffix_prefill(
                self.params, st.pool, st.bt_dev[slot:slot + 1],
                jnp.asarray(toks), jnp.asarray([cs.done], jnp.int32),
                jnp.asarray([len(chunk) - 1], jnp.int32), attend)
        cs.done += len(chunk)
        s = st.stats[req.uid]
        s["prefill_chunks"] = s.get("prefill_chunks", 0) + 1
        if final:
            del st.prefilling[slot]
            st.live[slot] = req
            self._commit_prefill(st, [slot], [req], logits)
            self._finish_admission(st, slot, req)
        return len(chunk)

    def _park_prefilling(self, st: "_SchedState"):
        """Pin still-prefilling slots out of the decode step's way:
        position ``max_seq - 1`` clamps the row's bogus K/V write to a
        fixed location that is never read before being overwritten, and
        a huge ``remaining`` keeps its done flag meaningless.  Re-applied
        every admission round because the step advances pos."""
        if not st.prefilling:
            return
        idx = jnp.asarray(sorted(st.prefilling), jnp.int32)
        st.pos = st.pos.at[idx].set(self.max_seq - 1)
        st.remaining = st.remaining.at[idx].set(1 << 30)

    def _admit_shared(self, st: "_SchedState"):
        """Prefix-sharing admission: requests admit *sequentially* — each
        prompt's prefill publishes its full pages to the index before the
        next request is planned, so N identical prompts arriving together
        share pages with each other, not just with earlier traffic.  The
        gate charges only the plan's private pages (the shared prefix is
        already resident), which admits strictly more requests from the
        same pool.

        A ``prefill_budget`` charges by *un-cached suffix* tokens — the
        tokens this admission actually prefills.  A warm prefix admits
        nearly free while a cold prompt spends the round's budget, so
        under load, prefix locality shows up directly in admit-to-first-
        token latency (the signal a cache-aware router banks on).  Swap
        resumes charge nothing: they restore pages, not prefill them."""
        used = 0
        budget = self.prefill_budget
        for slot in range(self.slots):
            if slot in st.live or not st.queue:
                continue
            if budget is not None and used >= budget and (
                    st.live or used):
                break  # budget spent; progress guaranteed when idle
            req = self._next_candidate(st)
            if req.uid in st.swaps:
                # swap resumes bypass the prefix planner: their pages are
                # restored verbatim, private, outside the sharing graph
                if not self._admit_swapped_row(st, slot, req):
                    break
                continue
            # replan the blocked queue head only when the allocator or the
            # index changed since its gate last failed: the gate is a pure
            # function of that state, and replanning every decode step
            # would both waste O(prompt + index) host work per token and
            # keep refreshing the blocked prompt's LRU stamps (skewing
            # eviction toward other, possibly hot, entries).  Under fault
            # injection the gate is additionally a function of the round
            # (the OOM hook), so the key must not outlive it.
            a = st.mgr.allocator
            key = (id(req), a.alloc_count, a.release_count, a.share_count,
                   st.mgr.index.version,
                   st.rnd if st.faults is not None else None)
            if st.gate_block == key:
                break
            with span(st, "serve.prefix_plan"):
                plan = st.mgr.plan_admit(req.prompt)
            if (not st.mgr.can_admit_plan(plan,
                                          headroom=self._headroom(st, 0))
                    or st.mgr.admit_prefix(slot, plan) is None):
                st.gate_block = key
                break
            st.gate_block = None
            st.queue.remove(req)
            used += len(req.prompt) - plan.cached_tokens
            self._bookkeep_admit(st, slot, req,
                                 time.perf_counter() - st.t0)
            # first-admission figure (a preemption resume re-matches its
            # own folded prompt, which would double-count the reuse)
            st.stats[req.uid].setdefault("cached_prefix_tokens",
                                         plan.cached_tokens)
            st.plans[slot] = plan
            self._prefill_group(st, [(slot, req)])
            if st.stats[req.uid].get("status") is None:
                # a quarantined prefill released the slot — its (trash)
                # table rows must not be published as cached prefix
                st.mgr.register_prefix(slot, req.prompt)
            self._finish_admission(st, slot, req)

    def _prefill_suffix_row(self, st: "_SchedState", slot: int,
                            req: Request, plan: AdmitPlan):
        """Admission prefill for a prefix-index hit: fork the boundary
        page if the plan calls for copy-on-write, then compute only the
        un-cached suffix through the paged cache (bucketed window — the
        shared prefix is read through the block tables, never copied)."""
        if plan.cow_src is not None:
            with span(st, "serve.scatter"):
                st.pool = copy_pages(
                    st.pool, jnp.asarray([plan.cow_src], jnp.int32),
                    jnp.asarray([plan.cow_dst], jnp.int32))
        st.mgr.cow_release(plan)  # the fork-source pin outlives the copy
        suffix = req.prompt[plan.cached_tokens:]
        t_b = _round_up(len(suffix), self.prompt_block)
        toks = np.zeros((1, t_b), np.int32)
        toks[0, :len(suffix)] = suffix
        attend = self._attend_len(plan.cached_tokens + t_b)
        self._upload_tables(st)
        self._count_prefill(st, len(suffix), plan.cached_tokens)
        with span(st, "serve.prefill", uid=req.uid, tokens=len(suffix),
                  bucket=t_b):
            logits, st.pool = self._suffix_prefill(
                self.params, st.pool, st.bt_dev[slot:slot + 1],
                jnp.asarray(toks),
                jnp.asarray([plan.cached_tokens], jnp.int32),
                jnp.asarray([len(suffix) - 1], jnp.int32), attend)
        if self.spec_k > 1:
            # the draft cache is a dense slot pool with no sharing: it
            # prefills the full prompt (draft quality only affects the
            # acceptance rate, never output values)
            full_b = min(self.max_seq,
                         _round_up(len(req.prompt), self.prompt_block))
            full = np.zeros((1, full_b), np.int32)
            full[0, :len(req.prompt)] = req.prompt
            _, dcache = self._draft_prefill(
                self.draft_params, {"tokens": jnp.asarray(full)},
                jnp.asarray([len(req.prompt) - 1], jnp.int32))
            st.draft_cache = write_slot(st.draft_cache, dcache, slot)
        self._commit_prefill(st, [slot], [req], logits)

    def _commit_prefill(self, st: "_SchedState", slots: List[int],
                        reqs: List[Request], logits):
        """Post-prefill slot-state commit, shared by the full and the
        suffix admission prefills (one implementation keeps the two paths
        behaviorally identical): sample each row's first token at
        position ``len(prompt)`` with its (uid, position) key, scatter
        pos/tok/remaining/uids (+ spec flags) into the slot state, and
        append the sampled token.  Rows whose prefill logits are
        non-finite (numerical blowup, corrupted shared prefix) are
        quarantined here — same guard as the decode steps."""
        lens = [len(r.prompt) for r in reqs]
        first = self._sample_at(logits, jnp.asarray(lens, jnp.int32),
                                jnp.asarray([r.uid for r in reqs],
                                            jnp.int32))
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        with span(st, "serve.prefill_fetch"):
            first_h, finite_h = jax.device_get((first, finite))
        st.counters["admissions"] += len(reqs)
        slot_idx = jnp.asarray(slots, jnp.int32)
        st.pos = st.pos.at[slot_idx].set(jnp.asarray(lens, jnp.int32))
        st.tok = st.tok.at[slot_idx].set(first)
        st.remaining = st.remaining.at[slot_idx].set(jnp.asarray(
            [r.max_new_tokens - len(r.generated) - 1 for r in reqs],
            jnp.int32))
        st.uids = st.uids.at[slot_idx].set(jnp.asarray(
            [r.uid for r in reqs], jnp.int32))
        if self.spec_k > 1:
            st.spec_mask = st.spec_mask.at[slot_idx].set(jnp.asarray(
                [bool(getattr(r, "spec", True))
                 and r.uid not in st.spec_disabled for r in reqs]))
        for slot, req, f, ok in zip(slots, reqs, first_h, finite_h):
            if not bool(ok):
                self._terminal(st, req, STATUS_FAILED, slot=slot,
                               reason="nan-logits")
                continue
            req.generated.append(int(f))

    def _prefill_group(self, st: "_SchedState", group: List[tuple]):
        """One prefill for k admitted (slot, request) pairs: bucketed
        right-padding + exact per-slot last-token logits (last_pos gather
        inside the model), then the layout-specific cache write."""
        if self.prefix_sharing and len(group) == 1:
            plan = st.plans.pop(group[0][0], None)
            if plan is not None and plan.cached_tokens > 0:
                return self._prefill_suffix_row(st, group[0][0],
                                                group[0][1], plan)
            if plan is not None:
                st.mgr.cow_release(plan)  # no-op unless the plan forked
        slots = [s for s, _ in group]
        reqs = [r for _, r in group]
        lens = [len(r.prompt) for r in reqs]
        if self.model.cfg.family in _PADDED_PREFILL_FAMILIES:
            bucket = min(self.max_seq,
                         _round_up(max(lens), self.prompt_block))
        else:
            # right-padding perturbs recurrent state / MoE capacity;
            # these families admit one request at its exact length
            bucket = max(lens)
        toks = np.zeros((len(reqs), bucket), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :lens[i]] = r.prompt
        last_pos = jnp.asarray([l - 1 for l in lens], jnp.int32)
        for n in lens:
            self._count_prefill(st, n)
        prefill = span(st, "serve.prefill",
                       uid=" ".join(str(r.uid) for r in reqs),
                       tokens=sum(lens), bucket=bucket)
        if st.mgr is not None:
            with prefill:
                logits, pcache = self._prefill_bucket(
                    self.params, {"tokens": jnp.asarray(toks)}, last_pos)
            n_blocks = cdiv(bucket, self.page_size)
            page_idx = np.stack([st.mgr.prefill_page_idx(s, n_blocks)
                                 for s in slots])
            with span(st, "serve.scatter"):
                st.pool = scatter_prefill(
                    st.pool, {"k": pcache["k"], "v": pcache["v"]},
                    jnp.asarray(page_idx))
        else:
            with prefill:
                logits, pcache = self._prefill_padded(
                    self.params, {"tokens": jnp.asarray(toks)}, last_pos)
            with span(st, "serve.scatter"):
                if len(group) == 1:
                    st.cache = write_slot(st.cache, pcache, slots[0])
                else:
                    st.cache = write_slots(st.cache, pcache,
                                           jnp.asarray(slots, jnp.int32))
        if self.spec_k > 1:
            # the draft proposes from its own cache: prefill it alongside
            # the target (same padded batch; draft logits are discarded —
            # the first committed token is the target's)
            _, dcache = self._draft_prefill(
                self.draft_params, {"tokens": jnp.asarray(toks)}, last_pos)
            if len(group) == 1:
                st.draft_cache = write_slot(st.draft_cache, dcache, slots[0])
            else:
                st.draft_cache = write_slots(
                    st.draft_cache, dcache, jnp.asarray(slots, jnp.int32))
        # the token sampled from prefill logits sits at position len(prompt)
        self._commit_prefill(st, slots, reqs, logits)

    # ----------------------------------------------------------- preemption
    def _grow_or_preempt(self, st: "_SchedState"):
        """Step boundary: every live slot's next write span must be
        mapped — one position for plain decode, ``spec_k`` for a
        speculative window (positions past ``max_seq`` need no page; their
        writes land in the trash).  Grow on demand; when the pool
        exhausts, preempt the newest request of the least-important
        class still holding a slot (LIFO within a class — the oldest
        always makes progress) and requeue it at the queue front with its
        generated tokens folded into its prompt."""
        with span(st, "serve.grow"):
            for slot in sorted(st.live, key=lambda s: st.admit_seq[s]):
                if slot not in st.live:
                    continue  # preempted while serving an older slot
                while slot in st.live:
                    first = st.slot_pos[slot]
                    if st.mgr.ensure_span(slot, first,
                                          first + self.spec_k - 1):
                        break
                    self._preempt(st, self._preempt_victim(st))

    def _slack_ms(self, st: "_SchedState", req: Request,
                  now_ms: float) -> float:
        """Remaining deadline slack in milliseconds (+inf for a request
        carrying no deadline): the minimum over its set deadlines,
        measured from the request's own enqueue time exactly like
        :meth:`_expired`."""
        dls = []
        age_ms = now_ms - st.stats[req.uid]["enqueued_s"] * 1e3
        if req.deadline_ms is not None:
            dls.append(req.deadline_ms - age_ms)
        if (req.ttft_deadline_ms is not None
                and "first_token_s" not in st.stats[req.uid]):
            dls.append(req.ttft_deadline_ms - age_ms)
        return min(dls) if dls else float("inf")

    def _preempt_victim(self, st: "_SchedState") -> int:
        """Most-slack first (deadline-aware: a request with no deadline,
        or the most time to spare, yields its slot before one about to
        miss), then the existing rule — newest of the least-important
        class, live or mid-chunked-prefill alike (an in-flight chunked
        prompt holds its whole page span — reclaiming it can unblock
        several decode slots).  Without deadlines in play every slack is
        +inf and the ordering reduces to the old rule bit-for-bit."""
        now_ms = (time.perf_counter() - st.t0) * 1e3

        def key(slot):
            req = (st.live[slot] if slot in st.live
                   else st.prefilling[slot].req)
            return (self._slack_ms(st, req, now_ms), req.priority,
                    st.admit_seq[slot])
        return max([*st.live, *st.prefilling], key=key)

    def _swap_wins(self, st: "_SchedState") -> bool:
        """Should this preemption take the swap tier?  Both resume costs
        are linear in the victim's resident tokens, so the policy is a
        static per-configuration comparison: host-transfer seconds per
        token (pool bytes per token over the link bandwidth) against
        recompute seconds per token (~2 * params FLOPs over the decode
        throughput).  The figures come from ``self.cost_model`` —
        defaults, an explicit model, or a construction-time
        microbenchmark under ``preempt_calibrate=True``."""
        if self.preempt == "requeue":
            return False
        if self.preempt == "swap":
            return True
        bytes_per_token = sum(leaf.nbytes for leaf in st.pool.values()) / (
            st.pool["k_pages"].shape[1] * self.page_size)
        return (bytes_per_token / self.cost_model.swap_gbps
                < 2.0 * self._n_params / self.cost_model.decode_flops_s)

    def _preempt(self, st: "_SchedState", slot: int):
        if slot in st.prefilling:
            # a mid-chunk prompt has no complete page image worth
            # snapshotting — chunked admissions always resume by recompute
            req = st.prefilling.pop(slot).req
            swap = False
        else:
            req = st.live.pop(slot)
            swap = self._swap_wins(st)
        if swap:
            # swap-tier resume: snapshot the slot's page contents (the
            # pages are sliced out before the release, so a same-round
            # admission cannot overwrite the snapshot), then restore into
            # fresh pages at re-admission — no recompute.  Pipelined, the
            # D2H materialization is deferred to the next commit boundary
            # (the device slice is issued now; JAX value semantics keep
            # the data alive) so a swap victim never stalls the next
            # dispatch; serial materializes it at once.
            handle = st.mgr.swap_out(slot, st.pool, st.slot_pos[slot],
                                     async_copy=True)
            st.pending_swaps.append(handle)
            if not self.pipeline:
                self._drain_swaps(st)
            st.swaps[req.uid] = handle
            s = st.stats[req.uid]
            s["swap_outs"] = s.get("swap_outs", 0) + 1
        else:
            st.mgr.release(slot)
        # recompute-style resume: re-prefilling prompt+generated recreates
        # the exact cache the slot held, so greedy output is unchanged and
        # (uid, position) sampling keys line up with the un-preempted run.
        # A swap resume rides the same folded copy (the queue entry and
        # the ledger stay identical across policies); admission just
        # restores its pages instead of prefilling them.
        # The caller's Request is not mutated — the resume rides a copy
        # (sharing the generated list, which is the accumulating output;
        # ``folded`` keeps a re-preempted resume from folding it twice).
        resume = dataclasses.replace(
            req, prompt=list(req.prompt) + req.generated[req.folded:],
            folded=len(req.generated))
        st.resumed.add(id(resume))
        st.queue.appendleft(resume)
        st.stats[req.uid]["preemptions"] += 1
        self.preemptions += 1

    # ------------------------------------------------------- swap admission
    def _admit_swapped_row(self, st: "_SchedState", slot: int,
                           req: Request) -> bool:
        """Resume a host-swapped request: map fresh private pages under
        the same headroom gate normal admission honors, scatter the saved
        page contents back, and rebuild the exact slot state the request
        held at preemption — no prefill, no sampling.  The preemption-
        pending token (``generated[-1]``, which folding placed at the
        resume prompt's last position) re-arms as ``tok`` at position
        ``handle.n_tokens``, so the next decode step replays precisely
        the step the preemption interrupted; the requeue path reaches the
        identical state by re-prefilling those positions instead.
        Returns False when the pool cannot grant the handle's pages yet
        (the caller blocks admission, exactly like a too-big prompt)."""
        handle = st.swaps[req.uid]
        if st.mgr.allocator.free - self._headroom(st, 0) < handle.n_blocks:
            return False
        pages = st.mgr.admit_swapped(slot, handle)
        if pages is None:
            return False  # denied at alloc (injected OOM) despite the gate
        del st.swaps[req.uid]
        st.queue.remove(req)
        st.pool = swap_in_pages(st.pool, handle.data,
                                jnp.asarray(pages, jnp.int32))
        self._bookkeep_admit(st, slot, req, time.perf_counter() - st.t0)
        n = handle.n_tokens
        st.slot_pos[slot] = n  # _bookkeep_admit assumed a full prefill
        st.pos = st.pos.at[slot].set(n)
        st.tok = st.tok.at[slot].set(int(req.prompt[-1]))
        # no token samples at a swap resume, so no -1 here: the requeue
        # path's prefill charges its sample against this same budget
        st.remaining = st.remaining.at[slot].set(
            req.max_new_tokens - len(req.generated))
        st.uids = st.uids.at[slot].set(req.uid)
        if self.spec_k > 1:
            st.spec_mask = st.spec_mask.at[slot].set(
                bool(req.spec) and req.uid not in st.spec_disabled)
            # the dense draft cache died with the slot: re-prefill it from
            # the folded prompt (draft state only steers acceptance, never
            # committed values — the window overwrites its own rows)
            full_b = min(self.max_seq,
                         _round_up(len(req.prompt), self.prompt_block))
            full = np.zeros((1, full_b), np.int32)
            full[0, :len(req.prompt)] = req.prompt
            _, dcache = self._draft_prefill(
                self.draft_params, {"tokens": jnp.asarray(full)},
                jnp.asarray([len(req.prompt) - 1], jnp.int32))
            st.draft_cache = write_slot(st.draft_cache, dcache, slot)
        s = st.stats[req.uid]
        s["swap_ins"] = s.get("swap_ins", 0) + 1
        self._finish_admission(st, slot, req)
        return True


@dataclasses.dataclass
class _ChunkState:
    """An admitted prompt mid-chunked-prefill: pages mapped, ``done``
    prompt tokens written, not yet in the decode batch."""
    req: Request
    done: int = 0


def _empty_timeseries() -> Dict[str, list]:
    # per round: one column per span of repro.serve.trace (host seconds
    # of that phase, e.g. dispatch_s, fetch_s, commit_s), overlap_s (the
    # sweep's seconds when it ran while a step was in flight; 0.0 when
    # serial), and one cumulative column per counter
    return {"t_s": [], "round": [], "queue_depth": [], "live_slots": [],
            "utilization": [], "free_pages": [], "overlap_s": [],
            **{column(name): [] for name in SPANS},
            **{name: [] for name in COUNTERS}}


@dataclasses.dataclass
class _SchedState:
    """Mutable per-session scheduler state (host-side bookkeeping)."""
    queue: deque
    mgr: Optional[PagedCacheManager]
    t0: float
    live: Dict[int, Request] = dataclasses.field(default_factory=dict)
    results: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    stats: Dict[Any, Any] = dataclasses.field(default_factory=dict)
    admit_seq: Dict[int, int] = dataclasses.field(default_factory=dict)
    next_seq: int = 0
    resumed: set = dataclasses.field(default_factory=set)
    slot_pos: List[int] = dataclasses.field(default_factory=list)
    plans: Dict[int, AdmitPlan] = dataclasses.field(default_factory=dict)
    gate_block: Any = None     # (req, allocator, index) state of the last
    #                            failed sharing-admission gate
    cache: Any = None          # dense layout
    pool: Any = None           # paged layout: {"k_pages", "v_pages"} plus
    #                            {"k_scales", "v_scales"} when quantized
    # host-swapped requests awaiting re-admission, keyed by uid.  The
    # handles record page *contents* in logical block order, not page
    # numbers, so they survive the wholesale pool rebuild of step-restart
    # recovery (the resume restores into whatever fresh pages it gets).
    swaps: Dict[int, SwapHandle] = dataclasses.field(default_factory=dict)
    bt_dev: Any = None         # paged layout: uploaded block tables
    pos: Any = None
    tok: Any = None
    remaining: Any = None
    uids: Any = None
    draft_cache: Any = None    # speculative decoding: dense draft slot pool
    spec_mask: Any = None      # speculative decoding: per-slot spec flag
    # ---- lifecycle / fault tolerance
    faults: Any = None         # FaultSchedule for this call (or None)
    prefill_only: bool = False  # cluster prefill role: admit, never decode
    rnd: int = -1              # scheduler round (fault-injection clock)
    step_no: int = 0           # decode steps actually dispatched
    recoveries: int = 0        # step restarts this serve()
    has_deadlines: bool = False
    forced_expired: set = dataclasses.field(default_factory=set)
    arrival: Dict[int, int] = dataclasses.field(default_factory=dict)
    zero_mask: Any = None      # cached all-false (slots,) injection mask
    seq_arrival: int = 0       # next arrival stamp (open-loop submissions)
    # ---- SLA-aware scheduling / observability
    prefilling: Dict[int, _ChunkState] = dataclasses.field(
        default_factory=dict)
    tbt: List[float] = dataclasses.field(default_factory=list)
    last_emit: Dict[int, float] = dataclasses.field(default_factory=dict)
    timeseries: Dict[str, list] = dataclasses.field(
        default_factory=_empty_timeseries)
    stragglers: List[dict] = dataclasses.field(default_factory=list)
    durations: List[float] = dataclasses.field(default_factory=list)
    spec_hist: Dict[int, deque] = dataclasses.field(default_factory=dict)
    spec_disabled: Dict[int, int] = dataclasses.field(default_factory=dict)
    # ---- overlapped round pipeline
    pending: Optional["PendingRound"] = None   # step in flight (dispatched,
    #                                            not yet committed)
    pending_swaps: List[SwapHandle] = dataclasses.field(
        default_factory=list)  # async swap-outs awaiting materialization
    overlapped: bool = False   # this round's sweep ran under a step
    # ---- spans and counters (repro.serve.trace)
    phase_s: Dict[str, float] = dataclasses.field(default_factory=new_phases)
    counters: Dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(COUNTERS, 0))
