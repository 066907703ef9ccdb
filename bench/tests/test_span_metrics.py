"""The reduction of the program's spans and the metrics that read the
program's spans and counters, on inputs with known answers, and once
end to end on the CPU at a tiny size."""

import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import run, spec, span_reduce, spans
from bench.families import dense_gqa
from bench.tests.test_harness import PEAK, SEED, tiny_cell
from bench.tests.test_trace_reduce import HOST, ev, small_trace
from bench.trace_reduce import reduce

US = 1e-6


def with_spans():
    """small_trace plus the engine's spans on the event loop's line
    (window 100..1100 us; device idle 250..600, 700..800, 1050..1100)."""
    return small_trace() + [
        ev(HOST, "python3", "serve.publish", 60, 50),       # clipped: 10
        ev(HOST, "python3", "serve.round", 120, 560),       # ..680
        ev(HOST, "python3", "serve.fetch", 200, 250),       # ..450
        ev(HOST, "python3", "serve.admit", 460, 190),       # ..650
        ev(HOST, "python3", "serve.prefill_fetch", 500, 140),   # ..640
        ev(HOST, "python3", "serve.round", 700, 300),       # ..1000
        ev(HOST, "python3", "serve.dispatch", 720, 40),     # ..760
        ev(HOST, "python3", "serve.ingest", 1020, 20),
        ev(HOST, "other thread", "serve.fetch", 260, 100),  # not the loop
    ]


def test_span_counts_seconds_and_self_time():
    s = span_reduce.reduce_spans(with_spans())
    want = {"serve.publish": (1, 10, 10), "serve.round": (2, 860, 380),
            "serve.fetch": (1, 250, 250), "serve.admit": (1, 190, 50),
            "serve.prefill_fetch": (1, 140, 140),
            "serve.dispatch": (1, 40, 40), "serve.ingest": (1, 20, 20)}
    assert set(s.spans) == set(want)
    for name, (n, secs, own) in want.items():
        assert s.spans[name][0] == n
        assert s.spans[name][1] == pytest.approx(secs * US)
        assert s.spans[name][2] == pytest.approx(own * US)


def test_idle_goes_to_the_innermost_span_and_sums_to_the_device_idle():
    s = span_reduce.reduce_spans(with_spans())
    r = reduce(with_spans())
    assert s.window_s == pytest.approx(r.window_s)
    assert s.idle_s == pytest.approx(r.window_s - r.busy_s)
    want = {"serve.fetch": 200, "serve.round": 70, "serve.admit": 40,
            "serve.prefill_fetch": 100, "serve.dispatch": 40,
            span_reduce.OUTSIDE: 50}
    assert s.idle_by_span == pytest.approx(
        {k: v * US for k, v in want.items()})
    assert sum(s.idle_by_span.values()) == pytest.approx(s.idle_s)
    assert s.fetch_idle_share() == pytest.approx(0.3)


def test_spans_leave_the_existing_reduction_as_it_was():
    old, new = reduce(small_trace()), reduce(with_spans())
    assert (new.window_s, new.busy_s, new.programs, new.ops) == (
        old.window_s, old.busy_s, old.programs, old.ops)
    assert old.idle_by_host == pytest.approx(
        {"engine.commit": 350 * US, "engine.round": 100 * US,
         "host:outside engine calls": 50 * US})


def test_no_spans_reads_nothing():
    assert span_reduce.reduce_spans(small_trace()) is None


# ------------------------------------------------------------- readers
DIMS = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
        "d_head": 32, "d_ff": 256, "vocab": 512}
SERIES = {
    "t": [1, 2, 3, 4, 5, 6],
    "round_s": [.1, .2, .3, .4, .5, .6],
    "ingest_s": [.01] * 6, "publish_s": [.02] * 6,
    "fetch_s": [.05, .1, .1, .2, .1, .1],
    "prefill_fetch_s": [0, 0, .05, 0, 0, 0], "swap_fetch_s": [0] * 6,
    "admit_s": [0, .05, .12, 0, .3, 0],
    "prefill_s": [0, .02, .04, 0, .1, 0],
    "admissions": [0, 1, 3, 3, 4, 4],
    "decode_ctx_tokens": [10, 40, 60, 100, 150, 210],
    "decode_grid_tokens": [100, 200, 500, 900, 1100, 2000],
    "prefill_causal_keys": [0, 0, 0, 100, 1100, 1100],
    "prefill_tokens": [0, 0, 0, 10, 60, 60],
}
FLASH_OPS = {"%flash_attention_fwd_kernel.3 = (bf16[8,256,32]) custom-call":
             [2, 0.5], "%fusion.1 = bf16[8] fusion": [1, 1.0]}


def fake_run(series=SERIES, ops=FLASH_OPS):
    """Window [2, 6), trace from 5: rounds ending at 2, 3, 4 are read
    untraced, the round ending at 5 ran under the trace."""
    return SimpleNamespace(
        timeseries={k: np.asarray(v, float) for k, v in series.items()},
        window=SimpleNamespace(open=2.0, close=6.0),
        worklog=SimpleNamespace(started=5.0),
        trace=SimpleNamespace(ops=ops), model=DIMS, family=dense_gqa,
        peak={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8})


@pytest.mark.parametrize("name,value", [
    # rounds 2..4: (.2+.03-.1), (.3+.03-.1-.05), (.4+.03-.2); mean .18 s
    ("host_round_ms", 180.0),
    # rounds 2 and 3 prefilled: (.05 + .12) s over 1 + 2 admissions
    ("admit_ms_per_request", 170.0 / 3),
    # rounds 2..5: (150 - 10) / (1100 - 100)
    ("paged_decode_live_share", 0.14),
    # round 5: 1000 causal keys x 4 L Hq dh = 1.024e6 flops (1.024 ms at
    # 1e9) > 50 tokens x L (2 Hq + 2 Hkv) dh 2 B = 76,800 B (0.768 ms);
    # over the named kernel's 0.5 s
    ("flash_prefill_roofline", 100 * 1.024e-3 / 0.5),
])
def test_reader_hand_value(name, value):
    assert spec.metric_reader(name)(fake_run()) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "host_round_ms", "admit_ms_per_request", "paged_decode_live_share",
    "flash_prefill_roofline"])
def test_reader_reads_nothing_without_spans_or_counters(name):
    # the program before spans and counters: only t and the old columns
    old = fake_run({"t": SERIES["t"], "live_slots": [1] * 6})
    assert spec.metric_reader(name)(old) is None


def test_flash_reader_needs_the_named_kernel():
    read = spec.metric_reader("flash_prefill_roofline")
    assert read(fake_run(ops={"%fusion.1 = bf16[8] fusion": [1, 1.0]})) \
        is None
    assert read(SimpleNamespace(**{**vars(fake_run()), "trace": None})) \
        is None


def test_traced_tiny_run_reports_the_program_metrics(monkeypatch):
    """End to end on the CPU: the readers of the program's spans and
    counters report.  Attention runs through jnp here, so there is no
    flash kernel to read, and the CPU's trace has no device plane, so
    the span reduction reads nothing."""
    monkeypatch.setattr("bench.check.MIN_TOKENS", 100)
    found = {}
    reduce_ = run.reduce

    def both(events):
        found.update(spans.summary(events))
        return reduce_(events)

    monkeypatch.setattr(run, "reduce", both)
    res = run.run_cell(tiny_cell("tiny-open"), seed=SEED, seconds=4.0,
                       trace=True, devices=jax.devices(), peak=PEAK,
                       t_start=time.perf_counter())
    m = res["metrics"]
    assert m["host_round_ms"]["value"] > 0
    assert m["admit_ms_per_request"]["value"] > 0
    assert 0 < m["paged_decode_live_share"]["value"] < 1
    assert "flash_prefill_roofline" not in m
    assert found == {"flash_ops": {}}
