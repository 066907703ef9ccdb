"""The trace reduction on a small trace with known answers."""

import pytest

from bench.trace_reduce import Event, reduce

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start_us, dur_us):
    return Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def small_trace():
    return [
        ev(HOST, "python3", "bench.window", 100, 1000),        # 100..1100
        ev(HOST, "python3", "engine.round", 90, 500),          # 90..590
        ev(HOST, "python3", "engine.commit", 400, 150),        # 400..550
        ev(HOST, "python3", "engine.round", 700, 300),         # 700..1000
        ev(DEV, "XLA Modules", "jit_paged_step_fn(1)", 50, 200),
        ev(DEV, "XLA Modules", "jit_paged_step_fn(1)", 600, 100),
        ev(DEV, "XLA Modules", "jit_prefill_bucket_fn(2)", 800, 250),
        ev(DEV, "XLA Ops", "paged_decode_attention_op.3", 50, 100),
        ev(DEV, "XLA Ops", "fusion.7", 150, 100),             # ..250
        ev(DEV, "XLA Ops", "paged_decode_attention_op.11", 600, 100),
        ev(DEV, "XLA Ops", "_fwd_kernel", 800, 200),
        ev(DEV, "XLA Ops", "fusion.9", 900, 150),             # overlaps
        ev(HOST, "XLA Ops", "ignored host op", 100, 900),
        ev(HOST, "other thread", "not the event loop", 200, 300),
    ]


def test_busy_window_and_idle():
    r = reduce(small_trace())
    assert r.window_s == pytest.approx(1000e-6)
    # busy inside [100, 1100): 100..250, 600..700, 800..1050
    assert r.busy_s == pytest.approx((150 + 100 + 250) * 1e-6)
    # gaps: 250..600 (mid 425: engine.commit is innermost),
    # 700..800 (mid 750: engine.round), 1050..1100 (outside any call)
    assert r.idle_by_host["engine.commit"] == pytest.approx(350e-6)
    assert r.idle_by_host["engine.round"] == pytest.approx(100e-6)
    assert r.idle_by_host["host:outside engine calls"] == pytest.approx(
        50e-6)
    assert sum(v for _, v in r.top_idle()) == pytest.approx(
        r.window_s - r.busy_s)


def test_programs_and_ops_count_events_starting_in_the_window():
    r = reduce(small_trace())
    # the step at 50 us starts before the window and is left out
    assert r.program_seconds("paged_step_fn") == (1, pytest.approx(100e-6))
    assert r.program_seconds("prefill_bucket_fn") == (1, pytest.approx(
        250e-6))
    calls, secs = r.op_seconds(("paged_decode_attention_op",))
    assert (calls, secs) == (1, pytest.approx(100e-6))
    assert r.op_seconds(("_fwd_kernel",)) == (1, pytest.approx(200e-6))
    assert r.top_ops(1) == [["fusion", pytest.approx(250e-6)]]
    assert "fusion" in r.ops and "fusion.9" not in r.ops


def test_no_window_or_no_device_reads_nothing():
    t = small_trace()
    assert reduce([e for e in t if e.name != "bench.window"]) is None
    assert reduce([e for e in t if not e.plane.startswith("/device")]) is None
