"""The knee sweep's rule, one rate of it on the CPU at a tiny size, and
the queue wait a traced run reads."""

import types

import jax
import pytest

from bench import spec, sweep, system
from bench.tests.test_harness import tiny_cell


@pytest.mark.parametrize("failed, thirds, expected", [
    (0, [3.0, 4.0, 4.5], True),       # within 2 requests
    (0, [20.0, 21.0, 23.5], True),    # within 20%
    (0, [3.0, 9.0, 15.0], False),     # grows
    (1, [3.0, 3.0, 3.0], False),      # a request never finished
    (0, None, False),                 # too few rounds to tell
])
def test_holds(failed, thirds, expected):
    assert sweep.holds(failed, thirds) is expected


def test_one_rate_reads_queue_and_tails():
    cell = tiny_cell("tiny-open")
    dims = cell.model
    with jax.default_device(jax.devices()[0]):
        engine = system.build_engine(cell.family, cell.config, dims,
                                     cell.family.make_params(dims, 5))
        system.warmup(engine, cell.traffic, dims["vocab"])
        row = sweep.one_rate(engine, cell.traffic, 4.0, seed=5,
                             seconds=2.0, vocab=dims["vocab"])
    assert row["counted"] > 0 and row["failed"] == 0
    assert row["queue_depth_by_third"] is not None
    assert row["ttft_p95_s"] > 0 and row["tbt_p95_ms"] > 0
    assert row["output_tokens_per_s"] > 0


@pytest.mark.parametrize("started, expected", [(None, 5.0), (15.0, 0.5)])
def test_queue_wait_counts_requests_due_before_the_trace(started, expected):
    # the request due after the trace began waited through the
    # profiler's stop; a traced run leaves it out
    reqs = [types.SimpleNamespace(due=float(d), admitted=d + w)
            for d, w in ((10, 0.5), (12, 0.5), (20, 5.0))]
    run = types.SimpleNamespace(counted=reqs,
                                worklog=system.WorkLog(started=started))
    assert spec.metric_reader("queue_wait_p95_s")(run) == pytest.approx(
        expected, abs=0.5 if started is None else 1e-9)
