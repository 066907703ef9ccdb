"""The traffic generator: same seed, same requests; other seeds, the
same set of sizes and gaps in each segment in another order, or in the
same order where the mix fixes its ``schedule_seed``."""

import json
from pathlib import Path

import numpy as np

from bench.traffic import generate

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SEED = 2 ** 31 + 12345


def _load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _open(spec, seed, seconds=30.0):
    return generate.open_loop(spec, seed, seconds, vocab=1000)


def test_open_loop_same_seed_same_requests():
    spec = _load("chat-poisson")
    a, b = _open(spec, SEED), _open(spec, SEED)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.uid, x.max_new, x.due_s) == (y.uid, y.max_new, y.due_s)
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_open_loop_window_holds_same_work_for_every_seed():
    spec = _load("chat-poisson")
    ramp, secs = spec["ramp_s"], 30.0

    def window(reqs):
        w = [r for r in reqs if ramp <= r.due_s < ramp + secs]
        return (sorted(len(r.prompt) for r in w),
                sorted(r.max_new for r in w))

    shuffled = {k: v for k, v in spec.items() if k != "schedule_seed"}
    a, b = _open(shuffled, 1), _open(shuffled, 2)
    assert window(a) == window(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    rate = spec["arrivals"]["rate_per_s"]
    assert len([r for r in a if ramp <= r.due_s < ramp + secs]) == round(
        rate * secs)
    # with schedule_seed every seed replays one schedule; tokens differ
    a, b = _open(spec, 1), _open(spec, 2)
    assert "schedule_seed" in spec
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == [
        (len(r.prompt), r.max_new, r.due_s) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_open_loop_lengths_follow_the_mix():
    spec = _load("chat-poisson")
    reqs = _open(spec, 3, seconds=200.0)
    p = np.asarray([len(r.prompt) for r in reqs])
    o = np.asarray([r.max_new for r in reqs])
    assert p.min() >= spec["prompt"]["min"]
    assert p.max() <= spec["prompt"]["max"]
    assert abs(np.median(p) / spec["prompt"]["median"] - 1) < 0.05
    assert abs(np.median(o) / spec["output"]["median"] - 1) < 0.05
    dues = np.asarray([r.due_s for r in reqs])
    assert np.all(np.diff(dues) >= 0)


def test_closed_loop_same_seed_same_clients():
    spec = _load("decode-closed")
    a = generate.closed_loop(spec, SEED, vocab=1000)
    b = generate.closed_loop(spec, SEED, vocab=1000)
    assert len(a) == spec["clients"]
    for ca, cb in zip(a, b):
        assert [(r.uid, r.max_new) for r in ca] == [(r.uid, r.max_new)
                                                   for r in cb]
    firsts = sorted(c[0].max_new for c in a)
    rest = [r.max_new for c in a for r in c[1:]]
    assert min(rest) >= spec["output"]["min"]
    assert firsts[0] < spec["output"]["min"]     # residual first requests


def test_tenants_share_their_system_prompt():
    spec = dict(_load("chat-poisson"),
                tenants={"count": 4, "zipf_s": 1.1, "system_len": 32})
    reqs = _open(spec, 5)
    by_tenant = {}
    for r in reqs:
        by_tenant.setdefault(r.tenant, []).append(r.prompt[:32].tobytes())
    assert set(by_tenant) == {0, 1, 2, 3}
    assert all(len(set(v)) == 1 for v in by_tenant.values())
    counts = generate.zipf_counts(100, 4, 1.1)
    assert counts.sum() == 100 and list(counts) == sorted(counts)[::-1]


def test_percentile_is_numpy_linear():
    assert generate.percentile([1, 2, 3, 4], 50) == 2.5
    assert np.isnan(generate.percentile([], 95))
