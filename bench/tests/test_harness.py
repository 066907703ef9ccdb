"""A whole run of the harness on the CPU at a tiny size, the look for a
chip skipped: a sound run is correct; the float8 control in the
program's place, and each fault a served cell can have planted in the
timed path, come out not correct.

The faults: a token altered where the decode step produces it, and a
decode step that hands back its KV state unchanged (no new K/V row
lands).  A served cell has no batch mean or exchange between chips to
break."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import check, run, spec

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 31 + 77


def tiny_cell(traffic: str) -> spec.Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((HERE / "tiny.json").read_text())
    return spec.Cell(name="tiny", chips=1, config=conf,
                     family=spec.family(conf),
                     traffic=json.loads((HERE / f"{traffic}.json")
                                        .read_text()),
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def _run(traffic="tiny-open", **kw):
    return run.run_cell(tiny_cell(traffic), seed=SEED, seconds=3.0,
                        trace=False, devices=jax.devices(), peak=PEAK,
                        t_start=time.perf_counter(), **kw)


@pytest.fixture(autouse=True)
def fewer_tokens(monkeypatch):
    # a tiny 3-second window serves a few hundred tokens
    monkeypatch.setattr(check, "MIN_TOKENS", 100)


def altered_token(engine):
    step = engine._paged_step
    vocab = engine.model.cfg.vocab

    def paged_step(*a, **k):
        pool, nxt, *rest = step(*a, **k)
        return (pool, (nxt + 1) % vocab, *rest)

    engine._paged_step = paged_step


def state_unchanged(engine):
    step = engine._paged_step

    def paged_step(params, pool, *a, **k):
        kept = jax.tree.map(jnp.copy, pool)
        _, *rest = step(params, pool, *a, **k)
        return (kept, *rest)

    engine._paged_step = paged_step


@pytest.mark.parametrize("traffic", ["tiny-open", "tiny-closed"])
def test_sound_run_is_correct(traffic):
    res = _run(traffic)
    assert res["correct"], res["checked"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checked"
    names = {m["name"] for m in tiny_cell(traffic).end_to_end}
    assert set(res["metrics"]) <= names
    assert "tbt_p95_ms" in res["metrics"] and "setup_s" in res["metrics"]


def test_control_is_not_correct():
    res = _run(control=True)
    assert not res["correct"], res["checked"]
    assert (res["checked"]["logit_gap"]["value"]
            > res["checked"]["logit_gap"]["limit"])


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
def test_fault_in_the_timed_path_is_not_correct(fault):
    res = _run(fault=fault)
    assert not res["correct"], res["checked"]


def test_sample_takes_the_longest_then_the_configured_requests():
    from types import SimpleNamespace as R
    done = [R(uid=u, n_prompt=10 * u, tokens=[1] * 200) for u in range(12)]
    three = check.sample(done, SEED)
    eight = check.sample(done, SEED, 8)
    assert three[0].uid == 11 and eight[0].uid == 11
    assert len(three) == 3 and len(eight) == 8
    assert [r.uid for r in eight[:3]] == [r.uid for r in three]
