"""Model families as files of their own.

* The dense GQA family gives what the harness gave before families were
  files: the same weights bit for bit, the same reference numbers, the
  same operation and byte counts (golden values, computed before the
  move and written here).
* A family written into another root, with its configuration and its
  cell, is found by ``spec.load_cell`` and used by ``check.compare``
  and by the metrics that count work, with no file of ``bench/``
  edited.
* A configuration that names no family, or one that does not exist,
  stops with the known families listed.
"""

import hashlib
import json
import shutil
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import check, spec
from bench.families import REQUIRED, dense_gqa

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

SMALL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
         "d_head": 32, "d_ff": 256, "vocab": 512, "tie_embeddings": True,
         "norm_eps": 1e-6, "rope_theta": 1e6}

# sha256 (first 16 hex digits) of each bf16 leaf's bytes, dtype, shape:
# make_params(SMALL, 2**33 + 5)
LEAVES = {
    "['embed']": "82f3ece4010ca111bfloat16(512, 128)",
    "['layers']['attn']['bk']": "52c357f011ec20e8bfloat16(2, 64)",
    "['layers']['attn']['bq']": "c6d3394065609dacbfloat16(2, 128)",
    "['layers']['attn']['bv']": "e26c1db623c93af5bfloat16(2, 64)",
    "['layers']['attn']['wk']": "3cc433c0044fa772bfloat16(2, 128, 64)",
    "['layers']['attn']['wo']": "8d9cc83f03caa4ddbfloat16(2, 128, 128)",
    "['layers']['attn']['wq']": "474d759fdbfa988ebfloat16(2, 128, 128)",
    "['layers']['attn']['wv']": "8a03ca60fb23c620bfloat16(2, 128, 64)",
    "['layers']['ln1']": "b28de98c52e3fcb5bfloat16(2, 128)",
    "['layers']['ln2']": "84c78dbe4b554161bfloat16(2, 128)",
    "['layers']['mlp']['w_down']": "e0a610ac2f87f00ebfloat16(2, 256, 128)",
    "['layers']['mlp']['w_gate']": "7573466b1df1efc8bfloat16(2, 128, 256)",
    "['layers']['mlp']['w_up']": "9e4516981aafdd25bfloat16(2, 128, 256)",
    "['ln_f']": "05106b5f8c44463cbfloat16(128,)",
}
LM_HEAD = {"['lm_head']": "c65ca19a39b16df3bfloat16(128, 512)"}

# logits(SMALL, 4, 20 tokens of default_rng(1)): the last row's first 8,
# the sum and the sum of magnitudes of all
LOGITS = {
    True: ([-0.06214474141597748, -0.21975204348564148, -0.3477182984352112,
            -0.11343079805374146, 0.2988508343696594, 0.1881733238697052,
            -0.09686800092458725, -0.10632073879241943],
           -44.07916980632581, 1739.539591104025),
    False: ([-0.5820737481117249, -0.25716203451156616, 0.09040507674217224,
             -0.8300318717956543, 0.6086833477020264, -0.07428300380706787,
             0.46113401651382446, -0.15164196491241455],
            427.2380964765325, 6678.25766189117),
}

# gaps(SMALL, 6, [70 tokens of default_rng(2), 50 of them prompt],
# control=True): sound, control
GAPS = {
    True: ([0.8758836984634399, 0.7422707676887512, 0.842863142490387,
            0.7658150792121887, 0.7179802656173706, 0.8234068155288696,
            0.4341386556625366, 0.30912908911705017, 0.8786997199058533,
            0.8030002117156982, 0.7618893384933472, 0.2575656473636627,
            0.730225682258606, 0.6005991697311401, 0.4284236431121826,
            1.0646395683288574, 0.3380240201950073, 0.7427384853363037,
            0.7133724093437195, 0.47713419795036316],
           [1.1920928955078125e-07, 1.1920928955078125e-07,
            1.1920928955078125e-07, 0.0, 0.027829110622406006, 0.0, 0.0,
            1.1920928955078125e-07, 2.384185791015625e-07,
            0.017000675201416016, 1.7881393432617188e-07,
            -5.960464477539063e-08, 0.014781475067138672,
            -1.1920928955078125e-07, 0.019484996795654297,
            0.15234217047691345, -2.9802322387695312e-08,
            -1.1920928955078125e-07, 0.0, -2.384185791015625e-07]),
    False: ([3.037295341491699, 1.7892760038375854, 2.3898606300354004,
             2.775047540664673, 2.6568944454193115, 3.1929330825805664,
             1.8670293092727661, 0.9701378345489502, 3.338104009628296,
             2.79807710647583, 3.3742594718933105, 3.0427451133728027,
             3.3510327339172363, 3.1189522743225098, 2.498443126678467,
             2.103214740753174, 3.819375991821289, 2.923961877822876,
             3.3240976333618164, 2.757669448852539],
            [2.384185791015625e-07, -4.76837158203125e-07,
             -2.384185791015625e-07, 0.0, 0.0, 4.76837158203125e-07,
             -4.76837158203125e-07, -4.76837158203125e-07, 0.0,
             -2.384185791015625e-07, 4.76837158203125e-07,
             -4.76837158203125e-07, -2.384185791015625e-07,
             -2.384185791015625e-07, -2.384185791015625e-07,
             0.04999971389770508, -2.384185791015625e-07,
             -7.152557373046875e-07, 0.0, 0.0]),
}

# the configurations' dims and counts at contexts [1, 100, 1300, 4097],
# prefill(1000), prefill(300, start=700), flash_kernel(1000)
CTX = [1, 100, 1300, 4097]
CONFIGS = {
    "qwen2-1.5b": {
        "dims": {"n_layers": 28, "d_model": 1536, "n_heads": 12,
                 "n_kv_heads": 2, "d_head": 128, "d_ff": 8960,
                 "vocab": 151936, "tie_embeddings": True, "norm_eps": 1e-06,
                 "rope_theta": 1000000.0},
        "layer_weights": 46792704, "weight_bytes": 3087428608,
        "kv_token_bytes": 28672, "attention_layers": 28,
        "decode_step": (13294387200.0, 3245181952.0),
        "decode_kernel": (945831936.0, 158326784.0),
        "prefill": (2706960187392.0, 3144772608.0),
        "prefill_cached": (830478139392.0, 3124702208.0),
        "flash_kernel": (86102016000.0, 200704000.0),
    },
    "qwen1.5-32b-8L": {
        "dims": {"n_layers": 8, "d_model": 5120, "n_heads": 40,
                 "n_kv_heads": 8, "d_head": 128, "d_ff": 27392,
                 "vocab": 152064, "tie_embeddings": False, "norm_eps": 1e-06,
                 "rope_theta": 1000000.0},
        "layer_weights": 483655680, "weight_bytes": 9295915008,
        "kv_token_bytes": 32768, "attention_layers": 8,
        "decode_step": (38083297280.0, 9476204544.0),
        "decode_kernel": (900792320.0, 180813824.0),
        "prefill": (7822049935360.0, 9361451008.0),
        "prefill_cached": (2364908175360.0, 9338513408.0),
        "flash_kernel": (82001920000.0, 196608000.0),
    },
}


# ------------------------------------------------- (a) golden values
@pytest.mark.parametrize("tie", [True, False])
def test_dense_weights_are_bit_for_bit_as_before(tie):
    params = dense_gqa.make_params(dict(SMALL, tie_embeddings=tie),
                                   2 ** 33 + 5)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    got = {jax.tree_util.keystr(k): hashlib.sha256(
        np.asarray(v).tobytes()).hexdigest()[:16]
        + f"{v.dtype}{tuple(v.shape)}" for k, v in flat}
    assert got == (LEAVES if tie else {**LEAVES, **LM_HEAD})


@pytest.mark.parametrize("tie", [True, False])
def test_dense_reference_logits_as_before(tie):
    toks = np.random.default_rng(1).integers(0, SMALL["vocab"], 20)
    lg = dense_gqa.logits(dict(SMALL, tie_embeddings=tie), 4, toks)
    first, total, size = LOGITS[tie]
    np.testing.assert_allclose(lg[-1, :8], first, rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.sum(lg, dtype=np.float64), total,
                               rtol=1e-5)
    np.testing.assert_allclose(np.sum(np.abs(lg), dtype=np.float64), size,
                               rtol=1e-5)


@pytest.mark.parametrize("tie", [True, False])
def test_dense_reference_gaps_as_before(tie):
    rng = np.random.default_rng(2)
    seqs = [(rng.integers(0, SMALL["vocab"], 70).astype(np.int32), 50)]
    sound, ctl = dense_gqa.gaps(dict(SMALL, tie_embeddings=tie), 6, seqs,
                                control=True)
    want_sound, want_ctl = GAPS[tie]
    np.testing.assert_allclose(sound[0], want_sound, rtol=0, atol=2e-5)
    np.testing.assert_allclose(ctl[0], want_ctl, rtol=0, atol=2e-5)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_dims_config_and_counts_as_before(name):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    want = CONFIGS[name]
    family = spec.family(conf)
    m = family.dims(conf)
    assert m == want["dims"]
    cfg = family.program_config(conf, m)
    assert (cfg.family, cfg.qkv_bias, cfg.attn_type) == ("dense", True,
                                                         "gqa")
    for key, value in m.items():
        assert getattr(cfg, key) == value
    assert family.layer_weights(m) == want["layer_weights"]
    assert family.weight_bytes(m) == want["weight_bytes"]
    assert family.kv_token_bytes(m) == want["kv_token_bytes"]
    assert family.attention_layers(m) == want["attention_layers"]
    assert family.decode_step(m, CTX) == want["decode_step"]
    assert family.decode_kernel(m, CTX) == want["decode_kernel"]
    assert family.prefill(m, 1000) == want["prefill"]
    assert family.prefill(m, 300, 700) == want["prefill_cached"]
    assert family.flash_kernel(m, 1000) == want["flash_kernel"]


# ---------------------------------------------- (b) a family as files
TOY = '''
"""A bigram model: the logits after token t are row t of a seeded
table.  Two paged kernel calls a layer."""

import jax.numpy as jnp
import numpy as np

from bench import precision, weights

LEAF_ID = {"table": 0}


def dims(conf):
    c = conf["config"]
    return {"vocab": c["vocabulary"], "n_layers": c["depth"]}


def program_config(conf, dims):
    return None                     # no engine serves it here


def _table(dims, seed, dtype=jnp.float32):
    spec = ((dims["vocab"], dims["vocab"]), "int", weights.exponent(1.0))
    return weights.leaf(weights.base_key(seed), LEAF_ID, "table", 0,
                        spec, dtype)


def make_params(dims, seed, dtype=jnp.bfloat16):
    return {"table": _table(dims, seed, dtype)}


def logits(dims, seed, tokens):
    return np.asarray(_table(dims, seed))[np.asarray(tokens)]


def gaps(dims, seed, seqs, *, control=False):
    table = np.asarray(_table(dims, seed))
    low = np.asarray(precision.fp8(jnp.asarray(table)))
    sound, ctl = [], []
    for toks, n_prompt in seqs:
        prev, served = toks[n_prompt - 1:-1], toks[n_prompt:]
        rows = table[prev]
        best = rows.max(-1)
        at = np.arange(len(rows))
        sound.append(best - rows[at, served])
        if control:
            ctl.append(best - rows[at, low[prev].argmax(-1)])
    return sound, (ctl if control else None)


def decode_step(dims, ctx):
    return 2.0 * dims["vocab"] * len(list(ctx)), 2.0 * dims["vocab"] ** 2


def decode_kernel(dims, ctx):
    keys = sum(ctx)
    return 4.0 * keys, 2.0 * keys


def prefill(dims, n, start=0):
    return 2.0 * dims["vocab"] * n, 2.0 * dims["vocab"] ** 2


def flash_kernel(dims, n):
    return 2.0 * n * (n + 1), 2.0 * n


def attention_layers(dims):
    return 2 * dims["n_layers"]
'''

TOY_CONF = {"name": "toy", "family": "toy",
            "source": "test configuration: a seeded bigram table",
            "config": {"vocabulary": 64, "depth": 3}, "reduced": {},
            "logit_gap_limit": 0.01}


def toy_root(tmp_path: Path) -> Path:
    """A checkout with only the toy: its family, configuration, traffic
    and cell, written as files and entries."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "toy", "source": TOY_CONF["source"],
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "a family added as a file"}]
    bench["workloads"] = [{"name": "toy.tiny", "config": "toy",
                           "traffic": "tiny-open", "chips": 1,
                           "why": "the toy family end to end"}]
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if m["name"] in ("setup_s", "tbt_p95_ms")]
    bench["per_layer"] = [dict(m, workloads=["toy.tiny"])
                          for m in bench["per_layer"]
                          if m["name"] in ("decode_mfu",
                                           "paged_decode_roofline")]
    for sub in ("families", "configs", "traffic"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "families" / "toy.py").write_text(TOY)
    (tmp_path / "bench" / "configs" / "toy.json").write_text(
        json.dumps(TOY_CONF))
    shutil.copy(HERE / "tiny-open.json",
                tmp_path / "bench" / "traffic" / "tiny-open.json")
    return tmp_path


def _bench_files():
    return sorted(str(p.relative_to(ROOT)) for p in (ROOT / "bench")
                  .rglob("*") if "__pycache__" not in p.parts)


def _greedy(family, dims, seed, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        toks.append(int(np.argmax(family.logits(dims, seed, toks)[-1])))
    return toks[len(prompt):]


def test_a_family_added_as_files_serves_check_and_metrics(tmp_path):
    before = _bench_files()
    cell = spec.load_cell("toy.tiny", root=toy_root(tmp_path))
    assert cell.family.dims is not dense_gqa.dims
    assert cell.model == {"vocab": 64, "n_layers": 3}
    assert [m["name"] for m in cell.per_layer] == [
        "decode_mfu", "paged_decode_roofline"]

    seed, limit = 2 ** 40 + 3, cell.config["logit_gap_limit"]
    rng = np.random.default_rng(0)
    picked = []
    for uid in range(3):
        prompt = rng.integers(0, 64, 5).astype(np.int32)
        picked.append(types.SimpleNamespace(
            uid=uid, prompt=prompt, n_prompt=5, max_new=200,
            tokens=_greedy(cell.family, cell.model, seed, prompt, 200)))

    def compare(picked, control=False):
        return check.compare(cell.family, cell.model, seed, picked,
                             limit=limit, max_seq=1024, control=control)

    sound = compare(picked)
    assert check.passed(sound), sound
    assert sound["logit_gap"]["value"] == 0
    ctl = compare(picked, control=True)
    assert not check.passed(ctl)
    assert ctl["logit_gap"]["value"] > limit
    altered = [types.SimpleNamespace(**vars(picked[0]))] + picked[1:]
    altered[0].tokens = list(altered[0].tokens)
    altered[0].tokens[7] = (altered[0].tokens[7] + 1) % 64
    assert not check.passed(compare(altered))

    # decode_mfu: steps [3, 5] and [7] cost 2 x 64 x (2 + 1) = 384
    # operations, scaled from 2 logged steps to the trace's 4 programs
    # over its 2 s
    peak = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(
            program_seconds=lambda name: (4, 2.0),
            op_seconds=lambda names: (12, 1.0)),
        worklog=types.SimpleNamespace(decode=[[3, 5], [7]]),
        model=cell.model, family=cell.family, peak=peak)
    assert spec.metric_reader("decode_mfu")(run) == pytest.approx(
        100 * 384 * (4 / 2) / (2.0 * 1e3))
    # paged_decode_roofline: 12 calls at 6 a step are 2 steps, each the
    # mean of max(4 k / 1e3, 2 k / 1e2) over k = 8 and 7 keys
    assert spec.metric_reader("paged_decode_roofline")(run) == \
        pytest.approx(100 * 2 * (0.16 + 0.14) / 2 / 1.0)
    assert _bench_files() == before


# ----------------------------------------- (c) no family, no default
@pytest.mark.parametrize("conf", [{"name": "x"},
                                  {"name": "x", "family": "moe_gqa"},
                                  {"name": "x", "family": "../configs/x"}])
def test_missing_or_unknown_family_lists_the_known(conf):
    with pytest.raises(SystemExit,
                       match=r"known families are \['dense_gqa'\]"):
        spec.family(conf)


def test_family_lacking_a_contract_name_is_refused(tmp_path):
    root = toy_root(tmp_path)
    path = root / "bench" / "families" / "half.py"
    path.write_text("def dims(conf):\n    return {}\n")
    with pytest.raises(SystemExit, match=r"'half' lacks \[.*'gaps'"):
        spec.family({"name": "x", "family": "half"}, root)


def test_every_family_here_has_the_contract():
    for path in (ROOT / "bench" / "families").glob("[!_]*.py"):
        family = spec.family({"name": "x", "family": path.stem})
        assert all(callable(getattr(family, n)) for n in REQUIRED)
