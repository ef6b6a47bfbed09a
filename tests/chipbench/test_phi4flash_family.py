"""The configuration ``phi4-mini-flash-vp8``, its family and its cell (ISSUE
34): the file against the catalog's row, the counts against hand arithmetic
(this chip's 697,094,272 parameters, the whole model's 3.85 B), the two new
readers on synthetic records, and pins of what this PR appended, stated so
that they stay true when a later PR appends a family, a cell or a metric:
every entry accepted at the parent commit keeps its order, its keys and its
first cells; every file the benchmark had there is here byte for byte. CPU
only."""

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

BENCH = harness.load_benchmark()
CELL = "phi4flash-seq8k-train"
CONFIG = "phi4-mini-flash-vp8"
SOURCE = "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
NEW_METRICS = ["ssm.scan_fwd_roofline", "ssm.scan_ms"]
APPENDED_TO = [
    "device.idle_pct", "step.mfu_pct", "loader.wait_pct", "loader.max_step_ms",
    "step.device_ms", "staging.direct_pct", "loader.first_batch_s",
    "queue.get_wait_pct", "staging.max_transfer_ms", "staging.unpack_ms",
    "attention.fwd_roofline", "attention.window_fwd_roofline",
]

# The catalog's row of Phi-4-mini-flash-reasoning (the model-configs guide),
# every key of its ``config``.
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
    "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20,
    "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
}


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(BENCH, CELL)[1]


@pytest.fixture(scope="module")
def parent():
    """``tests/chipbench/parent_6c8e389.json``: the digest of every file of
    ``chipbench/`` and ``tests/chipbench/`` at the parent commit, and that
    commit's ``BENCHMARK.json``."""
    with open(os.path.join(HERE, "parent_6c8e389.json")) as f:
        return json.load(f)


def test_the_file_holds_the_published_widths_and_states_every_cut(cfg):
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["source"] == SOURCE == cfg["source"] and cfg["family"] == "phi4flash"
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json" and len(entry["why"]) <= 200
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "vocab_size", "num_rows"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["reduced_why"]) == reduced == set(cfg["published"])
    # No width among the keys that were cut, and the scan's sizes, which the
    # published config has no key for, as Mamba's own defaults.
    assert not [k for k in reduced if k.endswith(("_size", "_dim", "_rank")) and k != "vocab_size"]
    assert cfg["ssm"] == {"state_size": 16, "conv_kernel": 4, "expand": 2, "dt_rank": 160}
    assert cfg["ssm"]["dt_rank"] == -(-cfg["hidden_size"] // 16)
    assert (cfg["num_hidden_layers"], cfg["first_layer"], cfg["vocab_size"]) == (6, 14, 25008)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_rows"] * 8 == cfg["published"]["num_rows"]
    for said in ("8 ways over the vocabulary", "rows 0-25007", "layers 14-19", "No width is cut"):
        assert said in cfg["deployment"], said
    assert "2 : 1 : 1 : 1 : 1" in cfg["reduced_why"]["num_hidden_layers"]
    assert len(cfg["assumed"]) >= 10
    assert set(cfg["guarantees"]) == {"exactly_once", "rows_intact", "order"}
    assert cfg["data_spec"] == {"tokens": [0, 25008, "int32", 8192]}
    assert (cfg["loader"], cfg["batch_size"], cfg["num_rows"]) == ("stream", 1, 2048)
    assert (cfg["num_files"], cfg["row_groups_per_file"], cfg["num_reducers"]) == (8, 4, 4)
    assert cfg["max_concurrent_epochs"] == 2
    assert cfg["optimizer"] == {
        "name": "adam", "learning_rate": 1e-05, "b1": 0.9, "b2": 0.999, "eps": 1e-08,
    }
    assert cfg["model"]["compute_dtype"] == "bfloat16" and cfg["model"]["scan_dtype"] == "float32"
    assert set(cfg["limits"]) == {"grad_diff", "grad_norm_mid_gap", "change_norm_gap"}
    assert "TO BE READ" not in cfg["limits_why"] + cfg["rehearsal"]["limits_why"]
    assert set(cfg["kernels"]) == {"attention_block_q", "attention_block_k"}
    # ``change_norm_gap`` lies above what a lambda vector can read on it (a
    # leaf of 64 numbers moves at most 3 steps x 1e-5 x 8 = 2.4e-4 where the
    # median leaf moves 1.0e-3: 0.48 if both sides move it oppositely) and
    # under 1, a state left unchanged (``limits_why``).
    assert 0.48 < cfg["limits"]["change_norm_gap"] < 1
    assert "lambda" in cfg["limits_why"]
    toy = cfg["rehearsal"]
    assert (toy["hidden_size"], toy["num_attention_heads"], toy["sliding_window"]) == (64, 8, 16)
    assert toy["data_spec"]["tokens"][3] == 64 and toy["ssm"]["state_size"] == 4


def test_the_counts_against_hand_arithmetic(cfg):
    counts = harness.load_family(cfg).counts
    assert counts.layers(cfg) == [
        (14, "mamba"), (15, "attention_window"), (16, "mamba"), (17, "attention"),
        (18, "memory_unit"), (19, "cross_attention"),
    ]
    h, wide, di, n, r, d = 2560, 10240, 5120, 16, 160, 64
    mlp, norms = 3 * h * wide, 4 * h
    assert (mlp, norms) == (78_643_200, 10_240)
    mamba = (
        h * 2 * di + di * 4 + di + di * (r + 2 * n) + r * di + di + di * n + di + di * h
    )
    attention = h * (40 + 20 + 20) * d + (40 + 20 + 20) * d + 40 * d * h + h + 4 * d + 2 * d
    memory_unit = 2 * h * di
    cross = h * 40 * d + 40 * d + 40 * d * h + h + 4 * d + 2 * d
    assert (mamba, attention) == (41_241_600, 19_668_864)
    by_kind = {
        "mamba": 119_895_040, "attention_window": 98_322_304, "attention": 98_322_304,
        "memory_unit": 104_867_840, "cross_attention": 91_766_144,
    }
    for kind, mixer in [
        ("mamba", mamba), ("attention_window", attention), ("attention", attention),
        ("memory_unit", memory_unit), ("cross_attention", cross),
    ]:
        assert counts.layer_parameters(cfg, kind) == mixer + mlp + norms == by_kind[kind], kind
    layers = 2 * by_kind["mamba"] + 2 * by_kind["attention"] + by_kind["memory_unit"] + by_kind["cross_attention"]
    assert layers == 633_068_672
    embedding = 25008 * h
    assert embedding == 64_020_480
    assert counts.num_parameters(cfg) == layers + embedding + 2 * h == 697_094_272
    assert counts.state_bytes(cfg) == 12 * 697_094_272
    assert round(16 * counts.num_parameters(cfg) / 1e9, 2) == 11.15
    assert 16 * counts.num_parameters(cfg) > 0.25 * 16e9
    # The whole model by the same arithmetic is the published 3.8 B: what
    # the assumed sizes of the scan and the differential heads are held to.
    whole = (
        9 * by_kind["mamba"] + 9 * by_kind["attention"] + 7 * by_kind["memory_unit"]
        + 7 * by_kind["cross_attention"] + 200064 * h + 2 * h
    )
    assert counts.published_parameters(cfg) == whole
    assert round(whole / 1e9, 2) == 3.85 and round(200064 * h / 1e6, 1) == 512.2
    # Forward and backward of one 8,192-token sequence: 6 FLOPs a matrix
    # parameter a token, three passes of the two maps of 20 pairs (40 heads
    # of 64 into values of 128) over the triangle in layers 17 and 19 and
    # over the band in layer 15, and of the two scans' recurrences.
    per_token = (
        25008 * h + 6 * mlp
        + 2 * (h * 2 * di + di * (r + 2 * n) + r * di + di * h)
        + 2 * (h * 80 * d + 40 * d * h) + memory_unit + 2 * h * 40 * d
    )
    assert per_token == 696_770_560 and round(6 * per_token * 8192 / 1e12, 2) == 34.25
    triangle = 40 * 2 * (8192 * 8192 // 2) * (d + 2 * d)
    pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert counts.band_pairs(cfg) == pairs == 4_063_488
    band = 40 * 2 * pairs * (d + 2 * d)
    scan = 8192 * di * n * 6
    assert round(triangle / 1e9, 1) == 515.4 and round(band / 1e9, 1) == 62.4
    assert counts.flops_per_row(cfg) == 6 * per_token * 8192 + 3 * (2 * triangle + band + 2 * scan)
    assert round(counts.flops_per_row(cfg) / 1e12, 2) == 37.55
    moved = 8192 * 2 * (40 * d + 40 * 2 * d + 2 * 20 * d)
    assert counts.attention_fwd_work(cfg, 1) == {"flops": triangle, "bytes": moved}
    assert counts.attention_window_fwd_work(cfg, 2) == {"flops": 2 * band, "bytes": 2 * moved}
    assert counts.ssm_scan_fwd_work(cfg, 1) == {
        "flops": scan, "exps": 8192 * di * n, "bytes": 8192 * 4 * (3 * di + 2 * n),
    }
    # 671 M exponentials a layer a row; the bytes bind the published roofs:
    # 0.02 ms of FLOPs, 0.62 ms of bytes.
    assert round(8192 * di * n / 1e6) == 671
    assert round(scan / 197e12 * 1e3, 2) == 0.02
    assert round(counts.ssm_scan_fwd_work(cfg, 1)["bytes"] / 819e9 * 1e3, 2) == 0.62
    toy = {**cfg, **cfg["rehearsal"]}
    assert counts.seq_len(toy) == 64 and counts.d_inner(toy) == 128 and counts.head_dim(toy) == 8
    assert counts.band_pairs(toy) == 16 * 17 // 2 + 48 * 16


def test_the_cell_and_its_entries(cfg):
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "per-batch-epochs", 1)
    assert len(cell["why"]) <= 200 and "two scans" in cell["why"]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better in [
        (NEW_METRICS[0], "%", "higher"), (NEW_METRICS[1], "ms", "lower"),
    ]:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["layer"], m["source"], m["moves"]) == ("kernels", "device_trace", "rows_per_s")
        assert (m["unit"], m["better"]) == (unit, better) and m["workloads"][0] == CELL
    listed = {m["name"] for m in harness.metrics_for(BENCH, "per_layer", CELL)}
    assert listed >= {*NEW_METRICS, *APPENDED_TO}
    assert not [n for n in listed if n.startswith(("moe.", "resident.", "interaction."))]
    assert not listed & {"shuffle.epoch_s", "runtime.task_wait_pct"}
    assert {m["name"] for m in harness.metrics_for(BENCH, "end_to_end", CELL)} == {
        "rows_per_s", "step_p95_ms", "setup_s",
    }
    # One share of the whole step's peak, and it is the accepted one.
    assert [n for n in by_name if "mfu" in n] == ["step.mfu_pct"]


def test_what_this_pr_appended_follows_what_was_there(parent):
    """One configuration, one cell, two metrics and twelve list entries, each
    after everything the parent had (later PRs may append after them)."""
    was = parent["benchmark"]
    for kind, name in [("configs", CONFIG), ("workloads", CELL)]:
        names = [e["name"] for e in BENCH[kind]]
        assert names[: len(was[kind])] == [e["name"] for e in was[kind]]
        assert names[len(was[kind])] == name
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[len(was["per_layer"]) : len(was["per_layer"]) + 2] == NEW_METRICS
    old = {m["name"]: m for m in was["per_layer"]}
    now = {m["name"]: m for m in BENCH["per_layer"]}
    grown = [n for n in old if now[n]["workloads"] != old[n]["workloads"]]
    assert grown == APPENDED_TO
    for n in grown:
        assert now[n]["workloads"][: len(old[n]["workloads"]) + 1] == [*old[n]["workloads"], CELL]
    assert BENCH["end_to_end"] == was["end_to_end"]


def test_every_file_the_benchmark_had_is_the_parent_s(parent):
    """Byte for byte: a PR that adds to the benchmark edits no file of it."""
    assert parent["commit"].startswith("6c8e389") and len(parent["files"]) == 60
    assert all(p.startswith(("chipbench/", "tests/chipbench/")) for p in parent["files"])
    for path, digest in parent["files"].items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


def _is_subsequence(few, many) -> bool:
    it = iter(many)
    return all(x in it for x in few)


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_every_entry_accepted_at_the_parent_keeps_its_order_keys_and_first_cells(parent, kind):
    was, now = parent["benchmark"][kind], BENCH[kind]
    names = [e["name"] for e in now]
    assert len(set(names)) == len(names)
    assert _is_subsequence([e["name"] for e in was], names)
    by_name = {e["name"]: e for e in now}
    for old in was:
        new = by_name[old["name"]]
        assert list(new) == list(old), old["name"]
        for key, value in old.items():
            if key == "workloads":
                assert new[key][: len(value)] == value, old["name"]
            else:
                assert new[key] == value, (old["name"], key)
    for key in ("command", "paths", "run_seconds"):
        assert BENCH[key] == parent["benchmark"][key]


@pytest.mark.parametrize("named", [None, "transformer-xl"])
def test_a_missing_or_unknown_family_is_an_error_that_lists_every_family_found(named, cfg):
    base = os.path.join(ROOT, "chipbench", "families")
    found = sorted(
        d for d in os.listdir(base) if os.path.isfile(os.path.join(base, d, "__init__.py"))
    )
    assert {"dlrm", "lfm2_moe", "laguna", "phi4flash"} <= set(found)
    broken = {k: v for k, v in cfg.items() if k != "family"}
    if named:
        broken["family"] = named
    with pytest.raises(KeyError) as err:
        harness.load_family(broken)
    assert str(found) in str(err.value) and repr(named) in str(err.value)


def test_the_weights_from_the_seed(cfg):
    """Norms one, biases zero, ``A = -(1..N)``, ``D`` one, ``softplus(dt_bias)``
    in [0.001, 0.1], the lambdas small, matrices of deviation 1/sqrt(fan_in);
    the same seed the same weights, a seed past 2**31 another."""
    import numpy as np

    toy = {**cfg, **cfg["rehearsal"]}
    family = harness.load_family(toy)
    w = family.reference.init_params(toy, 2**31 + 5)
    assert sorted(w) == sorted(family.counts.leaf_shapes(toy))
    assert all(w[k].shape == s for k, s in family.counts.leaf_shapes(toy).items())
    for leaf in ("l14.norm1.scale", "l15.attn.norm", "l16.ssm.D", "final_norm.scale"):
        assert np.all(np.asarray(w[leaf]) == 1.0), leaf
    for leaf in ("l14.norm2.bias", "l15.attn.k_bias", "l14.ssm.conv_bias", "l19.cross.o_bias"):
        assert not np.any(np.asarray(w[leaf])), leaf
    assert np.allclose(np.exp(np.asarray(w["l14.ssm.A_log"])), np.arange(1, 5)[None, :])
    step = np.log1p(np.exp(np.asarray(w["l16.ssm.dt_bias"])))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001
    assert 0.05 < float(np.std(np.asarray(w["l17.attn.lq1"]))) < 0.2
    assert float(np.std(np.asarray(w["l14.mlp.w1"]))) == pytest.approx(1 / 8, rel=0.1)
    again = family.reference.init_params(toy, 2**31 + 5)
    other = family.reference.init_params(toy, 5)
    assert np.array_equal(w["embed"], again["embed"])
    assert not np.array_equal(w["embed"], other["embed"])


# -- the new readers -------------------------------------------------------------------


def _ctx(cfg, ops=(), modules=(), peaks=True):
    return {
        "cfg": cfg, "family": harness.load_family(cfg), "chips": 1,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9} if peaks else None,
        "loader_stats": {},
        "trace": {"ops": list(ops), "modules": list(modules)},
    }


OPS = [
    ("%selective_scan_fwd.4 = (f32[1,8192,5,8,128], f32[1,128,5,16,8,128]) custom-call(", 0, 1_600_000),
    ("%selective_scan_fwd.5 = (f32[1,8192,5,8,128], f32[1,128,5,16,8,128]) custom-call(", 0, 1_700_000),
    ("%selective_scan_fwd.6 = (f32[1,8192,5,8,128], f32[1,128,5,16,8,128]) custom-call(", 0, 1_800_000),
    ("%selective_scan_bwd.2 = (f32[1,8192,5,8,128]) custom-call(", 0, 6_000_000),
    ("%flash_attention_fwd.2 = bf16[40,8192,128] custom-call", 0, 15_000_000),
    ("%flash_attention_window_fwd.1 = bf16[40,8192,128] custom-call", 0, 3_300_000),
    # Reads a kernel's output: names it as an operand, is not it.
    ("%fusion.9 = f32[8192,5120] fusion(f32[1,8192,5,8,128] %selective_scan_fwd.4)", 0, 90_000_000),
]
MODULES = [("jit_step_fn(1)", 0, 480_000_000), ("jit_step_fn(1)", 0, 481_000_000), ("jit_unpack", 0, 1000)]


def test_the_scan_reader_divides_the_recurrence_s_work_by_the_forward_kernel_s_time(cfg):
    read = harness.load_reader("ssm.scan_fwd_roofline")
    bytes_s = 8192 * 4 * (3 * 5120 + 32) / 819e9
    assert bytes_s > 8192 * 5120 * 16 * 6 / 197e12  # the bytes bind
    assert read(_ctx(cfg, OPS)) == pytest.approx(100.0 * bytes_s / 0.0017)
    assert read(_ctx(cfg, OPS)) < 100.0
    # The backward's events alone, no peaks, no trace, another family: nothing.
    assert read(_ctx(cfg, OPS[3:])) is None
    assert read(_ctx(cfg, OPS, peaks=False)) is None
    assert read({**_ctx(cfg), "trace": None}) is None
    for other in ("laguna-seq8k-train", "lfm2-seq8k-train", "stream-train"):
        assert read(_ctx(harness.load_cell(BENCH, other)[1], OPS)) is None


def test_the_scans_time_a_step_is_both_kernels_events_over_the_steps(cfg):
    read = harness.load_reader("ssm.scan_ms")
    assert read(_ctx(cfg, OPS, MODULES)) == pytest.approx((1.6 + 1.7 + 1.8 + 6.0) / 2)
    # No step, no scan, no trace: nothing, never 0.
    assert read(_ctx(cfg, OPS)) is None
    assert read(_ctx(cfg, OPS[4:], MODULES)) is None
    assert read({**_ctx(cfg), "trace": None}) is None
    # A program without the kernels (the parent's, any other cell's).
    assert read(_ctx(harness.load_cell(BENCH, "laguna-seq8k-train")[1], OPS[4:], MODULES)) is None


def test_the_accepted_attention_readers_take_this_family_s_work(cfg):
    """Two maps of 20 pairs: 40 heads of 64 into values of 128."""
    triangle_s = 40 * 2 * (8192 * 8192 // 2) * 192 / 197e12
    band_s = 40 * 2 * 4_063_488 * 192 / 197e12
    full = harness.load_reader("attention.fwd_roofline")
    window = harness.load_reader("attention.window_fwd_roofline")
    assert full(_ctx(cfg, OPS)) == pytest.approx(100.0 * triangle_s / 0.015)
    assert window(_ctx(cfg, OPS)) == pytest.approx(100.0 * band_s / 0.0033)
    assert full(_ctx(cfg, OPS)) < 100.0 and window(_ctx(cfg, OPS)) < 100.0
