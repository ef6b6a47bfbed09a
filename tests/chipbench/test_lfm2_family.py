"""The configuration ``lfm2-24b-a2b-ep8``, its family and its cell (ISSUE
28): the counts against hand arithmetic, the file against the catalog's row,
the new readers on synthetic records, and that everything the benchmark had
is what the parent commit had. CPU only."""

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
CELL = "lfm2-seq8k-train"
CONFIG = "lfm2-24b-a2b-ep8"
NEW_METRICS = ["attention.fwd_roofline", "moe.experts_roofline", "moe.load_max_over_mean"]

# The catalog's row of LFM2-24B-A2B (the model-configs guide), every number
# of its ``config``.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + [
    "full_attention", "conv",
]


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(BENCH, CELL)[1]


def test_the_file_holds_the_published_widths_and_states_every_cut(cfg):
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    )
    assert cfg["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size", "num_rows"}
    assert cfg["layer_types"] == LAYER_TYPES
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] != value, key
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key
    # No width among the keys that were cut.
    assert not [k for k in reduced if k.endswith(("_size", "_dim", "_rank")) and k != "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 8, 8192)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "8 chips" in cfg["deployment"] and "experts 0-7" in cfg["deployment"]
    assert cfg["assumed"] and set(cfg["guarantees"]) == {"exactly_once", "rows_intact", "order"}
    assert cfg["data_spec"] == {"tokens": [0, 8192, "int32", 8192]}
    assert (cfg["loader"], cfg["batch_size"], cfg["num_rows"]) == ("stream", 4, 2048)


def test_the_counts_against_hand_arithmetic(cfg):
    counts = harness.load_family(cfg).counts
    assert counts.layers(cfg) == [
        (1, "conv", True), (2, "full_attention", False), (3, "conv", False),
        (4, "conv", False), (5, "conv", False),
    ]
    h = 2048
    conv = h * 3 * h + h * h
    attention = h * 2048 + 2 * h * 512 + 2048 * h
    dense = 3 * h * 11776
    expert = 3 * h * 1536
    matrices = (
        conv + dense + attention + 3 * conv + 4 * h * 64 + 4 * 8 * expert + 2 * 8192 * h
    )
    assert round(conv / 1e6, 2) == 16.78 and round(attention / 1e6, 2) == 10.49
    assert round(dense / 1e6, 2) == 72.35 and round(expert / 1e6, 2) == 9.44
    # (ISSUE 28 adds its own parts up to 485.5 M; they are 89.1 + 60.8 + 0.5
    # + 302.0 + 33.6 = 486.0 M.)
    assert round(matrices / 1e6, 1) == 486.0
    small = 11 * h + 2 * 64 + 4 * h * 3 + 4 * 64  # norms, taps, selection bias
    assert counts.num_parameters(cfg) == matrices + small == 486_062_464
    assert counts.state_bytes(cfg) == 12 * 486_062_464
    assert 16 * counts.num_parameters(cfg) > 0.25 * 16e9
    # Forward and backward of one 8,192-token sequence: 6 FLOPs a matrix
    # parameter a token, the experts at 4 x 8/64 of one a token, and three
    # passes of the causal attention.
    per_token = conv + dense + attention + 3 * conv + 4 * h * 64 + 8192 * h + 4 * expert // 2
    triangle = 32 * 2 * 2 * 8192 * 8192 * 64 // 2
    assert counts.flops_per_row(cfg) == 6 * per_token * 8192 + 3 * triangle
    assert round(counts.flops_per_row(cfg) / 8192 / 1e9, 2) == 1.22
    assert counts.attention_fwd_work(cfg, 4) == {
        "flops": 4 * triangle, "bytes": 4 * 8192 * 64 * 2 * (2 * 32 + 2 * 8),
    }
    assert counts.tokens_routed_here(cfg, 4) == 4 * 8192 * 4 // 8 == 8 * 2048
    assert counts.experts_fwd_work(cfg, 16384) == {
        "flops": 16384 * 2 * expert,
        "bytes": 8 * expert * 2 + 16384 * 2 * (2 * h + 4 * 1536),
    }
    toy = {**cfg, **cfg["rehearsal"]}
    assert counts.seq_len(toy) == 64 and counts.experts_routed(toy) == 16


def test_the_cell_and_its_entries_are_additions_at_the_end():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[-1] == CELL
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "per-batch-epochs", 1)
    assert len(cell["why"]) <= 200 and "epoch" in cell["why"]
    assert [m["name"] for m in BENCH["per_layer"]][-3:] == NEW_METRICS
    for m in BENCH["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    listed = [m["name"] for m in harness.metrics_for(BENCH, "per_layer", CELL)]
    assert set(NEW_METRICS) <= set(listed)
    assert {"device.idle_pct", "step.mfu_pct", "step.device_ms", "staging.direct_pct"} <= set(listed)
    assert "interaction.fwd_roofline" not in listed and "resident.handover_ms" not in listed
    # The window lies inside one epoch: no ``shuffle:epoch`` begins and ends
    # in it, and the next epoch's pool tasks end in it only by a race with
    # the profiler's start, so the cell does not promise those two.
    assert "shuffle.epoch_s" not in listed and "runtime.task_wait_pct" not in listed
    assert {m["name"] for m in harness.metrics_for(BENCH, "end_to_end", CELL)} == {
        "rows_per_s", "step_p95_ms", "setup_s",
    }


def test_everything_the_benchmark_had_is_the_parent_s():
    """Every file of ``chipbench/`` and ``tests/chipbench/`` at commit
    ac23546 is here byte for byte, and ``BENCHMARK.json`` without this PR's
    entries and without the new cell's name in the accepted lists is that
    commit's."""
    with open(os.path.join(HERE, "parent_ac23546.json")) as f:
        parent = json.load(f)
    assert len(parent) == 40
    for path, digest in parent.items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path
    bench = json.loads(json.dumps(BENCH))
    bench["configs"] = [c for c in bench["configs"] if c["name"] != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in NEW_METRICS]
    for m in bench["per_layer"]:
        if m["workloads"][-1] == CELL:
            m["workloads"] = m["workloads"][:-1]
        assert CELL not in m["workloads"]
    assert hashlib.sha256(json.dumps(bench, sort_keys=True).encode()).hexdigest() == (
        "30873faf9917eea93c0068bc08792f15d6ec58fc924651ab01675ab8212491cb"
    )


# -- what the two outdated tests guard (see conftest.py) -------------------------------


@pytest.mark.parametrize("named", [None, "transformer-xl"])
def test_a_missing_or_unknown_family_is_an_error_that_lists_every_family(named, cfg):
    found = sorted(
        d for d in os.listdir(os.path.join(ROOT, "chipbench", "families"))
        if os.path.isfile(os.path.join(ROOT, "chipbench", "families", d, "__init__.py"))
    )
    assert found == ["dlrm", "lfm2_moe"]
    broken = {k: v for k, v in cfg.items() if k != "family"}
    if named:
        broken["family"] = named
    with pytest.raises(KeyError) as err:
        harness.load_family(broken)
    assert str(found) in str(err.value) and repr(named) in str(err.value)


def test_pr_25_s_entries_keep_their_place_keys_and_cells():
    names = [m["name"] for m in BENCH["per_layer"]]
    tail = [
        "runtime.task_wait_pct", "shuffle.epoch_s", "queue.get_wait_pct",
        "staging.max_transfer_ms", "staging.unpack_ms", "resident.handover_ms",
    ]
    at = names.index(tail[0])
    assert names[at : at + 6] == tail and names[at + 6 :] == NEW_METRICS
    for m in BENCH["per_layer"][at : at + 6]:
        first = "resident-train" if m["name"].startswith("resident") else "stream-train"
        assert m["workloads"][0] == first and set(m["workloads"][1:]) <= {CELL}
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


# -- the new readers ---------------------------------------------------------------------


def _ctx(cfg, ops=(), layers=None, peaks=True):
    return {
        "cfg": cfg, "family": harness.load_family(cfg), "chips": 1,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9} if peaks else None,
        "loader_stats": {} if layers is None else {"layers": layers},
        "trace": {"ops": list(ops), "modules": []},
    }


def test_the_attention_reader_divides_the_family_s_work_by_the_kernel_s_time(cfg):
    read = harness.load_reader("attention.fwd_roofline")
    ops = [
        ("%flash_attention_fwd.1 = bf16[128,8192,64] custom-call(...tpu_custom_call", 0, 20_000_000),
        ("%flash_attention_bwd_dq.1 = custom-call", 0, 90_000_000),
        ("%flash_attention_fwd.2 = bf16[128,8192,64] custom-call", 0, 22_000_000),
    ]
    least_s = 4 * 32 * 2 * 2 * 8192 * 8192 * 64 // 2 / 197e12
    assert read(_ctx(cfg, ops)) == pytest.approx(100.0 * least_s / 0.021)
    assert read(_ctx(cfg, ops)) < 100.0
    assert read(_ctx(cfg, ops[1:2])) is None
    assert read(_ctx(cfg, ops, peaks=False)) is None
    dlrm = harness.load_cell(BENCH, "stream-train")[1]
    assert read(_ctx(dlrm, ops)) is None


def test_the_experts_reader_takes_the_step_s_own_count_of_tokens(cfg):
    read = harness.load_reader("moe.experts_roofline")
    ops = [(f"%moe_experts_fwd.{i} = custom-call", 0, 1_000_000) for i in range(6)]
    ops.append(("%moe_experts_bwd_weights.1 = custom-call", 0, 9_000_000))
    expert = 3 * 2048 * 1536
    even = 16384 * 2 * expert / 197e12
    assert read(_ctx(cfg, ops)) == pytest.approx(100.0 * even / 0.003)
    counted = {"train step": {"moe:load": {"spans": 5, "sum": {"mean": 5000.0, "max": 6500.0}}}}
    assert read(_ctx(cfg, ops, counted)) == pytest.approx(
        100.0 * (8000 * 2 * expert / 197e12) / 0.003
    )
    assert read(_ctx(cfg, ops[-1:])) is None
    assert read(_ctx(harness.load_cell(BENCH, "stream-train")[1], ops)) is None


def test_the_load_reader_is_the_fullest_expert_over_the_mean(cfg):
    read = harness.load_reader("moe.load_max_over_mean")
    counted = {"train step": {"moe:load": {"spans": 5, "sum": {"mean": 10240.0, "max": 10752.0}}}}
    assert read(_ctx(cfg, layers=counted)) == pytest.approx(1.05)
    assert read(_ctx(cfg, layers={"staging": {}})) is None
    assert read(_ctx(cfg)) is None
    assert read({"loader_stats": None}) is None
