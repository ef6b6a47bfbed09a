"""The configuration ``laguna-xs2-ep8``, its family and its cell (ISSUE 32):
the file against the catalog's row, the counts against hand arithmetic, the
two new readers on synthetic records, and what five pins of
``test_lfm2_family.py`` guard (``tests/conftest.py`` marks them outdated),
stated so that it stays true when a family, a cell or a metric is appended:
every accepted entry keeps its relative order, its keys and its first cells;
the error lists every family found; every file the benchmark had at the
parent commit is here byte for byte. CPU only."""

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
CELL = "laguna-seq8k-train"
CONFIG = "laguna-xs2-ep8"
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
NEW_METRICS = ["attention.window_fwd_roofline", "moe.fallback_pct"]

# The catalog's row of Laguna-XS.2 (the model-configs guide), every key of
# its ``config``.
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1,
        },
        "original_max_position_embeddings": 4096,
    },
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(BENCH, CELL)[1]


@pytest.fixture(scope="module")
def parent():
    """``tests/chipbench/parent_01a534d.json``: the digest of every file of
    ``chipbench/`` and ``tests/chipbench/`` at the parent commit, and that
    commit's ``BENCHMARK.json``."""
    with open(os.path.join(HERE, "parent_01a534d.json")) as f:
        return json.load(f)


def test_the_file_holds_the_published_widths_and_states_every_cut(cfg):
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["source"].startswith(SOURCE) and len(entry["source"]) <= 200
    assert "workloads.md" in entry["source"]
    assert cfg["source"] == SOURCE and cfg["family"] == "laguna"
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size", "num_rows"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] != value, key
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key
    # No width among the keys that were cut.
    assert not [k for k in reduced if k.endswith(("_size", "_dim", "_rank")) and k != "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 32, 12544)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["published"]["num_experts"]
    assert "8 chips" in cfg["deployment"] and "experts 0-31" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 8
    assert set(cfg["guarantees"]) == {"exactly_once", "rows_intact", "order"}
    assert cfg["data_spec"] == {"tokens": [0, 12544, "int32", 8192]}
    assert (cfg["loader"], cfg["batch_size"], cfg["num_rows"]) == ("stream", 1, 2048)
    assert (cfg["num_files"], cfg["row_groups_per_file"], cfg["num_reducers"]) == (8, 4, 4)
    assert set(cfg["limits"]) == {"grad_diff", "grad_norm_mid_gap", "change_norm_gap"}
    assert cfg["limits_why"] and cfg["model"]["recomputation"]
    assert set(cfg["kernels"]) == {"attention_block_q", "attention_block_k", "expert_row_tile"}
    toy = cfg["rehearsal"]
    assert (toy["hidden_size"], toy["head_dim"], toy["sliding_window"]) == (64, 16, 16)
    assert toy["num_attention_heads_per_layer"][:5] == [6, 8, 8, 8, 6]
    assert (toy["num_experts"], toy["published"]["num_experts"]) == (4, 16)
    assert toy["num_experts_per_tok"] == 2 and toy["data_spec"]["tokens"][3] == 64


def test_the_counts_against_hand_arithmetic(cfg):
    counts = harness.load_family(cfg).counts
    assert counts.layers(cfg) == [
        (0, "full_attention", 48, True), (1, "sliding_attention", 64, False),
        (2, "sliding_attention", 64, False), (3, "sliding_attention", 64, False),
        (4, "full_attention", 48, False),
    ]
    h = 2048
    full = 2 * h * 48 * 128 + 2 * h * 1024
    sliding = 2 * h * 64 * 128 + 2 * h * 1024
    dense = 3 * h * 8192
    expert = 3 * h * 512
    router = h * 256
    assert round(full / 1e6, 2) == 29.36 and round(sliding / 1e6, 2) == 37.75
    assert round(dense / 1e6, 2) == 50.33 and expert == 3_145_728
    assert round(router / 1e6, 3) == 0.524
    sparse_layer = expert + router + 32 * expert  # shared, router, 32 held
    assert round((sliding + sparse_layer) / 1e6, 2) == 142.08
    assert round((full + sparse_layer) / 1e6, 2) == 133.69
    matrices = (
        2 * 12544 * h + full + dense + 3 * (sliding + sparse_layer) + full + sparse_layer
    )
    assert round(2 * 12544 * h / 1e6, 2) == 51.38 and round(matrices / 1e6) == 691
    norms = 11 * h
    assert counts.num_parameters(cfg) == matrices + norms == 691_034_112
    assert counts.state_bytes(cfg) == 12 * 691_034_112
    assert round(16 * counts.num_parameters(cfg) / 1e9, 2) == 11.06
    assert 16 * counts.num_parameters(cfg) > 0.25 * 16e9
    # The whole model by the same arithmetic is the published 33.4 B.
    whole = (
        2 * 100352 * h + 10 * full + 30 * sliding + dense
        + 39 * (256 * expert + expert + router)
    )
    assert round(whole / 1e9, 2) == 33.44
    # Forward and backward of one 8,192-token sequence: 6 FLOPs a matrix
    # parameter a token with the routed experts at 8 x 32/256 of one a token,
    # three passes of the triangle in the two full layers (48 heads) and of
    # the band in the three sliding ones (64 heads).
    per_token = (
        12544 * h + 2 * full + 3 * sliding + dense + 4 * (router + expert + expert)
    )
    assert round(per_token / 1e6, 1) == 275.3
    triangle = 48 * 2 * 2 * (8192 * 8192 // 2) * 128
    pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert counts.band_pairs(cfg) == pairs == 4_063_488
    band = 64 * 2 * 2 * pairs * 128
    assert round(triangle / 1e12, 3) == 0.825 and round(band / 1e12, 3) == 0.133
    assert counts.flops_per_row(cfg) == 6 * per_token * 8192 + 3 * (2 * triangle + 3 * band)
    assert round(counts.flops_per_row(cfg) / 1e12, 1) == 19.7
    # Run as plain causal the three sliding layers alone would be 9.9 TFLOP.
    assert round(3 * 3 * 64 * 4 * (8192 * 8192 // 2) * 128 / 1e12, 1) == 9.9
    assert counts.attention_fwd_work(cfg, 1) == {
        "flops": triangle, "bytes": 8192 * 128 * 2 * (2 * 48 + 2 * 8),
    }
    assert counts.attention_window_fwd_work(cfg, 2) == {
        "flops": 2 * band, "bytes": 2 * 8192 * 128 * 2 * (2 * 64 + 2 * 8),
    }
    assert counts.tokens_routed_here(cfg, 1) == 8192 * 8 // 8 == 32 * 256
    work = counts.experts_fwd_work(cfg, 8192)
    assert work == {
        "flops": 8192 * 2 * expert, "bytes": 32 * expert * 2 + 8192 * 2 * (2 * h + 4 * 512),
    }
    # At 256 tokens an expert the weights' bytes bind: 0.26 ms of FLOPs,
    # 0.37 ms of bytes a layer.
    assert round(work["flops"] / 197e12 * 1e3, 2) == 0.26
    assert round(work["bytes"] / 819e9 * 1e3, 2) == 0.37
    toy = {**cfg, **cfg["rehearsal"]}
    assert counts.seq_len(toy) == 64 and counts.experts_routed(toy) == 16
    assert counts.band_pairs(toy) == 16 * 17 // 2 + 48 * 16
    assert counts.heads_of(toy, "full_attention") == 6
    assert counts.heads_of({**cfg, "first_layer": 1, "num_hidden_layers": 3}, "full_attention") == 0


def test_the_cell_and_its_entries(cfg):
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "per-batch-epochs", 1)
    assert len(cell["why"]) <= 200 and "attention its whole share" in cell["why"]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer, source in [
        (NEW_METRICS[0], "kernels", "device_trace"),
        (NEW_METRICS[1], "train step", "program_counter"),
    ]:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["layer"], m["source"], m["moves"], m["unit"]) == (
            layer, source, "rows_per_s", "%",
        )
        assert m["workloads"][0] == CELL
    assert by_name[NEW_METRICS[0]]["better"] == "higher"
    assert by_name[NEW_METRICS[1]]["better"] == "lower"
    listed = {m["name"] for m in harness.metrics_for(BENCH, "per_layer", CELL)}
    assert listed >= {
        *NEW_METRICS, "device.idle_pct", "step.mfu_pct", "loader.wait_pct",
        "loader.max_step_ms", "step.device_ms", "staging.direct_pct",
        "loader.first_batch_s", "queue.get_wait_pct", "staging.max_transfer_ms",
        "staging.unpack_ms", "attention.fwd_roofline", "moe.experts_roofline",
        "moe.load_max_over_mean",
    }
    assert not listed & {
        "interaction.fwd_roofline", "resident.handover_ms",
        "resident.permute_roofline", "shuffle.epoch_s", "runtime.task_wait_pct",
    }
    assert {m["name"] for m in harness.metrics_for(BENCH, "end_to_end", CELL)} == {
        "rows_per_s", "step_p95_ms", "setup_s",
    }
    # One share of the whole step's peak, and it is the accepted one.
    assert [n for n in by_name if "mfu" in n] == ["step.mfu_pct"]


# -- what the five outdated pins guard (see tests/conftest.py) ----------------------


def test_every_file_the_benchmark_had_is_the_parent_s(parent):
    """Byte for byte: a PR that adds to the benchmark edits no file of it."""
    assert parent["commit"].startswith("01a534d") and len(parent["files"]) == 51
    assert all(
        p.startswith(("chipbench/", "tests/chipbench/")) for p in parent["files"]
    )
    for path, digest in parent["files"].items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


def _is_subsequence(few, many) -> bool:
    it = iter(many)
    return all(x in it for x in few)


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_every_accepted_entry_keeps_its_order_its_keys_and_its_first_cells(parent, kind):
    """Whatever has been appended since: the parent's entries are all
    here, in the order they had, each with the keys and values it had; a
    metric's list of cells may only have grown at its end."""
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
    # What was appended comes after everything that was there.
    added = [n for n in names if n not in {e["name"] for e in was}]
    assert names[len(was):] == added


@pytest.mark.parametrize("named", [None, "transformer-xl"])
def test_a_missing_or_unknown_family_is_an_error_that_lists_every_family_found(named, cfg):
    base = os.path.join(ROOT, "chipbench", "families")
    found = sorted(
        d for d in os.listdir(base) if os.path.isfile(os.path.join(base, d, "__init__.py"))
    )
    assert {"dlrm", "lfm2_moe", "laguna"} <= set(found)
    broken = {k: v for k, v in cfg.items() if k != "family"}
    if named:
        broken["family"] = named
    with pytest.raises(KeyError) as err:
        harness.load_family(broken)
    assert str(found) in str(err.value) and repr(named) in str(err.value)


# -- the new readers ---------------------------------------------------------------------


def _ctx(cfg, ops=(), layers=None, peaks=True):
    return {
        "cfg": cfg, "family": harness.load_family(cfg), "chips": 1,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9} if peaks else None,
        "loader_stats": {} if layers is None else {"layers": layers},
        "trace": {"ops": list(ops), "modules": []},
    }


def test_the_window_reader_divides_the_band_s_work_by_the_windowed_kernel_s_time(cfg):
    read = harness.load_reader("attention.window_fwd_roofline")
    ops = [
        ("%flash_attention_window_fwd.1 = bf16[64,8192,128] custom-call(...tpu_custom_call", 0, 4_000_000),
        ("%flash_attention_window_bwd_dq.1 = custom-call", 0, 90_000_000),
        ("%flash_attention_fwd.2 = bf16[48,8192,128] custom-call", 0, 22_000_000),
        ("%flash_attention_window_fwd.2 = bf16[64,8192,128] custom-call", 0, 5_000_000),
        ("%flash_attention_window_fwd.3 = bf16[64,8192,128] custom-call", 0, 6_000_000),
        # Reads the kernel's output: names it as an operand, is not it.
        ("%fusion.7 = bf16[8192,8192] fusion(bf16[64,8192,128] %flash_attention_window_fwd.3)", 0, 50_000_000),
    ]
    band_s = 64 * 2 * 2 * 4_063_488 * 128 / 197e12
    assert band_s > 8192 * 128 * 2 * (2 * 64 + 2 * 8) / 819e9  # the FLOPs bind
    assert read(_ctx(cfg, ops)) == pytest.approx(100.0 * band_s / 0.005)
    assert read(_ctx(cfg, ops)) < 100.0
    # The plain kernel's events alone, no peaks, another family: nothing.
    assert read(_ctx(cfg, ops[1:3] + ops[5:])) is None
    assert read(_ctx(cfg, ops, peaks=False)) is None
    assert read(_ctx(harness.load_cell(BENCH, "lfm2-seq8k-train")[1], ops)) is None
    assert read(_ctx(harness.load_cell(BENCH, "stream-train")[1], ops)) is None
    # The accepted reader of the full layers does not match the windowed names.
    full = harness.load_reader("attention.fwd_roofline")
    triangle_s = 48 * 2 * 2 * (8192 * 8192 // 2) * 128 / 197e12
    assert full(_ctx(cfg, ops)) == pytest.approx(100.0 * triangle_s / 0.022)


def test_the_fallback_reader_is_the_share_of_layer_executions_that_fell_back(cfg):
    read = harness.load_reader("moe.fallback_pct")
    load = {"mean": 5000.0, "max": 6500.0, "dropped": 0, "layers": 480, "fallback": 12}
    counted = {"train step": {"moe:load": {"spans": 120, "sum": load}}}
    assert read(_ctx(cfg, layers=counted)) == pytest.approx(2.5)
    load["fallback"] = 0
    assert read(_ctx(cfg, layers=counted)) == 0.0
    # A program that counted no ``fallback`` (before PR 29), no layers, no
    # counter, no stats: nothing, never 0.
    del load["fallback"]
    assert read(_ctx(cfg, layers=counted)) is None
    assert read(_ctx(cfg, layers={"train step": {"moe:load": {"spans": 0, "sum": {}}}})) is None
    assert read(_ctx(cfg, layers={"staging": {}})) is None
    assert read(_ctx(cfg)) is None
    assert read({"loader_stats": None}) is None


def test_the_accepted_experts_reader_takes_this_family_s_work(cfg):
    """``moe.experts_roofline`` on the new cell: the bytes bind here."""
    read = harness.load_reader("moe.experts_roofline")
    ops = [(f"%moe_experts_fwd.{i} = custom-call", 0, 400_000) for i in range(6)]
    expert = 3 * 2048 * 512
    bytes_s = (32 * expert * 2 + 8192 * 2 * (2 * 2048 + 4 * 512)) / 819e9
    assert read(_ctx(cfg, ops)) == pytest.approx(100.0 * bytes_s / 0.0012)
