"""The configuration ``keye-vl2-30b-a3b-ep8``, its family and its cell: the
file against the catalog's row, the counts against hand arithmetic
(this chip's 562,290,560 parameters, the whole model's 30.6 B), the four new
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
CELL = "keye-seq16k-train"
CONFIG = "keye-vl2-30b-a3b-ep8"
SOURCE = "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
NEW_METRICS = [
    "attention.sparse_fwd_roofline", "indexer.fwd_roofline", "scope.indexer_ms",
    "attention.sparse_blocks_pct",
]
APPENDED_TO = [
    "device.idle_pct", "step.mfu_pct", "loader.wait_pct", "loader.max_step_ms",
    "step.device_ms", "staging.direct_pct", "loader.first_batch_s",
    "queue.get_wait_pct", "staging.max_transfer_ms", "staging.unpack_ms",
    "moe.experts_roofline", "moe.load_max_over_mean", "moe.fallback_pct",
    "step.forward_ms", "step.backward_ms", "step.optimizer_ms", "step.unscoped_ms",
    "scope.attention_ms", "scope.experts_ms", "scope.router_ms", "scope.head_ms",
    "step.scratch_bytes",
]

# The catalog's row of Keye-VL-2.0-30B-A3B (the model-configs guide), every
# key of its ``config``.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(BENCH, CELL)[1]


@pytest.fixture(scope="module")
def parent():
    """``tests/chipbench/parent_3d3a8a6.json``: the digest of every file of
    ``chipbench/`` and ``tests/chipbench/`` at the parent commit, and that
    commit's ``BENCHMARK.json``."""
    with open(os.path.join(HERE, "parent_3d3a8a6.json")) as f:
        return json.load(f)


def test_the_file_holds_the_published_config_and_states_every_cut(cfg):
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["source"] == SOURCE == cfg["source"] and cfg["family"] == "keye"
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json" and len(entry["why"]) <= 200
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size", "num_rows"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["reduced_why"]) == reduced == set(cfg["published"])
    assert (cfg["num_hidden_layers"], cfg["first_layer"], cfg["num_experts"]) == (5, 0, 16)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["data_spec"]["tokens"] == [0, 18992, "int32", 16384]
    assert "8 chips" in cfg["deployment"] and "SigLIP" in cfg["left_out"]["vision_tower"]
    # Every point taken without a published key is stated as assumed.
    said = " ".join(cfg["assumed"])
    for point in ("mrope_section", "indexer_rope_head_dim 32", "Hadamard", "q_chunk_size",
                  "weight 1", "float32 at the highest", "norm_topk_prob"):
        assert point in said, point


def test_the_counts_against_hand_arithmetic(cfg):
    counts = harness.load_family(cfg).counts
    h, d = 2048, 128
    attention = 2 * h * 32 * d + 2 * h * 4 * d + 2 * d
    indexer = h * 16 * 64 + h * 64 + h * 16 + 2 * 64
    router, norms, expert = h * 128, 2 * h, 3 * h * 768
    assert (attention, indexer, router, norms) == (18_874_624, 2_261_120, 262_144, 4_096)
    assert attention + indexer + router + norms == 21_401_984
    layer = 21_401_984 + 16 * expert
    assert (16 * expert, layer) == (75_497_472, 96_899_456)
    vocab = 2 * 18992 * h
    assert vocab == 77_791_232
    assert counts.num_parameters(cfg) == 5 * layer + vocab + h == 562_290_560
    assert counts.state_bytes(cfg) == 12 * 562_290_560
    whole = 48 * (21_401_984 + 128 * expert) + 2 * 151936 * h
    assert 30.5e9 < whole < 30.7e9
    assert counts.selected_pairs(cfg) == 31_458_304 and counts.causal_pairs(cfg) == 134_225_920
    sparse = counts.attention_sparse_fwd_work(cfg, 1)
    assert sparse["flops"] == 32 * 4 * 31_458_304 * 128
    assert sparse["bytes"] == 16384 * 128 * 2 * (2 * 32 + 2 * 4)
    index = counts.index_fwd_work(cfg, 1)
    assert index["flops"] == 134_225_920 * 16 * 64 * 2
    assert index["bytes"] == 16384 * (16 * 64 + 64 + 16) * 4 + 16384 * 16384 // 8
    assert counts.tokens_routed_here(cfg, 1) == 16384 * 8 * 16 // 128 == 16384
    per_token = h * 18992 + 5 * (18_874_368 + h * (1024 + 64 + 16) + router + 8 * 16 * expert // 128)
    assert counts.flops_per_row(cfg) == 6 * per_token * 16384 + 3 * 5 * (
        sparse["flops"] + index["flops"]
    )


def test_the_cell_and_its_entries(cfg):
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "per-batch-epochs", 1)
    assert len(cell["why"]) <= 200 and "indexer" in cell["why"]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, source, layer in [
        (NEW_METRICS[0], "%", "higher", "device_trace", "kernels"),
        (NEW_METRICS[1], "%", "higher", "device_trace", "kernels"),
        (NEW_METRICS[2], "ms", "lower", "device_trace", "train step"),
        (NEW_METRICS[3], "%", "lower", "program_counter", "train step"),
    ]:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (unit, better, source, layer)
        assert m["moves"] == "rows_per_s" and m["workloads"] == [CELL]
    listed = {m["name"] for m in harness.metrics_for(BENCH, "per_layer", CELL)}
    assert listed >= {*NEW_METRICS, *APPENDED_TO}
    assert "attention.fwd_roofline" not in listed
    assert {m["name"] for m in harness.metrics_for(BENCH, "end_to_end", CELL)} == {
        "rows_per_s", "step_p95_ms", "setup_s",
    }
    assert [n for n in by_name if "mfu" in n] == ["step.mfu_pct"]


def test_what_this_pr_appended_follows_what_was_there(parent):
    """One configuration, one cell, four metrics and 22 list entries, each
    after everything the parent had (later PRs may append after them)."""
    was = parent["benchmark"]
    for kind, name in [("configs", CONFIG), ("workloads", CELL)]:
        names = [e["name"] for e in BENCH[kind]]
        assert names[: len(was[kind])] == [e["name"] for e in was[kind]]
        assert names[len(was[kind])] == name
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[len(was["per_layer"]) : len(was["per_layer"]) + 4] == NEW_METRICS
    old = {m["name"]: m for m in was["per_layer"]}
    now = {m["name"]: m for m in BENCH["per_layer"]}
    for n in APPENDED_TO:
        assert now[n]["workloads"][: len(old[n]["workloads"]) + 1] == [*old[n]["workloads"], CELL]
    for n in set(old) - set(APPENDED_TO):
        assert now[n]["workloads"][: len(old[n]["workloads"])] == old[n]["workloads"]
        assert CELL not in now[n]["workloads"], n
    assert BENCH["end_to_end"] == was["end_to_end"]


def test_every_file_the_benchmark_had_is_the_parent_s(parent):
    """Byte for byte: a PR that adds to the benchmark edits no file of it."""
    assert parent["commit"].startswith("3d3a8a6") and len(parent["files"]) == 88
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


def test_the_weights_from_the_seed(cfg):
    import numpy as np

    toy = {**cfg, **cfg["rehearsal"]}
    family = harness.load_family(toy)
    w = family.reference.init_params(toy, 2**31 + 5)
    assert sorted(w) == sorted(family.counts.leaf_shapes(toy))
    for leaf in ("l0.in_norm", "l3.attn.q_norm", "l2.idx.k_norm", "final_norm"):
        assert np.all(np.asarray(w[leaf]) == 1.0), leaf
    assert not np.any(np.asarray(w["l1.idx.k_bias"]))
    assert float(np.std(np.asarray(w["l0.moe.w1"]))) == pytest.approx(1 / 8, rel=0.1)
    again = family.reference.init_params(toy, 2**31 + 5)
    other = family.reference.init_params(toy, 5)
    assert np.array_equal(w["embed"], again["embed"])
    assert not np.array_equal(w["embed"], other["embed"])


# -- the new readers -------------------------------------------------------------------


def _ctx(cfg, ops=(), layers=None, peaks=True):
    return {
        "cfg": cfg, "family": harness.load_family(cfg), "chips": 1,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9} if peaks else None,
        "loader_stats": {"layers": {"train step": layers or {}}},
        "trace": {"ops": list(ops), "modules": [("jit_step_fn(1)", 0, 1_000_000_000)] * 2},
    }


OPS = [
    ("%flash_attention_sparse_fwd.3 = bf16[32,16384,128] custom-call(", 0, 80_000_000),
    ("%flash_attention_sparse_fwd.4 = bf16[32,16384,128] custom-call(", 0, 90_000_000),
    ("%flash_attention_sparse_fwd.5 = bf16[32,16384,128] custom-call(", 0, 100_000_000),
    ("%sparse_index_fwd.1 = (s32[1,32,16,16384]) custom-call(", 0, 20_000_000),
    ("%sparse_index_fwd.2 = (s32[1,32,16,16384]) custom-call(", 0, 30_000_000),
    ("%flash_attention_fwd.2 = bf16[40,8192,128] custom-call", 0, 15_000_000),
    # Reads a kernel's output: names it as an operand, is not it.
    ("%fusion.9 = f32[16384,4096] fusion(bf16[32,16384,128] %flash_attention_sparse_fwd.3)",
     0, 900_000_000),
]


def test_the_sparse_attention_reader_divides_the_selected_pairs_work_by_the_kernel_s_time(cfg):
    read = harness.load_reader("attention.sparse_fwd_roofline")
    flops_s = 32 * 4 * 31_458_304 * 128 / 197e12
    assert flops_s > 16384 * 128 * 2 * 72 / 819e9  # the FLOPs bind
    assert read(_ctx(cfg, OPS)) == pytest.approx(100.0 * flops_s / 0.090)
    assert read(_ctx(cfg, OPS)) < 100.0
    assert read(_ctx(cfg, OPS[3:6])) is None
    assert read(_ctx(cfg, OPS, peaks=False)) is None
    assert read({**_ctx(cfg), "trace": None}) is None
    for other in ("laguna-seq8k-train", "lfm2-seq8k-train", "stream-train"):
        assert read(_ctx(harness.load_cell(BENCH, other)[1], OPS)) is None


def test_the_indexer_reader_divides_the_causal_scores_work_by_the_kernel_s_time(cfg):
    read = harness.load_reader("indexer.fwd_roofline")
    flops_s = 134_225_920 * 16 * 64 * 2 / 197e12
    bytes_s = (16384 * 1104 * 4 + 16384 * 16384 // 8) / 819e9
    assert read(_ctx(cfg, OPS)) == pytest.approx(100.0 * max(flops_s, bytes_s) / 0.025)
    assert read(_ctx(cfg, OPS[:3])) is None
    assert read(_ctx(harness.load_cell(BENCH, "laguna-seq8k-train")[1], OPS)) is None


def test_the_blocks_reader_is_the_share_of_causal_blocks_holding_a_selected_pair(cfg):
    read = harness.load_reader("attention.sparse_blocks_pct")
    layers = {"sparse:select": {"spans": 3, "sum": {
        "blocks": 3 * 5 * 520, "causal_blocks": 3 * 5 * 528, "pairs": 3 * 5 * 31_458_304,
    }}}
    assert read(_ctx(cfg, layers=layers)) == pytest.approx(100.0 * 520 / 528)
    # No counter (the parent's program, any other family): nothing, never 0.
    assert read(_ctx(cfg)) is None
    assert read({**_ctx(cfg), "loader_stats": None}) is None


def test_the_indexer_scope_reader_is_the_scope_s_self_time_a_step(cfg):
    from chipbench import scope_time

    read = harness.load_reader("scope.indexer_ms")
    program = "jit_step_fn"
    table = {
        "fusion.1": "jit(step_fn)/jvp(loss)/KeyeLM/layer_0/indexer/dot_general",
        "sparse_index_fwd.1": "jit(step_fn)/jvp(loss)/KeyeLM/layer_0/indexer/pallas_call",
        "flash_attention_sparse_fwd.1": "jit(step_fn)/jvp(loss)/KeyeLM/layer_0/attention/pallas_call",
    }
    assert scope_time.in_scope(table["fusion.1"], "indexer")
    assert not scope_time.in_scope(table["flash_attention_sparse_fwd.1"], "indexer")
    assert not scope_time.in_scope(table["sparse_index_fwd.1"], "attention")
    ctx = _ctx(cfg, layers={"step:ops": {"program": program, "table": table}})
    # Without the module's events the reader finds no step: nothing.
    assert read({**ctx, "trace": None}) is None
    assert read(_ctx(cfg)) is None
