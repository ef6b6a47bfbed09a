"""The configuration ``kimi-vl-a3b-ep8``, its family and its cell: the file
against the catalog's row, the counts against hand arithmetic (this chip's
568,484,608 parameters, the whole model's 15.96 B), the two new readers on
synthetic records, and pins of what this PR appended, stated so that they
stay true when a later PR appends a family, a cell or a metric: every entry
accepted at the parent commit keeps its order, its keys and its first
cells; every file the benchmark had there is here byte for byte. CPU
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
CELL = "kimi-seq16k-train"
CONFIG = "kimi-vl-a3b-ep8"
SOURCE = "https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json"
NEW_METRICS = ["attention.latent_fwd_roofline", "scope.latent_ms"]
APPENDED_TO = [
    "device.idle_pct", "step.mfu_pct", "step.device_ms", "loader.wait_pct",
    "loader.max_step_ms", "loader.first_batch_s", "staging.direct_pct",
    "staging.max_transfer_ms", "staging.unpack_ms", "queue.get_wait_pct",
    "moe.experts_roofline", "moe.load_max_over_mean", "moe.fallback_pct",
    "step.forward_ms", "step.backward_ms", "step.optimizer_ms", "step.unscoped_ms",
    "step.scratch_bytes", "scope.attention_ms", "scope.experts_ms", "scope.router_ms",
    "scope.dense_ffn_ms", "scope.head_ms",
]

# The catalog's row of Kimi-VL-A3B-Instruct (the model-configs guide), every
# key of its ``config``.
PUBLISHED = {
    "vocab_size": 163840, "max_position_embeddings": 131072, "hidden_size": 2048,
    "intermediate_size": 11264, "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2, "n_routed_experts": 64, "ep_size": 1,
    "routed_scaling_factor": 2.446, "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
    "moe_layer_freq": 1, "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000,
    "rope_scaling": None, "attention_bias": False, "tie_word_embeddings": False,
}


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(BENCH, CELL)[1]


@pytest.fixture(scope="module")
def parent():
    """``tests/chipbench/parent_56a63b9.json``: the digest of every file of
    ``chipbench/`` and ``tests/chipbench/`` at the parent commit, and that
    commit's ``BENCHMARK.json``."""
    with open(os.path.join(HERE, "parent_56a63b9.json")) as f:
        return json.load(f)


def test_the_file_holds_the_published_config_and_states_every_cut(cfg):
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["source"] == SOURCE == cfg["source"] and cfg["family"] == "kimi"
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json" and len(entry["why"]) <= 200
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size", "num_rows"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["reduced_why"]) == reduced == set(cfg["published"])
    assert (cfg["num_hidden_layers"], cfg["first_layer"], cfg["n_routed_experts"]) == (5, 0, 8)
    # The expert readers of the benchmark take the count held as ``num_experts``.
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    assert cfg["rehearsal"]["num_experts"] == cfg["rehearsal"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["data_spec"]["tokens"] == [0, 20480, "int32", 16384]
    assert "8 chips" in cfg["deployment"] and "MoonViT" in cfg["left_out"]["vision_tower"]
    # Every point taken without a published key is stated as assumed.
    said = " ".join(cfg["assumed"])
    for point in ("half-split", "interleaved", "held constant", "n_group 1", "q_lora_rank null",
                  "2 x 1,408", "sqrt(192)", "float32 at the highest", "learning rate of 1e-5"):
        assert point in said, point
    assert "temp" in cfg["memory"] and "GB" in cfg["memory"]


def test_the_counts_against_hand_arithmetic(cfg):
    counts = harness.load_family(cfg).counts
    h = 2048
    mla = h * 16 * 192 + h * 576 + 512 + 512 * 16 * 256 + 16 * 128 * h
    assert mla == 13_763_072
    norms, router, shared, expert = 2 * h, h * 64 + 64, 3 * h * 2816, 3 * h * 1408
    outside = mla + norms + router + shared
    assert (router, shared, outside, expert) == (131_136, 17_301_504, 31_199_808, 8_650_752)
    dense = mla + norms + 3 * h * 11264
    assert dense == 82_973_184
    vocab = 2 * 20480 * h
    assert counts.num_parameters(cfg) == dense + 4 * (outside + 8 * expert) + vocab + h == 568_484_608
    assert counts.state_bytes(cfg) == 12 * 568_484_608
    whole = dense + 26 * (outside + 64 * expert) + 2 * 163840 * h
    assert 15.95e9 < whole < 15.97e9
    assert counts.causal_pairs(cfg) == 134_225_920
    work = counts.attention_latent_fwd_work(cfg, 1)
    assert work["flops"] == 134_225_920 * 16 * (192 + 128) * 2
    assert work["bytes"] == 16384 * 2 * (16 * 192 + 16 * 128 + 64 + 2 * 16 * 128)
    assert counts.tokens_routed_here(cfg, 1) == 16384 * 6 * 8 // 64 == 12288
    assert counts.experts_fwd_work(cfg, 12288)["flops"] == 12288 * 6 * h * 1408
    per_token = h * 20480 + 5 * (mla - 512) + 3 * h * 11264 + 4 * (
        h * 64 + shared + 6 * 8 * expert // 64
    )
    assert counts.flops_per_row(cfg) == 6 * per_token * 16384 + 3 * 5 * work["flops"]


def test_the_cell_and_its_entries(cfg):
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "per-batch-epochs", 1)
    assert len(cell["why"]) <= 200 and "latent attention" in cell["why"]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, layer in [
        (NEW_METRICS[0], "%", "higher", "kernels"),
        (NEW_METRICS[1], "ms", "lower", "train step"),
    ]:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (unit, better, "device_trace", layer)
        assert m["moves"] == "rows_per_s" and m["workloads"] == [CELL]
    listed = {m["name"] for m in harness.metrics_for(BENCH, "per_layer", CELL)}
    assert listed == {*NEW_METRICS, *APPENDED_TO}
    assert {m["name"] for m in harness.metrics_for(BENCH, "end_to_end", CELL)} == {
        "rows_per_s", "step_p95_ms", "setup_s",
    }
    assert [n for n in by_name if "mfu" in n] == ["step.mfu_pct"]


def test_what_this_pr_appended_follows_what_was_there(parent):
    """One configuration, one cell, two metrics and 23 list entries, each
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
    for n in APPENDED_TO:
        assert now[n]["workloads"][: len(old[n]["workloads"]) + 1] == [*old[n]["workloads"], CELL]
    for n in set(old) - set(APPENDED_TO):
        assert now[n]["workloads"][: len(old[n]["workloads"])] == old[n]["workloads"]
        assert CELL not in now[n]["workloads"], n
    assert BENCH["end_to_end"] == was["end_to_end"]


def test_every_file_the_benchmark_had_is_the_parent_s(parent):
    """Byte for byte: a PR that adds to the benchmark edits no file of it."""
    assert parent["commit"].startswith("56a63b9") and len(parent["files"]) == 99
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
    for leaf in ("l0.in_norm", "l3.attn.kv_norm", "l2.post_norm", "final_norm"):
        assert np.all(np.asarray(w[leaf]) == 1.0), leaf
    assert float(np.std(np.asarray(w["l1.moe.bias"]))) == pytest.approx(0.01, rel=0.5)
    assert "l0.moe.bias" not in w and "l0.ffn.w1" in w and "l1.shared.w1" in w
    assert float(np.std(np.asarray(w["l1.moe.w1"]))) == pytest.approx(1 / 8, rel=0.1)
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
    ("%flash_attention_latent_fwd.3 = bf16[16,16384,128] custom-call(", 0, 30_000_000),
    ("%flash_attention_latent_fwd.4 = bf16[16,16384,128] custom-call(", 0, 40_000_000),
    ("%flash_attention_latent_fwd.5 = bf16[16,16384,128] custom-call(", 0, 50_000_000),
    ("%flash_attention_latent_bwd_dkv.1 = bf16[16,16384,128] custom-call(", 0, 90_000_000),
    ("%flash_attention_fwd.2 = bf16[40,8192,128] custom-call", 0, 15_000_000),
    # Reads a kernel's output: names it as an operand, is not it.
    ("%fusion.9 = bf16[16384,2048] fusion(bf16[16,16384,128] %flash_attention_latent_fwd.3)",
     0, 900_000_000),
]


def test_the_latent_attention_reader_divides_the_causal_work_by_the_kernel_s_time(cfg):
    read = harness.load_reader("attention.latent_fwd_roofline")
    flops_s = 134_225_920 * 16 * 320 * 2 / 197e12
    assert flops_s > 16384 * 2 * (16 * 192 + 16 * 128 + 64 + 32 * 128) / 819e9  # the FLOPs bind
    assert read(_ctx(cfg, OPS)) == pytest.approx(100.0 * flops_s / 0.040)
    assert read(_ctx(cfg, OPS)) < 100.0
    assert read(_ctx(cfg, OPS[3:5])) is None
    assert read(_ctx(cfg, OPS, peaks=False)) is None
    assert read({**_ctx(cfg), "trace": None}) is None
    for other in ("laguna-seq8k-train", "keye-seq16k-train", "stream-train"):
        assert read(_ctx(harness.load_cell(BENCH, other)[1], OPS)) is None
    # The sisters' readers find nothing of the latent kernels.
    for name in ("attention.fwd_roofline", "attention.sparse_fwd_roofline"):
        sister = harness.load_reader(name)
        assert sister(_ctx(harness.load_cell(BENCH, "laguna-seq8k-train")[1], OPS[:4])) is None


def test_the_latent_scope_reader_is_a_part_of_the_attention_scope(cfg):
    from chipbench import scope_time

    read = harness.load_reader("scope.latent_ms")
    attention = harness.load_reader("scope.attention_ms")
    table = {
        "fusion.1": "jit(step_fn)/jvp(loss)/KimiLM/layer_1/self_attn/attention/latent/dot_general",
        "fusion.2": "jit(step_fn)/transpose(jvp(loss))/KimiLM/layer_1/self_attn/attention/latent/mul",
        "flash_attention_latent_fwd.1": "jit(step_fn)/jvp(loss)/KimiLM/layer_1/self_attn/attention/pallas_call",
        "fusion.3": "jit(step_fn)/jvp(loss)/KimiLM/layer_1/mlp/experts/dot_general",
    }
    assert scope_time.in_scope(table["fusion.1"], "latent")
    assert not scope_time.in_scope(table["flash_attention_latent_fwd.1"], "latent")
    ops = [("%fusion.1 = f32[1]", 0, 4_000_000), ("%fusion.2 = f32[1]", 10_000_000, 2_000_000),
           ("%flash_attention_latent_fwd.1 = bf16[1]", 20_000_000, 30_000_000),
           ("%fusion.3 = f32[1]", 60_000_000, 8_000_000)]
    ctx = _ctx(cfg, ops, layers={"step:ops": {"program": "jit_step_fn", "table": table}})
    ctx["trace"]["modules"] = [("jit_step_fn(1)", 0, 100_000_000)]
    assert read(ctx) == pytest.approx(6.0)
    assert attention(ctx) == pytest.approx(36.0)
    # Without the module's events or the table the reader finds no step: nothing.
    assert read({**ctx, "trace": None}) is None
    assert read(_ctx(cfg)) is None
