"""Phi-4-mini-flash (ISSUE 34): values wider than queries and keys in the
attention kernels, the program's model against the benchmark's plain float32
reference (the scans, the memory and the shared keys and values that cross
layers, the tied vocabulary slice), what the step says it was built for, and
a model without any expert layer. CPU only, toy sizes, the kernels in the
Pallas interpreter."""

import collections
import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, harness, limits  # noqa: E402
from ray_shuffling_data_loader_tpu.models import blocks  # noqa: E402
from ray_shuffling_data_loader_tpu.models.phi4flash import (  # noqa: E402
    CROSS,
    FULL,
    MAMBA,
    MEMORY_UNIT,
    WINDOW,
    Phi4FlashConfig,
    Phi4FlashLM,
    lambda_init,
)
from ray_shuffling_data_loader_tpu.ops.flash_attention import flash_attention  # noqa: E402
from ray_shuffling_data_loader_tpu.ops.ring_attention import attention_reference  # noqa: E402
from ray_shuffling_data_loader_tpu.parallel import make_mesh  # noqa: E402

BENCH = harness.load_benchmark()
CELL = "phi4flash-seq8k-train"
SEED = 2**31 + 34


# -- (a) values wider than queries and keys ------------------------------------------


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("value_dim", [32, 8])
def test_a_value_width_of_its_own_against_the_dense_reference(window, value_dim):
    """Heads of 16 over values of 32 (a differential pair's) and of 8, plain
    and windowed, grouped two query heads a key head, a sequence that is no
    multiple of the blocks: output and the three gradients."""
    k = jax.random.split(jax.random.key(3), 4)
    b, t, h, hk, d = 2, 80, 4, 2, 16
    q = jax.random.normal(k[0], (b, t, h, d))
    key = jax.random.normal(k[1], (b, t, hk, d))
    v = jax.random.normal(k[2], (b, t, hk, value_dim))
    ct = jax.random.normal(k[3], (b, t, h, value_dim))

    def kernel(q, key, v):
        return flash_attention(
            q, key, v, causal=True, use_pallas=True, interpret=True,
            block_q=32, block_k=16, window=window,
        )

    def dense(q, key, v):
        return attention_reference(
            q, jnp.repeat(key, 2, 2), jnp.repeat(v, 2, 2), causal=True, window=window
        )

    out = kernel(q, key, v)
    assert out.shape == (b, t, h, value_dim)
    assert np.allclose(out, dense(q, key, v), atol=2e-5)
    got = jax.grad(lambda *x: jnp.sum(kernel(*x) * ct), (0, 1, 2))(q, key, v)
    want = jax.grad(lambda *x: jnp.sum(dense(*x) * ct), (0, 1, 2))(q, key, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape and np.allclose(g, w, atol=5e-5)


# -- (b) the configuration, the kinds of layers, the tree ---------------------------------


def toy_config(**over):
    """The benchmark's configuration at its rehearsal sizes, in float32
    unless told otherwise: the program then differs from the reference by
    summation order alone."""
    _, cfg, _ = harness.load_cell(BENCH, CELL)
    cfg = {**cfg, **cfg["rehearsal"]}
    cfg["model"] = {**cfg["model"], "compute_dtype": "float32"}
    return {**cfg, **over}


def two_cross_layers(**over):
    """A toy of 12 published layers, its layers 6-11: the Mamba layer that
    hands on the memory, the full layer that hands on keys and values, then
    two memory units and two cross layers."""
    cfg = toy_config(first_layer=6, num_hidden_layers=6, **over)
    cfg["published"] = {**cfg["published"], "num_hidden_layers": 12}
    return cfg


def _model_config(cfg) -> Phi4FlashConfig:
    return Phi4FlashConfig.from_dict(harness.load_family(cfg).program.model_config(cfg))


@pytest.fixture(scope="module")
def family():
    return harness.load_family(toy_config())


def test_which_published_index_is_which_kind_of_layer(family):
    _, published, _ = harness.load_cell(BENCH, CELL)
    whole = _model_config({**published, "first_layer": 0, "num_hidden_layers": 32})
    kinds = [kind for _, kind in whole.layers()]
    assert kinds[:17] == [MAMBA, WINDOW] * 8 + [MAMBA] and kinds[17] == FULL
    assert kinds[18:] == [MEMORY_UNIT, CROSS] * 7
    assert collections.Counter(kinds) == {
        MAMBA: 9, WINDOW: 8, FULL: 1, MEMORY_UNIT: 7, CROSS: 7,
    }
    kept = _model_config(published).layers()
    assert kept == [
        (14, MAMBA), (15, WINDOW), (16, MAMBA), (17, FULL), (18, MEMORY_UNIT),
        (19, CROSS),
    ]
    assert kept == family.counts.layers(published)
    toy = _model_config(two_cross_layers())
    assert toy.layers() == [
        (6, MAMBA), (7, FULL), (8, MEMORY_UNIT), (9, CROSS), (10, MEMORY_UNIT),
        (11, CROSS),
    ]
    assert toy.memory_layer == 6 and whole.memory_layer == 16
    assert whole.d_inner == 5120 and whole.dt_rank == 160 and whole.head_dim == 64
    assert lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))


def test_the_program_s_tree_is_the_configuration_s_leaf_for_leaf(family):
    """At the published sizes, by shapes alone: 697,094,272 parameters, one
    matrix for embedding and head, and the family's names there and back."""
    _, cfg, _ = harness.load_cell(BENCH, CELL)
    model = Phi4FlashLM(_model_config(cfg))
    own = jax.eval_shape(
        lambda: model.init(jax.random.key(0), {"tokens": jnp.zeros((1, 64), jnp.int32)})
    )
    sizes = [int(np.prod(x.shape)) for x in jax.tree.leaves(own)]
    assert sum(sizes) == family.counts.num_parameters(cfg) == 697_094_272
    assert "head" not in own["params"] and own["params"]["embed"].shape == (25008, 2560)
    side = family.program.Side.__new__(family.program.Side)
    side.leaves = list(family.counts.leaf_shapes(cfg))
    weights = {
        k: jax.ShapeDtypeStruct(s, jnp.float32)
        for k, s in family.counts.leaf_shapes(cfg).items()
    }
    tree = side.tree(weights)
    assert jax.tree.map(lambda x: x.shape, tree) == jax.tree.map(lambda x: x.shape, own)
    back = side.flat(tree)
    assert sorted(back) == sorted(weights) and all(back[k] is weights[k] for k in weights)


# -- (c) the program against the plain reference -----------------------------------------


def _readings(cfg, family, seed=SEED, steps=3):
    """The program's compiled step (kernels in the interpreter) and the
    plain reference over the same batches from the same weights."""
    mesh = make_mesh(devices=jax.devices()[:1])
    batches = limits.generator_batches(cfg, seed, steps)
    prog = limits.program_readings(cfg, family, mesh, seed, batches, True)
    ref_batches = [family.reference.batch_of(cfg, b) for b in batches]
    make = lambda: family.reference.init_params(cfg, seed)  # noqa: E731
    return prog, make, ref_batches


CUTS = {
    "a Mamba layer": lambda: toy_config(first_layer=14, num_hidden_layers=1),
    "a window layer": lambda: toy_config(first_layer=15, num_hidden_layers=1, batch_size=2),
    "the whole cut": lambda: toy_config(batch_size=2),
    "two readers of the memory and of k, v": two_cross_layers,
}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_the_program_follows_the_reference_in_float32(family, cut):
    """Loss of three steps, every leaf of the first gradient, every leaf's
    change after three Adam steps. In the last cut the memory is read by two
    memory units and layer 7's keys and values by itself and two cross
    layers: their gradients are sums over the readers, and layer 6's scan and
    layer 7's ``W_qkv`` would read wrong if a reader's share were lost."""
    cfg = CUTS[cut]()
    prog, make, ref_batches = _readings(cfg, family)
    ref = family.reference.Reference(cfg).follow(make, ref_batches)
    assert np.allclose(prog["loss"], ref["loss"], rtol=2e-5), (prog["loss"], ref["loss"])
    assert set(prog["grad_norm"]) == set(family.counts.leaf_shapes(cfg))
    median = float(np.median(list(ref["grad_norm"].values())))
    for leaf, want in ref["grad_norm"].items():
        assert prog["grad_norm"][leaf] == pytest.approx(want, rel=2e-3, abs=1e-6), leaf
        gap = np.linalg.norm(
            np.asarray(prog["grad_sketch"][leaf]) - np.asarray(ref["grad_sketch"][leaf])
        )
        assert gap <= 2e-3 * max(want, 1e-3 * median), (leaf, gap, want)
    numbers = check.training_numbers(prog, ref)
    assert numbers["grad_diff"] < 1e-3 and numbers["loss_gap"] < 1e-4, numbers
    assert numbers["change_norm_gap"] < 0.05, numbers
    if "readers" in cut:
        # The keys' bias alone has no gradient (a softmax ignores a shift of
        # every score): it is a leaf of its own for that.
        assert ref["grad_norm"]["l7.attn.k_bias"] < 1e-3 * median
        assert ref["grad_norm"]["l7.attn.v_bias"] > 0.01 * median


def test_without_the_kernels_loss_and_gradients_are_the_reference_s_too(family):
    """``use_pallas=False``: the ``lax.scan`` and the dense attention."""
    cfg = two_cross_layers(batch_size=2)
    weights = family.reference.init_params(cfg, SEED)
    tokens = family.reference.batch_of(cfg, limits.generator_batches(cfg, SEED, 1)[0])
    side = family.program.Side.__new__(family.program.Side)
    side.leaves = list(weights)
    model = Phi4FlashLM(
        _model_config(cfg), compute_dtype=jnp.float32, use_pallas=False
    )
    loss, grads = jax.value_and_grad(
        lambda p: model.apply(p, {"tokens": jnp.asarray(tokens)})[0]
    )(side.tree(weights))
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(
            lambda p: family.reference.loss_sum(cfg, p, jnp.asarray(tokens)) / len(tokens)
        )(weights)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    got_g = side.flat(grads)
    scale = float(np.median([float(jnp.linalg.norm(g)) for g in want_g.values()]))
    for leaf, w in want_g.items():
        assert got_g[leaf].shape == w.shape, leaf
        assert float(jnp.linalg.norm(got_g[leaf] - w)) <= 2e-3 * max(
            float(jnp.linalg.norm(w)), 1e-3 * scale
        ), leaf


def test_in_bfloat16_the_program_is_inside_the_limits_and_float8_is_not(family):
    cfg = toy_config()
    cfg["model"] = {**cfg["model"], "compute_dtype": "bfloat16"}
    assert cfg["limits"] == cfg["rehearsal"]["limits"]
    prog, make, ref_batches = _readings(cfg, family)
    reference = family.reference
    plain = reference.Reference(cfg).follow(make, ref_batches)

    def judged(side):
        numbers = check.training_numbers(side, plain)
        for name in check.PRINTED:
            numbers.pop(name)
        return check.judge(numbers, cfg["limits"])

    ok, compared = judged(prog)
    assert ok, compared
    control = reference.Reference(cfg, quant=reference.CONTROL).follow(make, ref_batches)
    ok, compared = judged(control)
    assert not ok and not compared["grad_diff"]["ok"], compared


def test_the_tied_matrix_s_gradient_is_the_embedding_s_plus_the_head_s():
    """An untied twin (``tie_word_embeddings`` false, its head the
    embedding's transpose) computes the same loss; the tied matrix's
    gradient is the twin's embedding's plus its head's."""
    import dataclasses

    cfg = _model_config(toy_config(first_layer=14, num_hidden_layers=2))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 64), 0, 256)}
    tied = Phi4FlashLM(cfg, compute_dtype=jnp.float32, use_pallas=False)
    twin = Phi4FlashLM(
        dataclasses.replace(cfg, tie_word_embeddings=False),
        compute_dtype=jnp.float32, use_pallas=False,
    )
    params = tied.init(jax.random.key(2), batch)
    assert "head" not in params["params"]
    own = twin.init(jax.random.key(2), batch)
    assert own["params"]["head"].shape == (64, 256)
    twin_params = {"params": {**params["params"], "head": params["params"]["embed"].T}}
    loss, g = jax.value_and_grad(lambda p: tied.apply(p, batch)[0])(params)
    loss2, g2 = jax.value_and_grad(lambda p: twin.apply(p, batch)[0])(twin_params)
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    both = g2["params"]["embed"] + g2["params"]["head"].T
    assert np.allclose(g["params"]["embed"], both, rtol=1e-4, atol=1e-7)
    assert float(jnp.linalg.norm(g2["params"]["head"])) > 0
    assert float(jnp.linalg.norm(g2["params"]["embed"])) > 0


def test_the_loss_is_the_mean_cross_entropy_of_every_position_but_the_last():
    """``next_token_loss`` (a sequence's logits at a time, recomputed in the
    backward pass) against the formula written out over all the logits."""
    k = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(k[0], (2, 32, 8))
    head = jax.random.normal(k[1], (8, 50))
    tokens = jax.random.randint(k[2], (2, 32), 0, 50)

    def written_out(x, head):
        log_p = jax.nn.log_softmax(jnp.einsum("bth,hv->btv", x, head)[:, :-1])
        return -jnp.mean(jnp.take_along_axis(log_p, tokens[:, 1:, None], axis=-1))

    want, want_g = jax.value_and_grad(written_out, (0, 1))(x, head)
    got, got_g = jax.value_and_grad(
        lambda x, h: blocks.next_token_loss(x, h, tokens), (0, 1)
    )(x, head)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert all(np.allclose(a, b, atol=1e-6) for a, b in zip(got_g, want_g))
    # The last position's state has no target: no gradient reaches it.
    assert not np.asarray(got_g[0][:, -1]).any()


# -- (d) the step: what it was built for, its scopes, its kernels, no expert layer ------------


def _kernel_model(**over):
    """The cut at its rehearsal sizes as the benchmark builds it (bfloat16
    compute, every kernel in the interpreter) and a batch of tokens."""
    cfg = toy_config(**over)
    kernels = cfg["kernels"]
    model = Phi4FlashLM(
        _model_config(cfg), use_pallas=True, interpret=True,
        block_q=kernels["attention_block_q"], block_k=kernels["attention_block_k"],
    )
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 64), 0, 256)}
    return model, batch


@contextlib.contextmanager
def _tracing(monkeypatch):
    from ray_shuffling_data_loader_tpu.telemetry import trace

    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    trace.reset_state()
    try:
        yield
    finally:
        monkeypatch.delenv("RSDL_TRACE")
        trace.refresh_from_env()
        trace.reset_state()


def _pallas_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_each_attention_forward_runs_once_a_step_and_each_scan_twice():
    """Layers 15, 17 and 19 keep their kernel's output and statistics; the
    two Mamba layers run their scan again where they are recomputed."""
    model, batch = _kernel_model()
    params = jax.eval_shape(lambda: model.init(jax.random.key(2), batch))
    grad = jax.grad(lambda p: model.apply(p, batch)[0])
    jaxpr = jax.make_jaxpr(grad)(params)
    assert collections.Counter(_pallas_calls(jaxpr.jaxpr)) == {
        "flash_attention_fwd": 2, "flash_attention_bwd_dkv": 2,
        "flash_attention_bwd_dq": 2, "flash_attention_window_fwd": 1,
        "flash_attention_window_bwd_dkv": 1, "flash_attention_window_bwd_dq": 1,
        "selective_scan_fwd": 4, "selective_scan_bwd": 2,
    }
    # No state of every position in the step's memory: nothing outside the
    # kernels (whose VMEM scratch holds ONE chunk's) has [seq, d_inner (a
    # block of 1,024 here), N] elements.
    assert max(_sizes_outside_kernels(jaxpr.jaxpr)) < 64 * 1024 * 4


def _sizes_outside_kernels(jaxpr):
    sizes = [1]
    for eqn in jaxpr.eqns:
        sizes += [int(np.prod(v.aval.shape)) for v in eqn.outvars if hasattr(v.aval, "shape")]
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                sizes += _sizes_outside_kernels(sub)
    return sizes


# What ``step:build`` says of the program the step compiled and kept.
BYTES = ("temp_bytes", "argument_bytes", "output_bytes", "alias_bytes", "code_bytes")


def test_the_step_says_what_it_was_built_for_and_names_its_scopes(monkeypatch):
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.parallel import init_state, make_train_step

    model, batch = _kernel_model()
    model = model.clone(use_pallas=False, interpret=False)
    mesh = make_mesh(devices=jax.devices()[:1])
    optimizer = optax.adam(1e-5)
    with _tracing(monkeypatch):
        state, shardings = init_state(model, optimizer, mesh, batch)
        step = make_train_step(model, optimizer, mesh, shardings)
        lowered = step.lower(state, batch).as_text(debug_info=True)
        state, metrics = step(state, batch)
        spans = telemetry.local_spans()
    build, *traced = [s["args"] for s in spans if s["name"] == "step:build"]
    assert build == {
        "model": "phi4flash", "layers": 6, "ssm_layers": 2, "memory_units": 1,
        "cross_layers": 1, "window": 16, "shared_from": [16, 17],
        "attention_kept": 3, "memory_kept": 1,
    }
    # The first step that ran while tracing was on says it again, once for
    # the shape it compiled, with what hangs on the batch's shape (8 heads
    # a layer, 6 blocks with work a head of the full and the cross layer, 5
    # of the windowed one, a grid step each) and the compiled program's bytes.
    blocks = 8 * (6 + 6 + 5)
    (traced,) = traced
    sizes = {k: traced.pop(k) for k in BYTES}
    assert traced == {
        **build, "attention_grid_steps": blocks, "attention_blocks": blocks,
    }
    assert sizes["argument_bytes"] > 0 and sizes["temp_bytes"] > 0
    # A model without experts: the loss alone, and no ``moe:load`` span.
    assert set(metrics) == {"loss"} and np.isfinite(float(metrics["loss"]))
    assert not [s for s in spans if s["name"] == "moe:load"]
    for scope in (
        "mamba", "mamba/ssm_scan", "memory_unit", "attention", "attention_window",
        "cross_attention", "dense_ffn", "head", "short_conv_taps",
    ):
        assert re.search(rf'loss[^"]*/{scope}/', lowered), scope


def test_a_cut_without_the_memory_layer_keeps_no_memory(monkeypatch):
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.parallel import make_train_step

    model, _ = _kernel_model(first_layer=14, num_hidden_layers=2)
    with _tracing(monkeypatch):
        make_train_step(model, optax.adam(1e-5), make_mesh(devices=jax.devices()[:1]), None)
        spans = telemetry.local_spans()
    (build,) = [s["args"] for s in spans if s["name"] == "step:build"]
    assert (build["ssm_layers"], build["attention_kept"], build["memory_kept"]) == (1, 1, 0)
    assert (build["memory_units"], build["cross_layers"]) == (0, 0)


def test_a_model_that_holds_no_experts_counts_no_load_and_one_without_a_layer_zeros():
    """The repair: ``step_counters`` is empty for ``experts_held`` 0 (the
    step then returns no ``moe_*`` metrics and is not wrapped), and a step
    that holds experts but kept no expert layer counts zeros, not the
    maximum of nothing."""
    model, _ = _kernel_model()
    assert model.cfg.experts_held == 0 and model.step_counters == {}
    from ray_shuffling_data_loader_tpu.models.laguna import LagunaLM
    from ray_shuffling_data_loader_tpu.models.lfm2_moe import Lfm2MoeLM

    for sparse in (LagunaLM, Lfm2MoeLM):
        (name, (keys, fold)), = sparse.step_counters.fget(
            type("m", (), {"cfg": type("c", (), {"experts_held": 4})})
        ).items()
        assert name == "moe:load" and fold is blocks.moe_load_counts
        assert keys == ("moe_load", "moe_dropped", "moe_fallback")
    none = blocks.moe_load_counts(
        np.zeros((0, 4), np.int32), np.zeros((0,), np.int32), np.zeros((0,), np.int32)
    )
    assert none == {"max": 0, "mean": 0.0, "dropped": 0, "layers": 0, "fallback": 0}
    some = blocks.moe_load_counts(
        np.array([[3, 5], [1, 7]]), np.array([0, 2]), np.array([0, 1])
    )
    assert some == {"max": 7, "mean": 4.0, "dropped": 2, "layers": 2, "fallback": 1}
