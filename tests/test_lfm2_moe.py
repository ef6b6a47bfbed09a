"""LFM2-MoE (ISSUE 28): the program's model, expert layer, grouped-query
attention and short convolution against the benchmark's plain float32
reference and against dense formulas. CPU only, toy sizes, the kernels in
the Pallas interpreter."""

import collections
import contextlib
import json
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
from ray_shuffling_data_loader_tpu.models import lfm2_moe  # noqa: E402
from ray_shuffling_data_loader_tpu.models.lfm2_moe import (  # noqa: E402
    Lfm2MoeConfig,
    Lfm2MoeLM,
)
from ray_shuffling_data_loader_tpu.ops import flash_attention, moe  # noqa: E402
from ray_shuffling_data_loader_tpu.ops.flash_attention import (  # noqa: E402
    ATTENTION_OUT,
    ATTENTION_STATS,
)
from ray_shuffling_data_loader_tpu.ops.short_conv import (  # noqa: E402
    causal_depthwise_conv1d,
)
from ray_shuffling_data_loader_tpu.parallel import (  # noqa: E402
    TrainState,
    make_mesh,
)
from ray_shuffling_data_loader_tpu.parallel.train import (  # noqa: E402
    bce_loss,
    make_step_body,
)

BENCH = harness.load_benchmark()
SEED = 2**31 + 28


def toy_config(**over):
    """The benchmark's configuration at its rehearsal sizes, in float32
    unless told otherwise: the program then differs from the reference by
    summation order alone."""
    _, cfg, _ = harness.load_cell(BENCH, "lfm2-seq8k-train")
    cfg = {**cfg, **cfg["rehearsal"]}
    cfg["model"] = {**cfg["model"], "compute_dtype": "float32"}
    return {**cfg, **over}


@pytest.fixture(scope="module")
def family():
    return harness.load_family(toy_config())


def _readings(cfg, family, seed=SEED, steps=3):
    """The program's compiled step and the plain reference over the same
    three batches from the same weights: what the comparison reads."""
    mesh = make_mesh(devices=jax.devices()[:1])
    batches = limits.generator_batches(cfg, seed, steps)
    prog = limits.program_readings(cfg, family, mesh, seed, batches, True)
    ref_batches = [family.reference.batch_of(cfg, b) for b in batches]
    make = lambda: family.reference.init_params(cfg, seed)  # noqa: E731
    return prog, make, ref_batches


# The layers kept, by the published index of the first and their count:
# each kind of layer alone, then the configuration's own cut.
CUTS = {
    "conv + dense FFN": (1, 1),
    "attention + experts": (2, 1),
    "conv + experts": (3, 1),
    "the whole cut": (1, 5),
}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_the_program_follows_the_reference_in_float32(family, cut):
    """Loss of three steps, every leaf of the first gradient, every leaf's
    change after three Adam steps."""
    first, count = CUTS[cut]
    cfg = toy_config(first_layer=first, num_hidden_layers=count)
    prog, make, ref_batches = _readings(cfg, family)
    ref = family.reference.Reference(cfg).follow(make, ref_batches)
    assert np.allclose(prog["loss"], ref["loss"], rtol=2e-5), (prog["loss"], ref["loss"])
    assert set(prog["grad_norm"]) == set(family.counts.leaf_shapes(cfg))
    for leaf, want in ref["grad_norm"].items():
        assert prog["grad_norm"][leaf] == pytest.approx(want, rel=2e-3, abs=1e-7), leaf
        gap = np.linalg.norm(
            np.asarray(prog["grad_sketch"][leaf]) - np.asarray(ref["grad_sketch"][leaf])
        )
        assert gap <= 2e-3 * max(want, 1e-6), (leaf, gap, want)
    for leaf, want in ref["change_norm"].items():
        assert prog["change_norm"][leaf] == pytest.approx(want, rel=2e-2, abs=1e-7), leaf
    numbers = check.training_numbers(prog, ref)
    assert numbers["grad_diff"] < 1e-3 and numbers["loss_gap"] < 1e-4, numbers


def test_in_bfloat16_the_program_is_inside_the_limits_and_float8_is_not(family):
    cfg = toy_config()
    cfg["model"] = {**cfg["model"], "compute_dtype": "bfloat16"}
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
    half = reference.Reference(cfg).follow(make, ref_batches, rows_used=2)
    ok, compared = judged(half)
    assert not ok, compared


# -- the shares add up ---------------------------------------------------------


def test_the_expert_shares_add_up_to_the_uncut_layer(family):
    """What the 4 shares of 4 experts give, summed, is what the reference
    gives for all 16: through the program's layer and through the
    reference's own share."""
    cfg = toy_config()
    ref = family.reference
    routed, held = 16, 4
    params = ref.init_params(cfg, SEED)
    h, w = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    keys = jax.random.split(jax.random.key(7), 4)
    p = {
        "l2.moe.gate": params["l2.moe.gate"],
        "l2.moe.bias": params["l2.moe.bias"],
        "l2.moe.w1": jax.random.normal(keys[0], (routed, h, w)) / np.sqrt(h),
        "l2.moe.w3": jax.random.normal(keys[1], (routed, h, w)) / np.sqrt(h),
        "l2.moe.w2": jax.random.normal(keys[2], (routed, w, h)) / np.sqrt(w),
    }
    x = jax.random.normal(keys[3], (2, 48, h))
    same = lambda v: v  # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole = ref.experts_ffn(cfg, p, "l2.", x, same, first=0, held=routed)
        of_reference, of_program, loads = 0.0, 0.0, []
        tokens = x.reshape(-1, h)
        experts, weights = moe.route(
            tokens, p["l2.moe.gate"], p["l2.moe.bias"], 4, True, 1.0
        )
        for first in range(0, routed, held):
            share = {
                k: v[first : first + held] if k[-2:] in ("w1", "w3", "w2") else v
                for k, v in p.items()
            }
            of_reference += ref.experts_ffn(
                cfg, share, "l2.", x, same, first=first, held=held
            )
            y, load, dropped, fallback = moe.experts_ffn(
                tokens, experts, weights, share["l2.moe.w1"],
                share["l2.moe.w3"], share["l2.moe.w2"], first, routed,
                tile=8, use_pallas=True, interpret=True,
            )
            of_program += y.reshape(x.shape)
            loads.append(np.asarray(load))
            # A quarter of the experts under a random router: every share
            # fits the buffer for twice the even load.
            assert int(dropped) == 0 and int(fallback) == 0
    assert float(jnp.abs(whole).max()) > 0.1
    assert np.allclose(of_reference, whole, atol=1e-5)
    assert np.allclose(of_program, whole, atol=1e-5)
    # Every (token, expert) assignment was computed by exactly one share.
    assert int(np.concatenate(loads).sum()) == tokens.shape[0] * 4


def test_a_sliced_vocabulary_gives_the_matching_columns_of_the_whole(family):
    cfg = toy_config()
    ref = family.reference
    whole = toy_config(vocab_size=4 * int(cfg["vocab_size"]))
    params = ref.init_params(whole, SEED)
    v = int(cfg["vocab_size"])
    sliced = {**params, "embed": params["embed"][:v], "head": params["head"][:, :v]}
    tokens = jax.random.randint(jax.random.key(3), (2, 64), 0, v)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(whole, params, tokens)[..., :v]
        assert np.allclose(ref.logits(cfg, sliced, tokens), want, atol=1e-5)
        side_cfg = family.program.model_config(cfg)
        model = Lfm2MoeLM(
            Lfm2MoeConfig.from_dict(side_cfg), compute_dtype=jnp.float32,
            use_pallas=True, interpret=True, block_q=32, block_k=16, row_tile=8,
        )
        side = family.program.Side.__new__(family.program.Side)
        side.leaves = list(family.counts.leaf_shapes(cfg))
        got = model.apply(side.tree(sliced), {"tokens": tokens}, logits=True)
    assert np.allclose(got, want, atol=2e-4), float(jnp.abs(got - want).max())


# -- the expert layer -----------------------------------------------------------


# The expert layer at toy sizes: 40 tokens x 4 choices = 160 assignments over
# 16 experts, 4 of them held, in tiles of 8 rows. The even share is 40
# assignments, so the bounded buffer has (80 / 8 + 4) tiles = 112 rows and the
# worst-case one (160 / 8 + 4) tiles = 192.
TOKENS, TOP_K, HIDDEN, WIDTH, HELD, ROUTED, TILE = 40, 4, 16, 24, 4, 16, 8
ASSIGNMENTS = TOKENS * TOP_K
WORST_ROWS = moe.buffer_rows(ASSIGNMENTS, HELD, TILE)
BOUNDED_ROWS = moe.bounded_rows(ASSIGNMENTS, HELD, ROUTED, TILE)

# Tokens that choose each held expert (token t chooses e where t < loads[e];
# the rest of its four choices go to experts held elsewhere) -> the tiles the
# plan uses (an empty group keeps one) and whether the layer falls back.
LOADS = {
    "fits": ((0, 10, 10, 10), 7, 0),
    "ends on the bounded buffer's last row": ((0, 40, 32, 25), 14, 0),
    "one tile over": ((0, 40, 32, 33), 15, 1),
    "three of four choices held": ((0, 40, 40, 40), 16, 1),
    "the worst case": ((40, 40, 40, 40), 20, 1),
}
REMAT = dict(policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _expert_inputs():
    keys = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(keys[0], (TOKENS, HIDDEN))
    w1 = jax.random.normal(keys[1], (HELD, HIDDEN, WIDTH)) / 4
    w3 = jax.random.normal(keys[2], (HELD, HIDDEN, WIDTH)) / 4
    w2 = jax.random.normal(keys[3], (HELD, WIDTH, HIDDEN)) / 3
    weights = jax.random.uniform(keys[4], (TOKENS, TOP_K)) + 0.1
    return x, weights, w1, w3, w2


def _choices(loads):
    """``[TOKENS, TOP_K]`` expert ids, a token's all different."""
    rows = []
    for t in range(TOKENS):
        held = [e for e in range(HELD) if t < loads[e]]
        rows.append(held + list(range(9, 9 + TOP_K - len(held))))
    return jnp.asarray(rows, jnp.int32)


def _kernel_options(kernel):
    return dict(use_pallas=kernel == "pallas", interpret=kernel == "pallas")


def _weighed(y):
    """A scalar whose gradient differs at every element of ``y``."""
    return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=y.dtype).reshape(y.shape)))


def _layer(experts, routed, kw):
    """``experts_ffn`` as the model's layer runs it, a function of what is
    differentiated: ``-> (scalar, (y, load, dropped, fallback))``."""

    def layer(x, weights, w1, w3, w2):
        y, *counts = moe.experts_ffn(
            x, experts, weights, w1, w3, w2, 0, routed, tile=TILE, **kw
        )
        return _weighed(y), (y, *counts)

    return layer


def _worst_case_only(experts, kw):
    """The parent's layer: the same body in the whole plan's buffer."""

    def layer(x, weights, w1, w3, w2):
        plan = moe.plan_dispatch(experts, weights, 0, HELD, TILE)
        y = moe._held_experts(
            WORST_ROWS, TILE, kw["use_pallas"], kw["interpret"],
            x, weights, w1, w3, w2, plan,
        )
        return _weighed(y), (y, plan.load, plan.dropped)

    return layer


def _through_remat(layer):
    """Value and gradients with the layer recomputed in the backward pass
    under the model's policy (``nn.remat`` is ``jax.checkpoint`` lifted)."""
    return jax.jit(jax.value_and_grad(
        jax.checkpoint(layer, **REMAT), argnums=(0, 1, 2, 3, 4), has_aux=True
    ))


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("kernel", ["ragged_dot", "pallas"])
def test_routing_drops_no_token_under_a_skewed_router(kernel, load):
    """Whatever share of the assignments the held experts draw (under the
    bounded buffer, on its last row, a tile over it, three choices of four,
    all of them), every assignment is computed, and the result and every
    gradient are those of the worst-case buffer alone, bit for bit."""
    loads, tiles, falls_back = LOADS[load]
    assert (WORST_ROWS, BOUNDED_ROWS) == (192, 112)
    inputs = x, weights, w1, w3, w2 = _expert_inputs()
    experts = _choices(loads)
    kw = _kernel_options(kernel)
    with jax.default_matmul_precision("highest"):
        (_, (y, load_, dropped, fallback)), grads = _through_remat(
            _layer(experts, ROUTED, kw)
        )(*inputs)
        (_, (y_worst, load_worst, _)), grads_worst = _through_remat(
            _worst_case_only(experts, kw)
        )(*inputs)
        want = sum(
            ((experts == e) * weights).sum(-1, keepdims=True)
            * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
            for e in range(HELD)
        )
    assert load_.tolist() == list(loads) == load_worst.tolist()
    assert int(dropped) == 0 and int(fallback) == falls_back
    assert np.allclose(y, want, atol=1e-5)
    assert np.array_equal(y, y_worst)
    for name, got, ref in zip(("x", "weights", "w1", "w3", "w2"), grads, grads_worst):
        assert np.array_equal(got, ref), name
        assert float(jnp.abs(got).max()) > 0, name
    plan = moe.plan_dispatch(experts, weights, 0, HELD, TILE)
    assert int(plan.tiles_used[0]) == tiles
    assert (tiles * TILE <= BOUNDED_ROWS) == (not falls_back)
    # The count is read off the plan: a buffer row lost is an assignment
    # dropped.
    lost = plan._replace(source=plan.source.at[plan.position[0, 1]].set(TOKENS))
    assert int(jnp.sum(lost.load) - jnp.sum(lost.source < TOKENS)) == 1
    # Every assignment held here has a buffer row of its own, under the
    # tiles used; every other lies past the worst-case buffer, so past the
    # bounded one too.
    held = np.asarray(experts) < HELD
    rows = np.asarray(plan.position)[held]
    assert len(set(rows.tolist())) == sum(loads) and rows.max() < tiles * TILE
    assert (np.asarray(plan.position)[~held] == WORST_ROWS).all()


def _subjaxprs(value):
    from jax.extend import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _equations(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of what it calls, kernels' bodies
    left out, with the ``cond`` branches it lies in (``(eqn, index)``)."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "cond":
            for i, branch in enumerate(eqn.params["branches"]):
                yield from _equations(branch.jaxpr, inside + ((eqn, i),))
            continue
        for value in eqn.params.values():
            for sub in _subjaxprs(value):
                yield from _equations(sub, inside)


def _wide(eqn, rows):
    """The equation's float outputs of two or more axes with ``rows`` or
    more along one: rows x hidden or rows x width."""
    return [
        v.aval.shape for v in eqn.outvars
        if hasattr(v.aval, "shape") and len(v.aval.shape) >= 2
        and jnp.issubdtype(v.aval.dtype, jnp.floating)
        and max(v.aval.shape) >= rows
    ]


@pytest.mark.parametrize("kernel", ["ragged_dot", "pallas"])
def test_the_bounded_branch_moves_no_array_of_the_worst_case_s_rows(kernel):
    """The point of the bounded buffer, guarded where no chip is: in the
    branch taken when the load fits, forward and backward, nothing wide has
    the worst case's rows; the only wide arrays of an assignment a row are
    gathers by ``position`` (the combine, and the dispatch's backward)."""
    experts = _choices(LOADS["fits"][0])
    layer = _layer(experts, ROUTED, _kernel_options(kernel))
    grad = jax.grad(
        jax.checkpoint(lambda *a: layer(*a)[0], **REMAT), argnums=(0, 1, 2, 3, 4)
    )
    equations = list(_equations(jax.make_jaxpr(grad)(*_expert_inputs()).jaxpr))
    conds = {id(e): e for e, _ in equations if e.primitive.name == "cond"}
    # One in the forward pass, one in the backward pass (which recomputes
    # the forward inside its own branch).
    assert len(conds) >= 2
    seen = {(id(e), i): 0 for e in conds.values() for i in (0, 1)}
    for eqn, inside in equations:
        if not inside:
            # Around the branches: integers of the plan only.
            assert not _wide(eqn, ASSIGNMENTS), eqn
            continue
        (cond, branch), = inside
        seen[id(cond), branch] += len(_wide(eqn, WORST_ROWS))
        if branch == 1:
            assert not _wide(eqn, WORST_ROWS), eqn
            for shape in _wide(eqn, ASSIGNMENTS):
                # ``jnp.take`` is a jitted ``_take`` around its gather.
                assert "gather" == eqn.primitive.name or (
                    eqn.params.get("name") == "_take"
                ), eqn
                assert shape == (ASSIGNMENTS, HIDDEN)
    # The other branch of each is the worst case's body, and is wide.
    assert all(seen[id(e), 0] >= 5 for e in conds.values())


@pytest.mark.parametrize("routed", [HELD, 2 * HELD])
def test_a_chip_that_holds_half_the_experts_or_more_runs_the_parent_s_layer(routed):
    """No smaller buffer to take, so no ``cond`` is built: the traced layer
    is the worst-case body, and ``fallback`` a constant 0."""
    assert moe.bounded_rows(ASSIGNMENTS, HELD, routed, TILE) == WORST_ROWS
    experts = _choices(LOADS["the worst case"][0])
    inputs = _expert_inputs()
    kw = _kernel_options("ragged_dot")
    layer, worst = _layer(experts, routed, kw), _worst_case_only(experts, kw)
    names = {
        e.primitive.name
        for e, _ in _equations(jax.make_jaxpr(layer)(*inputs).jaxpr)
    }
    assert "cond" not in names and "custom_vjp_call" in names
    _, (y, _, dropped, fallback) = jax.jit(layer)(*inputs)
    assert np.array_equal(y, jax.jit(worst)(*inputs)[1][0])
    assert int(fallback) == 0 and int(dropped) == 0
    with_cond = _layer(experts, 4 * HELD, kw)
    assert "cond" in {
        e.primitive.name
        for e, _ in _equations(jax.make_jaxpr(with_cond)(*inputs).jaxpr)
    }


# -- the routing and the plan under recomputation (ISSUE 33) -------------------------


def _routed_layer(bias):
    """``route`` + ``experts_ffn`` on the XLA path, a function of what is
    differentiated: ``-> (scalar, (y, load, dropped, fallback))``."""

    def layer(x, gate, w1, w3, w2):
        experts, weights = moe.route(x, gate, bias, TOP_K)
        y, *counts = moe.experts_ffn(
            x, experts, weights, w1, w3, w2, 0, ROUTED, tile=TILE, use_pallas=False
        )
        return _weighed(y), (y, *counts)

    return layer


def _routed_inputs():
    x, _, w1, w3, w2 = _expert_inputs()
    gate = jax.random.normal(jax.random.key(6), (HIDDEN, ROUTED))
    return x, gate, w1, w3, w2


# What the recomputation keeps -> plans built (one stable sort each) in the
# compiled gradient.
RECOMPUTED = {
    "the routing named": (
        jax.checkpoint_policies.save_only_these_names(moe.ROUTING), 1
    ),
    "the model's own": (lfm2_moe.KEPT, 1),
    "nothing kept": (None, 2),
    "the matmuls' outputs alone": (REMAT["policy"], 2),
}
# A selection bias towards the four experts held sends every token to them:
# the load outgrows the bounded buffer and the layer falls back.
ROUTERS = {
    "fits": (None, 0),
    "falls back": (jnp.where(jnp.arange(ROUTED) < HELD, 10.0, 0.0), 1),
}


@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("kept", sorted(RECOMPUTED))
def test_a_recomputed_layer_builds_its_plan_once_where_the_routing_is_kept(
    kept, router
):
    """The backward pass of a recomputed layer reads the experts chosen and
    the plan. Under a policy that lists ``moe.ROUTING`` they stay from the
    forward pass and the second build is dead code; under any other the
    compiled gradient sorts twice and scatters eight times for six. Counted
    in the optimised HLO: the jaxpr keeps the dead equations in ``remat2``
    until compilation. The numbers are the same either way ..."""
    policy, builds = RECOMPUTED[kept]
    bias, falls_back = ROUTERS[router]
    inputs = _routed_inputs()

    def gradient(layer):
        return jax.jit(
            jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4), has_aux=True)
        )

    layer = _routed_layer(bias)
    compiled = gradient(jax.checkpoint(layer, policy=policy)).lower(*inputs).compile()
    text = compiled.as_text()
    assert len(re.findall(r" sort\(", text)) == builds
    assert len(re.findall(r" scatter\(", text)) == 4 + 2 * builds
    (_, (y, load, dropped, fallback)), grads = got = compiled(*inputs)
    assert int(fallback) == falls_back and int(dropped) == 0
    assert 0 < int(load.sum()) <= ASSIGNMENTS
    assert (int(load.sum()) == ASSIGNMENTS) == bool(falls_back)
    # ... as without any recomputation.
    want = gradient(layer)(*inputs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(a, b)
    assert all(float(jnp.abs(g).max()) > 0 for g in grads)


@pytest.mark.parametrize("load", ["fits", "one tile over"])
def test_without_a_policy_the_names_are_identities(monkeypatch, load):
    """A bare caller (no recomputation, no policy): value, ``load``,
    ``dropped``, ``fallback`` and every gradient of ``experts_ffn``, and
    ``route``'s two results, are bit for bit those of the module with
    ``checkpoint_name`` patched to the identity."""
    loads, _, falls_back = LOADS[load]
    experts = _choices(loads)
    inputs = _expert_inputs()
    x, gate, *_ = _routed_inputs()

    def readings():
        layer = _layer(experts, ROUTED, _kernel_options("ragged_dot"))
        out = jax.jit(jax.value_and_grad(
            layer, argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(*inputs)
        return out, jax.jit(lambda x, gate: moe.route(x, gate, None, TOP_K))(x, gate)

    named = readings()
    assert any(
        eqn.primitive.name == "name" and eqn.params["name"] == moe.ROUTING
        for eqn, _ in _equations(
            jax.make_jaxpr(lambda *a: moe.plan_dispatch(*a, 0, HELD, TILE))(
                experts, inputs[1]
            ).jaxpr
        )
    )
    monkeypatch.setattr(moe, "checkpoint_name", lambda value, name: value)
    jax.clear_caches()
    bare = readings()
    assert int(named[0][0][1][3]) == falls_back
    leaves = jax.tree.leaves(named)
    assert len(leaves) == 1 + 4 + 5 + 2
    for got, want in zip(leaves, jax.tree.leaves(bare)):
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_the_router_chooses_by_the_bias_and_weighs_without_it():
    x = jnp.eye(4)
    gate = jnp.array([[2.0, 1.0, 0.0, -1.0]] * 4)
    bias = jnp.array([0.0, 0.0, 0.0, 5.0])
    experts, weights = moe.route(x, gate, bias, 2, True, 1.0)
    assert sorted(experts[0].tolist()) == [0, 3]
    s = jax.nn.sigmoid(jnp.array([2.0, -1.0]))
    assert np.allclose(sorted(weights[0].tolist()), sorted((s / s.sum()).tolist()))
    experts, _ = moe.route(x, gate, None, 2, True, 1.0)
    assert sorted(experts[0].tolist()) == [0, 1]


# -- attention and the convolution ------------------------------------------------


def _dense_attention(q, k, v):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 1), (4, 4)])
def test_grouped_query_attention_against_the_dense_formula(heads, kv_heads):
    keys = jax.random.split(jax.random.key(heads), 3)
    b, t, d = 2, 80, 16
    q = jax.random.normal(keys[0], (b, t, heads, d))
    k = jax.random.normal(keys[1], (b, t, kv_heads, d))
    v = jax.random.normal(keys[2], (b, t, kv_heads, d))

    def kernel(q, k, v):
        return flash_attention(
            q, k, v, causal=True, use_pallas=True, interpret=True,
            block_q=32, block_k=16,
        )

    with jax.default_matmul_precision("highest"):
        assert np.allclose(kernel(q, k, v), _dense_attention(q, k, v), atol=2e-5)
        loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)  # noqa: E731
        got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(_dense_attention), argnums=(0, 1, 2))(q, k, v)
        xla = flash_attention(q, k, v, causal=True, use_pallas=False)
    for g, w_ in zip(got, want):
        assert g.shape == w_.shape and np.allclose(g, w_, atol=1e-4)
    assert np.allclose(xla, _dense_attention(q, k, v), atol=2e-5)


def test_query_heads_must_be_a_multiple_of_the_key_value_heads():
    q = jnp.zeros((1, 16, 6, 8))
    kv = jnp.zeros((1, 16, 4, 8))
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, kv, kv, causal=True, use_pallas=True, interpret=True)


def test_the_short_convolution_sees_the_past_only():
    u = jax.random.normal(jax.random.key(0), (2, 12, 5))
    w = jax.random.normal(jax.random.key(1), (5, 3))
    y = causal_depthwise_conv1d(u, w)
    want = np.zeros_like(y)
    un, wn = np.asarray(u), np.asarray(w)
    for t in range(12):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += wn[:, j] * un[:, t - 2 + j]
    assert np.allclose(y, want, atol=1e-6)
    # A later position changes nothing before it.
    bumped = causal_depthwise_conv1d(u.at[:, 7].add(1.0), w)
    assert np.allclose(bumped[:, :7], y[:, :7]) and not np.allclose(bumped[:, 7], y[:, 7])


# -- the step: the loss comes with the model, the DLRM's is what it was -----------


def _old_step_body(model, optimizer):
    """``parallel/train.py``'s step as it was before a model could bring
    its loss (commit ac23546), kept here to compare traces."""

    def step_fn(state, features, labels):
        def loss_fn(params):
            with jax.named_scope("loss"):
                logits = model.apply(params, features)
                return bce_loss(logits, labels)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return TrainState(state.step + 1, params, opt_state), {"loss": loss}

    return step_fn


def test_the_dlrm_step_is_the_program_it_was():
    from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
    from ray_shuffling_data_loader_tpu.models.dlrm import example_features

    model = dlrm_for_data_spec(
        embed_dim=8, top_mlp=(16, 8), vocab_cap=50, use_pallas_interaction=False
    )
    optimizer = optax.adam(1e-3)
    features = example_features(model, 32)
    labels = jnp.linspace(0.0, 1.0, 32)
    params = model.init(jax.random.key(0), features)
    state = TrainState(jnp.zeros((), jnp.int32), params, optimizer.init(params))
    new = jax.make_jaxpr(make_step_body(model, optimizer))(state, features, labels)
    old = jax.make_jaxpr(_old_step_body(model, optimizer))(state, features, labels)
    assert str(new) == str(old)


@contextlib.contextmanager
def _tracing(monkeypatch):
    """``RSDL_TRACE`` on and the span buffer empty inside; off and empty
    again after."""
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


def _biased_towards_the_held(params):
    """``params`` with every expert layer's selection bias at 10 for the four
    experts held and 0 for the twelve others: every token chooses the held.
    (A buffer of its own a layer: a step donates its state.)"""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.where(jnp.arange(16) < 4, 10.0, 0.0)
        if path[-1].key == "expert_bias" else leaf,
        params,
    )


@pytest.mark.parametrize("router", ["even", "collapsed"])
def test_the_step_takes_the_model_s_loss_and_hands_its_counters_to_the_trace(
    monkeypatch, router
):
    """``collapsed``: a selection bias sends every token to the four experts
    held, so every expert layer outgrows the bounded buffer and falls back,
    and still drops nothing."""
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.jax_dataset import layer_counts
    from ray_shuffling_data_loader_tpu.parallel import init_state, make_train_step

    cfg = toy_config()
    family = harness.load_family(cfg)
    model = Lfm2MoeLM(
        Lfm2MoeConfig.from_dict(family.program.model_config(cfg)),
        use_pallas=False, row_tile=8,
    )
    mesh = make_mesh(devices=jax.devices()[:1])
    batch = {"tokens": jax.random.randint(jax.random.key(0), (4, 64), 0, 256)}
    optimizer = optax.adam(1e-3)
    with _tracing(monkeypatch):
        state, shardings = init_state(model, optimizer, mesh, batch)
        if router == "collapsed":
            state = state._replace(params=_biased_towards_the_held(state.params))
        step = make_train_step(model, optimizer, mesh, shardings)
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert metrics["moe_load"].shape == (4, 4)
        assert metrics["moe_dropped"].tolist() == [0, 0, 0, 0]
        assert metrics["moe_fallback"].tolist() == [router == "collapsed"] * 4
        assert losses[2] < losses[0]
        spans = telemetry.local_spans()
    build = [s for s in spans if s["name"] == "step:build"]
    assert build and build[-1]["args"]["model"] == "lfm2_moe"
    assert build[-1]["args"]["experts_held"] == 4 and build[-1]["args"]["layers"] == 5
    # The cut's one attention layer keeps its kernel's residuals, its four
    # expert layers their routing and plan.
    assert build[-1]["args"]["attention_kept"] == 1
    assert build[-1]["args"]["routing_kept"] == 4
    # The span of the step's trace adds what hangs on the batch's shape: 4
    # sequences of 64 positions, under one block, are a block a head.
    assert "attention_blocks" not in build[0]["args"]
    assert build[-1]["args"]["attention_grid_steps"] == 4 * 4
    assert build[-1]["args"]["attention_blocks"] == 4 * 4
    loads = [s["args"] for s in spans if s["name"] == "moe:load"]
    assert len(loads) == 3 and all(a["dropped"] == 0 for a in loads)
    # 4 sequences x 64 tokens x 4 choices, a quarter of the experts held:
    # each draws a sixteenth of the assignments, or every token.
    if router == "even":
        assert loads[0]["mean"] == pytest.approx(4 * 64 * 4 / 16, rel=0.2)
    else:
        assert loads[0]["mean"] == loads[0]["max"] == 4 * 64
    assert all(a["fallback"] == (4 if router == "collapsed" else 0) for a in loads)
    # The loader folds a step's counters by category, whatever their name.
    assert all(s["cat"] == "train" for s in spans if s["name"] == "moe:load")
    folded = layer_counts(spans)["train step"]["moe:load"]
    assert folded["spans"] == 3 and folded["sum"]["dropped"] == 0
    assert folded["sum"]["layers"] == 12
    # Of the expert layers' executions, the share that missed the bounded
    # buffer: fallback / layers.
    assert folded["sum"]["fallback"] == (12 if router == "collapsed" else 0)
    assert folded["sum"]["max"] >= folded["sum"]["mean"] > 0
    assert folded["sum"]["mean"] == pytest.approx(sum(a["mean"] for a in loads))


# -- what a recomputed layer keeps ------------------------------------------------


def _kernel_model():
    """The cut at its rehearsal sizes as the benchmark builds it (bfloat16
    compute, every kernel in the interpreter) and a batch of tokens."""
    cfg = toy_config()
    family = harness.load_family(cfg)
    kernels = cfg["kernels"]
    model = Lfm2MoeLM(
        Lfm2MoeConfig.from_dict(family.program.model_config(cfg)),
        use_pallas=True, interpret=True,
        block_q=kernels["attention_block_q"], block_k=kernels["attention_block_k"],
        row_tile=kernels["expert_row_tile"],
    )
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 64), 0, 256)}
    params = model.init(jax.random.key(2), batch)
    return model, params, batch


def _kernel_calls(model, params, batch):
    """How often the gradient of the model's loss calls each Pallas kernel."""
    grad = jax.grad(lambda p: model.apply(p, batch)[0])
    return collections.Counter(
        eqn.params["name"]
        for eqn, _ in _equations(jax.make_jaxpr(grad)(params).jaxpr)
        if eqn.primitive.name == "pallas_call"
    )


def test_the_attention_kernel_s_forward_runs_once_a_step():
    """The layer is recomputed in the backward pass; the kernel's output and
    row statistics are kept, so its forward is dead code there. The expert
    layer is recomputed as it was."""
    calls = _kernel_calls(*_kernel_model())
    attention = {n: c for n, c in calls.items() if n.startswith("flash_attention")}
    assert attention == {
        "flash_attention_fwd": 1,
        "flash_attention_bwd_dkv": 1,
        "flash_attention_bwd_dq": 1,
    }
    # Four expert layers, forward and recomputed, three products each, in
    # each of the two buffers' branches.
    assert calls["moe_experts_fwd"] == 4 * 2 * 3 * 2


def test_what_is_kept_changes_no_number(monkeypatch):
    """Loss and every gradient leaf under ``KEPT`` are, bit for bit, those
    under the matmuls' policy alone (the parent's: two forwards)."""
    model, params, batch = _kernel_model()

    def loss_and_gradient():
        # (Jitted anew each time: the policy is read when the model is traced.)
        return jax.jit(jax.value_and_grad(lambda p: model.apply(p, batch)[0]))(params)

    kept = loss_and_gradient()
    calls = _kernel_calls(model, params, batch)
    monkeypatch.setattr(lfm2_moe, "KEPT", REMAT["policy"])
    jax.clear_caches()
    assert _kernel_calls(model, params, batch) == {**calls, "flash_attention_fwd": 2}
    parent = loss_and_gradient()
    leaves = jax.tree.leaves(kept)
    assert len(leaves) == 1 + len(jax.tree.leaves(params))
    for got, want in zip(leaves, jax.tree.leaves(parent)):
        assert np.isfinite(got).all() and np.array_equal(got, want)


# ``KEPT`` as it stood before ISSUE 33: the matmuls' outputs and the attention
# kernel's residuals, the routing and the plan built again.
KEPT_BEFORE_ROUTING = jax.checkpoint_policies.save_from_both_policies(
    REMAT["policy"],
    jax.checkpoint_policies.save_only_these_names(ATTENTION_OUT, ATTENTION_STATS),
)


@pytest.mark.parametrize("router", ["even", "collapsed"])
def test_keeping_the_routing_and_the_plan_changes_no_number(monkeypatch, router):
    """Loss, counters and every gradient leaf under ``KEPT`` are, bit for
    bit, those under the parent's ``KEPT`` (which builds every plan twice):
    under the router as initialised, and under one whose selection bias
    sends every token to the experts held, so that every expert layer runs
    in the worst-case buffer."""
    model, params, batch = _kernel_model()
    if router == "collapsed":
        params = _biased_towards_the_held(params)

    def readings():
        # (Jitted anew each time: the policy is read when the model is traced.)
        return jax.jit(
            jax.value_and_grad(lambda p: model.apply(p, batch), has_aux=True)
        )(params)

    assert lfm2_moe.KEPT is not KEPT_BEFORE_ROUTING
    kept = readings()
    monkeypatch.setattr(lfm2_moe, "KEPT", KEPT_BEFORE_ROUTING)
    jax.clear_caches()
    parent = readings()
    (_, counters), _ = kept
    assert counters["moe_fallback"].tolist() == [int(router == "collapsed")] * 4
    assert counters["moe_dropped"].tolist() == [0] * 4
    leaves = jax.tree.leaves(kept)
    assert len(leaves) == 1 + 3 + len(jax.tree.leaves(params))
    for got, want in zip(leaves, jax.tree.leaves(parent)):
        assert np.isfinite(got).all() and np.array_equal(got, want)


# (published index of the first layer, layers) -> the attention layers whose
# kernel's residuals are kept, the expert layers whose routing and plan are.
KEPT_BY_CUT = {
    "the whole cut": (1, 5, 1, 4),
    "the period: attention, conv, conv, conv, each + experts": (2, 4, 1, 4),
    "attention + experts": (2, 1, 1, 1),
    "conv + experts": (3, 1, 0, 1),
    "conv + dense FFN": (1, 1, 0, 0),
}


@pytest.mark.parametrize("cut", sorted(KEPT_BY_CUT))
def test_step_build_counts_the_attention_layers_whose_residuals_are_kept(
    monkeypatch, cut
):
    """Facts of the traced step, recorded when it is built (nothing is
    compiled here): ``attention_kept`` 0 for a cut without attention,
    ``routing_kept`` 0 for one without expert layers."""
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.parallel import make_train_step

    first, count, attention, routing = KEPT_BY_CUT[cut]
    cfg = toy_config(first_layer=first, num_hidden_layers=count)
    model = Lfm2MoeLM(
        Lfm2MoeConfig.from_dict(harness.load_family(cfg).program.model_config(cfg))
    )
    with _tracing(monkeypatch):
        mesh = make_mesh(devices=jax.devices()[:1])
        make_train_step(model, optax.adam(1e-3), mesh, None)
        spans = telemetry.local_spans()
    (build,) = [s["args"] for s in spans if s["name"] == "step:build"]
    assert build["attention_kept"] == attention and build["layers"] == count
    assert build["routing_kept"] == routing


def test_the_family_s_tree_carries_every_leaf_there_and_back(family):
    cfg = toy_config()
    weights = family.reference.init_params(cfg, SEED)
    side = family.program.Side.__new__(family.program.Side)
    side.leaves = list(family.counts.leaf_shapes(cfg))
    tree = side.tree(weights)
    model = Lfm2MoeLM(Lfm2MoeConfig.from_dict(family.program.model_config(cfg)))
    own = jax.eval_shape(
        lambda: model.init(jax.random.key(0), {"tokens": jnp.zeros((1, 64), jnp.int32)})
    )
    assert jax.tree.map(lambda x: x.shape, tree) == jax.tree.map(lambda x: x.shape, own)
    back = side.flat(tree)
    assert sorted(back) == sorted(weights)
    assert all(back[k] is weights[k] for k in weights)
    assert json.dumps(sorted(weights)) == json.dumps(sorted(family.counts.leaf_shapes(cfg)))
