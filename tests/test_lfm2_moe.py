"""LFM2-MoE (ISSUE 28): the program's model, expert layer, grouped-query
attention and short convolution against the benchmark's plain float32
reference and against dense formulas. CPU only, toy sizes, the kernels in
the Pallas interpreter."""

import json
import os
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
from ray_shuffling_data_loader_tpu.models.lfm2_moe import (  # noqa: E402
    Lfm2MoeConfig,
    Lfm2MoeLM,
)
from ray_shuffling_data_loader_tpu.ops import flash_attention, moe  # noqa: E402
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
            y, load, dropped = moe.experts_ffn(
                tokens, experts, weights, share["l2.moe.w1"],
                share["l2.moe.w3"], share["l2.moe.w2"], first, tile=8,
                use_pallas=True, interpret=True,
            )
            of_program += y.reshape(x.shape)
            loads.append(np.asarray(load))
            assert int(dropped) == 0
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


@pytest.mark.parametrize("kernel", ["ragged_dot", "pallas"])
def test_routing_drops_no_token_under_a_skewed_router(kernel):
    """Every token chooses the same four experts, three of them held: the
    buffer is its worst case but for one expert's share, and every
    assignment is computed."""
    t, h, w, held = 40, 16, 8, 4
    keys = jax.random.split(jax.random.key(5), 4)
    x = jax.random.normal(keys[0], (t, h))
    w1 = jax.random.normal(keys[1], (held, h, w)) / 4
    w3 = jax.random.normal(keys[2], (held, h, w)) / 4
    w2 = jax.random.normal(keys[3], (held, w, h)) / 3
    experts = jnp.tile(jnp.array([[1, 2, 3, 9]], jnp.int32), (t, 1))
    weights = jnp.full((t, 4), 0.25)
    kw = dict(use_pallas=kernel == "pallas", interpret=kernel == "pallas")
    with jax.default_matmul_precision("highest"):
        y, load, dropped = moe.experts_ffn(
            x, experts, weights, w1, w3, w2, 0, tile=8, **kw
        )
        want = sum(
            0.25 * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]) for e in (1, 2, 3)
        )
    assert load.tolist() == [0, t, t, t] and int(dropped) == 0
    assert np.allclose(y, want, atol=1e-5)
    plan = moe.plan_dispatch(experts, weights, 0, held, 8)
    # The count is read off the plan: a buffer row lost is an assignment
    # dropped.
    lost = plan._replace(source=plan.source.at[plan.position[0, 0]].set(t))
    assert int(jnp.sum(lost.load) - jnp.sum(lost.source < t)) == 1
    assert int(plan.tiles_used[0]) == 1 + 3 * (t // 8)
    # Every assignment held here has a buffer row of its own.
    rows = np.asarray(plan.position)[:, :3].reshape(-1)
    assert len(set(rows.tolist())) == 3 * t and rows.max() < plan.source.shape[0]
    assert (np.asarray(plan.position)[:, 3] == plan.source.shape[0]).all()


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


def test_the_step_takes_the_model_s_loss_and_hands_its_counters_to_the_trace(monkeypatch):
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.jax_dataset import layer_counts
    from ray_shuffling_data_loader_tpu.parallel import init_state, make_train_step
    from ray_shuffling_data_loader_tpu.telemetry import trace

    cfg = toy_config()
    family = harness.load_family(cfg)
    model = Lfm2MoeLM(
        Lfm2MoeConfig.from_dict(family.program.model_config(cfg)),
        use_pallas=False, row_tile=8,
    )
    mesh = make_mesh(devices=jax.devices()[:1])
    batch = {"tokens": jax.random.randint(jax.random.key(0), (4, 64), 0, 256)}
    optimizer = optax.adam(1e-3)
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    trace.reset_state()
    try:
        state, shardings = init_state(model, optimizer, mesh, batch)
        step = make_train_step(model, optimizer, mesh, shardings)
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert metrics["moe_load"].shape == (4, 4)
        assert metrics["moe_dropped"].tolist() == [0, 0, 0, 0]
        assert losses[2] < losses[0]
        spans = telemetry.local_spans()
    finally:
        monkeypatch.delenv("RSDL_TRACE")
        trace.refresh_from_env()
        trace.reset_state()
    build = [s for s in spans if s["name"] == "step:build"]
    assert build and build[-1]["args"]["model"] == "lfm2_moe"
    assert build[-1]["args"]["experts_held"] == 4 and build[-1]["args"]["layers"] == 5
    loads = [s["args"] for s in spans if s["name"] == "moe:load"]
    assert len(loads) == 3 and all(a["dropped"] == 0 for a in loads)
    # 4 sequences x 64 tokens x 4 choices, a quarter of the experts held.
    assert loads[0]["mean"] == pytest.approx(4 * 64 * 4 / 16, rel=0.2)
    # The loader folds a step's counters by category, whatever their name.
    assert all(s["cat"] == "train" for s in spans if s["name"] == "moe:load")
    folded = layer_counts(spans)["train step"]["moe:load"]
    assert folded["spans"] == 3 and folded["sum"]["dropped"] == 0
    assert folded["sum"]["layers"] == 12
    assert folded["sum"]["max"] >= folded["sum"]["mean"] > 0
    assert folded["sum"]["mean"] == pytest.approx(sum(a["mean"] for a in loads))


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
