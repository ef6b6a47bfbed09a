"""Kimi-VL's language model: the latent attention operator against the
family's plain reference, the three kernels with a shared key part against
the XLA path and against the plain kernels on the repeated key, the
``noaux_tc`` router, the expert shares against the uncut layer, the program
against the benchmark's plain float32 reference, what the step keeps and
says, that the rotary key reaches the kernels as one head, and that the
attention kernels without a shared key lower to the pinned text. CPU
only, toy sizes, the kernels in the Pallas interpreter."""

import collections
import contextlib
import hashlib
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
from ray_shuffling_data_loader_tpu.models import blocks  # noqa: E402
from ray_shuffling_data_loader_tpu.models.kimi import KimiConfig, KimiLM  # noqa: E402
from ray_shuffling_data_loader_tpu.ops import moe  # noqa: E402
from ray_shuffling_data_loader_tpu.ops.flash_attention import flash_attention  # noqa: E402
from ray_shuffling_data_loader_tpu.parallel import make_mesh  # noqa: E402

BENCH = harness.load_benchmark()
SEED = 2**31 + 40
CELL = "kimi-seq16k-train"


def toy_config(**over):
    """The benchmark's configuration at its rehearsal sizes, in float32
    unless told otherwise."""
    _, cfg, _ = harness.load_cell(BENCH, CELL)
    cfg = {**cfg, **cfg["rehearsal"]}
    cfg["model"] = {**cfg["model"], "compute_dtype": "float32"}
    return {**cfg, **over}


def _model_config(cfg) -> KimiConfig:
    return KimiConfig.from_dict(harness.load_family(cfg).program.model_config(cfg))


@pytest.fixture(scope="module")
def family():
    return harness.load_family(toy_config())


def _side(family, cfg):
    side = family.program.Side.__new__(family.program.Side)
    side.leaves = list(family.counts.leaf_shapes(cfg))
    return side


# -- the operator against the reference ----------------------------------------------


@pytest.mark.parametrize("pallas", [False, True])
def test_latent_attention_is_the_reference_s(family, pallas):
    """The program's operator on the seed's weights against the reference's
    own latent path (its key repeated to the heads and concatenated), in
    float32: through the kernels in the interpreter and through the XLA
    path."""
    cfg = toy_config()
    params = family.reference.init_params(cfg, SEED)
    tree = _side(family, cfg).tree(params)["params"]["layer_1"]["self_attn"]
    x = jax.random.normal(jax.random.key(3), (2, 64, int(cfg["hidden_size"])))
    mc = _model_config(cfg)
    op = blocks.LatentAttention(
        mc.num_attention_heads, mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim,
        mc.kv_lora_rank, blocks.Rope(mc.qk_rope_head_dim, float(mc.rope_theta)), mc.norm_eps,
        jnp.float32, pallas, pallas, 32, 16,
    )
    with jax.default_matmul_precision("highest"):
        got = op.apply({"params": tree}, x)
        want = family.reference._attention(cfg, params, "l1.", x, lambda v: v)
    assert float(jnp.abs(want).max()) > 0.1
    assert np.allclose(got, want, atol=2e-5), float(jnp.abs(got - want).max())


# -- the kernels with a shared key part -----------------------------------------------------


def _latent_inputs(kv_heads=4, seed=5):
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (2, 64, 4, 24))
    k = jax.random.normal(keys[1], (2, 64, kv_heads, 16))
    v = jax.random.normal(keys[2], (2, 64, kv_heads, 12))
    k_rope = jax.random.normal(keys[3], (2, 64, 1, 8))
    ct = jax.random.normal(keys[4], (2, 64, 4, 12))
    return q, k, v, k_rope, ct


def _value_and_grads(f, q, k, v, k_rope, ct):
    def loss(q, k, v, k_rope):
        out = f(q, k, v, k_rope)
        return jnp.sum(out * ct), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(
            q, k, v, k_rope
        )
    return (out, *grads)


@pytest.mark.parametrize("kv_heads", [4, 2])
@pytest.mark.parametrize("blocks_qk", [(32, 32), (32, 16)])
def test_the_shared_key_kernels_against_the_xla_path(blocks_qk, kv_heads):
    """Forward and the four gradients (``dk_shared`` summed over the heads)
    of the three kernels in the interpreter against the XLA path, which
    concatenates the repeated shared part: 4 query heads of 16 + 8, values
    of 12, over 4 key heads or grouped over 2."""
    bq, bk = blocks_qk
    args = _latent_inputs(kv_heads)

    def run(pallas):
        return _value_and_grads(
            lambda q, k, v, kr: flash_attention(
                q, k, v, causal=True, use_pallas=pallas, interpret=pallas, block_q=bq,
                block_k=bk, k_shared=kr,
            ),
            *args,
        )

    got, want = run(True), run(False)
    assert got[4].shape == (2, 64, 1, 8)
    for name, g, w in zip(("out", "dq", "dk", "dv", "dk_shared"), got, want):
        assert np.allclose(g, w, atol=1e-4 * float(jnp.abs(w).max())), (
            name, float(jnp.abs(g - w).max())
        )


def test_the_shared_key_kernels_equal_the_plain_kernels_on_the_repeated_key():
    """The same attention through the plain kernels, the shared part
    repeated to every head and concatenated in HBM: the same output and
    gradients, the shared part's summed over the heads by autodiff."""
    args = _latent_inputs()
    kw = dict(causal=True, use_pallas=True, interpret=True, block_q=32, block_k=16)
    latent = _value_and_grads(
        lambda q, k, v, kr: flash_attention(q, k, v, k_shared=kr, **kw), *args
    )
    plain = _value_and_grads(
        lambda q, k, v, kr: flash_attention(
            q, jnp.concatenate([k, jnp.broadcast_to(kr, (*k.shape[:3], 8))], -1), v, **kw
        ),
        *args,
    )
    for name, a, b in zip(("out", "dq", "dk", "dv", "dk_shared"), latent, plain):
        assert np.allclose(a, b, atol=1e-5 * float(jnp.abs(b).max())), name


def test_a_shared_key_part_is_refused_where_it_does_not_fit():
    q, k, v, k_rope, _ = _latent_inputs()
    with pytest.raises(ValueError, match="window or a selection"):
        flash_attention(q, k, v, causal=True, window=16, k_shared=k_rope)
    with pytest.raises(ValueError, match="does not complete"):
        flash_attention(q, k, v, causal=True, k_shared=k_rope[..., :4])
    with pytest.raises(ValueError, match="does not complete"):
        flash_attention(q, k, v, causal=True, k_shared=jnp.tile(k_rope, (1, 1, 4, 1)))


# -- the router -----------------------------------------------------------------------------


def test_noaux_tc_with_one_group_is_a_plain_top_k_of_the_biased_scores(family):
    """The reference's ``noaux_tc`` over one group against the program's
    router (sigmoid, the top 6 of ``s + b``, the chosen ``s`` renormalised
    times 2.446); over 4 groups keeping 2, the choice stays inside the two
    best groups and is another one."""
    _, cfg, _ = harness.load_cell(BENCH, CELL)
    spec = _model_config(cfg).experts
    assert (spec.selection_bias, spec.norm_topk, spec.scaling, spec.scoring) == (
        True, True, 2.446, "sigmoid"
    )
    assert (spec.routed, spec.held, spec.top_k, spec.width) == (64, 8, 6, 1408)
    x = jax.random.normal(jax.random.key(0), (256, 32))
    gate = jax.random.normal(jax.random.key(1), (32, 64))
    bias = 0.3 * jax.random.normal(jax.random.key(2), (64,))
    experts, weights = moe.route(x, gate, bias, 6, True, 2.446, "sigmoid")
    scores = jax.nn.sigmoid(jnp.dot(x, gate, precision="highest"))
    want_experts, want_weights = family.reference.noaux_tc(cfg, scores, bias)
    assert np.array_equal(np.sort(experts, -1), np.sort(want_experts, -1))
    order = lambda e, w: np.take_along_axis(np.asarray(w), np.argsort(e, -1), -1)  # noqa: E731
    assert np.allclose(order(experts, weights), order(want_experts, want_weights), atol=1e-5)
    # The bias moves the choice and not the weights.
    unbiased, _ = moe.route(x, gate, None, 6, True, 2.446, "sigmoid")
    assert not np.array_equal(np.sort(unbiased, -1), np.sort(experts, -1))
    grouped, _ = family.reference.noaux_tc({**cfg, "n_group": 4, "topk_group": 2}, scores, bias)
    group_of = np.asarray(grouped) // 16
    assert (np.array([len(set(g)) for g in group_of]) <= 2).all()
    assert not np.array_equal(np.sort(grouped, -1), np.sort(want_experts, -1))


@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("q_lora_rank", 1536), ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
])
def test_the_model_refuses_what_its_layer_does_not_implement(key, value):
    cfg = toy_config(**{key: value})
    with pytest.raises(ValueError):
        _model_config(cfg)


# -- the shares add up ------------------------------------------------------------------


def test_the_expert_shares_and_what_every_chip_computes_add_up_to_the_uncut_layer():
    """The 8 shares of 2 routed experts, summed, plus the latent attention
    and the shared experts (every chip computes them alike) counted once,
    are the reference's whole layer with all 16 experts: through the
    program's kernels and through the reference's own share."""
    cfg = toy_config()
    ref = harness.load_family(cfg).reference
    routed, held, top_k = 16, 2, int(cfg["num_experts_per_tok"])
    params = ref.init_params(cfg, SEED)
    h, w = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    keys = jax.random.split(jax.random.key(7), 4)
    p = {
        **{k: v for k, v in params.items() if k.startswith("l1.")},
        "l1.moe.w1": jax.random.normal(keys[0], (routed, h, w)) / np.sqrt(h),
        "l1.moe.w3": jax.random.normal(keys[1], (routed, h, w)) / np.sqrt(h),
        "l1.moe.w2": jax.random.normal(keys[2], (routed, w, h)) / np.sqrt(w),
    }
    x = jax.random.normal(keys[3], (1, 64, h))
    same = lambda v: v  # noqa: E731
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        whole_cfg = {**cfg, "n_routed_experts": routed}
        whole = ref.layer_out(whole_cfg, p, "l1.", x, False, same)
        mid = x + ref._attention(cfg, p, "l1.", ref._rmsnorm(x, p["l1.in_norm"], eps), same)
        normed = ref._rmsnorm(mid, p["l1.post_norm"], eps)
        every_chip = mid + ref.shared_ffn(cfg, p, "l1.", normed, same)
        of_reference, of_program, loads = every_chip, every_chip, []
        tokens = normed.reshape(-1, h)
        experts, weights = moe.route(tokens, p["l1.moe.gate"], p["l1.moe.bias"], top_k, True,
                                     float(cfg["routed_scaling_factor"]), "sigmoid")
        for first in range(0, routed, held):
            share = {
                k: v[first : first + held] if k[-2:] in ("w1", "w3", "w2") and ".moe." in k else v
                for k, v in p.items()
            }
            of_reference += ref.routed_ffn(cfg, share, "l1.", normed, same, first=first, held=held)
            y, load, dropped, _ = moe.experts_ffn(
                tokens, experts, weights, share["l1.moe.w1"], share["l1.moe.w3"],
                share["l1.moe.w2"], first, routed, tile=8, use_pallas=True, interpret=True,
            )
            of_program += y.reshape(x.shape)
            loads.append(np.asarray(load))
            assert int(dropped) == 0
    assert float(jnp.abs(whole - every_chip).max()) > 0.1
    assert float(jnp.abs(every_chip - mid).max()) > 0.1 and float(jnp.abs(mid - x).max()) > 0.1
    assert np.allclose(of_reference, whole, atol=1e-5)
    assert np.allclose(of_program, whole, atol=1e-5)
    assert int(np.concatenate(loads).sum()) == tokens.shape[0] * top_k
    assert np.allclose(weights.sum(axis=-1), float(cfg["routed_scaling_factor"]), atol=1e-5)


# -- the program against the reference ------------------------------------------------------


def _readings(cfg, family, seed=SEED, steps=3):
    mesh = make_mesh(devices=jax.devices()[:1])
    batches = limits.generator_batches(cfg, seed, steps)
    prog = limits.program_readings(cfg, family, mesh, seed, batches, True)
    ref_batches = [family.reference.batch_of(cfg, b) for b in batches]
    make = lambda: family.reference.init_params(cfg, seed)  # noqa: E731
    return prog, make, ref_batches


@pytest.mark.parametrize("layers", [1, 2])
def test_the_program_follows_the_reference_in_float32(family, layers):
    """Loss of three steps, every leaf of the first gradient, every leaf's
    change after three Adam steps: the dense layer alone, and with the
    first expert layer after it."""
    cfg = toy_config(num_hidden_layers=layers)
    prog, make, ref_batches = _readings(cfg, family)
    ref = family.reference.Reference(cfg).follow(make, ref_batches)
    assert np.allclose(prog["loss"], ref["loss"], rtol=2e-5), (prog["loss"], ref["loss"])
    assert set(prog["grad_norm"]) == set(family.counts.leaf_shapes(cfg))
    for leaf, want in ref["grad_norm"].items():
        assert prog["grad_norm"][leaf] == pytest.approx(want, rel=2e-3, abs=1e-7), leaf
    numbers = check.training_numbers(prog, ref)
    assert numbers["grad_diff"] < 2e-3 and numbers["loss_gap"] < 1e-4, numbers


def test_in_bfloat16_the_program_is_inside_the_limits_and_float8_is_not(family):
    """At the rehearsal's sizes and its own limits; the configuration's
    limits are read on the chip at the published sizes."""
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
    assert not ok, compared


# -- the step: what is kept, what the trace is told ---------------------------------------------


def _pallas_eqns(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_eqns(sub)
    return found


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _kernel_model(layers=2, pallas=True):
    cfg = toy_config(num_hidden_layers=layers)
    kernels = cfg["kernels"]
    model = KimiLM(
        _model_config(cfg), use_pallas=pallas, interpret=pallas,
        block_q=kernels["attention_block_q"], block_k=kernels["attention_block_k"],
        row_tile=kernels["expert_row_tile"],
    )
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 64), 0, 256)}
    return model, batch


def _grad_jaxpr(model, batch):
    params = jax.eval_shape(lambda: model.init(jax.random.key(2), batch))
    return jax.make_jaxpr(jax.grad(lambda p: model.apply(p, batch)[0]))(params).jaxpr


def test_each_latent_kernel_s_forward_runs_once_a_step():
    """Two layers, each recomputed in the backward pass with the attention's
    residuals kept: one forward kernel a layer, and no plain kernel."""
    model, batch = _kernel_model()
    calls = collections.Counter(e.params["name"] for e in _pallas_eqns(_grad_jaxpr(model, batch)))
    assert {n: c for n, c in calls.items() if n.startswith("flash")} == {
        "flash_attention_latent_fwd": 2,
        "flash_attention_latent_bwd_dkv": 2,
        "flash_attention_latent_bwd_dq": 2,
    }


@pytest.mark.parametrize("pallas", [True, False])
def test_the_rotary_key_reaches_the_kernels_as_one_head(pallas):
    """In the step's forward and backward, nothing repeats a ``[.., 1, 8]``
    key to the 4 heads where the kernels run; the XLA path does."""
    model, batch = _kernel_model(pallas=pallas)
    repeated = [
        e for e in _eqns(_grad_jaxpr(model, batch))
        if e.primitive.name == "broadcast_in_dim" and e.outvars[0].aval.shape == (1, 64, 4, 8)
    ]
    assert bool(repeated) == (not pallas)


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


BYTES = ("temp_bytes", "argument_bytes", "output_bytes", "alias_bytes", "code_bytes")


@pytest.mark.parametrize("pallas", [False, True])
def test_the_step_says_what_it_was_built_for_and_names_its_scopes(monkeypatch, pallas):
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.parallel import init_state, make_train_step

    model, batch = _kernel_model()
    model = model.clone(use_pallas=pallas, interpret=pallas)
    mesh = make_mesh(devices=jax.devices()[:1])
    optimizer = optax.adam(1e-5)
    with _tracing(monkeypatch):
        state, shardings = init_state(model, optimizer, mesh, batch)
        step = make_train_step(model, optimizer, mesh, shardings)
        lowered = step.lower(state, batch).as_text(debug_info=True)
        step(state, batch)
        spans = telemetry.local_spans()
    build, *traced = [s["args"] for s in spans if s["name"] == "step:build"]
    assert build == {
        "model": "kimi", "experts_held": 4, "layers": 2, "kv_lora_rank": 32,
        "qk_head_dim": 24, "v_head_dim": 16, "rope_head_dim": 8, "shared_key": True,
        "latent_kernels": pallas, "attention_kept": 2, "routing_kept": 1,
    }
    # 64 positions in query blocks of 32 and key blocks of 16: 6 causal
    # blocks a head, 4 heads, 2 layers.
    (traced,) = traced
    sizes = {k: traced.pop(k) for k in BYTES}
    assert traced == {**build, "attention_grid_steps": 2 * 4 * 6, "attention_blocks": 2 * 4 * 6}
    assert sizes["temp_bytes"] > 0
    (load,) = [s["args"] for s in spans if s["name"] == "moe:load"]
    assert load["layers"] == 1 and load["dropped"] == 0
    for scope in ("attention", "router", "experts", "shared_expert", "dense_ffn", "head"):
        assert re.search(rf'loss[^"]*/{scope}/', lowered), scope
    # The low-rank path lies inside the attention's scope, and nothing else does.
    assert re.search(r'loss[^"]*/attention/latent/', lowered)
    assert not re.search(r'/latent/[^"]*/attention/|loss[^"]*/latent/', lowered.replace(
        "/attention/latent/", "/attention/LATENT/"))


def test_the_family_s_tree_carries_every_leaf_there_and_back(family):
    cfg = toy_config()
    weights = family.reference.init_params(cfg, SEED)
    side = _side(family, cfg)
    tree = side.tree(weights)
    model = KimiLM(_model_config(cfg))
    own = jax.eval_shape(
        lambda: model.init(jax.random.key(0), {"tokens": jnp.zeros((1, 64), jnp.int32)})
    )
    assert jax.tree.map(lambda x: x.shape, tree) == jax.tree.map(lambda x: x.shape, own)
    back = side.flat(tree)
    assert all(back[k] is weights[k] for k in weights)


# -- the sisters' attention kernels lower to the pinned text ----------------------------------


LOWERED = {
    "lfm2 32/8 heads of 64": ((4, 8192, 32, 64), (4, 8192, 8, 64), 64, None, False),
    "laguna full 48/8 heads of 128": ((1, 8192, 48, 128), (1, 8192, 8, 128), 128, None, False),
    "laguna window 512, 64/8 heads": ((1, 8192, 64, 128), (1, 8192, 8, 128), 128, 512, False),
    "phi4flash differential 40/20 heads of 64, values 128": (
        (1, 8192, 40, 64), (1, 8192, 20, 64), 128, None, False),
    "phi4flash window 512 differential": ((1, 8192, 40, 64), (1, 8192, 20, 64), 128, 512, False),
    "keye sparse 32/4 heads of 128": ((1, 16384, 32, 128), (1, 16384, 4, 128), 128, None, True),
}


@pytest.mark.parametrize("call", sorted(LOWERED))
def test_without_a_shared_key_the_kernels_lower_to_the_parent_s_text(call):
    """Forward and backward of the sister cells' calls at their shapes,
    lowered for the TPU (Mosaic's kernels serialised in the text), against
    the digests pinned in ``tests/fixtures``: a shared key part moves none
    of them, and a change to the plain kernels pins them again from its own
    lowering (the forward key-major on rows was the last). Source
    locations are left out of the lowering on both sides: a kernel's text
    otherwise carries the line numbers of the file it was written in."""
    with open(os.path.join(ROOT, "tests", "fixtures", "flash_lowered_without_shared_key.json")) as f:
        pinned = json.load(f)
    if pinned["jax"] != jax.__version__:
        pytest.skip(f"pinned under jax {pinned['jax']}, this is {jax.__version__}")
    qs, ks, dv, window, sparse = LOWERED[call]
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(qs, bf), jax.ShapeDtypeStruct(ks, bf),
            jax.ShapeDtypeStruct(ks[:3] + (dv,), bf)]
    if sparse:
        args.append(jax.ShapeDtypeStruct((qs[0], qs[1] // 512, 16, qs[1]), jnp.int32))

    def loss(q, k, v, *words):
        out = flash_attention(q, k, v, causal=True, window=window, use_pallas=True,
                              block_q=512, block_k=512, selected=words[0] if words else None)
        return (out[0] if sparse else out).astype(jnp.float32).sum()

    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert hashlib.sha256(text.encode()).hexdigest() == pinned["calls"][call]
    assert "latent" not in text
