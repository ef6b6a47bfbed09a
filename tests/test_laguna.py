"""Laguna (ISSUE 32): the windowed attention kernels against plain masked
softmax attention, the program's model against the benchmark's plain float32
reference, the expert shares against the uncut layer, the rotary tables
against their formulas, and that LFM2's traced step is the pinned one. CPU
only, toy sizes, the kernels in the Pallas interpreter."""

import collections
import contextlib
import hashlib
import json
import math
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
from ray_shuffling_data_loader_tpu.models import blocks, laguna  # noqa: E402
from ray_shuffling_data_loader_tpu.models.laguna import (  # noqa: E402
    LagunaConfig,
    LagunaLM,
)
from ray_shuffling_data_loader_tpu.ops import moe  # noqa: E402
from ray_shuffling_data_loader_tpu.ops.flash_attention import (  # noqa: E402
    ATTENTION_OUT,
    ATTENTION_STATS,
    flash_attention,
)
from ray_shuffling_data_loader_tpu.parallel import (  # noqa: E402
    TrainState,
    make_mesh,
)
from ray_shuffling_data_loader_tpu.parallel.train import make_step_body  # noqa: E402

BENCH = harness.load_benchmark()
SEED = 2**31 + 32


# -- (a) the windowed kernels against plain masked softmax attention ---------------


def _masked_softmax_attention(q, k, v, window):
    """Float32 at ``highest``: every query against every key, the keys
    outside ``i - window < j <= i`` masked."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(
        q.shape[-1]
    )
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.where((behind >= 0) & (behind < window), s, -jnp.inf)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision="highest"
    )


def _qkv_ct(seq, heads, kv_heads, d, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shape = lambda h: (2, seq, h, d)  # noqa: E731
    return (
        jax.random.normal(keys[0], shape(heads)),
        jax.random.normal(keys[1], shape(kv_heads)),
        jax.random.normal(keys[2], shape(kv_heads)),
        jax.random.normal(keys[3], shape(heads)),
    )


# seq, block_q, block_k, window: what the band does to the blocks.
BANDS = {
    "window < block": (64, 16, 16, 8),
    "window = block": (64, 16, 16, 16),
    "no multiple of the block, blocks inside the band": (64, 16, 16, 40),
    "window = sequence: causal": (64, 16, 16, 64),
    "window > sequence: causal": (64, 16, 16, 100),
    "ragged sequence, unequal blocks": (100, 32, 16, 40),
}


@pytest.mark.parametrize("heads", [6, 8])
@pytest.mark.parametrize("band", sorted(BANDS))
def test_the_windowed_kernels_against_masked_softmax_attention(band, heads):
    """Forward and the three gradients, heads of 128, groups of 6 and of 8
    query heads a key head."""
    seq, block_q, block_k, window = BANDS[band]
    q, k, v, ct = _qkv_ct(seq, heads, 1, 128)

    def kernel(q, k, v, window=window):
        return flash_attention(
            q, k, v, causal=True, use_pallas=True, interpret=True,
            block_q=block_q, block_k=block_k, window=window,
        )

    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(
        lambda q, k, v: _masked_softmax_attention(q, k, v, window), q, k, v
    )
    assert np.allclose(out, want, atol=2e-5)
    for got, wanted in zip(vjp(ct), want_vjp(ct)):
        assert np.allclose(got, wanted, atol=5e-5)
    if window >= seq:
        # Not merely close: the plain causal kernels themselves.
        assert np.array_equal(out, kernel(q, k, v, window=None))


def _pallas_calls(jaxpr):
    """``[(name, grid)]`` of every Pallas call under ``jaxpr``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grid = tuple(eqn.params["grid_mapping"].grid)
            found.append((eqn.params["name"], grid))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


@pytest.mark.parametrize(
    "window,names,blocks",
    [
        # 8,192 positions in blocks of 512: a query block sees the key blocks
        # up to its own without a window (136 pairs), its own and the one
        # before with one of 512 (31), two before with 700 or 1,024 (45).
        (None, "flash_attention_", 136),
        (512, "flash_attention_window_", 31),
        (700, "flash_attention_window_", 45),
        (1024, "flash_attention_window_", 45),
        (8192, "flash_attention_", 136),
    ],
)
def test_a_window_s_grid_visits_the_band_s_blocks_only(window, names, blocks):
    """At the cell's own shapes (traced, nothing runs): forward, dK/dV
    (a key head's 8 query heads times the band), dQ: a step a block with
    work."""
    q = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=True, use_pallas=True, block_q=512, block_k=512,
            window=window,
        ).astype(jnp.float32).sum()

    calls = _pallas_calls(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv).jaxpr)
    assert [name for name, _ in calls] == [
        names + which for which in ("fwd", "bwd_dkv", "bwd_dq")
    ]
    assert [grid for _, grid in calls] == [
        (64, blocks), (8, 8 * blocks), (64, blocks),
    ]


def test_a_window_is_a_causal_query_s_and_at_least_one_key():
    q, k, v, _ = _qkv_ct(16, 2, 1, 8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=4, use_pallas=False)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0, use_pallas=False)
    # The XLA path of other backends masks the same band.
    got = flash_attention(q, k, v, causal=True, window=4, use_pallas=False)
    assert np.allclose(got, _masked_softmax_attention(q, k, v, 4), atol=1e-5)


# -- (g) LFM2's traced call and step are the ones pinned -------------------------------


def _traced(jaxpr) -> str:
    """The jaxpr's text without the addresses of the functions it names."""
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.fixture(scope="module")
def pinned_traces():
    """Digests of the traced text as the key-major forward left it (its
    ``m`` and ``l`` lane-dense rows, the values read as turned blocks), on
    the fused backward's lane-dense ``lse`` and ``D`` rows with dK/dV
    key-major and the kernels' grids over the blocks with work. A change to ``models/blocks.py``
    or ``ops/flash_attention.py`` that is not meant to move LFM2's step
    leaves them; one that is pins again and says why. A jaxpr's text
    belongs to one jax version."""
    with open(os.path.join(ROOT, "tests", "fixtures", "lfm2_traced_grid_of_work.json")) as f:
        pinned = json.load(f)
    if pinned["jax"] != jax.__version__:
        pytest.skip(f"pinned under jax {pinned['jax']}, this is {jax.__version__}")
    return pinned


def test_without_a_window_lfm2_s_attention_call_is_the_pinned_one(pinned_traces):
    """Forward and backward of ``lfm2-seq8k-train``'s call, at its shapes:
    kernels, names, grids, index maps, the named residuals."""
    q = jax.ShapeDtypeStruct((4, 8192, 32, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((4, 8192, 8, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=True, use_pallas=True, block_q=512, block_k=512
        ).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    assert _traced(traced) == pinned_traces["attention"]
    assert "flash_attention_window" not in str(traced)


def _lfm2_step_traced():
    """The whole train step of LFM2 at its rehearsal sizes, every kernel in
    it, as a jaxpr."""
    from ray_shuffling_data_loader_tpu.models.lfm2_moe import (
        Lfm2MoeConfig,
        Lfm2MoeLM,
    )

    _, cfg, _ = harness.load_cell(BENCH, "lfm2-seq8k-train")
    cfg = {**cfg, **cfg["rehearsal"]}
    family = harness.load_family(cfg)
    kernels = cfg["kernels"]
    model = Lfm2MoeLM(
        Lfm2MoeConfig.from_dict(family.program.model_config(cfg)),
        use_pallas=True, interpret=True,
        block_q=kernels["attention_block_q"], block_k=kernels["attention_block_k"],
        row_tile=kernels["expert_row_tile"],
    )
    batch = {"tokens": jnp.zeros((4, 64), jnp.int32)}
    optimizer = optax.adam(1e-5)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), batch))
    state = jax.eval_shape(
        lambda p: TrainState(jnp.zeros((), jnp.int32), p, optimizer.init(p)), params
    )
    return jax.make_jaxpr(make_step_body(model, optimizer))(state, batch)


def test_lfm2_s_traced_step_is_the_pinned_one(pinned_traces):
    """Moving LFM2's layer parts to ``models/blocks.py``, a window and a
    value width in the attention kernels changed no equation of its step
    (ISSUE 32, 34); ISSUE 33 named the routing and the plan in
    ``ops/moe.py`` (nine ``name`` equations an expert layer); ISSUE 35
    gave the three attention kernels their tables and a grid of two axes,
    and ISSUE 37 the backward kernels their statistics as rows: each pinned
    the step again as it then stood, and so did the key-major forward."""
    traced = _lfm2_step_traced()
    assert str(traced).count(f"name[name={moe.ROUTING}]") == pinned_traces["names"] == 9 * 4
    assert _traced(traced) == pinned_traces["step"]


# -- (d) the rotary tables ---------------------------------------------------------------


def toy_config(**over):
    """The benchmark's configuration at its rehearsal sizes, in float32
    unless told otherwise: the program then differs from the reference by
    summation order alone."""
    _, cfg, _ = harness.load_cell(BENCH, "laguna-seq8k-train")
    cfg = {**cfg, **cfg["rehearsal"]}
    cfg["model"] = {**cfg["model"], "compute_dtype": "float32"}
    return {**cfg, **over}


def _model_config(cfg) -> LagunaConfig:
    return LagunaConfig.from_dict(harness.load_family(cfg).program.model_config(cfg))


def test_the_yarn_table_against_its_formula():
    """The full layers' frequencies at the published sizes: ``dim`` 64,
    base 500,000, factor 64 over 4,096 positions, ``beta_fast`` 64,
    ``beta_slow`` 1."""
    _, cfg, _ = harness.load_cell(BENCH, "laguna-seq8k-train")
    rope = _model_config(cfg).rope_full
    assert (rope.dim, rope.theta, rope.factor, rope.original_length) == (
        64, 500000.0, 64.0, 4096,
    )
    assert rope.attention_factor == 1.4158883083359672
    low, high = blocks.yarn_bounds(64, 500000.0, 4096, 64.0, 1.0)
    assert (low, high) == (5, 16)
    table = np.asarray(rope.inv_freq(), np.float64)
    assert table.shape == (32,)

    def by_formula(i):
        f = 500000.0 ** (2 * i / 64)
        ramp = min(max((i - 5) / (16 - 5), 0.0), 1.0)
        return ramp / (64 * f) + (1 - ramp) / f

    for i in range(32):
        assert table[i] == pytest.approx(by_formula(i), rel=2e-6), i
    # A pair that keeps its frequency, one on the ramp, one interpolated.
    assert table[0] == 1.0
    assert table[10] == pytest.approx(0.009147, rel=1e-3)
    assert table[31] == pytest.approx(4.7086e-08, rel=1e-3)
    # The reference's own table (float64 on the host) agrees.
    reference = harness.load_family(cfg).reference
    assert reference.yarn_ramp(64, cfg["rope_parameters"]["full_attention"])[:2] == (5, 16)
    cos, sin, dim = reference.rope_table(cfg, "full_attention", 8)
    assert dim == 64 and cos.shape == (8, 32)
    assert float(cos[0, 0]) == pytest.approx(1.4158883083359672)
    assert np.allclose(sin[1], 1.4158883083359672 * np.sin(table), rtol=1e-5)


def test_the_sliding_layers_turn_the_whole_head_the_full_ones_its_first_half():
    _, cfg, _ = harness.load_cell(BENCH, "laguna-seq8k-train")
    model_cfg = _model_config(cfg)
    sliding, full = model_cfg.rope_sliding, model_cfg.rope_full
    assert (sliding.dim, sliding.theta, sliding.factor) == (128, 10000.0, 1.0)
    assert sliding.attention_factor == 1.0 and full.dim == 64
    want = 10000.0 ** (-np.arange(0, 128, 2) / 128)
    assert np.allclose(sliding.inv_freq(), want, rtol=1e-6)
    x = jax.random.normal(jax.random.key(3), (1, 5, 2, 128))
    turned = blocks.rotary(x, sliding)
    assert np.array_equal(turned[:, 0], x[:, 0])  # position 0 turns by nothing
    assert (np.asarray(turned[:, 1:] != x[:, 1:]).mean(axis=(0, 1, 2)) > 0).all()
    half = blocks.rotary(x, full)
    assert np.array_equal(half[..., 64:], x[..., 64:])
    assert (np.asarray(half[..., :64] != x[..., :64]).mean(axis=(0, 1, 2)) > 0).all()
    # Position 0 of a full layer is scaled by the attention factor alone.
    assert np.allclose(half[:, 0, :, :64], 1.4158883083359672 * x[:, 0, :, :64])
    # Half-split: dimension i turns with i + dim / 2.
    angle = float(sliding.inv_freq()[3])
    assert float(turned[0, 1, 0, 3]) == pytest.approx(
        float(x[0, 1, 0, 3]) * math.cos(angle) - float(x[0, 1, 0, 67]) * math.sin(angle),
        abs=1e-5,
    )


# -- (b) the program against the reference --------------------------------------------------


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
    "full attention + dense FFN": (0, 1),
    "sliding attention + experts": (1, 1),
    "full attention + experts": (4, 1),
    "the whole cut": (0, 5),
}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_the_program_follows_the_reference_in_float32(family, cut):
    """Loss of three steps, every leaf of the first gradient, every leaf's
    change after three Adam steps."""
    first, count = CUTS[cut]
    cfg = toy_config(first_layer=first, num_hidden_layers=count, batch_size=2)
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
    """At the rehearsal's sizes and its own limits (a few hundred tokens
    through four routed layers: a near tie among the router's scores
    decided by rounding sends a token to another expert, and the routed
    scale of 2.5 weighs that; the configuration's limits are read on the
    chip at the published sizes)."""
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


# -- (c) the shares add up --------------------------------------------------------------------


def test_the_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(family):
    """What the 8 shares of 2 routed experts give, summed, plus the shared
    expert counted once, is what the reference gives for the whole layer
    with all 16: through the program's layer and through the reference's
    own share."""
    cfg = toy_config()
    ref = family.reference
    routed, held, top_k = 16, 2, int(cfg["num_experts_per_tok"])
    params = ref.init_params(cfg, SEED)
    h, w = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    keys = jax.random.split(jax.random.key(7), 4)
    p = {
        **{k: v for k, v in params.items() if k.startswith("l1.shared")},
        "l1.moe.gate": params["l1.moe.gate"],
        "l1.moe.w1": jax.random.normal(keys[0], (routed, h, w)) / np.sqrt(h),
        "l1.moe.w3": jax.random.normal(keys[1], (routed, h, w)) / np.sqrt(h),
        "l1.moe.w2": jax.random.normal(keys[2], (routed, w, h)) / np.sqrt(w),
    }
    x = jax.random.normal(keys[3], (2, 48, h))
    same = lambda v: v  # noqa: E731
    with jax.default_matmul_precision("highest"):
        shared = ref.shared_ffn(cfg, p, "l1.", x, same)
        whole = shared + ref.routed_ffn(cfg, p, "l1.", x, same, first=0, held=routed)
        # The program's shared expert: the model's own dense FFN at width 32.
        of_model = blocks.DenseFFN(w, jnp.float32, "shared_expert").apply(
            {"params": {n: p[f"l1.shared.{n}"] for n in ("w1", "w3", "w2")}}, x
        )
        of_reference, of_program, loads = shared, of_model, []
        tokens = x.reshape(-1, h)
        experts, weights = moe.route(tokens, p["l1.moe.gate"], None, top_k, True, 2.5)
        for first in range(0, routed, held):
            share = {
                k: v[first : first + held] if k[-2:] in ("w1", "w3", "w2") and "moe" in k else v
                for k, v in p.items()
            }
            of_reference += ref.routed_ffn(cfg, share, "l1.", x, same, first=first, held=held)
            y, load, dropped, fallback = moe.experts_ffn(
                tokens, experts, weights, share["l1.moe.w1"],
                share["l1.moe.w3"], share["l1.moe.w2"], first, routed,
                tile=8, use_pallas=True, interpret=True,
            )
            of_program += y.reshape(x.shape)
            loads.append(np.asarray(load))
            assert int(dropped) == 0
    assert float(jnp.abs(whole - shared).max()) > 0.1 and float(jnp.abs(shared).max()) > 0.1
    assert np.allclose(of_reference, whole, atol=1e-5)
    assert np.allclose(of_program, whole, atol=1e-5)
    # Every (token, expert) assignment was computed by exactly one share,
    # and the routed weights of a token add up to the routed scale.
    assert int(np.concatenate(loads).sum()) == tokens.shape[0] * top_k
    assert np.allclose(weights.sum(axis=-1), 2.5, atol=1e-5)


def test_the_router_is_route_as_it_stands():
    """No bias, the chosen scores renormalised, times the routed scale:
    ``route(x, gate, None, 8, True, 2.5)``, which is what the layer calls."""
    spec = _model_config(toy_config()).experts
    assert (spec.selection_bias, spec.norm_topk, spec.scaling) == (False, True, 2.5)
    assert (spec.routed, spec.held, spec.first, spec.top_k, spec.width) == (16, 4, 0, 2, 32)
    x = jax.random.normal(jax.random.key(0), (12, 8))
    gate = jax.random.normal(jax.random.key(1), (8, 16))
    experts, weights = moe.route(x, gate, None, 2, True, 2.5)
    scores = jax.nn.sigmoid(jnp.dot(x, gate, precision="highest"))
    top = np.argsort(-np.asarray(scores), axis=-1)[:, :2]
    assert np.array_equal(np.sort(experts, axis=-1), np.sort(top, axis=-1))
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(experts), axis=-1)
    assert np.allclose(weights, 2.5 * chosen / chosen.sum(axis=-1, keepdims=True), atol=1e-6)


# -- the step: what is kept, what the trace is told --------------------------------------------


def _kernel_model(**over):
    """The cut at its rehearsal sizes as the benchmark builds it (bfloat16
    compute, every kernel in the interpreter) and a batch of tokens."""
    cfg = toy_config(**over)
    kernels = cfg["kernels"]
    model = LagunaLM(
        _model_config(cfg), use_pallas=True, interpret=True,
        block_q=kernels["attention_block_q"], block_k=kernels["attention_block_k"],
        row_tile=kernels["expert_row_tile"],
    )
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 64), 0, 256)}
    return model, batch


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


def test_each_attention_kernel_s_forward_runs_once_a_step():
    """Three sliding layers and two full ones, each recomputed in the
    backward pass with its kernel's output and row statistics kept."""
    model, batch = _kernel_model()
    params = jax.eval_shape(lambda: model.init(jax.random.key(2), batch))
    grad = jax.grad(lambda p: model.apply(p, batch)[0])
    calls = collections.Counter(
        name for name, _ in _pallas_calls(jax.make_jaxpr(grad)(params).jaxpr)
    )
    assert {n: c for n, c in calls.items() if n.startswith("flash_attention")} == {
        "flash_attention_fwd": 2,
        "flash_attention_bwd_dkv": 2,
        "flash_attention_bwd_dq": 2,
        "flash_attention_window_fwd": 3,
        "flash_attention_window_bwd_dkv": 3,
        "flash_attention_window_bwd_dq": 3,
    }
    # Four expert layers, forward and recomputed, three products each, in
    # each of the two buffers' branches.
    assert calls["moe_experts_fwd"] == 4 * 2 * 3 * 2


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
        "model": "laguna", "experts_held": 4, "layers": 5, "window": 16,
        "heads_full": 6, "heads_window": 8, "attention_kept": 5,
        "routing_kept": 4,
    }
    # The first step that ran while tracing was on says it again, once for
    # the shape it compiled (lowering alone says nothing), with what hangs
    # on the batch's shape: 64 positions in query blocks of 32 and key
    # blocks of 16 are 6 blocks with work a full head (two layers of 6
    # heads) and 5 a windowed one (three of 8), a grid step each; and with
    # the bytes the compiled program takes.
    blocks = 2 * 6 * 6 + 3 * 8 * 5
    (traced,) = traced
    sizes = {k: traced.pop(k) for k in BYTES}
    assert traced == {
        **build, "attention_grid_steps": blocks, "attention_blocks": blocks,
    }
    assert sizes["argument_bytes"] > 0 and sizes["temp_bytes"] > 0
    (load,) = [s["args"] for s in spans if s["name"] == "moe:load"]
    assert set(load) == {"max", "mean", "dropped", "layers", "fallback"}
    assert load["layers"] == 4 and load["dropped"] == 0
    assert metrics["moe_load"].shape == (4, 4) and np.isfinite(float(metrics["loss"]))
    for scope in (
        "attention", "attention_window", "router", "experts", "shared_expert",
        "dense_ffn", "head",
    ):
        assert re.search(rf'loss[^"]*/{scope}/', lowered), scope


# ``KEPT`` as it stood before ISSUE 33: the attention kernels' residuals
# alone, the routing and the plan built again.
KEPT_BEFORE_ROUTING = jax.checkpoint_policies.save_only_these_names(
    ATTENTION_OUT, ATTENTION_STATS
)


@pytest.mark.parametrize("router", ["even", "collapsed"])
def test_keeping_the_routing_and_the_plan_changes_no_number(monkeypatch, router):
    """Loss, counters and every gradient leaf under ``KEPT`` are, bit for
    bit, those under the parent's ``KEPT`` (which builds every plan twice):
    under the router as initialised, and under one that scores the experts
    held elsewhere a flat 0.5 (their gate columns zeroed; there is no
    selection bias to lean on), so that the four held draw four fifths of
    the choices and every expert layer runs in the worst-case buffer."""
    model, batch = _kernel_model()
    params = model.init(jax.random.key(2), batch)
    if router == "collapsed":
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf.at[:, 4:].set(0.0)
            if path[-1].key == "gate" else leaf,
            params,
        )

    def readings():
        # (Jitted anew each time: the policy is read when the model is traced.)
        return jax.jit(
            jax.value_and_grad(lambda p: model.apply(p, batch), has_aux=True)
        )(params)

    kept = readings()
    monkeypatch.setattr(laguna, "KEPT", KEPT_BEFORE_ROUTING)
    jax.clear_caches()
    parent = readings()
    (_, counters), _ = kept
    assert counters["moe_fallback"].tolist() == [int(router == "collapsed")] * 4
    assert counters["moe_dropped"].tolist() == [0] * 4
    leaves = jax.tree.leaves(kept)
    assert len(leaves) == 1 + 3 + len(jax.tree.leaves(params))
    for got, want in zip(leaves, jax.tree.leaves(parent)):
        assert np.isfinite(got).all() and np.array_equal(got, want)


# (published index of the first layer, layers) -> the layers whose attention
# residuals are kept, the expert layers whose routing and plan are.
KEPT_BY_CUT = {
    "the whole cut": (0, 5, 5, 4),
    "the period: three sliding layers and a full one, each + experts": (1, 4, 4, 4),
    "sliding attention + experts": (1, 1, 1, 1),
    "full attention + dense FFN": (0, 1, 1, 0),
}


@pytest.mark.parametrize("cut", sorted(KEPT_BY_CUT))
def test_step_build_counts_the_layers_whose_residuals_are_kept(monkeypatch, cut):
    """Facts of the traced step, recorded when it is built (nothing is
    compiled here): ``routing_kept`` 0 for a cut without expert layers."""
    from ray_shuffling_data_loader_tpu import telemetry
    from ray_shuffling_data_loader_tpu.parallel import make_train_step

    first, count, attention, routing = KEPT_BY_CUT[cut]
    model = LagunaLM(
        _model_config(toy_config(first_layer=first, num_hidden_layers=count))
    )
    with _tracing(monkeypatch):
        mesh = make_mesh(devices=jax.devices()[:1])
        make_train_step(model, optax.adam(1e-5), mesh, None)
        spans = telemetry.local_spans()
    (build,) = [s["args"] for s in spans if s["name"] == "step:build"]
    assert build["layers"] == count and build["attention_kept"] == attention
    assert build["routing_kept"] == routing


def test_the_family_s_tree_carries_every_leaf_there_and_back(family):
    cfg = toy_config()
    weights = family.reference.init_params(cfg, SEED)
    side = family.program.Side.__new__(family.program.Side)
    side.leaves = list(family.counts.leaf_shapes(cfg))
    tree = side.tree(weights)
    model = LagunaLM(_model_config(cfg))
    own = jax.eval_shape(
        lambda: model.init(jax.random.key(0), {"tokens": jnp.zeros((1, 64), jnp.int32)})
    )
    assert jax.tree.map(lambda x: x.shape, tree) == jax.tree.map(lambda x: x.shape, own)
    back = side.flat(tree)
    assert sorted(back) == sorted(weights)
    assert all(back[k] is weights[k] for k in weights)
