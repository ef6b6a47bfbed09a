"""Keye-VL-2.0's language model: the indexer's scores and exact top-k, the
sparse attention kernels and the indexer's loss against plain formulas, the
softmax router, text MRoPE, the expert shares against the uncut layer, the
program against the benchmark's plain float32 reference, what the step keeps
and says, and that the attention kernels without a selection trace to the
pinned text. CPU only, toy sizes, the kernels in the Pallas interpreter."""

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
from ray_shuffling_data_loader_tpu.models import blocks  # noqa: E402
from ray_shuffling_data_loader_tpu.models.keye import KeyeConfig, KeyeLM  # noqa: E402
from ray_shuffling_data_loader_tpu.ops import moe  # noqa: E402
from ray_shuffling_data_loader_tpu.ops import sparse_attention as sa  # noqa: E402
from ray_shuffling_data_loader_tpu.ops.flash_attention import flash_attention  # noqa: E402
from ray_shuffling_data_loader_tpu.parallel import make_mesh  # noqa: E402

BENCH = harness.load_benchmark()
SEED = 2**31 + 38
CELL = "keye-seq16k-train"


def toy_config(**over):
    """The benchmark's configuration at its rehearsal sizes, in float32
    unless told otherwise."""
    _, cfg, _ = harness.load_cell(BENCH, CELL)
    cfg = {**cfg, **cfg["rehearsal"]}
    cfg["model"] = {**cfg["model"], "compute_dtype": "float32"}
    return {**cfg, **over}


def _model_config(cfg) -> KeyeConfig:
    return KeyeConfig.from_dict(harness.load_family(cfg).program.model_config(cfg))


def _indexer_inputs(seq, heads=4, dim=8, ties=False, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(keys[0], (2, seq, heads, dim))
    k = jax.random.normal(keys[1], (2, seq, dim))
    w = jax.random.normal(keys[2], (2, seq, heads))
    if ties:  # coarse values: many scores exactly equal
        q, k, w = (jnp.round(x * 2) / 2 for x in (q, k, w))
    return q, k, w


def _top_k_selection(q, k, w, topk):
    """The formula: every causal key scored, ``lax.top_k`` of each row, the
    keys past the query dropped."""
    t = q.shape[1]
    scores = jnp.einsum(
        "bths,bth->bts",
        jax.nn.relu(jnp.einsum("bthd,bsd->bths", q, k, precision="highest")), w,
        precision="highest",
    )
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = np.asarray(jnp.where(causal, scores, -jnp.inf))
    _, idx = jax.lax.top_k(scores, min(topk, t))
    out = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for i in range(t):
            keep = np.asarray(idx[b, i])[: min(topk, i + 1)]
            out[b, i, keep] = True
    return out, scores


# -- the indexer's selection --------------------------------------------------------


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("blocks_qk", [(32, 32), (32, 16), (64, 32)])
@pytest.mark.parametrize("pallas", [False, True])
def test_the_selection_is_lax_top_k_of_the_causal_row_ties_to_the_lower_index(
    pallas, blocks_qk, ties
):
    bq, bk = blocks_qk
    q, k, w = _indexer_inputs(128, ties=ties)
    words, lse = sa.index_select(q, k, w, 24, bq, bk, use_pallas=pallas,
                                 interpret=pallas)
    assert words.shape == (2, 128 // bq, bq // 32, 128) and words.dtype == jnp.int32
    want, scores = _top_k_selection(q, k, w, 24)
    got = np.asarray(sa.unpack(words))
    assert np.array_equal(got, want)
    assert got.sum() == 2 * sa.selected_pairs(128, 24)
    assert np.array_equal(got.sum(axis=-1)[0, :30], np.minimum(np.arange(1, 31), 24))
    want_lse = jax.nn.logsumexp(np.where(want, scores, -np.inf), axis=-1)
    assert np.allclose(lse, want_lse, atol=1e-5)
    if ties:  # the coarse inputs do tie at the k-th place
        kth = np.sort(np.where(want, scores, np.inf), axis=-1)[..., 0]
        dropped = np.where(~want & (np.arange(128)[None, :, None] >= np.arange(128)), scores, -np.inf)
        assert (dropped.max(axis=-1) == kth).any()


def test_the_words_pack_a_block_as_the_kernels_expand_it():
    sel = jax.random.bernoulli(jax.random.key(4), 0.3, (1, 128, 64))
    words = sa.pack(sel, 64)
    assert np.array_equal(sa.unpack(words), sel)
    # Query block 1, key block 1 of 32: R = 2 words a key, 32 bits each.
    block = sa.expand(words[0, 1, :, 32:64], 64)
    assert np.array_equal(block, sel[0, 64:128, 32:64])
    work = sa.block_work(words, 32)
    assert work.shape == (1, 2, 2) and bool(work.all())
    counts = sa.select_counts(words, 32)
    # Causal blocks: query block 0 sees key block 0 and 1 (its 64 queries
    # reach key 63), query block 1 both.
    assert counts.tolist() == [4, 4, int(sel.sum())]


# -- the sparse attention kernels -----------------------------------------------------


def _gathered_attention(q, k, v, selected):
    """Each query's kept keys gathered, softmax over them alone: float32 at
    ``highest``."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    out = np.zeros(q.shape, np.float32)
    sel = np.asarray(selected)
    for bi in range(b):
        for i in range(t):
            keys = np.flatnonzero(sel[bi, i])
            kk = np.repeat(np.asarray(k[bi, keys]), group, axis=1)
            vv = np.repeat(np.asarray(v[bi, keys]), group, axis=1)
            s = np.einsum("hd,khd->hk", np.asarray(q[bi, i]), kk) / math.sqrt(d)
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            out[bi, i] = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True), vv)
    return out


@pytest.mark.parametrize("blocks_qk", [(32, 32), (32, 16)])
def test_the_sparse_kernels_against_gathered_attention(blocks_qk):
    """Forward, its log-sum-exp and the three gradients of the kernels in
    the interpreter against the dense masked formula (whose forward is the
    gathered one), grouped heads 4 over 2."""
    bq, bk = blocks_qk
    qi, ki, wi = _indexer_inputs(64, seed=1)
    words, _ = sa.index_select(qi, ki, wi, 12, bq, bk, use_pallas=False)
    keys = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(keys[0], (2, 64, 4, 16))
    k, v = (jax.random.normal(x, (2, 64, 2, 16)) for x in keys[1:3])
    ct = jax.random.normal(keys[3], (2, 64, 4, 16))

    def run(pallas):
        def loss(q, k, v):
            out, lse = flash_attention(q, k, v, causal=True, use_pallas=pallas,
                                       interpret=pallas, block_q=bq, block_k=bk,
                                       selected=words)
            return jnp.sum(out * ct), (out, lse)

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, (out, lse)), grads = run(True)
    (_, (want_out, want_lse)), want_grads = run(False)
    assert np.allclose(out, _gathered_attention(q, k, v, sa.unpack(words)), atol=1e-5)
    assert np.allclose(out, want_out, atol=1e-5) and np.allclose(lse, want_lse, atol=1e-5)
    for got, want in zip(grads, want_grads):
        assert np.allclose(got, want, atol=1e-4), float(jnp.abs(got - want).max())


def test_a_selection_of_every_causal_key_is_causal_attention():
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 64, 2, 16)) for i in range(3))
    everything = sa.pack(jnp.ones((1, 64, 64), bool), 32)
    out, _ = flash_attention(q, k, v, causal=True, use_pallas=True, interpret=True,
                             block_q=32, block_k=16, selected=everything)
    plain = flash_attention(q, k, v, causal=True, use_pallas=False)
    assert np.allclose(out, plain, atol=1e-5)


# -- the indexer's loss ----------------------------------------------------------------


def _loss_inputs(bq=32, bk=16, seed=2):
    qi, ki, wi = _indexer_inputs(64, seed=seed)
    words, lse_i = sa.index_select(qi, ki, wi, 12, bq, bk, use_pallas=False)
    keys = jax.random.split(jax.random.key(seed + 10), 2)
    q = jax.random.normal(keys[0], (2, 64, 4, 16))
    k = jax.random.normal(keys[1], (2, 64, 2, 16))
    _, lse = flash_attention(q, k, k, causal=True, use_pallas=False, block_q=bq,
                             block_k=bk, selected=words)
    return (qi, ki, wi), (q, k, lse, lse_i, words)


class _ZAgain:
    """Stands in for the loss kernel's ``z_scr``: a write is dropped, a read
    of head ``j`` is its product ``q^I_j @ k^Iᵀ`` again, in the kernel's
    six bfloat16 terms."""

    def __init__(self, qi_ref, kt_ref):
        self.qi_ref, self.kt_ref = qi_ref, kt_ref

    def __setitem__(self, j, z):
        pass

    def __getitem__(self, j):
        return sa._dot(sa._lhs6(self.qi_ref[0, j]), sa._rhs6(self.kt_ref[0]))


def _recomputing_z(monkeypatch):
    """The loss kernel as it was before it kept ``z``: every read of the
    scratch computes the product again."""
    kernel = sa._index_bwd_kernel

    def recomputing(*refs, **kw):
        return kernel(*refs[:-1], _ZAgain(refs[3], refs[5]), **kw)

    monkeypatch.setattr(sa, "_index_bwd_kernel", recomputing)


@pytest.mark.parametrize("blocks_qk", [(32, 32), (32, 16), (32, 8)])
def test_the_indexer_loss_kernel_is_the_formula_and_its_gradient(blocks_qk, monkeypatch):
    """``L = mean_t sum_S pbar (log pbar - log softmax_S(I))`` by hand, the
    kernel's loss and three gradients against the XLA path's autodiff, and
    equal to the bit to the kernel that computes each ``z`` again in its
    gradient loop: a ``z`` left in the scratch by another key block (up to
    8 a query block at blocks of 32 / 8) would show."""
    bq, bk = blocks_qk
    (qi, ki, wi), (q, k, lse, lse_i, words) = _loss_inputs(bq, bk)
    sel = np.asarray(sa.unpack(words))
    scores = np.asarray(sa.index_scores(qi, ki, wi))
    group = 2
    s = np.einsum("bthd,bshd->bhts", q, np.repeat(k, group, axis=2)) / 4.0
    p = np.where(sel[:, None], np.exp(s - np.asarray(lse)[..., None]), 0.0)
    pbar = p.mean(axis=1)
    log_soft = scores - np.log(np.where(sel, np.exp(scores), 0).sum(-1, keepdims=True))
    by_hand = np.where(sel, pbar * (np.log(np.where(sel, pbar, 1.0)) - log_soft), 0).sum() / (2 * 64)

    def of(pallas):
        return jax.value_and_grad(
            lambda a, b, c: sa.index_loss(a, b, c, q, k, lse, lse_i, words, bq, bk,
                                          use_pallas=pallas, interpret=pallas),
            argnums=(0, 1, 2),
        )(qi, ki, wi)

    (got, grads), (want, want_grads) = of(True), of(False)
    assert float(want) == pytest.approx(by_hand, rel=1e-4)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, want_grads):
        assert np.allclose(g, w, atol=1e-5 * float(jnp.abs(w).max())), float(jnp.abs(g - w).max())
    _recomputing_z(monkeypatch)
    again, again_grads = of(True)
    for x, y in zip((got, *grads), (again, *again_grads)):
        assert np.array_equal(x, y), float(jnp.abs(x - y).max())


def _pallas_eqns(jaxpr):
    """Every Pallas call's equation under ``jaxpr``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_eqns(sub)
    return found


def _kernel_dots(fn, args, name):
    """``{(precision, operand dtype, contraction): count}`` of the
    ``dot_general``s in the kernel of the Pallas call ``name`` that ``fn``
    traces, into its loops and ``pl.when`` bodies; traced only."""
    (call,) = [e for e in _pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
               if e.params["name"] == name]

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    def contraction(e):
        (lhs, _), _ = e.params["dimension_numbers"]
        return math.prod(e.invars[0].aval.shape[i] for i in lhs)

    return collections.Counter(
        (str(e.params["precision"]), str(e.invars[0].aval.dtype), contraction(e))
        for e in dots(call.params["jaxpr"])
    )


def _loss_kernel_dots():
    """The dots of ``sparse_index_bwd``'s kernel: 4 main heads of 16 in
    bfloat16 over 2 key heads, 4 index heads of 8, blocks 32 / 16."""
    (qi, ki, wi), (q, k, lse, lse_i, words) = _loss_inputs()
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    grad = jax.grad(
        lambda a, b, c: sa.index_loss(a, b, c, q, k, lse, lse_i, words, 32, 16,
                                      use_pallas=True, interpret=True),
        argnums=(0, 1, 2),
    )
    return _kernel_dots(grad, (qi, ki, wi), "sparse_index_bwd")


def test_the_indexer_loss_kernel_computes_each_index_head_s_z_once_a_pair(monkeypatch):
    """An index head's products a pair are bfloat16 and hold ``highest``'s
    six terms: ``z`` one product of contraction ``6 id`` (48), ``dq`` one
    of ``4 bk`` (64), ``dkᵀ`` three of ``bq`` (32); no float32 product at
    ``highest`` is left; one bfloat16 product a main head (16). Computing
    ``z`` again in the gradient loop, as the kernel once did, makes it two
    ``z`` products a head."""
    bf16 = ("None", "bfloat16")
    kept = {(*bf16, 16): 4, (*bf16, 6 * 8): 4, (*bf16, 4 * 16): 4, (*bf16, 32): 3 * 4}
    assert _loss_kernel_dots() == kept
    _recomputing_z(monkeypatch)
    assert _loss_kernel_dots() == {**kept, (*bf16, 6 * 8): 2 * 4}


def test_the_indexer_select_kernel_computes_z_as_six_bfloat16_terms():
    """``sparse_index_fwd``'s one product an index head a key block is
    bfloat16 of contraction ``6 id``: ``z`` at ``highest`` in six terms, no
    float32 product left."""
    q, k, w = _indexer_inputs(128)
    select = lambda q, k, w: sa.index_select(q, k, w, 24, 32, 16, use_pallas=True,  # noqa: E731
                                             interpret=True)
    assert _kernel_dots(select, (q, k, w), "sparse_index_fwd") == {
        ("None", "bfloat16", 6 * 8): 4
    }


def _precision_operands(m, k, n, seed):
    """float32 ``[m, k]``, ``[k, n]``: normal numbers times ``2**e``, ``e``
    drawn in [-30, 30] for each entry, a tenth of the entries zero."""
    rng = np.random.default_rng(seed)

    def draw(shape):
        x = rng.standard_normal(shape) * 2.0 ** rng.integers(-30, 31, shape)
        x[rng.random(shape) < 0.1] = 0
        return x.astype(np.float32)

    return draw((m, k)), draw((k, n))


def _three_terms(a, b):
    """``bf16_3x``: each side split in two, hi·hi + hi·lo + lo·hi."""
    def two(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (a_hi, a_lo), (b_hi, b_lo) = two(jnp.asarray(a)), two(jnp.asarray(b))
    return sa._dot(a_hi, b_hi) + sa._dot(a_hi, b_lo) + sa._dot(a_lo, b_hi)


def _six_terms(product, a, b):
    """``a @ b`` as the loss and select kernels compute ``product``."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    if product == "z":  # q^I @ kᵀ
        return sa._dot(sa._lhs6(a), sa._rhs6(b))
    if product == "dq":  # dz @ k, k handed over as kᵀ
        halves = sa._dq6(sa._split3(a), sa._dq_parts(b.T))
        return halves[:, : b.shape[1]] + halves[:, b.shape[1] :]
    return sa._dkt6(sa._stack3(a), sa._split3(b))  # dkᵀ: q^Iᵀ @ dz


@pytest.mark.parametrize("form", ["six_terms", "three_terms"])
@pytest.mark.parametrize("product,shape", [("z", (512, 64, 512)), ("dq", (512, 512, 64)),
                                           ("dkt", (64, 512, 512))])
def test_the_indexer_s_bfloat16_products_keep_float32_s_precision(product, shape, form):
    """The six bfloat16 terms against float64 on the kernels' shapes, the
    error over ``|a| @ |b|`` entry by entry: within 1e-6, as float32 at
    ``highest`` (about 2e-7); the three-term form (about 5e-6) is not, so
    the bound tells the two apart."""
    a, b = _precision_operands(*shape, seed=len(product))
    exact = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
    got = _six_terms(product, a, b) if form == "six_terms" else _three_terms(a, b)
    live = scale > 0
    err = float((np.abs(np.asarray(got, np.float64) - exact)[live] / scale[live]).max())
    assert (err <= 1e-6) == (form == "six_terms"), err


def test_the_indexer_loss_kernel_refuses_a_scratch_over_its_vmem_limit():
    """DSA's own indexer, 64 index heads of 128 beside 128 heads, at blocks
    of 512: its ``z`` alone is 64 MiB of VMEM."""
    b, t = 1, 1024
    f32 = jnp.float32
    args = (
        jax.ShapeDtypeStruct((b, t, 64, 128), f32), jax.ShapeDtypeStruct((b, t, 128), f32),
        jax.ShapeDtypeStruct((b, t, 64), f32),
        jax.ShapeDtypeStruct((b, t, 128, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, t, 1, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, 128, t), f32), jax.ShapeDtypeStruct((b, t), f32),
        jax.ShapeDtypeStruct((b, 2, 16, t), jnp.int32),
    )
    with pytest.raises(ValueError, match=r"sparse_index_bwd's scratch .* 64 index heads of 128"):
        jax.eval_shape(lambda *a: sa.index_loss(*a, 512, 512, use_pallas=True), *args)


@pytest.mark.parametrize("tokens", [16_384, 65_536])
def test_the_indexer_select_kernel_refuses_a_scratch_over_its_vmem_limit(tokens):
    """Keye's indexer, 16 heads of 64 at blocks of 512: at 16,384 tokens the
    row of keys (32 MiB), the weights' columns and ``q``'s parts fit; at
    65,536 the row alone is 128 MiB of VMEM."""
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((1, tokens, 16, 64), f32),
            jax.ShapeDtypeStruct((1, tokens, 64), f32),
            jax.ShapeDtypeStruct((1, tokens, 16), f32))
    select = lambda *a: sa.index_select(*a, 2048, 512, 512, use_pallas=True)  # noqa: E731
    if tokens == 16_384:
        words, _ = jax.eval_shape(select, *args)
        assert words.shape == (1, 32, 16, tokens)
        return
    with pytest.raises(ValueError, match=r"sparse_index_fwd's scratch .* 65536 keys"):
        jax.eval_shape(select, *args)


def test_the_indexer_loss_trains_the_indexer_alone():
    """Through the whole model: the indexer's leaves get a gradient from
    its loss and from nothing else, and every other leaf's gradient is the
    next-token loss's alone (the indexer's input is detached, ``pbar`` has
    no gradient)."""
    cfg = toy_config(num_hidden_layers=2)
    model = KeyeLM(_model_config(cfg), compute_dtype=jnp.float32, use_pallas=False,
                   block_q=32, block_k=16)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 64), 0, 256)}
    params = model.init(jax.random.key(2), batch)
    whole = jax.grad(lambda p: model.apply(p, batch)[0])(params)

    import ray_shuffling_data_loader_tpu.models.keye as keye

    real = keye.index_loss
    try:
        keye.index_loss = lambda *a, **kw: jnp.float32(0.0)
        lm_only = jax.grad(lambda p: model.apply(p, batch)[0])(params)
    finally:
        keye.index_loss = real
    flat = jax.tree_util.tree_flatten_with_path(whole)[0]
    lm = dict(jax.tree_util.tree_flatten_with_path(lm_only)[0])
    indexer = [path for path, _ in flat if "indexer" in jax.tree_util.keystr(path)]
    assert len(indexer) == 2 * 5  # q, k, weights, the key norm's scale and bias
    for path, g in flat:
        if path in indexer:
            assert float(jnp.abs(g).max()) > 0 and float(jnp.abs(lm[path]).max()) == 0
        else:
            assert np.allclose(g, lm[path], atol=1e-6, rtol=1e-5), jax.tree_util.keystr(path)


# -- the router and the rotary positions ----------------------------------------------------


def test_the_router_is_softmax_over_all_experts_then_top_k_renormalised():
    spec = _model_config(toy_config()).experts
    assert (spec.selection_bias, spec.norm_topk, spec.scaling, spec.scoring) == (
        False, True, 1.0, "softmax"
    )
    assert (spec.routed, spec.held, spec.top_k) == (16, 4, 2)
    x = jax.random.normal(jax.random.key(0), (12, 8))
    gate = jax.random.normal(jax.random.key(1), (8, 16))
    experts, weights = moe.route(x, gate, None, 2, True, 1.0, "softmax")
    probs = np.asarray(jax.nn.softmax(jnp.dot(x, gate, precision="highest"), axis=-1))
    top = np.argsort(-probs, axis=-1)[:, :2]
    assert np.array_equal(np.sort(experts, axis=-1), np.sort(top, axis=-1))
    chosen = np.take_along_axis(probs, np.asarray(experts), axis=-1)
    assert np.allclose(weights, chosen / chosen.sum(axis=-1, keepdims=True), atol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, gate, None, 2, True, 1.0, "cosine")


def test_text_mrope_is_1d_rotary_over_the_whole_head():
    """The published ``mrope_section`` [16, 24, 24] with the three position
    ids equal (text) turns a head of 128 as plain rotary at ``rope_theta``."""
    _, cfg, _ = harness.load_cell(BENCH, CELL)
    reference = harness.load_family(cfg).reference
    seq = 40
    cos, sin = reference.mrope_table(cfg, np.tile(np.arange(seq), (3, 1)))
    x = jax.random.normal(jax.random.key(3), (1, seq, 2, 128))
    with jax.default_matmul_precision("highest"):
        turned = reference._rotary(x, (cos, sin))
    plain = blocks.rotary(x, blocks.Rope(128, float(cfg["rope_theta"])))
    assert np.allclose(turned, plain, atol=2e-5)
    # Other position ids a section would turn otherwise: the table does read them.
    moved = reference.mrope_table(cfg, np.stack([np.arange(seq), np.zeros(seq), np.zeros(seq)]))
    assert not np.allclose(moved[0], cos)
    assert np.allclose(moved[0][:, :16], cos[:, :16])


# -- the shares add up ------------------------------------------------------------------


def test_the_expert_shares_and_what_every_chip_computes_add_up_to_the_uncut_layer():
    """The 8 shares of 2 routed experts, summed, plus the attention (every
    chip computes it alike) counted once, are the reference's whole layer
    with all 16 experts: through the program's kernels and through the
    reference's own share."""
    cfg = toy_config()
    ref = harness.load_family(cfg).reference
    routed, held, top_k = 16, 2, int(cfg["num_experts_per_tok"])
    params = ref.init_params(cfg, SEED)
    h, w = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    keys = jax.random.split(jax.random.key(7), 4)
    p = {
        **{k: v for k, v in params.items() if k.startswith("l0.")},
        "l0.moe.w1": jax.random.normal(keys[0], (routed, h, w)) / np.sqrt(h),
        "l0.moe.w3": jax.random.normal(keys[1], (routed, h, w)) / np.sqrt(h),
        "l0.moe.w2": jax.random.normal(keys[2], (routed, w, h)) / np.sqrt(w),
    }
    x = jax.random.normal(keys[3], (1, 64, h))
    same = lambda v: v  # noqa: E731
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        attn, _ = ref._attention(cfg, p, "l0.", ref._rmsnorm(x, p["l0.in_norm"], eps), same)
        mid = x + attn
        normed = ref._rmsnorm(mid, p["l0.post_norm"], eps)
        whole = mid + ref.routed_ffn(cfg, p, "l0.", normed, same, first=0, held=routed)
        of_reference, of_program, loads = mid, mid, []
        tokens = normed.reshape(-1, h)
        experts, weights = moe.route(tokens, p["l0.moe.gate"], None, top_k, True, 1.0, "softmax")
        for first in range(0, routed, held):
            share = {
                k: v[first : first + held] if k[-2:] in ("w1", "w3", "w2") else v
                for k, v in p.items()
            }
            of_reference += ref.routed_ffn(cfg, share, "l0.", normed, same, first=first, held=held)
            y, load, dropped, _ = moe.experts_ffn(
                tokens, experts, weights, share["l0.moe.w1"], share["l0.moe.w3"],
                share["l0.moe.w2"], first, routed, tile=8, use_pallas=True, interpret=True,
            )
            of_program += y.reshape(x.shape)
            loads.append(np.asarray(load))
            assert int(dropped) == 0
    assert float(jnp.abs(whole - mid).max()) > 0.1 and float(jnp.abs(attn).max()) > 0.1
    assert np.allclose(of_reference, whole, atol=1e-5)
    assert np.allclose(of_program, whole, atol=1e-5)
    assert int(np.concatenate(loads).sum()) == tokens.shape[0] * top_k
    assert np.allclose(weights.sum(axis=-1), 1.0, atol=1e-5)


# -- the program against the reference ------------------------------------------------------


@pytest.fixture(scope="module")
def family():
    return harness.load_family(toy_config())


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
    change after three Adam steps; the selections are the same in float32
    at these sizes (a rounding can split a near tie, which would show
    here)."""
    cfg = toy_config(num_hidden_layers=layers)
    prog, make, ref_batches = _readings(cfg, family)
    ref = family.reference.Reference(cfg).follow(make, ref_batches)
    assert np.allclose(prog["loss"], ref["loss"], rtol=2e-5), (prog["loss"], ref["loss"])
    assert set(prog["grad_norm"]) == set(family.counts.leaf_shapes(cfg))
    for leaf, want in ref["grad_norm"].items():
        assert prog["grad_norm"][leaf] == pytest.approx(want, rel=2e-3, abs=1e-7), leaf
    numbers = check.training_numbers(prog, ref)
    assert numbers["grad_diff"] < 2e-3 and numbers["loss_gap"] < 1e-4, numbers


def test_the_reference_s_selection_is_the_program_s_in_float32(family):
    cfg = toy_config(num_hidden_layers=1)
    params = family.reference.init_params(cfg, SEED)
    tokens = jax.random.randint(jax.random.key(9), (1, 64), 0, 256)
    with jax.default_matmul_precision("highest"):
        want = family.reference.selection(cfg, params, tokens, 0)
    side = family.program.Side.__new__(family.program.Side)
    side.leaves = list(family.counts.leaf_shapes(cfg))
    model = KeyeLM(_model_config(cfg), compute_dtype=jnp.float32, use_pallas=True,
                   interpret=True, block_q=32, block_k=16)
    _, state = model.apply(side.tree(params), {"tokens": tokens},
                           capture_intermediates=lambda mdl, _: mdl.name == "indexer",
                           mutable=["intermediates"])
    qi, ki, wi = state["intermediates"]["layer_0"]["indexer"]["__call__"][0]
    words, _ = sa.index_select(qi, ki, wi, 16, 32, 16, use_pallas=True, interpret=True)
    assert np.array_equal(sa.unpack(words), want)


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


def _pallas_calls(jaxpr):
    """``[(name, grid)]`` of every Pallas call under ``jaxpr``."""
    return [(e.params["name"], tuple(e.params["grid_mapping"].grid))
            for e in _pallas_eqns(jaxpr)]


def _kernel_model(layers=2):
    cfg = toy_config(num_hidden_layers=layers)
    kernels = cfg["kernels"]
    model = KeyeLM(
        _model_config(cfg), use_pallas=True, interpret=True,
        block_q=kernels["attention_block_q"], block_k=kernels["attention_block_k"],
        row_tile=kernels["expert_row_tile"],
    )
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 64), 0, 256)}
    return model, batch


def test_each_indexer_and_attention_kernel_s_forward_runs_once_a_step():
    """Two layers, each recomputed in the backward pass with the selection,
    the indexer loss's outputs and the attention's residuals kept."""
    model, batch = _kernel_model()
    params = jax.eval_shape(lambda: model.init(jax.random.key(2), batch))
    grad = jax.grad(lambda p: model.apply(p, batch)[0])
    calls = collections.Counter(
        name for name, _ in _pallas_calls(jax.make_jaxpr(grad)(params).jaxpr)
    )
    assert {n: c for n, c in calls.items() if "sparse" in n} == {
        "sparse_index_fwd": 2,
        "sparse_index_bwd": 2,
        "flash_attention_sparse_fwd": 2,
        "flash_attention_sparse_bwd_dkv": 2,
        "flash_attention_sparse_bwd_dq": 2,
    }
    assert not any(n.startswith(("flash_attention_fwd", "flash_attention_window")) for n in calls)


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


def test_the_step_says_what_it_was_built_for_counts_the_selection_and_names_its_scopes(
    monkeypatch,
):
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
        "model": "keye", "experts_held": 4, "layers": 2, "index_topk": 16,
        "index_heads": 4, "attention_kept": 2, "routing_kept": 2,
        "selection_kept": 2,
    }
    # 64 positions in query blocks of 32 and key blocks of 16: 6 causal
    # blocks a head, 4 heads, 2 layers; 904 pairs kept a layer.
    (traced,) = traced
    sizes = {k: traced.pop(k) for k in BYTES}
    assert traced == {
        **build, "attention_grid_steps": 2 * 4 * 6, "attention_blocks": 2 * 4 * 6,
        "selected_pairs": 2 * sa.selected_pairs(64, 16),
    }
    assert sizes["temp_bytes"] > 0
    (select,) = [s["args"] for s in spans if s["name"] == "sparse:select"]
    assert select == {"blocks": 12, "causal_blocks": 12, "pairs": 2 * 904}
    (load,) = [s["args"] for s in spans if s["name"] == "moe:load"]
    assert load["layers"] == 2 and load["dropped"] == 0
    assert metrics["sparse_select"].tolist() == [12, 12, 1808]
    for scope in ("attention", "indexer", "router", "experts", "head"):
        assert re.search(rf'loss[^"]*/{scope}/', lowered), scope
    # The indexer is no part of the attention's scope.
    assert not re.search(r'/attention/[^"]*indexer|/indexer/[^"]*/attention/', lowered)


def test_the_family_s_tree_carries_every_leaf_there_and_back(family):
    cfg = toy_config()
    weights = family.reference.init_params(cfg, SEED)
    side = family.program.Side.__new__(family.program.Side)
    side.leaves = list(family.counts.leaf_shapes(cfg))
    tree = side.tree(weights)
    model = KeyeLM(_model_config(cfg))
    own = jax.eval_shape(
        lambda: model.init(jax.random.key(0), {"tokens": jnp.zeros((1, 64), jnp.int32)})
    )
    assert jax.tree.map(lambda x: x.shape, tree) == jax.tree.map(lambda x: x.shape, own)
    back = side.flat(tree)
    assert all(back[k] is weights[k] for k in weights)


# -- the sisters' attention kernels trace to the pinned text ------------------------------


CALLS = {
    "laguna full 48/8 heads of 128": ((1, 8192, 48, 128), (1, 8192, 8, 128), 128, None, True),
    "laguna window 512, 64/8 heads": ((1, 8192, 64, 128), (1, 8192, 8, 128), 128, 512, True),
    "phi4flash differential 40/20 heads of 64, values 128": (
        (1, 8192, 40, 64), (1, 8192, 20, 64), 128, None, True),
    "phi4flash window 512 differential": ((1, 8192, 40, 64), (1, 8192, 20, 64), 128, 512, True),
    "toy rectangle blocks 32/16": ((2, 64, 4, 16), (2, 64, 2, 16), 16, None, False),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_without_a_selection_the_attention_call_is_the_parent_s(call):
    """Forward and backward of the sister cells' calls trace to the pinned
    text (digests in ``tests/fixtures``: a selection moves none of them, and
    a change to the plain kernels pins them again from its own trace, the
    forward key-major on rows the last; LFM2's call is
    ``tests/test_laguna.py``'s pin)."""
    with open(os.path.join(ROOT, "tests", "fixtures", "flash_traced_without_selection.json")) as f:
        pinned = json.load(f)
    if pinned["jax"] != jax.__version__:
        pytest.skip(f"pinned under jax {pinned['jax']}, this is {jax.__version__}")
    qs, ks, dv, window, causal = CALLS[call]
    bq, bk = (32, 16) if qs[1] == 64 else (512, 512)
    q, k = jax.ShapeDtypeStruct(qs, jnp.bfloat16), jax.ShapeDtypeStruct(ks, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(ks[:3] + (dv,), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window, use_pallas=True,
                               block_q=bq, block_k=bk).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(traced))
    assert hashlib.sha256(text.encode()).hexdigest() == pinned["calls"][call]
    assert "sparse" not in text
