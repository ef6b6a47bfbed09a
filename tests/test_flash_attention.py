"""Pallas flash-attention kernel vs the dense reference, interpreter mode
(the compiled-on-TPU check lives in ``tests/test_ops_tpu.py``'s pattern;
CI has no TPU)."""

import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from ray_shuffling_data_loader_tpu.ops import attention_reference
from ray_shuffling_data_loader_tpu.ops import sparse_attention as sa
from ray_shuffling_data_loader_tpu.ops.flash_attention import (
    ATTENTION_OUT,
    ATTENTION_STATS,
    flash_attention,
)


# (``ops.flash_attention`` is the function; this is its module.)
flash_module = importlib.import_module(
    "ray_shuffling_data_loader_tpu.ops.flash_attention"
)


def _qkv(shape, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal(shape).astype(np.float32), dtype)
        for _ in range(3)
    )


def _dense_forward(q, k, v, admitted, k_shared=None):
    """``(out, m, l)`` of dense float32 attention over the ``admitted [b, t,
    t]`` pairs, ``m`` and ``l`` ``[b, h, t]``: the softmax statistics as the
    kernel keeps them, ``NEG_INF`` and 0 (and an output of 0) on a query
    that admits no key."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if k_shared is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(k_shared, (*k.shape[:3], k_shared.shape[-1]))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    s = jnp.where(admitted[:, None], s, flash_module.NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(admitted[:, None], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-30)[..., None], v,
                     precision="highest")
    return out, m, l


# The first three shapes, causal or not, through ``flash_attention`` as they
# always were; then groups of 1 / 2 / 4, values of another width than the
# heads, ragged sequences under unequal blocks, windows, tables in runs, the
# latent split and the sparse selection, with the statistics (``more``: the
# key heads, the values' width, the window, ``latent`` the shared key's
# width, ``sparse`` a selection's share, ``limit`` the table entries).
_DENSE_SHAPES = [
    ((2, 64, 2, 8), (16, 16)),  # multiple kv blocks per q block
    ((1, 56, 2, 8), (16, 24)),  # ragged: seq divides neither block
    ((2, 8, 1, 4), (128, 128)),  # seq smaller than the block
]
_DENSE_CASES = [
    pytest.param(causal, shape, blocks, {}, id=f"shape{i}-blocks{i}-{causal}")
    for causal in (False, True) for i, (shape, blocks) in enumerate(_DENSE_SHAPES)
] + [
    pytest.param(True, (2, 64, 4, 8), (16, 16), {"kv": 2, "dv": 16}, id="group2-values16"),
    pytest.param(True, (1, 75, 4, 8), (32, 16), {"kv": 1}, id="group4-ragged"),
    pytest.param(False, (1, 90, 4, 8), (16, 32), {"kv": 2, "dv": 16}, id="noncausal-group2-ragged"),
    pytest.param(True, (1, 64, 2, 8), (16, 16), {"window": 7}, id="window7"),
    pytest.param(True, (1, 75, 4, 8), (32, 16), {"kv": 2, "dv": 16, "window": 16},
                 id="window16-group2-ragged"),
    pytest.param(True, (1, 64, 2, 8), (16, 32), {"window": 40}, id="window40"),
    pytest.param(True, (1, 90, 2, 8), (16, 32), {"limit": 6}, id="tables-in-runs"),
    pytest.param(True, (1, 75, 4, 8), (16, 16), {"kv": 2, "window": 16, "limit": 6},
                 id="window16-tables-in-runs"),
    pytest.param(True, (1, 64, 4, 12), (32, 16), {"latent": 4}, id="latent"),
    pytest.param(False, (2, 48, 4, 12), (16, 16), {"kv": 2, "latent": 4, "dv": 16},
                 id="latent-noncausal-group2"),
    pytest.param(True, (1, 64, 4, 8), (32, 16), {"kv": 2, "sparse": 0.3}, id="sparse"),
    pytest.param(True, (2, 64, 2, 8), (64, 32), {"sparse": 0.1, "dv": 16}, id="sparse-values16"),
]


@pytest.mark.parametrize("causal,shape,blocks,more", _DENSE_CASES)
def test_matches_dense_reference(monkeypatch, causal, shape, blocks, more):
    """The forward's output and its softmax statistics ``m``, ``l`` against
    dense float32 attention, within float32 round-off; a query whose
    selection keeps no key finishes as 0, with ``m`` at ``NEG_INF`` and
    ``l`` 0."""
    if not more:
        q, k, v = _qkv(shape, seed=1)
        got = flash_attention(
            q, k, v, causal=causal, use_pallas=True, block_q=blocks[0],
            block_k=blocks[1], interpret=True,
        )
        want = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
    if "limit" in more:
        monkeypatch.setattr(flash_module, "MAX_TABLE_ENTRIES", more["limit"])
    b, t, h, d = shape
    rng = np.random.default_rng(t + h + d)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    shared = more.get("latent", 0)
    hk = more.get("kv", h)
    q, k = normal(b, t, h, d), normal(b, t, hk, d - shared)
    v = normal(b, t, hk, more.get("dv", d))
    k_shared = normal(b, t, 1, shared) if shared else None
    pos = np.arange(t)
    admitted = np.ones((b, t, t), bool)
    if causal:
        admitted &= pos[:, None] >= pos[None, :]
    window = more.get("window")
    if window is not None:
        admitted &= pos[:, None] - pos[None, :] < window
    words = None
    if "sparse" in more:
        admitted &= rng.random((b, t, t)) < more["sparse"]
        admitted[:, 5] = False  # a query that keeps no key
        words = sa.pack(jnp.asarray(admitted), blocks[0])
    out, m, l = flash_module._flash_forward(
        q, k, v, causal, *blocks, True, return_stats=True, window=window,
        selected=words, k_shared=k_shared,
    )
    want = _dense_forward(q, k, v, jnp.asarray(admitted), k_shared)
    for got, w in zip((out, m, l), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(w), rtol=2e-5, atol=2e-5)
    if "sparse" in more:
        assert not np.any(np.asarray(out)[:, 5]) and not np.any(np.asarray(l)[:, :, 5])
        assert np.all(np.asarray(m)[:, :, 5] == flash_module.NEG_INF)


# -- the grid: the blocks that hold work, each once, in the kernels' order ---------

# (seq, block_q, block_k): equal blocks; unequal ones either way round over a
# sequence that is a multiple of neither; one block.
_GRIDS = [(64, 16, 16), (75, 32, 16), (90, 16, 32), (40, 128, 128)]
# None; under a block, a block, several blocks, over the sequence.
_WINDOWS = [None, 7, 16, 40, 1000]


def _walk(steps):
    """``[(outer, member, inner, first, last, runs)]`` of every grid step
    in the grid's order, read as the index maps and the kernels read them."""
    return [
        tuple(
            int(x) for x in
            (*steps.blocks(*at, *steps.tables), *steps.edges(*at, *steps.tables))
        )
        for at in np.ndindex(*steps.grid)
    ]


@pytest.mark.parametrize("limit", [flash_module.MAX_TABLE_ENTRIES, 6])
@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("causal,window", [(False, None)] + [(True, w) for w in _WINDOWS])
@pytest.mark.parametrize("seq,bq,bk", _GRIDS)
def test_the_steps_are_the_blocks_the_mask_admits_a_score_in(
    monkeypatch, seq, bq, bk, causal, window, group, limit
):
    """Each block with work once for each member of the group, in the order
    a kernel accumulates in; a table held to ``limit`` entries keeps that
    order in runs whose spare steps stay on the run's last block."""
    monkeypatch.setattr(flash_module, "MAX_TABLE_ENTRIES", limit)
    bq, bk = min(bq, seq), min(bk, seq)
    nq, nk = -(-seq // bq), -(-seq // bk)
    # The kernels' own mask over the padded square, block by block.
    q_pos = np.arange(nq * bq)[:, None]
    k_pos = np.arange(nk * bk)[None, :]
    valid = np.broadcast_to(k_pos < seq, (nq * bq, nk * bk)).copy()
    if causal:
        valid &= q_pos >= k_pos
    if window is not None:
        valid &= q_pos - k_pos < window
    want = valid.reshape(nq, bq, nk, bk).any(axis=(1, 3))
    work = flash_module._blocks_with_work(nq, nk, bq, bk, causal, window)
    assert np.array_equal(work, want)

    for table, by_key in ((work, False), (work.T, True)):
        members = group if by_key else 1
        steps = flash_module._Steps(table, members)
        walked = _walk(steps)
        assert len(walked) == steps.length
        ran = [(o, g, i) for o, g, i, _, _, runs in walked if runs]
        # Outer block after outer block, member after member, inner blocks
        # rising: every pair once for every member.
        assert ran == sorted(
            (o, g, i) for o, i in np.argwhere(table) for g in range(members)
        )
        if want.all():  # nothing to leave out: the rectangle, and no table
            assert steps.tables == () and len(steps.grid) == 2
        else:
            assert len(steps.grid) == 1
            assert len(steps.tables[0]) <= max(limit, members * len(table))
        if want.all() or limit >= len(ran):
            assert len(walked) == len(ran)
        for at, (o, g, i, first, last, runs) in enumerate(walked):
            before = walked[at - 1] if at else None
            after = walked[at + 1] if at + 1 < len(walked) else None
            assert first == (before is None or before[0] != o)
            assert last == (after is None or after[0] != o)
            if not runs:  # a spare step: the block before it, not fetched again
                assert (o, g, i) == before[:3]
    assert flash_module.grid_steps(seq, bq, bk, causal, window) == (
        flash_module._Steps(work).length, int(want.sum())
    )


# (seq, block_q, block_k, group, value_dim), heads of 8 dimensions: blocks that
# divide the sequence; the rehearsal's unequal blocks either way round over a
# sequence that is a multiple of neither (padded rows), values wider than the
# heads; eight query heads to a key head; the default blocks over a ragged tail.
_BACKWARD_SHAPES = [
    (32, 16, 16, 1, 8),
    (75, 32, 16, 2, 16),
    (90, 16, 32, 4, 16),
    (64, 32, 16, 8, 8),
    (300, 128, 128, 1, 8),
]
# Without ``causal`` every block holds work: no table, so no runs either.
_BACKWARD_MASKS = [(False, None, flash_module.MAX_TABLE_ENTRIES)] + [
    (True, w, limit) for w in _WINDOWS for limit in (flash_module.MAX_TABLE_ENTRIES, 6)
]


def _loss(causal, window, bq, bk):
    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal, use_pallas=True, interpret=True,
            block_q=bq, block_k=bk, window=window,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return loss


@pytest.mark.parametrize("causal,window,limit", _BACKWARD_MASKS)
@pytest.mark.parametrize("seq,bq,bk,group,dv", _BACKWARD_SHAPES)
def test_the_backward_is_the_dense_reference_s_gradient(
    monkeypatch, seq, bq, bk, group, dv, causal, window, limit
):
    """Value and gradients of the fused kernels against ``jax.grad`` of the
    dense reference: causal or not, every kind of window, groups of 1 to 8,
    values wider than the heads, padded rows, unequal blocks, the tables
    whole and in runs."""
    monkeypatch.setattr(flash_module, "MAX_TABLE_ENTRIES", limit)
    heads = max(group, 4)
    rng = np.random.default_rng(seq + group + (window or 0))
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = normal(2, seq, heads, 8)
    k, v = normal(2, seq, heads // group, 8), normal(2, seq, heads // group, dv)

    def dense(q, k, v):
        out = attention_reference(
            q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
            causal=causal, window=None if window is None or window >= seq else window,
        )
        return jnp.sum(out ** 2)

    got = jax.value_and_grad(_loss(causal, window, bq, bk), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4)


def _pallas_calls(jaxpr):
    """``{name: equation}`` of every Pallas call under ``jaxpr``."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.update(_pallas_calls(sub))
    return found


def _pallas_grids(jaxpr):
    """``{name: grid}`` of every Pallas call under ``jaxpr``."""
    return {
        name: tuple(eqn.params["grid_mapping"].grid)
        for name, eqn in _pallas_calls(jaxpr).items()
    }


def _eqns(jaxpr, names):
    """Every equation of a primitive in ``names`` under ``jaxpr``, into its
    ``cond``s' branches."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, names)


def _dots(jaxpr):
    """Every ``dot_general`` under ``jaxpr``, into its ``cond``s' branches."""
    return _eqns(jaxpr, ("dot_general",))


@pytest.mark.parametrize("window", [None, 16])
def test_the_backward_reads_lane_dense_rows_and_runs_dkv_key_major(window):
    """No operand of a backward kernel is a column (``[.., t, 1]``: the
    statistics are ``lse`` and ``D`` rows), and no product of dK/dV
    contracts its left operand's first dimension (a ``[bq, bk]`` block
    transposed): both accumulate ``[bk, bq] @ [bq, d]``. A windowed call
    traces the body twice, for blocks inside the band and on its edges."""
    q, k, v = _qkv((1, 64, 4, 8), seed=13)
    k, v = k[:, :, :2], v[:, :, :2]
    grad = jax.grad(_loss(True, window, 32, 16), (0, 1, 2))
    calls = _pallas_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)
    dkv, dq = (flash_module._kernel_name(window, w) for w in ("bwd_dkv", "bwd_dq"))
    assert sorted(n for n in calls if "_bwd_" in n) == sorted([dkv, dq])
    for name in (dkv, dq):
        shapes = [x.aval.shape for x in calls[name].invars]
        assert [s for s in shapes if s[-1:] == (1,)] == [], (name, shapes)
    contracted = [e.params["dimension_numbers"][0][0] for e in _dots(calls[dkv].params["jaxpr"])]
    assert len(contracted) == (4 if window is None else 8)
    assert all(0 not in c for c in contracted), contracted


@pytest.mark.parametrize("kind", ["plain", "window", "latent", "sparse"])
def test_the_forward_writes_lane_dense_rows_and_runs_key_major(kind):
    """No operand or output of the forward kernel is a column (``[.., t,
    1]``: ``m`` and ``l`` leave it as rows), every reduction in its body
    runs over the keys down the sublanes (axis 0 of a ``[bk, bq]`` block),
    and no product contracts a block's first dimension (a ``[bq, bk]`` or
    ``[bk, bq]`` block transposed)."""
    q, k, v = _qkv((1, 64, 4, 8), seed=13)
    k, v = k[:, :, :2], v[:, :, :2]
    kw = {}
    if kind == "window":
        kw["window"] = 16
    elif kind == "latent":
        k, kw["k_shared"] = k[..., :6], k[:, :, :1, 6:]
    elif kind == "sparse":
        kw["selected"] = sa.pack(jnp.ones((1, 64, 64), bool), 32)
    fwd = jax.make_jaxpr(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, use_pallas=True, interpret=True,
            block_q=32, block_k=16, **kw,
        )
    )
    calls = _pallas_calls(fwd(q, k, v).jaxpr)
    name = flash_module._kernel_name(
        kw.get("window"), "fwd", kind == "sparse", kind == "latent"
    )
    assert list(calls) == [name]
    call = calls[name]
    shapes = [x.aval.shape for x in (*call.invars, *call.outvars)]
    assert [s for s in shapes if s[-1:] == (1,)] == [], shapes
    body = call.params["jaxpr"]
    reduced = [e.params["axes"] for e in _eqns(body, ("reduce_max", "reduce_sum"))]
    assert len(reduced) == (2 if kind != "window" else 4)
    assert all(axes == (0,) for axes in reduced), reduced
    blocks = {(32, 16), (16, 32)}
    for dot in _dots(body):
        lhs = dot.invars[0].aval.shape
        (contracted, _), _ = dot.params["dimension_numbers"]
        assert not (lhs in blocks and 0 in contracted), (lhs, contracted)


def test_a_causal_head_takes_a_step_a_block_with_work_and_the_step_says_so(
    monkeypatch,
):
    """The traced calls' grids at a sequence cell's shape (nothing is
    compiled or run), and what ``step:build`` says of Laguna's step at its
    rehearsal sizes once it has compiled a batch's shape: the grid is the
    kernels', also where the XLA path runs in their place, as here."""
    import optax

    from chipbench import harness
    from ray_shuffling_data_loader_tpu.models.laguna import LagunaConfig, LagunaLM
    from ray_shuffling_data_loader_tpu.parallel import (
        init_state, make_mesh, make_train_step,
    )
    from ray_shuffling_data_loader_tpu.telemetry import trace

    q = jax.ShapeDtypeStruct((1, 8192, 48, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    grad = jax.grad(_loss(True, None, 512, 512), (0, 1, 2))
    nq = 8192 // 512
    assert _pallas_grids(jax.make_jaxpr(grad)(q, kv, kv).jaxpr) == {
        "flash_attention_fwd": (48, nq * (nq + 1) // 2),
        "flash_attention_bwd_dkv": (8, 6 * nq * (nq + 1) // 2),
        "flash_attention_bwd_dq": (48, nq * (nq + 1) // 2),
    }

    _, cfg, _ = harness.load_cell(harness.load_benchmark(), "laguna-seq8k-train")
    cfg = {**cfg, **cfg["rehearsal"]}
    model = LagunaLM(
        LagunaConfig.from_dict(harness.load_family(cfg).program.model_config(cfg)),
        use_pallas=False,
        block_q=cfg["kernels"]["attention_block_q"],
        block_k=cfg["kernels"]["attention_block_k"],
    )
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32)}
    optimizer = optax.adam(1e-5)
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    trace.reset_state()
    try:
        mesh = make_mesh(devices=jax.devices()[:1])
        state, shardings = init_state(model, optimizer, mesh, batch)
        step = make_train_step(model, optimizer, mesh, shardings)
        step.lower(state, batch)  # lowering alone says nothing
        state, _ = step(state, batch)
        step(state, batch)  # a second step of the shape says nothing more
        spans = trace.local_spans()
    finally:
        monkeypatch.delenv("RSDL_TRACE")
        trace.refresh_from_env()
        trace.reset_state()
    built, traced = [s["args"] for s in spans if s["name"] == "step:build"]
    assert "attention_blocks" not in built
    assert built["attention_kept"] == traced["attention_kept"] == 5
    # 64 positions, query blocks of 32 and key blocks of 16, two sequences:
    # a full layer's head (6 of them, layers 0 and 4) has 2 + 4 blocks with
    # work, a head of the three layers between (8, window 16) 2 + 3 (keys
    # 0-31, then 17-63).
    blocks = 2 * (2 * 6 * 6 + 3 * 8 * 5)
    assert traced["attention_blocks"] == traced["attention_grid_steps"] == blocks
    assert blocks < 2 * 5 * 8 * 2 * 4  # the rectangles'


def test_bfloat16(seed=3):
    q, k, v = _qkv((2, 32, 2, 8), seed=seed, dtype=jnp.bfloat16)
    got = flash_attention(
        q, k, v, use_pallas=True, block_q=16, block_k=16, interpret=True
    )
    assert got.dtype == jnp.bfloat16
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32),
        np.asarray(want, dtype=np.float32),
        rtol=5e-2,
        atol=5e-2,
    )


def test_gradients_sharded_mesh():
    """Forward AND fused backward in a multi-device jit with the mesh in
    context: ``shard_map`` splits both pallas calls batch-wise on the
    8-device mesh (each device's kernel sees batch 1, not 8); gradients
    match the dense reference."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    q, k, v = _qkv((8, 64, 2, 8), seed=11)
    sh = NamedSharding(mesh, P("data", None, None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, use_pallas=True, interpret=True
            )
            ** 2
        )

    with jax.set_mesh(mesh):
        grad_fn = jax.jit(jax.grad(loss_flash, (0, 1, 2)))
        assert "f32[1,64,2,8]" in str(jax.make_jaxpr(grad_fn)(qs, ks, vs))
        g_f = grad_fn(qs, ks, vs)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


# -- the residuals' names ---------------------------------------------------------


def _kernel_loss(q, k, v):
    out = flash_attention(
        q, k, v, causal=True, use_pallas=True,
        block_q=16, block_k=16, interpret=True,
    )
    return jnp.sum(out.astype(jnp.float32) ** 2)


def _kernels(jaxpr):
    """How often each Pallas kernel is called in ``jaxpr`` and what it
    calls, by the kernel's name."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _kernels(sub)
    return found


def test_the_residuals_names_change_nothing_for_a_bare_caller(monkeypatch):
    """Outside a policy that lists them the names are identities: value and
    gradients are, bit for bit, those of a forward rule that names nothing
    (the parent's)."""
    q, k, v = _qkv((2, 48, 4, 8), seed=11, dtype=jnp.bfloat16)
    k, v = k[:, :, :2], v[:, :, :2]
    grad = jax.value_and_grad(_kernel_loss, (0, 1, 2))
    named = grad(q, k, v)

    def names():
        fwd = jax.make_jaxpr(flash_module._fwd, static_argnums=(3, 4, 5, 6))
        return [
            e.params["name"] for e in fwd(q, k, v, True, 16, 16, True).eqns
            if e.primitive.name == "name"
        ]

    assert names() == [ATTENTION_OUT, ATTENTION_STATS, ATTENTION_STATS]
    monkeypatch.setattr(flash_module, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()
    assert names() == []
    bare = grad(q, k, v)
    for got, want in zip(jax.tree.leaves(named), jax.tree.leaves(bare)):
        assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize(
    "policy,forwards",
    [
        # ``models/lm.py`` / ``models/transformer.py``: a plain checkpoint, or
        # a policy that lists neither name, recomputes the kernel as before.
        ("none", 2),
        ("dots", 2),
        # A caller that lists the names keeps what the backward reads.
        ("names", 1),
    ],
)
def test_only_a_policy_that_lists_the_names_keeps_the_forward_kernel_s_outputs(
    policy, forwards
):
    policies = jax.checkpoint_policies
    kw = {
        "none": {},
        "dots": {"policy": policies.dots_with_no_batch_dims_saveable},
        "names": {
            "policy": policies.save_only_these_names(ATTENTION_OUT, ATTENTION_STATS)
        },
    }[policy]
    q, k, v = _qkv((1, 32, 2, 8), seed=12)
    grad = jax.grad(jax.checkpoint(_kernel_loss, **kw), (0, 1, 2))
    assert _kernels(jax.make_jaxpr(grad)(q, k, v).jaxpr) == {
        "flash_attention_fwd": forwards,
        "flash_attention_bwd_dkv": 1,
        "flash_attention_bwd_dq": 1,
    }
    for got, want in zip(grad(q, k, v), jax.grad(_kernel_loss, (0, 1, 2))(q, k, v)):
        assert np.array_equal(got, want)


def test_flash_backward_xla_escape_hatch(monkeypatch):
    """RSDL_FLASH_BWD=xla routes the VJP through the chunked-XLA
    backward; gradients stay exact."""
    monkeypatch.setenv("RSDL_FLASH_BWD", "xla")
    q, k, v = _qkv((1, 48, 2, 8), seed=12)
    g_f = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(
                q, k, v, causal=True, use_pallas=True, interpret=True,
                block_q=16, block_k=16,
            )
            ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    g_d = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_xla_fallback_path():
    q, k, v = _qkv((1, 16, 2, 4), seed=5)
    got = flash_attention(q, k, v, use_pallas=False)
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
