"""Mixture-of-experts layer for the experts ONE chip holds.

Expert parallelism gives a chip ``experts_held`` of a layer's experts,
``first_expert`` on. The layer here is told which: it routes every token
over ALL the experts (the router keeps its published width), computes its
own experts' part of the result for the tokens routed to them, and leaves
out what the absent experts would have added — that partial sum is what an
all-to-all around this layer would combine across chips. Nothing here
stands in for the absent chips.

No token is dropped, whatever the router's skew. The assignments to held
experts are laid out expert by expert in one buffer whose groups are
padded to whole row tiles (:func:`plan_dispatch`), so that every tile
belongs to one expert and a grouped matrix product is a tiled matmul that
picks its weight block per tile (:func:`grouped_matmul`: a Pallas kernel
on TPU, ``jax.lax.ragged_dot`` elsewhere); tiles past the last used one
are skipped, not computed.

The plan is laid out for the worst case (every assignment held here), but
the buffer the rows move through is sized for the load a chip's share can
expect: ``EXPECTED_LOAD_FACTOR`` times the even share of the assignments
(:func:`bounded_rows`). The groups start at row 0, so the plan's first
``bounded_rows`` entries are the whole plan whenever the step's load fits;
where it does not, the same body runs in the worst-case buffer behind one
``lax.cond`` (:func:`experts_ffn` counts that as ``fallback``). A chip
that holds half the routed experts or more has no smaller buffer to take,
and no ``cond``.

Moving rows in and out of the buffer is a gather in both directions
(``dispatch`` / ``combine`` carry custom VJPs): a buffer row holds at most
one assignment, so the transpose of each gather is the gather by the
inverse map, and no scatter-add is ever lowered.

A caller that recomputes the layer in its backward pass (``nn.remat`` /
``jax.checkpoint``) should keep the routing bookkeeping: :func:`route`'s
``experts`` and every field of :func:`plan_dispatch`'s plan pass through
``checkpoint_name`` under ``ROUTING``. All of it is integers but the plan's
``row_weight``; all of it is read again by the backward pass (``experts``
by the transpose of the router's ``take_along_axis``, the plan by the
dispatch's, the products' and the combine's VJPs), so without the name the
recomputation runs ``top_k``, the plan's sort and its four scatters a
second time to arrive at the integers the forward pass already held. With
``ROUTING`` in a ``save_only_these_names`` policy they stay from the
forward pass and the second build is dead code: ``4 * (2 * tokens * top_k
+ 2 * buffer_rows)`` bytes a layer and a few hundred more (2.1 MB at
32,768 tokens and 4 choices with 8 experts held, 1.1 MB at 8,192 tokens
and 8 choices with 32 held). Without such a policy the names are
identities.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_shuffling_data_loader_tpu.ops.placement import auto_pallas

# The ``checkpoint_name`` of a layer's routing bookkeeping (:func:`route`'s
# ``experts``, every field of :func:`plan_dispatch`'s plan), for a
# recomputing caller's policy to list: the module's docstring says why.
ROUTING = "moe_routing"


def _kept(value: jax.Array) -> jax.Array:
    """``value`` under the name ``ROUTING``: an identity but for a
    recomputing caller whose policy lists the name."""
    return checkpoint_name(value, ROUTING)

# Rows a tile of the dispatch buffer holds: every expert's group is padded
# to a multiple. 512 rows of a 2048-wide bf16 operand against a weight
# block are compute-bound on a v5e (512 FLOPs a weight byte against a
# ridge of 240).
ROW_TILE = 512

# The bounded buffer holds this many times the even share of the
# assignments. A held share's total is binomial around the even share
# (16,384 +- 120 of 131,072 at 8 experts of 64), and a router balanced by
# anything stays far under twice it; one that collapses onto the held
# experts takes the worst-case buffer and loses nothing but time.
EXPECTED_LOAD_FACTOR = 2


# -- routing ------------------------------------------------------------------


def route(
    x: jax.Array,
    gate: jax.Array,
    expert_bias: Optional[jax.Array],
    top_k: int,
    norm_topk_prob: bool = True,
    scaling: float = 1.0,
    scoring: str = "sigmoid",
):
    """Sigmoid routing with a selection bias: ``s = sigmoid(x @ gate)``;
    the ``top_k`` experts with the largest ``s + expert_bias`` are chosen;
    their weights are their own ``s`` (without the bias), divided by
    their sum where ``norm_topk_prob``, times ``scaling``. ``scoring``
    ``"softmax"`` (Qwen3-MoE's) takes ``s = softmax(x @ gate)`` over all the
    experts routed over instead, the rest alike.

    ``x`` ``[tokens, hidden]``, ``gate`` ``[hidden, experts]``. Scores are
    computed in float32 at the highest matmul precision: a near tie
    decided by rounding sends a token to another expert. Returns
    ``(experts [tokens, top_k] int32, weights [tokens, top_k] float32)``.

    ``experts`` is named ``ROUTING``: kept under a policy that lists the
    name, ``top_k`` does not run again in a recomputed backward pass (the
    scores and ``weights`` do: they carry the gradient).
    """
    logits = jnp.dot(
        x.astype(jnp.float32),
        gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown scoring {scoring!r}")
    chosen_by = scores if expert_bias is None else scores + expert_bias
    _, experts = jax.lax.top_k(chosen_by, top_k)
    experts = _kept(experts.astype(jnp.int32))
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts, weights * scaling


# -- the dispatch plan ----------------------------------------------------------


class DispatchPlan(NamedTuple):
    """Where each assignment to a held expert lies in the buffer.

    ``position``    ``[tokens, top_k]``: the assignment's buffer row, or
                    ``rows`` (out of range) where its expert is not held
    ``source``      ``[rows]``: the token a buffer row holds, or ``tokens``
                    (out of range) where it holds none
    ``row_weight``  ``[rows]``: the assignment's routing weight, 0 on
                    padding
    ``tile_expert`` ``[rows // tile]``: the held expert (0-based among
                    those held) every tile belongs to
    ``tiles_used``  ``[1]``: tiles up to the last group's end
    ``group_rows``  ``[experts_held]``: rows of each group, padding included
    ``load``        ``[experts_held]``: tokens routed to each held expert
    ``dropped``     ``[]``: assignments to held experts that no buffer row
                    holds: ``sum(load)`` less the rows ``source`` fills. The
                    layout leaves none out; this is read off the plan as
                    built, not assumed
    """

    position: jax.Array
    source: jax.Array
    row_weight: jax.Array
    tile_expert: jax.Array
    tiles_used: jax.Array
    group_rows: jax.Array
    load: jax.Array
    dropped: jax.Array


def buffer_rows(assignments: int, experts_held: int, tile: int) -> int:
    """Static size of the dispatch buffer: every assignment held here, each
    group padded by less than a tile and holding at least one."""
    return (-(-assignments // tile) + experts_held) * tile


def bounded_rows(
    assignments: int, experts_held: int, experts_routed: int, tile: int
) -> int:
    """Static size of the buffer for ``EXPECTED_LOAD_FACTOR`` times the
    even share of ``assignments`` (``experts_held`` of ``experts_routed``),
    and never more than the worst case's."""
    even = -(-assignments * experts_held // experts_routed)
    return min(
        buffer_rows(EXPECTED_LOAD_FACTOR * even, experts_held, tile),
        buffer_rows(assignments, experts_held, tile),
    )


def plan_dispatch(
    experts: jax.Array,
    weights: jax.Array,
    first_expert: int,
    experts_held: int,
    tile: int = ROW_TILE,
) -> DispatchPlan:
    """Lay the assignments to experts ``[first_expert, first_expert +
    experts_held)`` out expert by expert (a stable sort: token order kept
    within an expert), each group padded to whole tiles and to at least
    one. All integer work on ``[tokens * top_k]`` vectors: one stable sort,
    four scatters, three gathers.

    Every field of the plan returned is named ``ROUTING``: kept under a
    policy that lists the name, a recomputed backward pass reads the
    forward pass's plan and builds none of its own."""
    tokens, top_k = experts.shape
    n = tokens * top_k
    rows = buffer_rows(n, experts_held, tile)
    local = experts.reshape(-1) - first_expert
    held = (local >= 0) & (local < experts_held)
    key = jnp.where(held, local, experts_held)
    counts = jnp.zeros((experts_held + 1,), jnp.int32).at[key].add(1)
    load = counts[:experts_held]
    group_rows = jnp.maximum(-(-load // tile), 1) * tile
    group_start = jnp.cumsum(group_rows) - group_rows
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    first_of_key = jnp.cumsum(counts) - counts
    rank = jnp.arange(n, dtype=jnp.int32) - first_of_key[sorted_key]
    dest = jnp.where(
        sorted_key < experts_held,
        group_start[jnp.minimum(sorted_key, experts_held - 1)] + rank,
        rows,
    ).astype(jnp.int32)
    position = jnp.zeros((n,), jnp.int32).at[order].set(dest)
    source = jnp.full((rows,), tokens, jnp.int32).at[dest].set(
        (order // top_k).astype(jnp.int32), mode="drop"
    )
    row_weight = jnp.zeros((rows,), jnp.float32).at[position].set(
        weights.reshape(-1).astype(jnp.float32), mode="drop"
    )
    group_end = jnp.cumsum(group_rows)
    tile_start = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    tile_expert = jnp.minimum(
        jnp.searchsorted(group_end, tile_start, side="right"),
        experts_held - 1,
    ).astype(jnp.int32)
    # ``position`` is named while it is still the flat vector the gathers
    # read: as ``[tokens, top_k]`` a kept copy is padded to the lane tile
    # (16.8 MB for 0.5 at 32,768 x 4 on a TPU).
    return DispatchPlan(
        position=_kept(position).reshape(tokens, top_k),
        source=_kept(source),
        row_weight=_kept(row_weight),
        tile_expert=_kept(tile_expert),
        tiles_used=_kept((group_end[-1:] // tile).astype(jnp.int32)),
        group_rows=_kept(group_rows.astype(jnp.int32)),
        load=_kept(load),
        dropped=_kept(
            jnp.sum(load) - jnp.sum(source < tokens, dtype=jnp.int32)
        ),
    )


def _rows_of(table, index):
    """``table[index]`` by rows, zeros where ``index`` is out of range."""
    return jnp.take(table, index, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def dispatch(x, source, position):
    """Tokens into the buffer: row ``r`` is ``x[source[r]]`` (zeros on
    padding). Backward: a token's cotangent is the sum over its
    assignments' buffer rows, a gather by ``position``."""
    return _rows_of(x, source)


def _dispatch_fwd(x, source, position):
    return _rows_of(x, source), position


def _dispatch_bwd(position, ct):
    tokens, top_k = position.shape
    dx = _rows_of(ct, position.reshape(-1)).reshape(tokens, top_k, -1)
    return jnp.sum(dx.astype(jnp.float32), axis=1).astype(ct.dtype), None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(out, weights, position, source, row_weight):
    """The buffer's rows back to their tokens: ``y[t] = sum_j weights[t, j]
    * out[position[t, j]]`` over the assignments held here, accumulated in
    float32. Backward: a buffer row's cotangent is its token's, times its
    weight: a gather by ``source``."""
    return _combine(out, weights, position)


def _combine(out, weights, position):
    tokens, top_k = position.shape
    picked = _rows_of(out, position.reshape(-1)).reshape(tokens, top_k, -1)
    return jnp.einsum(
        "tkh,tk->th", picked.astype(jnp.float32), weights
    ).astype(out.dtype)


def _combine_fwd(out, weights, position, source, row_weight):
    return _combine(out, weights, position), (
        out, weights, position, source, row_weight
    )


def _combine_bwd(res, ct):
    out, weights, position, source, row_weight = res
    # Both cotangents from ONE gather, of the tokens' cotangents into the
    # buffer's rows: a row's own is that times its weight; a weight's is
    # its row's dot product with it, taken in the buffer and picked up as
    # one number an assignment.
    ct_rows = _rows_of(ct, source).astype(jnp.float32)
    d_out = (ct_rows * row_weight[:, None]).astype(out.dtype)
    row_dot = jnp.sum(ct_rows * out.astype(jnp.float32), axis=-1)
    d_weights = _rows_of(row_dot, position.reshape(-1)).reshape(position.shape)
    return d_out, d_weights.astype(weights.dtype), None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


# -- the grouped matrix product ---------------------------------------------------


def _clamped(m, n, tiles_used_ref, last_n):
    """Grid indices of a tile past the last used one, sent back to the last
    step of the last used tile: the same blocks as the step before, so
    nothing is fetched and nothing is written back for it."""
    last = tiles_used_ref[0] - 1
    past = m > last
    return jnp.minimum(m, last), jnp.where(past, last_n, n)


def _gmm_kernel(tile_expert_ref, tiles_used_ref, lhs_ref, rhs_ref, out_ref,
                *, transpose_rhs: bool):
    """One (row tile, column tile): the tile's rows against its expert's
    weight block, the whole contraction at once."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], contract,
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)


def _column_tile(n: int, want: int = 512) -> int:
    """The widest tile up to ``want`` that divides ``n`` in lanes of 128;
    ``n`` itself where none does."""
    for t in range(min(want, n), 127, -128):
        if n % t == 0 and t % 128 == 0:
            return t
    return n


def _gmm_pallas(lhs, rhs, plan_tiles, tile, transpose_rhs, interpret, name):
    """``lhs [rows, k] x rhs [g, k, n] -> [rows, n]`` (``transpose_rhs``:
    ``rhs [g, n, k]``), each row tile against ``rhs[tile_expert[tile]]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile_expert, tiles_used = plan_tiles
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _column_tile(n)
    grid = (rows // tile, n // tn)
    last_n = grid[1] - 1

    def lhs_map(m, j, te, tu):
        mm, _ = _clamped(m, j, tu, last_n)
        return mm, 0

    def rhs_map(m, j, te, tu):
        mm, jj = _clamped(m, j, tu, last_n)
        return (te[mm], jj, 0) if transpose_rhs else (te[mm], 0, jj)

    def out_map(m, j, te, tu):
        return _clamped(m, j, tu, last_n)

    rhs_block = (1, tn, k) if transpose_rhs else (1, k, tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile, k), lhs_map),
                pl.BlockSpec(rhs_block, rhs_map),
            ],
            out_specs=pl.BlockSpec((tile, tn), out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name=name,
    )(tile_expert, tiles_used, lhs, rhs)


def _tgmm_kernel(tile_expert_ref, tiles_used_ref, lhs_ref, ct_ref, out_ref):
    """One (k tile, n tile, row tile), rows innermost: ``lhs_tile^T @
    ct_tile`` summed into the block of the tile's expert, which stays in
    VMEM while the expert's tiles go by."""
    from jax.experimental import pallas as pl

    m = pl.program_id(2)
    used = m < tiles_used_ref[0]
    here = tile_expert_ref[jnp.minimum(m, tiles_used_ref[0] - 1)]
    before = tile_expert_ref[jnp.maximum(m - 1, 0)]

    @pl.when(used & ((m == 0) | (here != before)))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(used)
    def _():
        out_ref[0] += jax.lax.dot_general(
            lhs_ref[...], ct_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _tgmm_pallas(lhs, ct, plan_tiles, experts_held, tile, interpret, name):
    """``lhs [rows, k], ct [rows, n] -> [g, k, n]`` float32: every expert's
    ``lhs_e^T @ ct_e`` over its own row tiles. Each group holds at least
    one tile, so every expert's block is written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile_expert, tiles_used = plan_tiles
    rows, k = lhs.shape
    n = ct.shape[1]
    tk, tn = _column_tile(k), _column_tile(n)

    def rows_of(m, tu):
        return jnp.minimum(m, tu[0] - 1)

    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, tk), lambda i, j, m, te, tu: (rows_of(m, tu), i)),
                pl.BlockSpec((tile, tn), lambda i, j, m, te, tu: (rows_of(m, tu), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda i, j, m, te, tu: (te[rows_of(m, tu)], i, j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((experts_held, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name=name,
    )(tile_expert, tiles_used, lhs, ct)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, weights, plan_tiles, tile, interpret, name):
    return _gmm_pallas(
        lhs, weights.astype(lhs.dtype), plan_tiles, tile, False, interpret,
        name + "_fwd",
    )


def _gmm_fwd(lhs, weights, plan_tiles, tile, interpret, name):
    out = _gmm_pallas(
        lhs, weights.astype(lhs.dtype), plan_tiles, tile, False, interpret,
        name + "_fwd",
    )
    return out, (lhs, weights, plan_tiles)


def _gmm_bwd(tile, interpret, name, res, ct):
    lhs, weights, plan_tiles = res
    d_lhs = _gmm_pallas(
        ct, weights.astype(ct.dtype), plan_tiles, tile, True, interpret,
        name + "_bwd_lhs",
    )
    d_weights = _tgmm_pallas(
        lhs, ct, plan_tiles, weights.shape[0], tile, interpret,
        name + "_bwd_weights",
    )
    return d_lhs, d_weights.astype(weights.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(
    lhs: jax.Array,
    weights: jax.Array,
    plan: DispatchPlan,
    *,
    tile: int = ROW_TILE,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    name: str = "moe_experts",
) -> jax.Array:
    """``lhs [rows, k]`` against ``weights [experts_held, k, n]``: buffer
    row ``r`` times the weights of the expert its tile belongs to. Rows of
    tiles past ``plan.tiles_used`` are not computed and hold anything.

    ``weights`` may be of a wider type than ``lhs`` (float32 parameters
    under bfloat16 compute): they are cast on the way in, and their
    gradient comes back in their own type from a float32 accumulator.
    ``use_pallas=None``: the Pallas kernels (``<name>_fwd``,
    ``<name>_bwd_lhs``, ``<name>_bwd_weights`` in a trace) on a TPU
    backend, ``jax.lax.ragged_dot`` elsewhere. ``interpret`` is for tests
    on the CPU."""
    if use_pallas is None:
        use_pallas = auto_pallas()
    if not use_pallas:
        return jax.lax.ragged_dot(
            lhs, weights.astype(lhs.dtype), plan.group_rows,
            preferred_element_type=jnp.float32,
        ).astype(lhs.dtype)
    return _gmm(
        lhs, weights, (plan.tile_expert, plan.tiles_used), tile, interpret,
        name,
    )


# -- the layer ------------------------------------------------------------------


# Jitted (``rows``, ``tile``, ``use_pallas``, ``interpret`` static) so that a
# model's expert layers, whose shapes are the same, are traced and lowered
# once, not once a layer: a body holds three to nine kernels, and there are
# two bodies a pass behind the ``cond``.
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _held_experts(rows, tile, use_pallas, interpret,
                  x, weights, w1, w3, w2, plan):
    """Dispatch, the three grouped products and the combine through a
    buffer of the plan's first ``rows`` rows: the whole plan where
    ``plan.tiles_used * tile <= rows``. An assignment's ``position`` past
    ``rows`` stays out of range of the smaller buffer."""
    plan = plan._replace(
        source=plan.source[:rows],
        row_weight=plan.row_weight[:rows],
        tile_expert=plan.tile_expert[: rows // tile],
    )
    kernel = dict(tile=tile, use_pallas=use_pallas, interpret=interpret)
    xs = dispatch(x, plan.source, plan.position)
    h = jax.nn.silu(
        grouped_matmul(xs, w1, plan, **kernel).astype(jnp.float32)
    ) * grouped_matmul(xs, w3, plan, **kernel).astype(jnp.float32)
    out = grouped_matmul(h.astype(x.dtype), w2, plan, **kernel)
    return combine(out, weights, plan.position, plan.source, plan.row_weight)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _held_experts_vjp(rows, tile, use_pallas, interpret,
                      ct, x, weights, w1, w3, w2, plan):
    """The cotangents of ``(x, weights, w1, w3, w2)`` through
    :func:`_held_experts` at ``rows``, the body computed again from its
    inputs."""
    _, vjp = jax.vjp(
        lambda *diff: _held_experts(rows, tile, use_pallas, interpret, *diff, plan),
        x, weights, w1, w3, w2,
    )
    return vjp(ct)


def _one_of(body, rows, tile, use_pallas, interpret, fits, *operands):
    """``body`` at ``rows`` where the step's load ``fits``, at the rows of
    the whole plan (the last operand) where it does not: one branch runs."""
    worst = operands[-1].source.shape[0]
    return jax.lax.cond(
        fits,
        functools.partial(body, rows, tile, use_pallas, interpret),
        functools.partial(body, worst, tile, use_pallas, interpret),
        *operands,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _bounded_or_worst(rows, tile, use_pallas, interpret,
                      fits, x, weights, w1, w3, w2, plan):
    """:func:`_held_experts` in a buffer of ``rows`` rows where the step's
    load ``fits``, in the plan's whole buffer where it does not.
    Differentiated as a whole, from its inputs alone, the backward pass one
    ``cond`` of the two bodies' own VJPs: ``cond``'s own rule would join
    the branches' residuals, and the branch taken would write zeros of the
    worst case's size for the other's. The layer is recomputed in the
    backward pass anyway (``nn.remat``), so this costs no extra pass."""
    return _one_of(
        _held_experts, rows, tile, use_pallas, interpret,
        fits, x, weights, w1, w3, w2, plan,
    )


def _bounded_or_worst_fwd(rows, tile, use_pallas, interpret, fits, *inputs):
    out = _bounded_or_worst(rows, tile, use_pallas, interpret, fits, *inputs)
    return out, (fits, inputs)


def _bounded_or_worst_bwd(rows, tile, use_pallas, interpret, res, ct):
    fits, inputs = res
    grads = _one_of(
        _held_experts_vjp, rows, tile, use_pallas, interpret, fits, ct, *inputs
    )
    return (None, *grads, None)


_bounded_or_worst.defvjp(_bounded_or_worst_fwd, _bounded_or_worst_bwd)


def experts_ffn(
    x: jax.Array,
    experts: jax.Array,
    weights: jax.Array,
    w1: jax.Array,
    w3: jax.Array,
    w2: jax.Array,
    first_expert: int,
    experts_routed: int,
    *,
    tile: int = ROW_TILE,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """The held experts' part of a SwiGLU expert layer: ``y[t] = sum over
    the assignments (t, e) with e held here of weight * W2_e (silu(W1_e
    x_t) * W3_e x_t)``.

    ``x`` ``[tokens, hidden]``; ``experts`` / ``weights`` ``[tokens,
    top_k]`` from :func:`route` over ``experts_routed`` experts; ``w1``,
    ``w3`` ``[experts_held, hidden, width]``, ``w2`` ``[experts_held,
    width, hidden]``. Returns ``(y, load, dropped, fallback)``: ``load
    [experts_held]`` counts the tokens routed to each held expert,
    ``dropped []`` those of them the buffer left out (:class:`DispatchPlan`:
    none), ``fallback []`` is 1 where the load outgrew the bounded buffer
    (:func:`bounded_rows`) and the layer ran in the worst-case one, else
    0."""
    held = w1.shape[0]
    plan = plan_dispatch(experts, weights, first_expert, held, tile)
    rows = bounded_rows(experts.size, held, experts_routed, tile)
    static = (rows, tile, use_pallas, interpret)
    inputs = (x, weights, w1, w3, w2, plan)
    if rows == plan.source.shape[0]:
        y = _held_experts(*static, *inputs)
        fallback = jnp.zeros((), jnp.int32)
    else:
        fits = plan.tiles_used[0] * tile <= rows
        y = _bounded_or_worst(*static, fits, *inputs)
        fallback = 1 - fits.astype(jnp.int32)
    return y, plan.load, plan.dropped, fallback
