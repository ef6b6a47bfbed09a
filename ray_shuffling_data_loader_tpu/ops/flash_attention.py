"""Pallas TPU flash attention: fused blockwise softmax-attention kernel.

The XLA lowerings in :mod:`.ring_attention` keep exactness and memory
bounds but leave fusion to the compiler; this kernel hand-fuses one
(q-block × kv-block) tile pipeline in VMEM — scores, online softmax, and
the value matmul never round-trip to HBM, with K/V streamed block by
block across the innermost grid dimension into a revisited accumulator
(the flash-attention construction, written Pallas-idiomatically: MXU
matmuls via ``lax.dot_general``, ``@pl.when`` for first/last-block
prologue/epilogue, the running max and normalizer as lane-dense rows in
VMEM scratch).

Scope: attention over ``[batch, seq, heads, head_dim]`` (split over batch
and heads on multi-device meshes with ``shard_map``, see :mod:`.placement`).
The values may be wider (or narrower) than the queries and keys: ``v [..,
value_dim]`` gives an output of that width (a differential head multiplies
maps of 64-dimensional heads into values of 128), plain and windowed alike;
with ``value_dim == head_dim`` the traced kernels are what they were.
Grouped-query attention: ``k`` and ``v`` may carry fewer heads than ``q``
(``heads % kv_heads == 0``); a query head reads its group's key/value
blocks through the block index map, so the repeat never exists in HBM,
and the dK/dV kernel sums a group's query heads in its VMEM accumulator.
It composes with the sequence-parallel schedules (the Ulysses local body
and each ring hop are exactly this computation) but is wired as the
standalone ``flash_attention`` op — same auto-policy as the DLRM
interaction kernel (``ops/interaction.py``): Pallas on TPU backends, the
XLA reference on backends Mosaic cannot target, interpret mode by
explicit argument in CPU tests.

The grid: below its first axis (batch · heads) a kernel steps through the
(query block, key block) pairs that hold work and through no other.
:func:`_blocks_with_work` says in which pairs the mask admits a score
(every pair without ``causal``; on or under the diagonal with it; with
``window``, a causal query at position ``i`` seeing the keys ``i - window <
j <= i``, those the band touches), and :class:`_Steps` lays them out in the
order a kernel accumulates in: all of a query block's key blocks in a row
for the forward and dQ, for dK/dV a key block's query blocks, a query head
of the group after the other. Where some pairs hold none, the steps are
tables handed to the kernel as scalar-prefetch operands, which the block
index maps and the body read, so that no step fetches or computes a block
without work: a causal head of ``n`` blocks a side takes ``n (n + 1) / 2``
steps, not ``n * n``. Tables too long for the scalar memory
(:data:`MAX_TABLE_ENTRIES`) hold runs of up to 2, 4, .. blocks an entry
instead, whose last may fall short: those steps stay on the run's last
block and compute nothing. Where every pair holds work (no ``causal``, or
one block) the grid stays the rectangle of the blocks, with no table: an
index map that reads one costs a step about 0.02 µs an operand. The mask is
applied in every block of a plain call and, with a window, only in the
blocks that the band's two edges cut; the windowed Pallas calls are named
``flash_attention_window_*``. A call with a learned selection (``selected``,
the packed words of :mod:`.sparse_attention`) masks every block by it too
and steps through the causal blocks that hold a selected pair, from tables
made on the device each batch (:class:`_SelectedSteps`); its Pallas calls
are named ``flash_attention_sparse_*``. Without one the kernels are traced
as they were.

A key may be split in two (``k_shared``, latent attention's decoupled
rotary key): a part of each head's own, ``k [.., head_dim - d_s]``, and one
head of ``d_s`` that every query head reads, ``k_shared [batch, seq, 1,
d_s]``, against the last ``d_s`` dimensions of each query head. The kernels
take the query's two parts as two operands (each lane-aligned as it is
given: a head of 192 is 128 + 64) and add the two products of a score; the
shared part's block index ignores the head, so it is fetched per key block
and never repeated in HBM. The dK/dV kernel accumulates the shared key's
gradient of a key head's query heads beside dK, and the heads' rows are
summed after the call, as ``_sum_groups`` sums a group's. Its Pallas calls
are named ``flash_attention_latent_*``.

The forward runs key-major (the block ``[bk, bq]``, keys on the sublanes
and queries on the lanes): the running max and the normalizer are ``[1,
bq]`` rows, their reductions over the keys run down the sublanes with no
reduction across the lanes, and the output accumulates transposed, ``outᵀ
+= vᵀ @ pᵀ``, from the values handed in as turned blocks ``[dv, bk]``; a
query block's last step turns ``outᵀ`` once. It writes ``m`` and ``l`` as
lane-dense rows, not as ``[t, 1]`` columns, which the (8, 128) tile pads
128-fold and XLA lays out anew.

Differentiability: the kernel carries an exact, memory-safe custom VJP.
The forward emits its softmax row statistics (m, l) as outputs; the
backward is two fused Pallas kernels — dK/dV (q innermost, VMEM
accumulators) and dQ (kv innermost) — that recompute probability blocks
from those statistics, so no ``[T, T]`` block materializes in the
gradient and no stats-recompute pass is paid. They read the statistics as
two lane-dense float32 rows a query head, the log-sum-exp ``lse = m + log
l`` (``+inf`` on a row with no key, so ``p = exp(s − lse)`` is 0 there) and
``D = rowsum(dO ⊙ out)``. dK/dV runs key-major as the forward does, so its
two accumulating products ``pᵀ @ dO`` and ``dsᵀ @ q`` contract the block's
minor dimension and no block is transposed; dQ's ``ds @ k`` contracts the
keys, so dQ stays query-major and turns a query block's rows into columns
once, at its first step. ``RSDL_FLASH_BWD=xla`` falls back to the
chunked-XLA exact backward (shared with ``blockwise_attention``) in a call
without a selection or a shared key part.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name


from ray_shuffling_data_loader_tpu.ops.placement import (
    DATA_AXIS,
    MODEL_AXIS,
    auto_pallas,
    over_mesh,
)
from ray_shuffling_data_loader_tpu.ops.sparse_attention import (
    WORD,
    block_work,
    check_blocks,
    expand,
    unpack,
)
from ray_shuffling_data_loader_tpu.ops.ring_attention import (
    NEG_INF,
    _chunked_attention_bwd,
    attention_reference,
)


# The longest table of steps a kernel is handed: four int32 tables of this
# length are half of a v5e's 1 MiB of scalar memory (its compiler takes four
# of 32,896 entries and refuses four of 65,792). 8,192 tokens in blocks of 512
# are 136 entries a head, 1,088 in dK/dV with eight query heads to a key head.
MAX_TABLE_ENTRIES = 32768


def _blocks_with_work(nq, nk, block_q, block_k, causal, window) -> np.ndarray:
    """``[nq, nk]``: whether the mask admits a score of query block ``qi``
    against key block ``ki`` (``q - window < k <= q`` for some query and
    key of theirs)."""
    q0 = np.arange(nq)[:, None] * block_q
    k0 = np.arange(nk)[None, :] * block_k
    work = np.ones((nq, nk), bool)
    if causal:
        work &= q0 + block_q - 1 >= k0
    if window is not None:
        work &= q0 - (k0 + block_k - 1) < window
    return work


class _Steps:
    """A kernel's grid below its first axis (batch · heads): ``work[o, i]``
    says whether inner block ``i`` holds work against outer block ``o``,
    whose accumulator the kernel revisits; it does so once for each member
    of the ``group``. The steps go outer block by outer block, within one
    member by member, within a member through its blocks with work in
    rising order (a band: one run a row).

    Where every block holds work the grid is the rectangle ``(outer blocks,
    group · inner blocks)`` and there is no table. Otherwise it is one axis
    of ``length`` steps laid out in four int32 ``tables``, which the index
    maps and the kernel read: an entry is a run of ``count <= width`` inner
    blocks from ``start`` on, against ``outer`` for ``member``, and takes
    ``width`` grid steps; ``width`` is 1 (a step a block with work)
    wherever the tables then fit ``MAX_TABLE_ENTRIES``."""

    def __init__(self, work: np.ndarray, group: int = 1):
        self.group = group
        self.inner = work.shape[1]
        self.tables = ()
        self.width = 1
        if work.all():
            self.grid = (work.shape[0], group * self.inner)
            self.semantics = ("parallel", "arbitrary")
            return
        first, count = work.argmax(axis=1), work.sum(axis=1)
        # A run a row and member is the shortest a table gets.
        while (
            self.width < count.max()
            and group * (-(-count // self.width)).sum() > MAX_TABLE_ENTRIES
        ):
            self.width *= 2
        entries = [
            (o, g, s, min(self.width, first[o] + count[o] - s))
            for o in range(len(work))
            for g in range(group)
            for s in range(first[o], first[o] + count[o], self.width)
        ]
        self.tables = tuple(np.asarray(entries, np.int32).T)
        self.grid = (len(entries) * self.width,)
        self.semantics = ("arbitrary",)

    @property
    def length(self) -> int:
        """Grid steps a batch · head in all."""
        return int(np.prod(self.grid))

    def call(self, rows: int, **specs) -> dict:
        """``pallas_call``'s ``grid_spec`` and ``compiler_params`` for
        ``rows`` batch · heads: ``specs`` are the grid spec's own
        (``in_specs``, ``out_specs``, ``scratch_shapes``)."""
        from jax.experimental.pallas import tpu as pltpu

        return dict(
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(self.tables),
                grid=(rows, *self.grid),
                **specs,
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", *self.semantics),
            ),
        )

    def here(self, *table_refs):
        """Where a kernel is: its grid indices below the first and the
        tables, as :meth:`blocks` and :meth:`edges` take them (an index map
        is handed the same after its first argument)."""
        from jax.experimental import pallas as pl

        return *(pl.program_id(1 + a) for a in range(len(self.grid))), *table_refs

    def mapped(self, bh, at):
        """What :meth:`blocks` takes of an index map's arguments ``(bh,
        *at)``: ``at`` (the batch · head row matters to a table that differs
        by batch, :class:`_SelectedSteps`)."""
        return at

    def _entry(self, step):
        if self.width == 1:
            return step, 0
        return step // self.width, step % self.width

    def blocks(self, *at):
        """``(outer block, member, inner block)`` of a grid step. Past a
        run's last block the step stays on it: nothing is fetched."""
        if not self.tables:
            o, j = at
            return (o, 0, j) if self.group == 1 else (o, j // self.inner, j % self.inner)
        step, outer, member, start, count = at
        e, j = self._entry(step)
        inner = start[e]
        if self.width > 1:
            inner = inner + jnp.minimum(j, count[e] - 1)
        return outer[e], member[e], inner

    def edges(self, *at):
        """``(first, last, runs)``: whether a grid step is the first or the
        last of its outer block, and whether it holds a block at all
        (``True`` itself where every step does)."""
        if not self.tables:
            return at[1] == 0, at[1] == self.grid[1] - 1, True
        step, outer, _, _, count = at
        e, j = self._entry(step)
        n, w = len(self.tables[0]), self.width
        here = outer[e]
        first = (j == 0) & ((e == 0) | (outer[jnp.maximum(e - 1, 0)] != here))
        last = (j == w - 1) & (
            (e == n - 1) | (outer[jnp.minimum(e + 1, n - 1)] != here)
        )
        return first, last, True if w == 1 else j < count[e]


def grid_steps(seq_len, block_q, block_k, causal=True, window=None):
    """``(grid steps, blocks with work)`` a head of one forward call: equal
    unless the tables hold runs."""
    bq, bk = min(block_q, seq_len), min(block_k, seq_len)
    work = _blocks_with_work(
        -(-seq_len // bq), -(-seq_len // bk), bq, bk, causal, window
    )
    return _Steps(work).length, int(work.sum())


class _SelectedSteps(_Steps):
    """The steps of a call with a selection (``selected``): of the blocks
    the causal mask admits a score in (``work [outer, inner]``, fixed at
    trace time), those that hold a selected pair of a batch (``live [batch,
    outer, inner]``, on the device), in :class:`_Steps`'s order, each
    batch's its own. The tables are made on the device each batch, one
    entry a step, ``length`` entries a batch: the kept ones first, then the
    last kept one repeated with ``count`` 0, which fetches nothing new and
    computes nothing. An outer block's first entry is always kept, so that
    every output block is written (with no selected pair it is zero).
    ``rows`` is the grid's rows a batch (its heads)."""

    def __init__(self, work: np.ndarray, live: jax.Array, rows: int,
                 group: int = 1):
        self.group, self.inner, self.width, self.rows = group, work.shape[1], 1, rows
        outer, member, inner = (
            np.asarray(x, np.int32)
            for x in zip(*[
                (o, g, i)
                for o in range(len(work))
                for g in range(group)
                for i in np.flatnonzero(work[o])
            ])
        )
        steps = len(outer)
        first = np.r_[True, outer[1:] != outer[:-1]]
        keep = live[:, outer, inner] | first  # [batch, steps]
        order = jnp.argsort(jnp.logical_not(keep), axis=1, stable=True)
        kept = jnp.sum(keep, axis=1, keepdims=True, dtype=jnp.int32)
        e = jnp.arange(steps, dtype=jnp.int32)[None]
        src = jnp.where(e < kept, order, jnp.take_along_axis(order, kept - 1, axis=1))
        self.tables = (
            *(jnp.asarray(x)[src].reshape(-1) for x in (outer, member, inner)),
            (e < kept).astype(jnp.int32).reshape(-1),
        )
        self.grid = (steps,)
        self.semantics = ("arbitrary",)

    def here(self, *table_refs):
        from jax.experimental import pallas as pl

        return pl.program_id(0), pl.program_id(1), *table_refs

    def mapped(self, bh, at):
        return (bh, *at)

    def _at(self, bh, step):
        return (bh // self.rows) * self.grid[0] + step

    def blocks(self, bh, step, outer, member, start, count):
        e = self._at(bh, step)
        return outer[e], member[e], start[e]

    def edges(self, bh, step, outer, member, start, count):
        e = self._at(bh, step)
        after = jnp.minimum(e + 1, count.shape[0] - 1)
        first = (step == 0) | (outer[jnp.maximum(e - 1, 0)] != outer[e])
        last = (step == self.grid[0] - 1) | (count[after] == 0) | (
            outer[after] != outer[e]
        )
        return first, last, count[e] > 0


def _selected_block(sel_ref, block_q, key_major):
    """A kernel's block of the selection from its words (bool, ``[bq,
    bk]``, or ``[bk, bq]`` key-major)."""
    block = expand(sel_ref[0], block_q)
    if not key_major:
        return block
    return block.astype(jnp.int32).T == 1


def _update_block(runs, qi, ki, block_q, block_k, window, update):
    """``update(masked)`` in a step that holds a block (``runs``; ``True``
    itself where every step does). A plain call masks every block; a
    windowed one only a block that the diagonal or the window's far edge
    cuts, not one that lies whole inside the band (a padded key lies past
    every real query, so the diagonal's mask covers it)."""
    from jax.experimental import pallas as pl

    if window is None:
        if runs is True:
            update()
        else:
            pl.when(runs)(update)
        return
    inside = (ki * block_k + block_k - 1 <= qi * block_q) & (
        (qi + 1) * block_q - 1 - ki * block_k < window
    )
    pl.when(runs & inside)(functools.partial(update, False))
    pl.when(runs & jnp.logical_not(inside))(functools.partial(update, True))


def _kernel_name(window, which: str, sparse: bool = False,
                 latent: bool = False) -> str:
    """The Pallas call's name in a trace: the windowed kernels, and those of
    a call with a selection or a shared key part, are populations of their
    own."""
    kind = (
        "sparse_" if sparse else "latent_" if latent
        else "" if window is None else "window_"
    )
    return "flash_attention_" + kind + which


def _dot_t(a, b):
    """``a @ bᵀ`` in float32 (the two blocks' minor dimensions contracted)."""
    return jax.lax.dot_general(
        a,
        b,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _scores(a, b, shared):
    """``a @ bᵀ``, plus ``a₂ @ b₂ᵀ`` where ``shared`` is the pair of refs
    ``(a₂, b₂)`` of a split key's shared part (None: no such part)."""
    s = _dot_t(a, b)
    if shared is not None:
        s = s + _dot_t(shared[0][0], shared[1][0])
    return s


def _flash_kernel(
    *refs,
    steps: _Steps,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_len: int,
    window: Optional[int] = None,
    sparse: bool = False,
    latent: bool = False,
):
    """One grid cell: a query block against one of its key blocks with work
    (``steps``: a query block's in a row). ``refs``: the steps' tables if
    any, ``q, k, vᵀ`` (a key block's values as a ``[dv, bk]`` block), the
    query's and the key's shared parts where ``latent``, the selection's
    words where ``sparse``, the outputs ``o`` and the ``m``, ``l`` rows,
    the scratch.

    Key-major, as dK/dV: the block is ``[bk, bq]``, keys on the sublanes
    and queries on the lanes, so the softmax statistics, the running max
    ``m`` and normalizer ``l``, are ``[1, bq]`` rows broadcast down the
    sublanes, their reductions over the keys run down the sublanes, and
    the output accumulates transposed, a product that contracts its left
    operand's minor dimension::

        sᵀ    = k @ qᵀ · scale                 (masked)
        m'    = max(m, max over the keys of sᵀ)
        pᵀ    = exp(sᵀ − m')
        l     = l · exp(m − m') + Σ over the keys of pᵀ
        outᵀ  = outᵀ · exp(m − m') + vᵀ @ pᵀ

    The query block's last step turns ``outᵀ / l`` into its ``[bq, dv]``
    block once, and writes ``m`` and ``l`` as rows: the backward kernels
    and the ring schedule's stats merge consume them.
    """
    from jax.experimental import pallas as pl

    tables, refs = refs[: len(steps.tables)], refs[len(steps.tables):]
    if sparse:
        q_ref, k_ref, vt_ref, sel_ref, *refs = refs
    else:
        (q_ref, k_ref, vt_ref, *refs), sel_ref = refs, None
    qs_ref, ks_ref = (refs.pop(0), refs.pop(0)) if latent else (None, None)
    o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    at = steps.here(*tables)
    qi, _, ki = steps.blocks(*at)
    first, last, runs = steps.edges(*at)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def _update(masked=True):
        s = _masked_scores(
            k_ref[0], q_ref[0], scale, masked, 0, qi, ki, block_q, block_k,
            seq_len, causal, window,
            None if sel_ref is None else _selected_block(sel_ref, block_q, True),
            None if qs_ref is None else (ks_ref, qs_ref),
        )  # [bk, bq]
        m_prev = m_scr[:1]  # [1, bq] (sublanes replicated)
        l_prev = l_scr[:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # Queries with no valid key yet (m still NEG_INF) would see
        # exp(0) = 1; zero them so fully-masked rows finish as 0.
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            vt_ref[0].astype(jnp.float32),
            p.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # [dv, bq]
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    _update_block(runs, qi, ki, block_q, block_k, window, _update)

    @pl.when(last)
    def _fin():
        out = acc_scr[...] / jnp.maximum(l_scr[:1], 1e-30)
        o_ref[0] = out.T.astype(o_ref.dtype)
        m_ref[0] = m_scr[:1]
        l_ref[0] = l_scr[:1]


def _to_bh(x, t_pad):
    """``[b, t, heads, d] -> [b * heads, t_pad, d]``."""
    b, t, heads, d = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * heads, t, d)
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    return x


def _to_bh_blocks_t(x, t_pad, block):
    """``[b, t, heads, d] -> [b * heads * t_pad / block, d, block]``: each
    head's blocks of ``block`` positions, turned, in one transposing copy.
    A block is the array's last two dimensions whole, which Mosaic takes at
    any ``block`` (a ``(d, block)`` block of a ``[d, t]`` row would need
    ``block`` a multiple of 128 or the whole padded sequence)."""
    b, t, heads, d = x.shape
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    x = x.reshape(b, t_pad // block, block, heads, d)
    return jnp.transpose(x, (0, 3, 1, 4, 2)).reshape(-1, d, block)


def _kv_head_map(h: int, hk: int):
    """Row of the ``[b * hk, t, d]`` key/value arrays that row ``bh`` of
    the ``[b * h, t, d]`` queries reads: its group's head."""
    if h % hk:
        raise ValueError(
            f"{h} query heads are not a multiple of {hk} key/value heads"
        )
    group = h // hk
    if group == 1:
        return lambda bh: bh
    return lambda bh: (bh // h) * hk + (bh % h) // group


def _stat_rows(bq: int, nq: int, index):
    """Block spec of the statistics' rows (the forward's ``m`` and ``l``,
    the backward's ``lse`` and ``D``), ``[b·h·nq, 1, bq]``: the row of the
    query block that ``index``, a query-rows index map, names. The block is the array's last two dimensions whole, which
    Mosaic takes at any ``bq``: a ``(1, bq)`` block of a ``[1, t]`` row
    would need ``bq`` a multiple of 128 or the whole padded sequence."""
    from jax.experimental import pallas as pl

    def at(*a):
        row, qi, _ = index(*a)
        return row * nq + qi, 0, 0

    return pl.BlockSpec((1, 1, bq), at)


def _specs_by_query(steps: _Steps, bq: int, bk: int, nq: int, kv_of):
    """Block specs of the forward and dQ kernels, whose steps go query
    block by query block: ``q_rows(width)`` for what a query head's rows
    hold (q, out, dO, dq), ``kv_rows(width)`` for k and v, read at the
    group's head, and ``stat_rows`` for the statistics' rows (the
    forward's ``m`` and ``l``, the backward's ``lse`` and ``D``)."""
    from jax.experimental import pallas as pl

    def index(bh, *at):
        return bh, steps.blocks(*steps.mapped(bh, at))[0], 0

    def q_rows(width):
        return pl.BlockSpec((1, bq, width), index)

    def kv_rows(width):
        return pl.BlockSpec(
            (1, bk, width),
            lambda bh, *at: (kv_of(bh), steps.blocks(*steps.mapped(bh, at))[2], 0),
        )

    return q_rows, kv_rows, _stat_rows(bq, nq, index)


def _selection_rows(steps: _Steps, bq: int, bk: int, nq: int, rows: int,
                    key_major: bool):
    """Block spec of the selection's words, ``[b·nq, R, t]``: the ``[R,
    bk]`` words of a step's (query block, key block); ``rows`` grid rows a
    batch."""
    from jax.experimental import pallas as pl

    def at(bh, *a):
        outer, _, inner = steps.blocks(*steps.mapped(bh, a))
        qi, ki = (inner, outer) if key_major else (outer, inner)
        return (bh // rows) * nq + qi, 0, ki

    return pl.BlockSpec((1, bq // WORD, bk), at)


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
    return_stats: bool = False,
    window: Optional[int] = None,
    selected: Optional[jax.Array] = None,
    k_shared: Optional[jax.Array] = None,
):
    """Fused forward. With ``return_stats`` also returns the softmax row
    statistics ``(m, l)`` as float32 ``[b, h, t]`` — residuals for the
    fused backward and merge inputs for the ring schedule. The kernel runs
    key-major: it reads the values as turned blocks ``[dv, bk]`` and writes
    ``m`` and ``l`` as lane-dense rows ``[b·h·nq, 1, bq]``, the backward's
    own layout, of which ``[b, h, t]`` is a plain reshape. ``selected``
    (the words of ``ops/sparse_attention.py``) admits a score only where
    the selection does too, and the steps skip the blocks without one.
    ``k_shared`` (``[b, t, 1, d_s]``) is the key's part that every query
    head reads against its last ``d_s`` dimensions."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]  # the values' width, and the output's
    kv_of = _kv_head_map(h, k.shape[2])
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, t)
    bk = min(block_k, t)
    tq_pad = -(-t // bq) * bq
    tk_pad = -(-t // bk) * bk

    latent = k_shared is not None
    dn = k.shape[-1]  # the width of the part of a key that is its head's own
    if latent:
        qb, qsb = _to_bh(q[..., :dn], tq_pad), _to_bh(q[..., dn:], tq_pad)
    else:
        qb = _to_bh(q, tq_pad)
    kb = _to_bh(k, tk_pad)
    nq, nk = tq_pad // bq, tk_pad // bk
    vtb = _to_bh_blocks_t(v, tk_pad, bk)

    work = _blocks_with_work(nq, nk, bq, bk, causal, window)
    sparse = selected is not None
    in_specs, operands = [], ()
    if sparse:
        steps = _SelectedSteps(work, block_work(selected, bk), h)
        in_specs = [_selection_rows(steps, bq, bk, nq, h, False)]
        operands = (selected.reshape(-1, *selected.shape[2:]),)
    else:
        steps = _Steps(work)
    q_rows, kv_rows, stat_rows = _specs_by_query(steps, bq, bk, nq, kv_of)
    vt_blocks = pl.BlockSpec(
        (1, dv, bk),
        lambda bh, *at: (
            kv_of(bh) * nk + steps.blocks(*steps.mapped(bh, at))[2], 0, 0
        ),
    )
    if latent:
        ds = d - dn
        shared_rows = _specs_by_query(steps, bq, bk, nq, _kv_head_map(h, 1))[1]
        in_specs = [q_rows(ds), shared_rows(ds)]
        operands = (qsb, _to_bh(k_shared, tk_pad))
    out, m, l = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            steps=steps,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_k=bk,
            seq_len=t,
            window=window,
            sparse=sparse,
            latent=latent,
        ),
        **steps.call(
            b * h,
            in_specs=[q_rows(dn), kv_rows(dn), vt_blocks, *in_specs],
            out_specs=[q_rows(dv), stat_rows, stat_rows],
            scratch_shapes=[
                pltpu.VMEM((8, bq), jnp.float32),  # running max, sublanes replicated
                pltpu.VMEM((8, bq), jnp.float32),  # normalizer, sublanes replicated
                pltpu.VMEM((dv, bq), jnp.float32),  # output accumulator, turned
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h * nq, 1, bq), jnp.float32),
            jax.ShapeDtypeStruct((b * h * nq, 1, bq), jnp.float32),
        ],
        interpret=interpret,
        # The kernel's own name in the trace, whatever jit calls the
        # function that holds it.
        name=_kernel_name(window, "fwd", sparse, latent),
    )(*steps.tables, qb, kb, vtb, *operands)
    out = out[:, :t].reshape(b, h, t, dv)
    out = jnp.transpose(out, (0, 2, 1, 3))
    if not return_stats:
        return out
    return out, m.reshape(b, h, tq_pad)[..., :t], l.reshape(b, h, tq_pad)[..., :t]


def _masked_scores(a, b, scale, masked, keys_axis, qi, ki, block_q, block_k,
                   seq_len, causal, window=None, selected=None, shared=None):
    """The kernels' score block ``a @ bᵀ · scale``, ``NEG_INF`` where the
    mask admits no score (where ``masked``), whose keys lie along
    ``keys_axis``: 1 for a query-major block (``a`` the queries), 0 for a
    key-major one (``a`` the keys). ``selected``: the selection's block in
    the same orientation; ``shared``: the refs of a split key's shared
    parts in ``a``'s and ``b``'s order."""
    s = _scores(a, b, shared) * scale
    if (causal or seq_len % block_k != 0) and masked:
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, keys_axis
        )
        valid = k_pos < seq_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1 - keys_axis
            )
            valid = valid & (q_pos >= k_pos)
            if window is not None:
                valid = valid & (q_pos - k_pos < window)
        if selected is not None:
            valid = valid & selected
        s = jnp.where(valid, s, NEG_INF)
    return s


def _flash_bwd_dkv_kernel(
    *refs,
    steps: _Steps,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_len: int,
    window: Optional[int] = None,
    sparse: bool = False,
    latent: bool = False,
):
    """dK/dV: grid (batch·kv-head, ..), a key block against one query
    block with work of one query head of its group (``steps``: a key
    block's in a row, head after head); the dk/dv accumulators live in
    VMEM and are revisited across all of them. ``refs``: the steps' tables
    if any, ``q, k, v, dO``, the ``lse`` and ``D`` rows, the query's and
    the key's shared parts where ``latent``, the selection's words where
    ``sparse``, the outputs ``dk, dv`` and, where ``latent``, the shared
    key's gradient from this key head's query heads, the scratch.

    Key-major: the block is ``[bk, bq]``, keys on the sublanes and queries
    on the lanes, so both accumulating products contract the block's minor
    dimension and the statistics are ``[1, bq]`` rows broadcast down the
    sublanes::

        pᵀ  = exp(k @ qᵀ · scale − lse)
        dv += pᵀ @ dO
        dsᵀ = pᵀ ⊙ (v @ dOᵀ − D)
        dk += dsᵀ @ q · scale
    """
    from jax.experimental import pallas as pl

    tables, refs = refs[: len(steps.tables)], refs[len(steps.tables):]
    q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, *refs = refs
    qs_ref, ks_ref = (refs.pop(0), refs.pop(0)) if latent else (None, None)
    sel_ref = refs.pop(0) if sparse else None
    if latent:
        dk_ref, dv_ref, dks_ref, dk_scr, dv_scr, dks_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    at = steps.here(*tables)
    ki, _, qi = steps.blocks(*at)
    first, last, runs = steps.edges(*at)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])
        if latent:
            dks_scr[...] = jnp.zeros_like(dks_scr[...])

    def _update(masked=True):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        # lse is +inf on a query with no admitted key, padded rows among
        # them, so that p is 0 there with no guard. It is loaded before the
        # scores: the windowed kernel ran 2 % slower with the load after them.
        lse = lse_ref[0]
        p = jnp.exp(_masked_scores(
            k, q, scale, masked, 0, qi, ki, block_q, block_k, seq_len, causal,
            window,
            None if sel_ref is None else _selected_block(sel_ref, block_q, True),
            None if qs_ref is None else (ks_ref, qs_ref),
        ) - lse)  # [bk, bq]
        dv_scr[...] = dv_scr[...] + jax.lax.dot(
            p, do, preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            v,
            do,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d_ref[0])
        dk_scr[...] = dk_scr[...] + jax.lax.dot(
            ds, q, preferred_element_type=jnp.float32
        ) * scale
        if latent:
            dks_scr[...] = dks_scr[...] + jax.lax.dot(
                ds, qs_ref[0], preferred_element_type=jnp.float32
            ) * scale

    _update_block(runs, qi, ki, block_q, block_k, window, _update)

    @pl.when(last)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        if latent:
            dks_ref[0] = dks_scr[...]


def _flash_bwd_dq_kernel(
    *refs,
    steps: _Steps,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_len: int,
    window: Optional[int] = None,
    sparse: bool = False,
    latent: bool = False,
):
    """dQ: the forward's grid and ``refs`` but for ``v`` as it lies (not
    turned), ``dO`` and the ``lse`` and ``D`` rows after it (the shared
    parts and the words after them)
    and the output, ``dq``, and where ``latent`` the query's shared part's
    too; ``dq += ds @ k · scale`` accumulates in VMEM across a query
    block's key blocks.

    Query-major, since ``ds @ k`` contracts the keys: the block is ``[bq,
    bk]`` and the statistics are wanted as columns. A query block's first
    step turns its two rows into columns once, into scratch with the lanes
    replicated; the inner steps read them there."""
    from jax.experimental import pallas as pl

    tables, refs = refs[: len(steps.tables)], refs[len(steps.tables):]
    q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, *refs = refs
    qs_ref, ks_ref = (refs.pop(0), refs.pop(0)) if latent else (None, None)
    sel_ref = refs.pop(0) if sparse else None
    if latent:
        dq_ref, dqs_ref, lse_scr, d_scr, dq_scr, dqs_scr = refs
    else:
        dq_ref, lse_scr, d_scr, dq_scr = refs
    at = steps.here(*tables)
    qi, _, ki = steps.blocks(*at)
    first, last, runs = steps.edges(*at)

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        if latent:
            dqs_scr[...] = jnp.zeros_like(dqs_scr[...])
        lanes = (lse_scr.shape[1], block_q)
        lse_scr[...] = jnp.broadcast_to(lse_ref[0], lanes).T
        d_scr[...] = jnp.broadcast_to(d_ref[0], lanes).T

    def _update(masked=True):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_scr[:, :1]
        p = jnp.exp(_masked_scores(
            q, k, scale, masked, 1, qi, ki, block_q, block_k, seq_len, causal,
            window,
            None if sel_ref is None else _selected_block(sel_ref, block_q, False),
            None if qs_ref is None else (qs_ref, ks_ref),
        ) - lse)  # [bq, bk]
        dp = jax.lax.dot_general(
            do,
            v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d_scr[:, :1])
        dq_scr[...] = dq_scr[...] + jax.lax.dot(
            ds,
            k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale
        if latent:
            dqs_scr[...] = dqs_scr[...] + jax.lax.dot(
                ds,
                ks_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) * scale

    _update_block(runs, qi, ki, block_q, block_k, window, _update)

    @pl.when(last)
    def _fin():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)
        if latent:
            dqs_ref[0] = dqs_scr[...].astype(dqs_ref.dtype)


def _flash_backward_pallas(
    q, k, v, out, m, l, ct, causal, block_q, block_k, interpret, window=None,
    selected=None, k_shared=None,
):
    """Fused flash backward: two Pallas kernels (dK/dV, a key block's
    query blocks in a row, and dQ, a query block's key blocks) consuming
    the forward's saved statistics — no stats-recompute pass and no
    ``[T, T]`` block in HBM.

    The kernels read two lane-dense float32 rows a query head, ``[b·h·nq,
    1, bq]`` (plain reshapes of ``[b, h, t]``, so no relayout; a query
    block's row is an array's last two dimensions whole, which Mosaic takes
    at any ``bq``, under 128 lanes too): the
    log-sum-exp ``lse = m + log l``, ``+inf`` where ``l`` is 0 (a padded
    row, or one that admits no key), so that ``p = exp(s − lse)`` needs no
    divide and no guard, and ``D = rowsum(ct ⊙ out)`` (the softmax
    jacobian's diagonal term, one XLA reduce). dK/dV runs key-major on
    them (keys on the sublanes: no ``[bq, bk]`` block is transposed for its
    two products, and a statistic's block is a row of ``bq``); dQ contracts
    the keys, so it stays query-major and turns a query block's rows into
    columns once, at the block's first step.

    With ``k_shared`` it returns a fourth gradient, the shared key's from
    each key head's query heads, ``[b, t, kv_heads, d_s]`` float32, for the
    caller to sum over the heads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]
    hk = k.shape[2]
    group = h // hk
    kv_of = _kv_head_map(h, hk)
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, t)
    bk = min(block_k, t)
    tq_pad = -(-t // bq) * bq
    tk_pad = -(-t // bk) * bk
    nq = tq_pad // bq

    def row_bh(x, fill):  # [b, h, t] -> [bh * nq, 1, bq]
        if tq_pad != t:
            x = jnp.pad(
                x, ((0, 0), (0, 0), (0, tq_pad - t)), constant_values=fill
            )
        return x.reshape(b * h * nq, 1, bq)

    latent = k_shared is not None
    dn = k.shape[-1]  # the width of the part of a key that is its head's own
    if latent:
        ds = d - dn
        qb, qsb = _to_bh(q[..., :dn], tq_pad), _to_bh(q[..., dn:], tq_pad)
        shared = (qsb, _to_bh(k_shared, tk_pad))
    else:
        qb, shared = _to_bh(q, tq_pad), ()
    kb = _to_bh(k, tk_pad)
    vb = _to_bh(v, tk_pad)
    # Native dtype: the kernels cast each dO block to f32 on load, so a
    # host-side f32 copy would only double dO's HBM traffic.
    dob = _to_bh(ct, tq_pad)
    lse = row_bh(jnp.where(l > 0, m + jnp.log(l), jnp.inf), jnp.inf)
    big_d = row_bh(
        jnp.einsum(
            "bqhd,bqhd->bhq",
            ct.astype(jnp.float32),
            out.astype(jnp.float32),
        ),
        0.0,
    )

    work = _blocks_with_work(nq, tk_pad // bk, bq, bk, causal, window)
    sparse = selected is not None
    of_kernel = dict(
        scale=scale, causal=causal, block_q=bq, block_k=bk, seq_len=t,
        window=window,
    )
    if latent:
        of_kernel["latent"] = True
    if sparse:
        of_kernel["sparse"] = True
        live = block_work(selected, bk)
        words = (selected.reshape(-1, *selected.shape[2:]),)
        by_key = _SelectedSteps(work.T, jnp.swapaxes(live, 1, 2), hk, group)
        by_query = _SelectedSteps(work, live, h)
    else:
        words = ()
        # dK/dV: a key block's query blocks, for each query head of its group.
        by_key = _Steps(work.T, group)
        by_query = _Steps(work)

    def index(bkv, *at):  # q, dO and the statistics: a query head's block
        _, member, qi = by_key.blocks(*by_key.mapped(bkv, at))
        return (bkv // hk) * h + (bkv % hk) * group + member, qi, 0

    def q_rows(width):
        return pl.BlockSpec((1, bq, width), index)

    def kv_rows(width):  # k, dk [.., d] and v, dv [.., dv]
        return pl.BlockSpec(
            (1, bk, width),
            lambda bkv, *at: (bkv, by_key.blocks(*by_key.mapped(bkv, at))[0], 0),
        )

    stat_rows = _stat_rows(bq, nq, index)
    sel_rows = [_selection_rows(by_key, bq, bk, nq, hk, True)] if sparse else []
    in_shared = out_shared = scratch_shared = shape_shared = []
    if latent:
        # The shared key's block is its batch's: every head of a batch reads it.
        in_shared = [
            q_rows(ds),
            pl.BlockSpec(
                (1, bk, ds),
                lambda bkv, *at: (
                    bkv // hk, by_key.blocks(*by_key.mapped(bkv, at))[0], 0
                ),
            ),
        ]
        out_shared = [kv_rows(ds)]
        scratch_shared = [pltpu.VMEM((bk, ds), jnp.float32)]
        shape_shared = [jax.ShapeDtypeStruct((b * hk, tk_pad, ds), jnp.float32)]
    dkb, dvb, *dksb = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, steps=by_key, **of_kernel),
        **by_key.call(
            b * hk,
            in_specs=[q_rows(dn), kv_rows(dn), kv_rows(dv), q_rows(dv),
                      stat_rows, stat_rows, *in_shared, *sel_rows],
            out_specs=[kv_rows(dn), kv_rows(dv), *out_shared],
            scratch_shapes=[
                pltpu.VMEM((bk, dn), jnp.float32),
                pltpu.VMEM((bk, dv), jnp.float32),
                *scratch_shared,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, tk_pad, dn), k.dtype),
            jax.ShapeDtypeStruct((b * hk, tk_pad, dv), v.dtype),
            *shape_shared,
        ],
        interpret=interpret,
        name=_kernel_name(window, "bwd_dkv", sparse, latent),
    )(*by_key.tables, qb, kb, vb, dob, lse, big_d, *shared, *words)

    # dQ: the forward's steps.
    q_rows2, kv_rows2, stat_rows2 = _specs_by_query(
        by_query, bq, bk, nq, kv_of
    )
    if sparse:
        sel_rows = [_selection_rows(by_query, bq, bk, nq, h, False)]
    out_specs = q_rows2(dn)
    scratch = [pltpu.VMEM((bq, dn), jnp.float32)]
    out_shape = jax.ShapeDtypeStruct((b * h, tq_pad, dn), q.dtype)
    if latent:
        shared_rows = _specs_by_query(by_query, bq, bk, nq, _kv_head_map(h, 1))[1]
        in_shared = [q_rows2(ds), shared_rows(ds)]
        out_specs = [out_specs, q_rows2(ds)]
        scratch.append(pltpu.VMEM((bq, ds), jnp.float32))
        out_shape = [out_shape, jax.ShapeDtypeStruct((b * h, tq_pad, ds), q.dtype)]
    dqb = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, steps=by_query, **of_kernel),
        **by_query.call(
            b * h,
            in_specs=[q_rows2(dn), kv_rows2(dn), kv_rows2(dv), q_rows2(dv),
                      stat_rows2, stat_rows2, *in_shared, *sel_rows],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),  # lse, lanes replicated
                pltpu.VMEM((bq, 128), jnp.float32),  # D, lanes replicated
                *scratch,
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name=_kernel_name(window, "bwd_dq", sparse, latent),
    )(*by_query.tables, qb, kb, vb, dob, lse, big_d, *shared, *words)

    def from_bh(x):
        x = x[:, :t].reshape(b, -1, t, x.shape[-1])
        return jnp.transpose(x, (0, 2, 1, 3))

    if not latent:
        return from_bh(dqb), from_bh(dkb), from_bh(dvb)
    dq = jnp.concatenate([from_bh(dqb[0]), from_bh(dqb[1])], axis=-1)
    return dq, from_bh(dkb), from_bh(dvb), from_bh(dksb[0])


# What the fused backward reads of the forward kernel, under
# ``jax.ad_checkpoint.checkpoint_name``: the output ``[b, t, h, d]`` and the
# softmax row statistics ``m``, ``l`` ``[b, h, t]``. A caller that recomputes
# its layers (``jax.checkpoint`` / ``nn.remat``) lists both in its policy
# (``save_only_these_names``) to keep them, so that the backward pass does not
# run the forward kernel again; under any other policy, or none, the names
# are identities.
ATTENTION_OUT = "flash_attention_out"
ATTENTION_STATS = "flash_attention_stats"

# The kernels split over batch and heads. Sequence and head_dim stay whole
# on every device (each tile reads full K/V rows) — sequence sharding is
# the ring/Ulysses schedules' job, not this op's.
_QKV_DIMS = (DATA_AXIS, None, MODEL_AXIS, None)  # [b, t, h, d]
_STAT_DIMS = (DATA_AXIS, MODEL_AXIS, None)  # [b, h, t]


def _sharded_flash(causal, block_q, block_k, interpret, window,
                   return_stats=False):
    """The forward kernel, split over the context mesh (:mod:`.placement`)."""

    def run(q, k, v):
        return _flash_forward(
            q, k, v, causal, block_q, block_k, interpret,
            return_stats=return_stats, window=window,
        )

    out_dims = [_QKV_DIMS]
    if return_stats:
        out_dims += [_STAT_DIMS, _STAT_DIMS]
    return over_mesh(run, in_dims=[_QKV_DIMS] * 3, out_dims=out_dims)


def _sharded_flash_bwd(causal, block_q, block_k, interpret, window):
    """The fused backward under the same batch/head split."""

    def run(q, k, v, out, m, l, ct):
        return _flash_backward_pallas(
            q, k, v, out, m, l, ct, causal, block_q, block_k, interpret,
            window,
        )

    return over_mesh(
        run,
        in_dims=[_QKV_DIMS] * 4 + [_STAT_DIMS] * 2 + [_QKV_DIMS],
        out_dims=[_QKV_DIMS] * 3,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, block_q, block_k, interpret, window):
    return _sharded_flash(causal, block_q, block_k, interpret, window)(q, k, v)


def _fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    out, m, l = _sharded_flash(
        causal, block_q, block_k, interpret, window, return_stats=True
    )(q, k, v)
    # ``out`` joins the residuals (the backward needs D = rowsum(ct*out))
    # along with the softmax statistics the fused backward consumes. All
    # three are named in the form ``_bwd`` reads, for a caller's policy.
    out = checkpoint_name(out, ATTENTION_OUT)
    m = checkpoint_name(m, ATTENTION_STATS)
    l = checkpoint_name(l, ATTENTION_STATS)
    return out, (q, k, v, out, m, l)


def _bwd(causal, block_q, block_k, interpret, window, res, ct):
    q, k, v, out, m, l = res
    # Fused Pallas backward by default (consumes the forward's saved
    # statistics — no stats-recompute pass); RSDL_FLASH_BWD=xla selects
    # the chunked-XLA exact backward (shared with blockwise_attention)
    # as an escape hatch.
    if os.environ.get("RSDL_FLASH_BWD", "pallas").lower() == "xla":
        if window is not None:
            raise ValueError(
                "RSDL_FLASH_BWD=xla: the chunked backward knows no window"
            )
        group = q.shape[2] // k.shape[2]
        dq, dk, dv = _chunked_attention_bwd(
            q, _repeat_kv(k, group), _repeat_kv(v, group), out, ct, causal,
            max(block_k, 128),
        )
        return dq, _sum_groups(dk, group), _sum_groups(dv, group)
    return _sharded_flash_bwd(causal, block_q, block_k, interpret, window)(
        q, k, v, out, m, l, ct
    )


_flash_vjp.defvjp(_fwd, _bwd)

_WORD_DIMS = (DATA_AXIS, None, None, None)  # the selection's [b, nq, R, t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sparse_vjp(q, k, v, selected, block_q, block_k, interpret):
    return _sparse_fwd(q, k, v, selected, block_q, block_k, interpret)[0]


def _sparse_fwd(q, k, v, selected, block_q, block_k, interpret):
    """``(out, m, l)``, the residuals named as :func:`_fwd` names them. The
    statistics are outputs for the caller's indexer loss, which reads them
    without a gradient: their cotangents are dropped."""

    def run(q, k, v, selected):
        return _flash_forward(
            q, k, v, True, block_q, block_k, interpret, return_stats=True,
            selected=selected,
        )

    out, m, l = over_mesh(
        run, in_dims=[_QKV_DIMS] * 3 + [_WORD_DIMS],
        out_dims=[_QKV_DIMS, _STAT_DIMS, _STAT_DIMS],
    )(q, k, v, selected)
    out = checkpoint_name(out, ATTENTION_OUT)
    m = checkpoint_name(m, ATTENTION_STATS)
    l = checkpoint_name(l, ATTENTION_STATS)
    return (out, m, l), (q, k, v, out, m, l, selected)


def _sparse_bwd(block_q, block_k, interpret, res, ct):
    q, k, v, out, m, l, selected = res

    def run(q, k, v, out, m, l, ct, selected):
        return _flash_backward_pallas(
            q, k, v, out, m, l, ct, True, block_q, block_k, interpret,
            selected=selected,
        )

    dq, dk, dv = over_mesh(
        run,
        in_dims=[_QKV_DIMS] * 4 + [_STAT_DIMS] * 2 + [_QKV_DIMS, _WORD_DIMS],
        out_dims=[_QKV_DIMS] * 3,
    )(q, k, v, out, m, l, ct[0], selected)
    return dq, dk, dv, None


_sparse_vjp.defvjp(_sparse_fwd, _sparse_bwd)

_SHARED_DIMS = (DATA_AXIS, None, None, None)  # a shared key part's [b, t, 1, d_s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _latent_vjp(q, k, v, k_shared, causal, block_q, block_k, interpret):
    return _latent_fwd(q, k, v, k_shared, causal, block_q, block_k, interpret)[0]


def _latent_fwd(q, k, v, k_shared, causal, block_q, block_k, interpret):
    """The forward with a shared key part; the residuals named as
    :func:`_fwd` names them."""

    def run(q, k, v, k_shared):
        return _flash_forward(
            q, k, v, causal, block_q, block_k, interpret, return_stats=True,
            k_shared=k_shared,
        )

    out, m, l = over_mesh(
        run, in_dims=[_QKV_DIMS] * 3 + [_SHARED_DIMS],
        out_dims=[_QKV_DIMS, _STAT_DIMS, _STAT_DIMS],
    )(q, k, v, k_shared)
    out = checkpoint_name(out, ATTENTION_OUT)
    m = checkpoint_name(m, ATTENTION_STATS)
    l = checkpoint_name(l, ATTENTION_STATS)
    return out, (q, k, v, k_shared, out, m, l)


def _latent_bwd(causal, block_q, block_k, interpret, res, ct):
    """dQ, dK, dV and the shared part's gradient: the kernels' rows of it, a
    key head's query heads each, summed over the heads here (outside the
    mesh's split, so that heads on other devices are summed too)."""
    q, k, v, k_shared, out, m, l = res

    def run(q, k, v, k_shared, out, m, l, ct):
        return _flash_backward_pallas(
            q, k, v, out, m, l, ct, causal, block_q, block_k, interpret,
            k_shared=k_shared,
        )

    dq, dk, dv, dks = over_mesh(
        run,
        in_dims=[_QKV_DIMS] * 3 + [_SHARED_DIMS, _QKV_DIMS] + [_STAT_DIMS] * 2
        + [_QKV_DIMS],
        out_dims=[_QKV_DIMS] * 4,
    )(q, k, v, k_shared, out, m, l, ct)
    return dq, dk, dv, jnp.sum(dks, axis=2, keepdims=True).astype(k_shared.dtype)


_latent_vjp.defvjp(_latent_fwd, _latent_bwd)


def _with_shared(k: jax.Array, k_shared: jax.Array) -> jax.Array:
    """Each key head with the shared part appended, repeated in HBM (the XLA
    paths only)."""
    return jnp.concatenate(
        [k, jnp.broadcast_to(k_shared, (*k.shape[:3], k_shared.shape[-1]))], axis=-1
    )


def _sparse_reference(q, k, v, selected):
    """Dense softmax attention over the causal keys the selection keeps:
    ``(out, lse [b, h, t])``, float32 inside."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    admitted = unpack(selected) & (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32),
        _repeat_kv(k, group).astype(jnp.float32),
    ) / math.sqrt(d)
    s = jnp.where(admitted[:, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]),
        _repeat_kv(v, group).astype(jnp.float32),
    )
    return out.astype(q.dtype), lse


def _repeat_kv(x: jax.Array, group: int) -> jax.Array:
    """Key/value heads repeated to the query heads (the XLA paths only:
    the kernels read a group's head in place)."""
    return x if group == 1 else jnp.repeat(x, group, axis=2)


def _sum_groups(dx: jax.Array, group: int) -> jax.Array:
    if group == 1:
        return dx
    b, t, h, d = dx.shape
    return dx.reshape(b, t, h // group, group, d).sum(axis=3)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    use_pallas: Optional[bool] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: Optional[int] = None,
    selected: Optional[jax.Array] = None,
    k_shared: Optional[jax.Array] = None,
):
    """Fused attention over ``q [batch, seq, heads, head_dim]``, ``k
    [batch, seq, kv_heads, head_dim]`` and ``v [batch, seq, kv_heads,
    value_dim]``, to ``[batch, seq, heads, value_dim]``; ``kv_heads`` divides
    ``heads`` (grouped-query attention: query head ``i`` reads key/value
    head ``i // (heads // kv_heads)``).

    ``k_shared`` ``[batch, seq, 1, d_s]`` (no ``window``, no ``selected``):
    a key part that every head reads, so ``k`` is ``[.., head_dim - d_s]``
    and a score is ``q[..., :-d_s] · k + q[..., -d_s:] · k_shared``, scaled
    by ``1 / sqrt(head_dim)``; the kernels are ``flash_attention_latent_*``,
    and the shared part's gradient is summed over the heads.

    ``window`` (with ``causal``): position ``i`` sees the ``window`` keys
    ``i - window < j <= i``, itself among them, and the kernels visit the
    blocks of that band only. Any positive width: one that is no multiple
    of the blocks is masked where it ends, one of the sequence's length or
    more is plain causal attention and runs as it.

    ``selected`` (with ``causal``, no ``window``): the ``[batch, seq /
    block_q, block_q / 32, seq]`` int32 words of ``ops/sparse_attention.py``
    (query blocks of this call's ``block_q``); a causal query sees only the
    keys its words keep, the kernels (``flash_attention_sparse_*``) visit
    only the blocks that hold a kept pair, from tables made on the device,
    and the call returns ``(out, lse)``: ``lse [batch, heads, seq]`` float32,
    the log-sum-exp of each query's scores over its keys, read without a
    gradient.

    ``use_pallas=None`` auto-selects the kernel on any TPU backend (split
    batch/head-wise over the context mesh — same policy as
    :func:`~.interaction.dot_interaction`) and the XLA dense reference
    elsewhere. A kernel that does not compile
    raises. ``interpret=True`` runs the kernel in the Pallas interpreter;
    only tests on the CPU set it, and it is never derived from the
    backend.
    """
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"a window is a causal query's last window >= 1 keys, "
                f"not causal={causal}, window={window}"
            )
        if window >= q.shape[1]:
            window = None
    if use_pallas is None:
        use_pallas = auto_pallas()
    if k_shared is not None:
        if window is not None or selected is not None:
            raise ValueError("a shared key part is of attention without a window or a selection")
        if k_shared.shape != (*k.shape[:2], 1, q.shape[-1] - k.shape[-1]):
            raise ValueError(
                f"a shared key part of {k_shared.shape} does not complete keys of "
                f"{k.shape} to queries of {q.shape}"
            )
        if use_pallas:
            return _latent_vjp(q, k, v, k_shared, causal, block_q, block_k, interpret)
        k = _with_shared(k, k_shared)
    if selected is not None:
        if not causal or window is not None:
            raise ValueError("a selection is of causal attention without a window")
        check_blocks(q.shape[1], block_q, block_k)
        if not use_pallas:
            return _sparse_reference(q, k, v, selected)
        out, m, l = _sparse_vjp(q, k, v, selected, block_q, block_k, interpret)
        return out, jnp.where(l > 0, m + jnp.log(l), jnp.inf)
    if not use_pallas:
        group = q.shape[2] // k.shape[2]
        return attention_reference(
            q, _repeat_kv(k, group), _repeat_kv(v, group), causal=causal,
            window=window,
        )
    return _flash_vjp(q, k, v, causal, block_q, block_k, interpret, window)
