"""Pallas TPU flash attention: fused blockwise softmax-attention kernel.

The XLA lowerings in :mod:`.ring_attention` keep exactness and memory
bounds but leave fusion to the compiler; this kernel hand-fuses one
(q-block × kv-block) tile pipeline in VMEM — scores, online softmax, and
the value matmul never round-trip to HBM, with K/V streamed block by
block across the innermost grid dimension into a revisited accumulator
(the flash-attention construction, written Pallas-idiomatically: MXU
matmuls via ``lax.dot_general``, ``@pl.when`` for first/last-block
prologue/epilogue, lane-padded VMEM scratch for the running max and
normalizer).

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

Sliding window: with ``window`` a causal query at position ``i`` sees the
keys ``i - window < j <= i``. The three kernels then run on a grid of the
band alone: a query block's inner steps are the key blocks that intersect
its band (:func:`_key_blocks`; a key block's, for dK/dV, the query blocks
that intersect its own, :func:`_query_blocks`), the blocks outside are
neither fetched nor computed, and the mask is applied only in the blocks
that the band's two edges cut. Those Pallas calls are named
``flash_attention_window_*``. Without a window nothing here differs from
the plain kernels.

Differentiability: the kernel carries an exact, memory-safe custom VJP.
The forward emits its softmax row statistics (m, l) as outputs; the
backward is two fused Pallas kernels — dK/dV (q innermost, VMEM
accumulators) and dQ (kv innermost) — that recompute probability blocks
from those statistics, so no ``[T, T]`` block materializes in the
gradient and no stats-recompute pass is paid. ``RSDL_FLASH_BWD=xla``
falls back to the chunked-XLA exact backward (shared with
``blockwise_attention``).
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
from ray_shuffling_data_loader_tpu.ops.ring_attention import (
    NEG_INF,
    _chunked_attention_bwd,
    attention_reference,
)


def _key_blocks(qi, block_q, block_k, window, k_blocks, xp=jnp):
    """``(first, last)`` key block that the band of query block ``qi``
    touches: the keys ``q - window < k <= q`` of its queries."""
    first = xp.maximum(qi * block_q - (window - 1), 0) // block_k
    last = xp.minimum(((qi + 1) * block_q - 1) // block_k, k_blocks - 1)
    return first, last


def _query_blocks(ki, block_q, block_k, window, q_blocks, xp=jnp):
    """``(first, last)`` query block whose band touches key block ``ki``:
    the queries ``k <= q < k + window`` of its keys."""
    first = (ki * block_k) // block_q
    last = xp.minimum(
        ((ki + 1) * block_k + window - 2) // block_q, q_blocks - 1
    )
    return first, last


def _band_steps(blocks_of, blocks, *sizes) -> int:
    """Inner grid steps of a windowed kernel: the most blocks that
    ``blocks_of`` gives any of the ``blocks`` outer ones."""
    first, last = blocks_of(np.arange(blocks), *sizes, xp=np)
    return int((last - first + 1).max())


def _on_band(run, qi, ki, block_q, block_k, window, update):
    """``update(masked)`` where ``run``: unmasked in a block that lies whole
    inside the band, masked in one that the diagonal or the window's far
    edge cuts (a padded key lies past every real query, so the diagonal's
    mask covers it)."""
    from jax.experimental import pallas as pl

    inside = (ki * block_k + block_k - 1 <= qi * block_q) & (
        (qi + 1) * block_q - 1 - ki * block_k < window
    )
    pl.when(run & inside)(functools.partial(update, False))
    pl.when(run & jnp.logical_not(inside))(functools.partial(update, True))


def _kernel_name(window, which: str) -> str:
    """The Pallas call's name in a trace: the windowed kernels are a
    population of their own."""
    return "flash_attention_" + ("" if window is None else "window_") + which


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_len: int,
    window: Optional[int] = None,
    k_blocks: int = 0,
):
    """One (batch·head, q-block, kv-block) grid cell.

    The kv dimension is the innermost grid axis; the output block is
    revisited across it, carrying (running max, normalizer, accumulator)
    in VMEM scratch. The softmax statistics (row max ``m`` and
    normalizer ``l``) are emitted as outputs: the backward kernels and
    the ring schedule's stats merge consume them. With ``window`` the
    innermost axis steps through the query block's band of ``k_blocks``
    key blocks, from its first.
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    if window is None:
        ki = step
    else:
        first, last = _key_blocks(qi, block_q, block_k, window, k_blocks)
        ki = first + step

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def _update(masked=True):
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]
        s = (
            jax.lax.dot_general(
                q,
                k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [bq, bk]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        needs_mask = causal or seq_len % block_k != 0
        if needs_mask and masked:
            valid = k_pos < seq_len  # pad keys past the real sequence
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                valid = valid & (q_pos >= k_pos)
                if window is not None:
                    valid = valid & (q_pos - k_pos < window)
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:, :1]  # [bq, 1] (lanes replicated)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # Rows with no valid key yet (m still NEG_INF) would see
        # exp(0) = 1; zero them so fully-masked rows finish as 0.
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(jnp.float32),
            v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if window is not None:
        # The band's own steps; a query block near the start has fewer.
        _on_band(ki <= last, qi, ki, block_q, block_k, window, _update)
    elif causal:
        # Skip fully-masked (strictly upper-right) blocks: the first
        # valid kv block for q-block qi always exists at ki == 0, so the
        # ki == 0 initialization above is never the skipped cell.
        pl.when((qi + 1) * block_q > ki * block_k)(_update)
    else:
        _update()

    @pl.when(step == steps - 1)
    def _fin():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)
        m_ref[0] = m_scr[:, :1]
        l_ref[0] = l_scr[:, :1]


def _key_steps(nq, nk, bq, bk, window):
    """The inner grid axis of the forward and dQ kernels: ``(steps,
    key_block(i, j), kernel keywords)``. Every key block without a window;
    with one, query block ``i``'s band, and past its last block that block
    again, so that nothing is fetched for a step that does not run."""
    if window is None:
        return nk, (lambda i, j: j), {}
    band = (bq, bk, window, nk)

    def key_block(i, j):
        first, last = _key_blocks(i, *band)
        return jnp.minimum(first + j, last)

    return (
        _band_steps(_key_blocks, nq, *band),
        key_block,
        {"window": window, "k_blocks": nk},
    )


def _to_bh(x, t_pad):
    """``[b, t, heads, d] -> [b * heads, t_pad, d]``."""
    b, t, heads, d = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * heads, t, d)
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    return x


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
):
    """Fused forward. With ``return_stats`` also returns the softmax row
    statistics ``(m, l)`` as float32 ``[b, h, t]`` — residuals for the
    fused backward and merge inputs for the ring schedule. With ``window``
    (causal only) the grid's inner axis is the band's key blocks."""
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

    qb = _to_bh(q, tq_pad)
    kb = _to_bh(k, tk_pad)
    vb = _to_bh(v, tk_pad)

    nk = tk_pad // bk
    steps, key_block, of_band = _key_steps(tq_pad // bq, nk, bq, bk, window)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=bq,
        block_k=bk,
        seq_len=t,
        **of_band,
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid=(b * h, tq_pad // bq, steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec(
                (1, bk, d), lambda bh, i, j: (kv_of(bh), key_block(i, j), 0)
            ),
            pl.BlockSpec(
                (1, bk, dv), lambda bh, i, j: (kv_of(bh), key_block(i, j), 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h, tq_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max
            pltpu.VMEM((bq, 128), jnp.float32),  # normalizer
            pltpu.VMEM((bq, dv), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # The kernel's own name in the trace, whatever jit calls the
        # function that holds it.
        name=_kernel_name(window, "fwd"),
    )(qb, kb, vb)
    out = out[:, :t].reshape(b, h, t, dv)
    out = jnp.transpose(out, (0, 2, 1, 3))
    if not return_stats:
        return out
    return out, m[:, :t, 0].reshape(b, h, t), l[:, :t, 0].reshape(b, h, t)


def _bwd_probs(q, k, m, l, ki, scale, causal, block_q, block_k, seq_len, qi,
               window=None, masked=True):
    """Shared backward-kernel algebra: recompute the normalized
    probability block from the saved statistics."""
    s = (
        jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [bq, bk]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], k.shape[0]), 1
    )
    if (causal or seq_len % block_k != 0) and masked:
        valid = k_pos < seq_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (q.shape[0], k.shape[0]), 0
            )
            valid = valid & (q_pos >= k_pos)
            if window is not None:
                valid = valid & (q_pos - k_pos < window)
        s = jnp.where(valid, s, NEG_INF)
    p = jnp.exp(s - m) / jnp.maximum(l, 1e-30)
    # Fully-masked rows kept m at NEG_INF and must contribute nothing.
    return jnp.where(m > NEG_INF / 2, p, 0.0)


def _flash_bwd_dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    m_ref,
    l_ref,
    d_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_len: int,
    q_blocks: int,
    window: Optional[int] = None,
    band_steps: int = 0,
):
    """dK/dV: grid (batch·kv-head, kv-block, group·q-block) with the
    group's query heads and their q blocks innermost; the dk/dv
    accumulators live in VMEM and are revisited across all of them. With
    ``window`` a query head's inner steps are the ``band_steps`` query
    blocks from the first whose band touches this key block.

        p  = softmax block recomputed from (m, l)
        dv += pᵀ @ dO
        dp = dO @ vᵀ ; ds = p ⊙ (dp - D)
        dk += dsᵀ @ q · scale
    """
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    inner = pl.program_id(2)
    if window is None:
        qi = inner % q_blocks
    else:
        first, last = _query_blocks(ki, block_q, block_k, window, q_blocks)
        qi = first + inner % band_steps

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def _update(masked=True):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        p = _bwd_probs(
            q, k, m_ref[0], l_ref[0], ki, scale, causal, block_q,
            block_k, seq_len, qi, window, masked,
        )
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p,
            do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do,
            v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d_ref[0])
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds,
            q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    if window is not None:
        _on_band(qi <= last, qi, ki, block_q, block_k, window, _update)
    elif causal:
        # q blocks strictly above the diagonal see only masked scores.
        pl.when((qi + 1) * block_q > ki * block_k)(_update)
    else:
        _update()

    @pl.when(inner == pl.num_programs(2) - 1)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    m_ref,
    l_ref,
    d_ref,
    dq_ref,
    dq_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_len: int,
    window: Optional[int] = None,
    k_blocks: int = 0,
):
    """dQ: grid (batch·head, q-block, kv-block) with kv innermost;
    ``dq += ds @ k · scale`` accumulates in VMEM across kv blocks (with
    ``window``: across the band's, as in the forward)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    if window is None:
        ki = step
    else:
        first, last = _key_blocks(qi, block_q, block_k, window, k_blocks)
        ki = first + step

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    def _update(masked=True):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        p = _bwd_probs(
            q, k, m_ref[0], l_ref[0], ki, scale, causal, block_q,
            block_k, seq_len, qi, window, masked,
        )
        dp = jax.lax.dot_general(
            do,
            v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d_ref[0])
        dq_scr[...] = dq_scr[...] + jax.lax.dot(
            ds,
            k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale

    if window is not None:
        _on_band(ki <= last, qi, ki, block_q, block_k, window, _update)
    elif causal:
        pl.when((qi + 1) * block_q > ki * block_k)(_update)
    else:
        _update()

    @pl.when(step == steps - 1)
    def _fin():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_backward_pallas(
    q, k, v, out, m, l, ct, causal, block_q, block_k, interpret, window=None
):
    """Fused flash backward: two Pallas kernels (dK/dV with q innermost,
    dQ with kv innermost) consuming the forward's saved statistics — no
    stats-recompute pass and no ``[T, T]`` block in HBM. ``D`` (the
    softmax-jacobian diagonal term rowsum(ct ⊙ out)) is a cheap XLA
    elementwise-reduce."""
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

    def rows_bh(x, t_pad, fill=0.0):  # [b, h, t] -> [bh, t_pad, 1]
        x = x.reshape(b * h, t, 1)
        if t_pad != t:
            x = jnp.pad(
                x, ((0, 0), (0, t_pad - t), (0, 0)), constant_values=fill
            )
        return x

    qb = _to_bh(q, tq_pad)
    kb = _to_bh(k, tk_pad)
    vb = _to_bh(v, tk_pad)
    # Native dtype: the kernels cast each dO block to f32 on load, so a
    # host-side f32 copy would only double dO's HBM traffic.
    dob = _to_bh(ct, tq_pad)
    # Padded q rows carry m = -inf so the kernels' live-row guard
    # (m > NEG_INF/2) zeroes them directly, rather than relying on the
    # zero-padded q/dO rows keeping exp(0)/1e-30 products finite*0.
    mb = rows_bh(m, tq_pad, fill=NEG_INF)
    lb = rows_bh(l, tq_pad)
    big_d = jnp.einsum(
        "bqhd,bqhd->bhq",
        ct.astype(jnp.float32),
        out.astype(jnp.float32),
    )
    db = rows_bh(big_d, tq_pad)

    # dK/dV's inner axis: a query head's steps, every query block or (with
    # a window) those whose band touches the key block.
    if window is None:
        q_steps, dkv_band = nq, {}

        def q_block(j, i):
            return i % nq

    else:
        band = (bq, bk, window, nq)
        q_steps = _band_steps(_query_blocks, tk_pad // bk, *band)
        dkv_band = {"window": window, "band_steps": q_steps}

        def q_block(j, i):
            first, last = _query_blocks(j, *band)
            return jnp.minimum(first + i % q_steps, last)

    def q_of(bkv, inner):
        """The query row of this kv head's group that ``inner`` is at."""
        return (bkv // hk) * h + (bkv % hk) * group + inner // q_steps

    def q_rows(width):  # q [.., d] and dO [.., dv], a query head's block
        return pl.BlockSpec(
            (1, bq, width), lambda bkv, j, i: (q_of(bkv, i), q_block(j, i), 0)
        )

    def kv_rows(width):  # k, dk [.., d] and v, dv [.., dv]
        return pl.BlockSpec((1, bk, width), lambda bkv, j, i: (bkv, j, 0))

    row_spec = pl.BlockSpec(
        (1, bq, 1), lambda bkv, j, i: (q_of(bkv, i), q_block(j, i), 0)
    )
    dkv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_k=bk,
            seq_len=t,
            q_blocks=nq,
            **dkv_band,
        ),
        grid=(b * hk, tk_pad // bk, group * q_steps),
        in_specs=[q_rows(d), kv_rows(d), kv_rows(dv), q_rows(dv), row_spec,
                  row_spec, row_spec],
        out_specs=[kv_rows(d), kv_rows(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, tk_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * hk, tk_pad, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_kernel_name(window, "bwd_dkv"),
    )(qb, kb, vb, dob, mb, lb, db)
    dkb, dvb = dkv

    k_steps, key_block, dq_band = _key_steps(nq, tk_pad // bk, bq, bk, window)
    def q_rows2(width):
        return pl.BlockSpec((1, bq, width), lambda bh, i, j: (bh, i, 0))

    def kv_rows2(width):
        return pl.BlockSpec(
            (1, bk, width), lambda bh, i, j: (kv_of(bh), key_block(i, j), 0)
        )

    row_spec2 = pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0))
    dqb = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_k=bk,
            seq_len=t,
            **dq_band,
        ),
        grid=(b * h, tq_pad // bq, k_steps),
        in_specs=[q_rows2(d), kv_rows2(d), kv_rows2(dv), q_rows2(dv),
                  row_spec2, row_spec2, row_spec2],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_kernel_name(window, "bwd_dq"),
    )(qb, kb, vb, dob, mb, lb, db)

    def from_bh(x):
        x = x[:, :t].reshape(b, -1, t, x.shape[-1])
        return jnp.transpose(x, (0, 2, 1, 3))

    return from_bh(dqb), from_bh(dkb), from_bh(dvb)


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
) -> jax.Array:
    """Fused attention over ``q [batch, seq, heads, head_dim]``, ``k
    [batch, seq, kv_heads, head_dim]`` and ``v [batch, seq, kv_heads,
    value_dim]``, to ``[batch, seq, heads, value_dim]``; ``kv_heads`` divides
    ``heads`` (grouped-query attention: query head ``i`` reads key/value
    head ``i // (heads // kv_heads)``).

    ``window`` (with ``causal``): position ``i`` sees the ``window`` keys
    ``i - window < j <= i``, itself among them, and the kernels visit the
    blocks of that band only. Any positive width: one that is no multiple
    of the blocks is masked where it ends, one of the sequence's length or
    more is plain causal attention and runs as it.

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
    if not use_pallas:
        group = q.shape[2] // k.shape[2]
        return attention_reference(
            q, _repeat_kv(k, group), _repeat_kv(v, group), causal=causal,
            window=window,
        )
    return _flash_vjp(q, k, v, causal, block_q, block_k, interpret, window)
