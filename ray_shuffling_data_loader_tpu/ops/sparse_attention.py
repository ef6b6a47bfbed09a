"""Learned sparse attention (DeepSeek Sparse Attention's lightning indexer):
which keys each query attends to, chosen by a small scorer, and the loss
that trains the scorer.

The indexer scores every causal (query, key) pair with ``heads`` small
query heads against ONE key head::

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])        for s <= t

and each query ``t`` keeps exactly the ``min(topk, t + 1)`` keys with the
largest ``I[t, s]``, ties going to the lower index (as ``lax.top_k`` breaks
them). The scores that decide the selection are float32 at the highest
matmul precision: the selection is discrete, and a near tie decided by
bfloat16 rounding moves a key. In the kernels a float32 product (the
scores' ``z``, and the loss's ``dq`` and ``dkᵀ``) is ``highest``'s six
bfloat16 terms, each operand split into three bfloat16 parts (hi·hi,
hi·mid, mid·hi, hi·lo, lo·hi, mid·mid, all kept), issued side by side
along one contraction or one output (:func:`_lhs6`, :func:`_dq6`,
:func:`_dkt6`), so that heads of 64 fill the matrix unit where six
passes of depth 64 would each fill half of it.

The selection is a packed bitmask, ``[batch, nq, R, seq]`` int32 for query
blocks of ``block_q`` (``nq = seq / block_q``, ``R = block_q / 32``): bit
``j`` of ``words[b, i, r, s]`` says whether query ``i * block_q + j * R + r``
keeps key ``s``. A ``[block_q, block_k]`` block of it is the ``[R,
block_k]`` words of that block, repeated 32 times down the sublanes and
shifted by the row's ``j`` (:func:`expand`): no lane is moved. 32 MB a layer
at 16,384 tokens.

* :func:`index_select`: the scores, the exact top-k and the bitmask, with
  the log-sum-exp of the selected scores of each query. The Pallas kernel
  ``sparse_index_fwd`` holds one query block's row of scores in VMEM (as
  order-preserving int32 keys), finds the ``k``-th largest by bisection
  over the key's 32 bits and the ties' cut-off index by bisection over the
  index, and writes the block's words: no ``[t, t]`` array reaches HBM.
* :func:`index_loss`: the indexer's loss, DSA's sparse training stage::

      L = mean_t sum_{s in S_t} pbar[t, s] (log pbar[t, s] - log softmax_{S_t}(I[t])_s)

  with ``pbar`` the main attention's probabilities over ``S_t`` summed over
  the heads and normalised (no gradient). Its kernel ``sparse_index_bwd``
  takes one pass over a query block's causal key blocks: the main scores
  again from the kept log-sum-exp of every head, ``pbar`` in VMEM, each
  index head's ``z = q^I . k^I`` once into VMEM for the scores and read back
  for the gradient, ``dL/dI = softmax_{S_t}(I) - pbar`` on ``S_t``, and that
  back into the indexer's ``q``, ``k`` and ``w``; the loss and the three
  gradients come out together (a custom VJP hands the gradients over).

The XLA paths (``use_pallas=False``) are dense, for the CPU and as the
kernels' oracle. Everything a layer keeps of the two (the bitmask, the
log-sum-exp, the loss and its gradients) is named :data:`SELECTION`, so
that under a recomputing policy that lists the name neither kernel runs
again in the backward pass.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_shuffling_data_loader_tpu.ops.placement import auto_pallas

# The name under which a layer's selection (and what its loss computed
# with it) is kept across recomputation, as ``ops/moe.py`` ``ROUTING``.
SELECTION = "sparse_selection"

WORD = 32  # queries a word holds the bits of
INT_MIN = -(2**31)
_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 100 * 2**20


def check_blocks(seq: int, block_q: int, block_k: int) -> None:
    if block_q % WORD or seq % block_q or seq % block_k:
        raise ValueError(
            f"sparse attention takes a sequence of whole blocks and query "
            f"blocks of a multiple of {WORD}: seq {seq}, blocks {block_q} / "
            f"{block_k}"
        )


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs one sequence's selection keeps: ``min(topk, t +
    1)`` a query."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


# -- the bitmask ---------------------------------------------------------------


def pack(selected: jax.Array, block_q: int) -> jax.Array:
    """``[b, t, t]`` bool -> the ``[b, nq, R, t]`` int32 words."""
    b, t, s = selected.shape
    r = block_q // WORD
    bits = selected.reshape(b, t // block_q, WORD, r, s).astype(jnp.uint32)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)[None, None, :, None, None]
    return jax.lax.bitcast_convert_type(
        jnp.sum(bits << shifts, axis=2, dtype=jnp.uint32), jnp.int32
    )


def unpack(words: jax.Array) -> jax.Array:
    """The ``[b, nq, R, t]`` words -> ``[b, t, t]`` bool."""
    b, nq, r, s = words.shape
    shifts = jnp.arange(WORD, dtype=jnp.int32)[None, None, :, None, None]
    bits = jax.lax.shift_right_logical(words[:, :, None], shifts) & 1
    return bits.reshape(b, nq * WORD * r, s).astype(bool)


def expand(words: jax.Array, block_q: int) -> jax.Array:
    """A kernel's ``[R, bk]`` words -> its ``[block_q, bk]`` block of the
    selection (bool): the words repeated down the sublanes, row ``i`` shifted
    by ``i // R``."""
    r, bk = words.shape
    rows = jnp.broadcast_to(words[None], (block_q // r, r, bk)).reshape(block_q, bk)
    shift = jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0) // r
    return (jax.lax.shift_right_logical(rows, shift) & 1) == 1


def block_work(words: jax.Array, block_k: int) -> jax.Array:
    """``[b, nq, nk]`` bool: whether a (query block, key block) pair holds a
    selected pair, on the device."""
    b, nq, r, s = words.shape
    return jnp.any(words.reshape(b, nq, r, s // block_k, block_k) != 0, axis=(2, 4))


def select_counts(words: jax.Array, block_k: int) -> jax.Array:
    """``[3]`` int32 of one layer's selection: the (query block, key block)
    pairs that hold a selected pair, the causal ones, the selected pairs."""
    b, nq, r, s = words.shape
    block_q = r * WORD
    nk = s // block_k
    q_last = (np.arange(nq)[:, None] + 1) * block_q - 1
    causal = int((q_last >= np.arange(nk)[None, :] * block_k).sum()) * b
    return jnp.stack([
        jnp.sum(block_work(words, block_k), dtype=jnp.int32),
        jnp.int32(causal),
        jnp.sum(jax.lax.population_count(words), dtype=jnp.int32),
    ])


# -- the scores as order-preserving integers ----------------------------------


def _order_key(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _from_key(key):
    bits = jnp.where(key < 0, key ^ 0x7FFFFFFF, key)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# -- the XLA paths ---------------------------------------------------------------


def index_scores(q, k, w):
    """``I [b, t, s]`` float32 over every pair (the caller masks): ``q [b,
    t, heads, dim]``, ``k [b, s, dim]``, ``w [b, t, heads]``."""
    z = jnp.einsum(
        "bthd,bsd->bths", q.astype(jnp.float32), k.astype(jnp.float32),
        precision=_HIGHEST,
    )
    return jnp.einsum("bths,bth->bts", jax.nn.relu(z), w.astype(jnp.float32),
                      precision=_HIGHEST)


def _causal(t):
    return jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]


def _select_xla(q, k, w, topk: int, block_q: int):
    b, t = q.shape[:2]
    scores = jnp.where(_causal(t), index_scores(q, k, w), -jnp.inf)
    _, idx = jax.lax.top_k(scores, min(topk, t))
    keep = idx <= jnp.arange(t)[None, :, None]
    selected = jnp.zeros((b, t, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None], idx
    ].set(keep)
    lse = jax.nn.logsumexp(jnp.where(selected, scores, -jnp.inf), axis=-1)
    return pack(selected, block_q), lse


def _loss_xla(qi, ki, w, q, k, lse, lse_i, words):
    """``L`` by the formula, dense; differentiable in ``qi``, ``ki``, ``w``."""
    b, t, heads, d = q.shape
    del lse_i  # taken again from the scores, so that it carries their gradient
    selected = unpack(words)
    scores = index_scores(qi, ki, w)
    lse_i = jax.nn.logsumexp(jnp.where(selected, scores, -jnp.inf), axis=-1)
    log_soft = jnp.where(selected, scores - lse_i[..., None], 0.0)
    group = heads // k.shape[2]
    s = jnp.einsum(
        "bthd,bshd->bhts", q.astype(jnp.float32),
        jnp.repeat(k, group, axis=2).astype(jnp.float32),
    ) / math.sqrt(d)
    p = jnp.where(selected[:, None], jnp.exp(s - lse[..., None]), 0.0)
    pbar = jax.lax.stop_gradient(jnp.mean(p, axis=1))
    safe = jnp.where(pbar > 0, pbar, 1.0)
    terms = jnp.where(selected & (pbar > 0), pbar * (jnp.log(safe) - log_soft), 0.0)
    return jnp.sum(terms) / (b * t)


# -- the kernels ------------------------------------------------------------------


def _columns(row, lanes: int = 128):
    """A ``[1, n]`` row -> ``[n, lanes]``, each row the value, lanes
    replicated."""
    return jnp.broadcast_to(row, (lanes, row.shape[1])).T


def _vmem_bytes(shape, itemsize: int = 4) -> int:
    """Bytes a VMEM array of ``shape`` takes, its last two dimensions padded
    to the tile: (8, 128) of 4-byte numbers, (16, 128) of 2-byte ones."""
    *lead, rows, lanes = shape
    tile = 32 // itemsize
    return math.prod(lead) * -(-rows // tile) * tile * -(-lanes // 128) * 128 * itemsize


def _check_vmem(kernel: str, scratch, detail: str) -> None:
    """Refuse, at trace time, a kernel whose scratch (``(shape, dtype)``
    pairs) would not fit under :data:`_VMEM_LIMIT`."""
    need = sum(_vmem_bytes(s, jnp.dtype(dt).itemsize) for s, dt in scratch)
    if need > _VMEM_LIMIT:
        raise ValueError(
            f"{kernel}'s scratch takes {need} B of VMEM, over the limit of "
            f"{_VMEM_LIMIT}: {detail}"
        )


def _split3(x):
    """A float32 array as three bfloat16 parts ``hi + mid + lo``: 24 bits of
    mantissa in three of 8, ``x`` again to float32's rounding."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _dot(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _lhs6(a):
    """``a [m, k]`` float32 -> ``[m, 6k]`` bfloat16, ``[hi|hi|mid|hi|lo|mid]``;
    against ``_rhs6(b)`` ONE product of contraction ``6k``, accumulated in
    float32, is ``a @ b`` at ``highest``: its six bfloat16 terms (hi·hi,
    hi·mid, mid·hi, hi·lo, lo·hi, mid·mid) side by side, so the matrix unit
    runs full depth where six products of depth ``k`` = 64 fill half of it."""
    hi, mid, lo = _split3(a)
    return jnp.concatenate([hi, hi, mid, hi, lo, mid], axis=1)


def _rhs6(b):
    """``b [k, n]`` float32 -> ``[6k, n]`` bfloat16, ``[hi; mid; hi; lo; hi;
    mid]``: the right side of :func:`_lhs6`'s product."""
    hi, mid, lo = _split3(b)
    return jnp.concatenate([hi, mid, hi, lo, hi, mid], axis=0)


def _dq_parts(kt):
    """``kᵀ [id, bk]`` float32 -> ``[2 id, 4 bk]`` bfloat16: ``[[hi, hi, hi,
    0], [mid, mid, 0, lo]]``, the right side of :func:`_dq6`."""
    hi, mid, lo = _split3(kt)
    zero = jnp.zeros_like(hi)
    return jnp.concatenate([
        jnp.concatenate([hi, hi, hi, zero], axis=1),
        jnp.concatenate([mid, mid, zero, lo], axis=1),
    ], axis=0)


def _dq6(dz3, kt4):
    """``dz @ k`` at ``highest`` of ``dz`` split (:func:`_split3`) and ``kt4
    = _dq_parts(kᵀ)``: ONE product of contraction ``4 bk`` whose ``[bq, 2
    id]`` output holds dz·k_hi (dz's three parts) in its first ``id`` lanes
    and hi·mid, mid·mid, hi·lo in the rest, the six terms in an output as
    wide as the array where each term of 64 lanes would fill half of it;
    the caller sums the halves."""
    hi, mid, lo = dz3
    return jax.lax.dot_general(
        jnp.concatenate([hi, mid, lo, hi], axis=1), kt4,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _stack3(x):
    """``x [m, n]`` float32 -> its three parts stacked down the rows, ``[3m,
    n]`` bfloat16, the left side of :func:`_dkt6`."""
    return jnp.concatenate(_split3(x), axis=0)


def _dkt6(qit3, dz3):
    """``q^Iᵀ @ dz`` at ``highest`` of ``qit3 = _stack3(q^Iᵀ)`` and ``dz``
    split: three products, each part of ``dz`` latched once against the
    parts of ``q^Iᵀ`` it meets (hi against hi, mid, lo; mid against hi, mid;
    lo against hi), the six terms."""
    hi, mid, lo = dz3
    m = qit3.shape[0] // 3
    p3 = _dot(qit3, hi)
    p2 = _dot(qit3[: 2 * m], mid)
    return (p3[:m] + p3[m : 2 * m] + p3[2 * m :] + p2[:m] + p2[m:]
            + _dot(qit3[:m], lo))


def _lane_sum(blk, acc):
    """``acc [n, 128] + blk [n, m]`` summed 128 lanes at a time."""
    for c in range(blk.shape[1] // 128):
        acc = acc + blk[:, c * 128 : (c + 1) * 128]
    return acc


def _index_fwd_kernel(q_ref, kt_ref, w_ref, words_ref, lse_ref, key_scr,
                      wcol_scr, q6_scr, *, heads, topk, block_q, block_k, seq):
    """One query block: its row of scores as int32 keys in VMEM, the exact
    top-k, the words and the selected scores' log-sum-exp. Each head's
    ``q`` is split for :func:`_lhs6`'s product once, into ``q6_scr``; a key
    block's ``kᵀ`` once, for all the heads."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    nk = seq // block_k
    n_causal = ((i + 1) * block_q + block_k - 1) // block_k
    rows = (block_q, block_k)
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, rows, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, rows, 1)
    for j in range(heads):
        wcol_scr[j] = _columns(w_ref[0, 0, j : j + 1, :])
        q6_scr[j] = _lhs6(q_ref[0, j])

    def at(kb):
        return pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)

    def score(kb, top):
        kt6 = _rhs6(kt_ref[0, :, at(kb)])  # [6 dim, bk]
        acc = jnp.zeros(rows, jnp.float32)
        for j in range(heads):
            z = _dot(q6_scr[j], kt6)
            acc = acc + wcol_scr[j][:, :1] * jnp.maximum(z, 0.0)
        valid = q_pos >= kb * block_k + col
        key_scr[:, at(kb)] = jnp.where(valid, _order_key(acc), INT_MIN)
        return jnp.maximum(top, jnp.max(jnp.where(valid, acc, -jnp.inf), axis=1,
                                        keepdims=True))

    top = jax.lax.fori_loop(0, n_causal, score,
                            jnp.full((block_q, 1), -jnp.inf, jnp.float32))

    width = 128 if block_k % 128 == 0 else block_k

    def count(test):
        """Per row, how many causal entries pass ``test(keys, index)``."""

        def body(kb, acc):
            hit = test(key_scr[:, at(kb)], kb * block_k + col).astype(jnp.float32)
            return _lane_sum(hit, acc) if width == 128 else acc + hit

        acc = jax.lax.fori_loop(0, n_causal, body,
                                jnp.zeros((block_q, width), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    want = jnp.minimum(topk, q_pos[:, :1] + 1).astype(jnp.float32)

    # The k-th largest key: the largest threshold that k keys reach, built
    # bit by bit in the offset (unsigned) order.
    def bit_of_key(n, above):
        cand = above | jax.lax.shift_left(jnp.int32(1), 31 - n)
        ok = count(lambda keys, _: keys >= (cand ^ INT_MIN)) >= want
        return jnp.where(ok, cand, above)

    kth = jax.lax.fori_loop(0, 32, bit_of_key,
                            jnp.zeros((block_q, 1), jnp.int32)) ^ INT_MIN
    need = want - count(lambda keys, _: keys > kth)
    # Of the keys equal to the k-th, the ``need`` of lowest index: the
    # largest cut-off below which fewer than ``need`` of them lie.
    nbits = int(seq).bit_length()

    def bit_of_cut(n, cut):
        cand = cut | jax.lax.shift_left(jnp.int32(1), nbits - 1 - n)
        below = count(lambda keys, idx: (keys == kth) & (idx < cand))
        return jnp.where(below < need, cand, cut)

    cut = jax.lax.fori_loop(0, nbits, bit_of_cut, jnp.zeros((block_q, 1), jnp.int32))
    r = block_q // WORD

    def emit(kb, total):
        keys = key_scr[:, at(kb)]
        sel = (keys > kth) | ((keys == kth) & (kb * block_k + col <= cut))
        sel = sel & (kb < n_causal)
        word = jnp.zeros((r, block_k), jnp.int32)
        for j in range(WORD):
            word = word | jax.lax.shift_left(
                sel[j * r : (j + 1) * r].astype(jnp.int32), jnp.int32(j)
            )
        words_ref[0, 0, :, at(kb)] = word
        p = jnp.where(sel, jnp.exp(_from_key(keys) - top), 0.0)
        return total + jnp.sum(p, axis=1, keepdims=True)

    total = jax.lax.fori_loop(0, nk, emit, jnp.zeros((block_q, 1), jnp.float32))
    lse = top + jnp.log(total)  # [bq, 1]
    lse_ref[0, 0] = jnp.broadcast_to(lse, (block_q, 128)).T[:1]


def _index_fwd_pallas(qh, kt, wr, topk, block_q, block_k, interpret):
    """``qh [b, heads, t, dim]``, ``kt [b, dim, t]``, ``wr [b, nq, heads,
    bq]`` -> words ``[b, nq, R, t]``, log-sum-exp rows ``[b, nq, 1, bq]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, t, dim = qh.shape
    nq, r = t // block_q, block_q // WORD
    # The row of keys, w as lane-replicated columns, each head's q split.
    scratch = [((block_q, t), jnp.int32), ((heads, block_q, 128), jnp.float32),
               ((heads, block_q, 6 * dim), jnp.bfloat16)]
    _check_vmem("sparse_index_fwd", scratch,
                f"{heads} index heads of {dim}, {t} keys, block_q {block_q}")
    return pl.pallas_call(
        functools.partial(
            _index_fwd_kernel, heads=heads, topk=topk, block_q=block_q,
            block_k=block_k, seq=t,
        ),
        grid=(b, nq),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, dim), lambda bi, i: (bi, 0, i, 0)),
            pl.BlockSpec((1, dim, t), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, 1, heads, block_q), lambda bi, i: (bi, i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, r, t), lambda bi, i: (bi, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, i: (bi, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq, r, t), jnp.int32),
            jax.ShapeDtypeStruct((b, nq, 1, block_q), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(s, dt) for s, dt in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="sparse_index_fwd",
    )(qh, kt, wr)


def _index_bwd_kernel(
    q_ref, k_ref, lse_ref, qi_ref, qit_ref, kt_ref, w_ref, lsei_ref, words_ref,
    loss_ref, dq_ref, dw_ref, dkt_ref,
    lse_scr, w_scr, lsei_scr, loss_scr, dq_scr, dw_scr, q6_scr, qit3_scr, z_scr,
    *, heads, group, index_heads, scale, inv_count, block_q, block_k,
):
    """One (query block, causal key block) pair of the indexer's loss:
    ``pbar`` over the main heads, ``dI``, and its gradients; a query block's
    loss, ``dq`` and ``dw`` accumulate across its key blocks, ``dk`` is this
    pair's part. Each index head's ``z = q^I @ k^Iᵀ`` is computed once a
    pair as :func:`_lhs6`'s product (``q^I`` split at the query block's
    first step, into ``q6_scr``; ``k^Iᵀ`` once a pair), into ``z_scr``, and
    read back by the gradient loop. The gradient's two products take the same six
    bfloat16 terms, ``dz`` split once a head and ``k^Iᵀ`` once a pair:
    ``dq`` by :func:`_dq6` into ``dq_scr``'s two halves (summed at the query
    block's last step), ``dkᵀ`` by :func:`_dkt6` against ``q^Iᵀ``'s parts
    (``qit3_scr``, split at the query block's first step)."""
    from jax.experimental import pallas as pl

    i, kb = pl.program_id(1), pl.program_id(2)
    last_kb = ((i + 1) * block_q - 1) // block_k

    @pl.when(kb == 0)
    def _init():
        for h in range(heads):
            lse_scr[h] = _columns(lse_ref[0, 0, h : h + 1, :])
        for j in range(index_heads):
            w_scr[j] = _columns(w_ref[0, 0, j : j + 1, :])
            q6_scr[j] = _lhs6(qi_ref[0, j])
            qit3_scr[j] = _stack3(qit_ref[0, j])
        lsei_scr[...] = _columns(lsei_ref[0, 0])
        loss_scr[...] = jnp.zeros_like(loss_scr)
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(kb <= last_kb)
    def _pair():
        sel = expand(words_ref[0, 0], block_q)
        rows = (block_q, block_k)
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, rows, 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, rows, 1)
        sel = sel & (q_pos >= k_pos)
        pbar = jnp.zeros(rows, jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h // group],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            pbar = pbar + jnp.exp(s - lse_scr[h][:, :1])
        pbar = jnp.where(sel, pbar * (1.0 / heads), 0.0)
        kt = kt_ref[0]  # [dim, bk]
        kt6 = _rhs6(kt)
        scores = jnp.zeros(rows, jnp.float32)
        for j in range(index_heads):
            z = _dot(q6_scr[j], kt6)
            z_scr[j] = z
            scores = scores + w_scr[j][:, :1] * jnp.maximum(z, 0.0)
        log_soft = scores - lsei_scr[:, :1]
        soft = jnp.where(sel, jnp.exp(log_soft), 0.0)
        safe = jnp.where(pbar > 0, pbar, 1.0)
        terms = jnp.where(pbar > 0, pbar * (jnp.log(safe) - log_soft), 0.0)
        loss_scr[...] = loss_scr[...] + jnp.sum(terms, axis=1, keepdims=True)
        d_scores = (soft - pbar) * inv_count
        kt4 = _dq_parts(kt)
        dkt = jnp.zeros(dkt_ref.shape[2:], jnp.float32)
        for j in range(index_heads):
            z = z_scr[j]
            dw_scr[j] = dw_scr[j] + jnp.sum(d_scores * jnp.maximum(z, 0.0),
                                            axis=1, keepdims=True)
            dz3 = _split3(jnp.where(z > 0, d_scores * w_scr[j][:, :1], 0.0))
            dq_scr[j] = dq_scr[j] + _dq6(dz3, kt4)
            dkt = dkt + _dkt6(qit3_scr[j], dz3)
        dkt_ref[0, 0] = dkt

    @pl.when(kb == pl.num_programs(2) - 1)
    def _fin():
        loss_ref[0, 0] = jnp.broadcast_to(
            jnp.sum(loss_scr[...], axis=0, keepdims=True), (1, 128)
        )
        idim = dq_ref.shape[3]
        dq_ref[0] = dq_scr[:, :, :idim] + dq_scr[:, :, idim:]
        for j in range(index_heads):
            dw_ref[0, 0, j : j + 1, :] = jnp.broadcast_to(
                dw_scr[j], (block_q, 128)
            ).T[:1]


def _index_bwd_pallas(qi, ki, w, q, k, lse, lse_i, words, block_q, block_k,
                      interpret):
    """The loss and its gradients in ``qi [b, t, ih, id]``, ``ki [b, t,
    id]``, ``w [b, t, ih]`` (float32); ``q [b, t, h, d]``, ``k [b, t, hk,
    d]`` the main attention's, ``lse [b, h, t]`` its kept log-sum-exp,
    ``lse_i [b, t]`` the selected scores'."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, heads, d = q.shape
    hk = k.shape[2]
    ih, idim = qi.shape[2:]
    nq, nk, r = t // block_q, t // block_k, block_q // WORD

    def last(i):
        return ((i + 1) * block_q - 1) // block_k

    def kv_block(bi, i, kb):  # a causal key block; past the diagonal the last
        return jnp.minimum(kb, last(i))

    # lse, w and lse_i as lane-replicated columns; the loss, dq (its two
    # halves) and dw accumulators; each index head's q^I split for z and
    # q^Iᵀ's parts for dk; each index head's z of the pair.
    f32 = jnp.float32
    scratch = [((heads, block_q, 128), f32), ((ih, block_q, 128), f32),
               ((block_q, 128), f32), ((block_q, 1), f32),
               ((ih, block_q, 2 * idim), f32), ((ih, block_q, 1), f32),
               ((ih, block_q, 6 * idim), jnp.bfloat16),
               ((ih, 3 * idim, block_q), jnp.bfloat16),
               ((ih, block_q, block_k), f32)]
    _check_vmem(
        "sparse_index_bwd", scratch,
        f"{heads} heads, {ih} index heads of {idim}, blocks {block_q} / "
        f"{block_k} (the index heads' z alone {_vmem_bytes(scratch[-1][0])} B)",
    )
    qh = jnp.transpose(qi, (0, 2, 1, 3))  # [b, ih, t, id]
    rows = lambda x: x.reshape(b, -1, nq, block_q).transpose(0, 2, 1, 3)  # noqa: E731
    loss, dq, dw, dkt = pl.pallas_call(
        functools.partial(
            _index_bwd_kernel, heads=heads, group=heads // hk, index_heads=ih,
            scale=1.0 / math.sqrt(d), inv_count=1.0 / (b * t),
            block_q=block_q, block_k=block_k,
        ),
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((heads, block_q, d), lambda bi, i, kb: (bi, i, 0)),
            pl.BlockSpec((hk, block_k, d),
                         lambda bi, i, kb: (bi, kv_block(bi, i, kb), 0)),
            pl.BlockSpec((1, 1, heads, block_q), lambda bi, i, kb: (bi, i, 0, 0)),
            pl.BlockSpec((1, ih, block_q, idim), lambda bi, i, kb: (bi, 0, i, 0)),
            pl.BlockSpec((1, ih, idim, block_q), lambda bi, i, kb: (bi, 0, 0, i)),
            pl.BlockSpec((1, idim, block_k),
                         lambda bi, i, kb: (bi, 0, kv_block(bi, i, kb))),
            pl.BlockSpec((1, 1, ih, block_q), lambda bi, i, kb: (bi, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, i, kb: (bi, i, 0, 0)),
            pl.BlockSpec((1, 1, r, block_k),
                         lambda bi, i, kb: (bi, i, 0, kv_block(bi, i, kb))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, 128), lambda bi, i, kb: (bi, i, 0, 0)),
            pl.BlockSpec((1, ih, block_q, idim), lambda bi, i, kb: (bi, 0, i, 0)),
            pl.BlockSpec((1, 1, ih, block_q), lambda bi, i, kb: (bi, i, 0, 0)),
            pl.BlockSpec((1, 1, idim, block_k),
                         lambda bi, i, kb: (bi, i, 0, kv_block(bi, i, kb))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq, 1, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, ih, t, idim), jnp.float32),
            jax.ShapeDtypeStruct((b, nq, ih, block_q), jnp.float32),
            jax.ShapeDtypeStruct((b, nq, idim, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(s, dt) for s, dt in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="sparse_index_bwd",
    )(
        jnp.transpose(q, (0, 2, 1, 3)).reshape(b * heads, t, d),
        jnp.transpose(k, (0, 2, 1, 3)).reshape(b * hk, t, d),
        rows(lse),
        qh,
        jnp.swapaxes(qh, 2, 3),
        jnp.swapaxes(ki, 1, 2),
        rows(jnp.swapaxes(w, 1, 2)),
        rows(lse_i[:, None]),
        words,
    )
    # A query block writes the key blocks up to its diagonal only.
    causal = jnp.asarray(
        np.arange(t)[None, :] < (np.arange(nq)[:, None] + 1) * block_q
    )[None, :, None, :]
    dk = jnp.sum(jnp.where(causal, dkt, 0.0), axis=1).swapaxes(1, 2)
    dw = dw.transpose(0, 2, 1, 3).reshape(b, ih, t).swapaxes(1, 2)
    return (
        jnp.sum(loss[..., 0]) / (b * t),
        jnp.transpose(dq, (0, 2, 1, 3)),
        dk,
        dw,
    )


# -- the ops ------------------------------------------------------------------


def index_select(
    q: jax.Array,
    k: jax.Array,
    w: jax.Array,
    topk: int,
    block_q: int,
    block_k: int,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """``(words, lse)``: the selection of ``q [b, t, heads, dim]`` (the
    indexer's query heads), ``k [b, t, dim]`` (its one key head), ``w [b,
    t, heads]`` (the heads' weights, scale included) as the ``[b, nq, R,
    t]`` bitmask of query blocks of ``block_q``, and ``lse [b, t]``, the
    log-sum-exp of each query's selected scores. No gradient flows: the
    selection is discrete. Both are named :data:`SELECTION`."""
    b, t = q.shape[:2]
    check_blocks(t, block_q, block_k)
    q, k, w = (jax.lax.stop_gradient(x.astype(jnp.float32)) for x in (q, k, w))
    if use_pallas is None:
        use_pallas = auto_pallas()
    if use_pallas:
        nq = t // block_q
        words, lse = _index_fwd_pallas(
            jnp.transpose(q, (0, 2, 1, 3)),
            jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(w, 1, 2).reshape(b, -1, nq, block_q).transpose(0, 2, 1, 3),
            topk, block_q, block_k, interpret,
        )
        lse = lse.reshape(b, t)
    else:
        words, lse = _select_xla(q, k, w, topk, block_q)
    return checkpoint_name(words, SELECTION), checkpoint_name(lse, SELECTION)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _loss_kernel(qi, ki, w, q, k, lse, lse_i, words, block_q, block_k, interpret):
    return _loss_kernel_fwd(qi, ki, w, q, k, lse, lse_i, words, block_q, block_k,
                            interpret)[0]


def _loss_kernel_fwd(qi, ki, w, q, k, lse, lse_i, words, block_q, block_k,
                     interpret):
    loss, dq, dk, dw = _index_bwd_pallas(
        qi, ki, w, q, k, lse, lse_i, words, block_q, block_k, interpret
    )
    kept = tuple(checkpoint_name(x, SELECTION) for x in (loss, dq, dk, dw))
    return kept[0], kept[1:]


def _loss_kernel_bwd(block_q, block_k, interpret, res, g):
    dq, dk, dw = res
    return (g * dq, g * dk, g * dw, None, None, None, None, None)


_loss_kernel.defvjp(_loss_kernel_fwd, _loss_kernel_bwd)


def index_loss(
    qi: jax.Array,
    ki: jax.Array,
    w: jax.Array,
    q: jax.Array,
    k: jax.Array,
    lse: jax.Array,
    lse_i: jax.Array,
    words: jax.Array,
    block_q: int,
    block_k: int,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """The indexer's loss (the module's formula), a scalar whose gradient
    reaches ``qi [b, t, ih, id]``, ``ki [b, t, id]`` and ``w [b, t, ih]``
    only: ``q [b, t, h, d]`` and ``k [b, t, hk, d]`` are the main
    attention's (after its norms and rotary), ``lse [b, h, t]`` its
    log-sum-exp over the selection, ``lse_i [b, t]`` and ``words`` what
    :func:`index_select` returned."""
    q, k, lse, lse_i = (jax.lax.stop_gradient(x) for x in (q, k, lse, lse_i))
    qi, ki, w = (x.astype(jnp.float32) for x in (qi, ki, w))
    if use_pallas is None:
        use_pallas = auto_pallas()
    if not use_pallas:
        return _loss_xla(qi, ki, w, q, k, lse, lse_i, words)
    return _loss_kernel(qi, ki, w, q, k, lse, lse_i, words, block_q, block_k,
                        interpret)
