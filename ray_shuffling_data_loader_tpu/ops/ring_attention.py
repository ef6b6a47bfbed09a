"""Sequence-parallel exact attention over a mesh axis: ring and Ulysses.

Long-context support for the framework's model layer. The reference has no
attention anywhere (its workload is tabular row shuffling, SURVEY §5), so
these ops have no reference analog — they exist because a TPU-native
framework must scale sequence length past one chip's HBM. Two canonical
schedules, both exact (forward and gradients) vs the dense reference:

**Ring** (the Ring Attention construction of Liu et al., re-derived for
``shard_map``): Q stays put; K/V chunks take ``p`` hops around the ICI
ring (``lax.ppermute``), each hop accumulating with the online
(flash-style) softmax — running row max ``m``, normalizer ``l``, and
un-normalized ``o`` in float32. No device ever gathers the full sequence
or builds more than a [T/p, T/p] score block, so memory scales with the
shard, not T — the schedule for sequences that only fit sharded.

**Ulysses** (all-to-all): one ``all_to_all`` redistributes sequence↔heads
so each device holds the FULL sequence for H/p heads, attends locally in
KV chunks (blockwise online softmax — still no [T, T] matrix), and an
inverse ``all_to_all`` restores sequence shards. Activations DO hold the
full [T, H/p, D] sequence per device, so T must fit unsharded per head
group; within that regime it replaces ``p`` ring hops with two bulk
collectives, which overlap better when per-hop compute is too small to
hide latency. Requires ``heads % p == 0``.

Shared properties: causal masking is exact across chunk boundaries using
global positions; per-hop/per-chunk compute is mask-independent (no
data-dependent control flow — XLA-friendly); both differentiate exactly,
and the memory bound holds on the BACKWARD pass too: the ring carries a
custom VJP whose backward runs its own ring (re-rotating K/V and
recomputing score blocks — plain scan autodiff would save O(T) rotated
chunks plus O(T²/p) probability blocks per device), and the local bodies
(blockwise / flash kernel) recompute their chunks via
:func:`_chunked_attention_bwd`. Both drop into a train step unchanged.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu.ops.placement import auto_pallas

NEG_INF = -1e30  # finite "minus infinity": avoids NaN from (-inf) - (-inf)


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Dense softmax attention, [batch, seq, heads, head_dim] — the
    single-device reference the ring construction must match. ``window``
    (with ``causal``): each query's last ``window`` keys, its own among
    them."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            mask &= jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :] < window
        s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _stats_update(m, l, s):
    """Fold score block ``s`` ([b, h, tq, ck]) into the running softmax
    statistics; returns the rescale factor and probabilities too.

    Rows with no valid key yet (``m`` still at the finite NEG_INF) would
    see ``exp(s - m) = exp(0) = 1`` for their masked entries — the guard
    zeroes them so fully-masked rows accumulate nothing and finish as 0.
    """
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)  # rescale of prior accumulation
    p_ij = jnp.exp(s - m_new[..., None])
    p_ij = jnp.where(m_new[..., None] > NEG_INF / 2, p_ij, 0.0)
    l_new = l * alpha + jnp.sum(p_ij, axis=-1)
    return m_new, l_new, alpha, p_ij


def _online_update(o, m, l, s, v_c):
    """One flash-style accumulation step: statistics plus the
    un-normalized output against values ``v_c`` ([b, ck, h, d])."""
    m_new, l_new, alpha, p_ij = _stats_update(m, l, s)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p_ij, v_c.astype(jnp.float32)
    )
    return o_new, m_new, l_new


def _accum_init(b, h, tq, d):
    return (
        jnp.zeros((b, h, tq, d), jnp.float32),
        jnp.full((b, h, tq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, tq), jnp.float32),
    )


def _accum_finish(o, l, out_dtype):
    # Fully-masked rows (possible only for degenerate inputs) get 0, not
    # NaN: ``_stats_update`` zeroes their probabilities, so o == l == 0
    # and the clamped divide yields exactly 0.
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(out_dtype)


def _ring_mask(s, i, me, p, tq, tk):
    """Apply the global-position causal mask for hop ``i``."""
    chunk = (me - i) % p
    q_pos = me * tq + jnp.arange(tq)
    k_pos = chunk * tk + jnp.arange(tk)
    mask = q_pos[:, None] >= k_pos[None, :]
    return jnp.where(mask[None, None], s, NEG_INF)


def _ring_fwd_local(
    q, k, v, axis_name, causal, use_flash=None, interpret=False
):
    """Forward ring pass; returns ``(out, m, l)`` — the softmax statistics
    ride out as residuals for the backward ring.

    ``use_flash`` routes each hop's local block compute through the fused
    Pallas flash kernel (``None`` = auto: on for TPU backends;
    ``interpret`` is the kernel's explicit CPU-test switch). The hop
    is exactly the kernel's computation; its emitted (m, l) statistics
    merge into the ring accumulator in float32. Causal hops classify by
    the chunk's position: below the diagonal = plain kernel, on the
    diagonal = causal kernel (local positions coincide), above = fully
    masked, skipped outright — so no traced positions ever enter the
    kernel."""
    p = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    if use_flash is None:
        use_flash = auto_pallas()

    perm = [(j, (j + 1) % p) for j in range(p)]

    if use_flash:
        from ray_shuffling_data_loader_tpu.ops.flash_attention import (
            _flash_forward,
        )

        def _partial(causal_block):
            def run(q_, k_, v_):
                o_i, m_i, l_i = _flash_forward(
                    q_, k_, v_, causal_block, 128, 128, interpret,
                    return_stats=True,
                )
                return o_i.astype(jnp.float32), m_i, l_i

            return run

        def _masked(q_, k_, v_):
            return (
                jnp.zeros((b, tq, h, d), jnp.float32),
                jnp.full((b, h, tq), NEG_INF, jnp.float32),
                jnp.zeros((b, h, tq), jnp.float32),
            )

        def hop(carry, i):
            o, m, l, k_c, v_c = carry
            if causal:
                chunk = (me - i) % p
                idx = jnp.where(chunk == me, 0, jnp.where(chunk < me, 1, 2))
                o_i, m_i, l_i = lax.switch(
                    idx,
                    [_partial(True), _partial(False), _masked],
                    q,
                    k_c,
                    v_c,
                )
            else:
                o_i, m_i, l_i = _partial(False)(q, k_c, v_c)
            # Merge the hop's normalized block result into the running
            # accumulator: un-normalize with l_i, rescale both sides to
            # the joint max. Fully-masked rows have l == 0 on their side,
            # so their (possibly exp(0)=1) weights multiply zeros.
            o_i = jnp.transpose(o_i, (0, 2, 1, 3)) * l_i[..., None]
            m_new = jnp.maximum(m, m_i)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(m_i - m_new)
            o = o * alpha[..., None] + o_i * beta[..., None]
            l = l * alpha + l_i * beta
            k_c = lax.ppermute(k_c, axis_name, perm)
            v_c = lax.ppermute(v_c, axis_name, perm)
            return (o, m_new, l, k_c, v_c), None

    else:

        def hop(carry, i):
            o, m, l, k_c, v_c = carry
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32))
            if causal:
                s = _ring_mask(s, i, me, p, tq, tk)
            o, m, l = _online_update(o, m, l, s, v_c)
            k_c = lax.ppermute(k_c, axis_name, perm)
            v_c = lax.ppermute(v_c, axis_name, perm)
            return (o, m, l, k_c, v_c), None

    o0, m0, l0 = _accum_init(b, h, tq, d)
    (o, m, l, _, _), _ = lax.scan(hop, (o0, m0, l0, k, v), jnp.arange(p))
    return _accum_finish(o, l, q.dtype), m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_attention_local(
    q, k, v, axis_name, causal, use_flash=None, interpret=False
):
    """Per-device ring attention (runs inside ``shard_map``); q/k/v are
    the local sequence chunks ``[batch, chunk, heads, head_dim]``.

    Carries a custom VJP: the backward runs its OWN ring pass —
    recomputing each hop's score block from the saved softmax statistics
    and rotating ``(k, v, dk, dv)`` together — so gradient memory scales
    with the shard like the forward (plain scan autodiff would save every
    hop's rotated K/V chunks and probability blocks: O(T) + O(T²/p) per
    device; the advisor flagged exactly this)."""
    out, _, _ = _ring_fwd_local(
        q, k, v, axis_name, causal, use_flash, interpret
    )
    return out


def _ring_vjp_fwd(
    q, k, v, axis_name, causal, use_flash=None, interpret=False
):
    out, m, l = _ring_fwd_local(
        q, k, v, axis_name, causal, use_flash, interpret
    )
    return out, (q, k, v, out, m, l)


def _ring_vjp_bwd(axis_name, causal, use_flash, interpret, res, ct):
    q, k, v, out, m, l = res
    p = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    ctf = ct.astype(jnp.float32)
    l_safe = jnp.maximum(l, 1e-30)
    # Degenerate fully-masked rows kept their m at NEG_INF and produced 0
    # output; their probabilities must stay 0 in the recompute too.
    live = (m > NEG_INF / 2)[..., None]
    # D[b, h, tq] = rowsum(ct ⊙ out) — the softmax-jacobian diagonal term.
    big_d = jnp.einsum("bqhd,bqhd->bhq", ctf, out.astype(jnp.float32))

    perm = [(j, (j + 1) % p) for j in range(p)]

    def hop(carry, i):
        dq, k_c, v_c, dk_c, dv_c = carry
        s = (
            jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32))
            * scale
        )
        if causal:
            s = _ring_mask(s, i, me, p, tq, tk)
        prob = jnp.where(
            live, jnp.exp(s - m[..., None]) / l_safe[..., None], 0.0
        )
        dp = jnp.einsum("bqhd,bkhd->bhqk", ctf, v_c.astype(jnp.float32))
        ds = prob * (dp - big_d[..., None])
        dq = dq + jnp.einsum(
            "bhqk,bkhd->bqhd", ds, k_c.astype(jnp.float32)
        ) * scale
        dk_c = dk_c + jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
        dv_c = dv_c + jnp.einsum("bhqk,bqhd->bkhd", prob, ctf)
        # dk/dv rotate WITH their chunks: after p hops every chunk is back
        # home carrying contributions from all devices.
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        dk_c = lax.ppermute(dk_c, axis_name, perm)
        dv_c = lax.ppermute(dv_c, axis_name, perm)
        return (dq, k_c, v_c, dk_c, dv_c), None

    dq0 = jnp.zeros((b, tq, h, d), jnp.float32)
    zeros_kv = jnp.zeros((b, tk, h, d), jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        hop, (dq0, k, v, zeros_kv, zeros_kv), jnp.arange(p)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention_local.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def _blockwise_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    kv_chunk: int,
    with_output: bool = True,
):
    """Chunked forward returning ``(out, m, l)`` — the softmax statistics
    the flash backward recomputes probabilities from. ``out`` is in the
    inputs' dtype; ``m``/``l`` are float32 ``[b, h, tq]``.
    ``with_output=False`` skips the value accumulation (returns ``out``
    None) — the backward already holds the primal output and only needs
    the statistics."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    chunk = min(kv_chunk, tk)
    nch = -(-tk // chunk)
    pad = nch * chunk - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(tq)

    def masked_scores(i, k_c):
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32))
        # Static guard: the mask depends on the traced chunk index, so
        # XLA cannot fold it away — skip building it entirely in the
        # common unpadded non-causal case.
        if pad or causal:
            k_pos = i * chunk + jnp.arange(chunk)
            valid = (k_pos < tk)[None, :]
            if causal:
                valid = valid & (q_pos[:, None] >= k_pos[None, :])
            s = jnp.where(valid[None, None], s, NEG_INF)
        return s

    if with_output:

        def step(carry, i):
            o, m, l = carry
            k_c = lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=1)
            v_c = lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=1)
            o, m, l = _online_update(o, m, l, masked_scores(i, k_c), v_c)
            return (o, m, l), None

        (o, m, l), _ = lax.scan(
            step, _accum_init(b, h, tq, d), jnp.arange(nch)
        )
        return _accum_finish(o, l, q.dtype), m, l

    def stats_step(carry, i):
        m, l = carry
        k_c = lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=1)
        m, l, _, _ = _stats_update(m, l, masked_scores(i, k_c))
        return (m, l), None

    _, m0, l0 = _accum_init(b, h, tq, d)
    (m, l), _ = lax.scan(stats_step, (m0, l0), jnp.arange(nch))
    return None, m, l


def _chunked_attention_bwd(q, k, v, out, ct, causal, kv_chunk):
    """Memory-safe exact attention backward in KV chunks: recompute the
    softmax STATISTICS with one chunked stats pass (the primal ``out``
    rides the residuals), then accumulate dq and emit per-chunk dk/dv in
    a second chunked pass — peak extra memory is ``[b, h, tq, kv_chunk]``,
    never ``[T, T]``.

    Standard flash-attention gradient algebra: with ``p`` the softmax
    probabilities, ``dp = ct @ vᵀ``, ``D = rowsum(ct ⊙ out)``, then
    ``ds = p ⊙ (dp - D)``; ``dq = ds @ k``, ``dk = dsᵀ @ q`` (both times
    ``scale``), ``dv = pᵀ @ ct``. Shared by the Pallas flash kernel's VJP
    and :func:`blockwise_attention`'s.
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    chunk = min(kv_chunk, tk)
    nch = -(-tk // chunk)
    pad = nch * chunk - tk

    _, m, l = _blockwise_fwd(q, k, v, causal, kv_chunk, with_output=False)
    l = jnp.maximum(l, 1e-30)
    live = (m > NEG_INF / 2)[..., None]  # fully-masked rows stay 0

    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    ctf = ct.astype(jnp.float32)
    # D[b, h, tq] = rowsum(ct * out)
    big_d = jnp.einsum("bqhd,bqhd->bhq", ctf, out.astype(jnp.float32))
    q_pos = jnp.arange(tq)

    def step(dq, i):
        k_c = lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=1)
        v_c = lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=1)
        s = (
            jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32))
            * scale
        )
        if pad or causal:
            k_pos = i * chunk + jnp.arange(chunk)
            valid = (k_pos < tk)[None, :]
            if causal:
                valid = valid & (q_pos[:, None] >= k_pos[None, :])
            s = jnp.where(valid[None, None], s, NEG_INF)
        p = jnp.where(
            live, jnp.exp(s - m[..., None]) / l[..., None], 0.0
        )  # [b,h,tq,ck]
        dp = jnp.einsum("bqhd,bkhd->bhqk", ctf, v_c.astype(jnp.float32))
        ds = p * (dp - big_d[..., None])
        dq = dq + jnp.einsum(
            "bhqk,bkhd->bqhd", ds, k_c.astype(jnp.float32)
        ) * scale
        dk_c = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
        dv_c = jnp.einsum("bhqk,bqhd->bkhd", p, ctf)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((b, tq, h, d), jnp.float32)
    dq, (dk_chunks, dv_chunks) = lax.scan(step, dq0, jnp.arange(nch))
    # [nch, b, ck, h, d] -> [b, nch*ck, h, d] -> unpad
    dk = jnp.moveaxis(dk_chunks, 0, 1).reshape(b, nch * chunk, h, d)[:, :tk]
    dv = jnp.moveaxis(dv_chunks, 0, 1).reshape(b, nch * chunk, h, d)[:, :tk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blockwise_core(q, k, v, causal, kv_chunk):
    out, _, _ = _blockwise_fwd(q, k, v, causal, kv_chunk)
    return out


def _blockwise_core_fwd(q, k, v, causal, kv_chunk):
    out, _, _ = _blockwise_fwd(q, k, v, causal, kv_chunk)
    return out, (q, k, v, out)


def _blockwise_core_bwd(causal, kv_chunk, res, ct):
    q, k, v, out = res
    return _chunked_attention_bwd(q, k, v, out, ct, causal, kv_chunk)


_blockwise_core.defvjp(_blockwise_core_fwd, _blockwise_core_bwd)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Single-device exact attention in KV chunks (flash-style online
    softmax): peak score memory is [b, h, tq, kv_chunk], never [T, T].
    The local compute of the Ulysses body, and usable standalone for long
    sequences on one device. The memory bound holds for the BACKWARD too:
    a custom VJP recomputes score chunks (:func:`_chunked_attention_bwd`)
    instead of letting scan autodiff save every chunk's probabilities."""
    return _blockwise_core(q, k, v, causal, kv_chunk)


def _seq_parallel_jit(
    mesh: Mesh, axis_name: str, body, batch_axis: Optional[str] = None
):
    """Shared scaffolding for both schedules: shard q/k/v along the
    sequence dimension (and optionally the batch dimension along
    ``batch_axis`` — composes with data parallelism), run the per-device
    ``body`` under ``shard_map``, jit with matching in/out shardings."""
    spec = P(batch_axis, axis_name, None, None)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)
    return jax.jit(fn, in_shardings=(sharding,) * 3, out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def make_ring_attention(
    mesh: Mesh,
    axis_name: str = "data",
    causal: bool = False,
    batch_axis: Optional[str] = None,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
):
    """Build a jitted ring-attention over ``mesh``'s ``axis_name``.

    Returns ``fn(q, k, v) -> out`` operating on global arrays of shape
    ``[batch, seq, heads, head_dim]`` sharded (or shardable) along the
    sequence dimension; ``seq`` must divide evenly by the axis size.
    ``batch_axis`` additionally shards the batch dimension (dp × sp
    meshes — batch must then divide that axis size).

    Memoized on the argument tuple so repeated calls (incl. the one-shot
    :func:`ring_attention` wrapper in a step loop) reuse one
    traced/compiled function instead of re-compiling per call.
    """
    return _seq_parallel_jit(
        mesh,
        axis_name,
        # Positional call: custom_vjp nondiff args resolve by position.
        lambda q, k, v: _ring_attention_local(
            q, k, v, axis_name, causal, use_flash, interpret
        ),
        batch_axis=batch_axis,
    )


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    axis_name: str = "data",
    causal: bool = False,
) -> jax.Array:
    """One-shot convenience wrapper around :func:`make_ring_attention`;
    falls back to the dense reference when no mesh is given."""
    if mesh is None:
        return attention_reference(q, k, v, causal=causal)
    return make_ring_attention(mesh, axis_name, causal)(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# ---------------------------------------------------------------------------


def _ulysses_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool,
    kv_chunk: int,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
):
    """Per-device body: one ``all_to_all`` each way redistributes
    sequence↔heads, so this device attends over the FULL sequence for
    its H/p head subset — fused flash kernel on TPU, KV chunks
    (:func:`blockwise_attention`) elsewhere; either way no [T, T] block
    materializes, forward or backward. Activations still hold
    [T, H/p, D] per device (see the module docstring for the regime
    split vs ring).
    """
    # [B, Tl, H, D] -> [B, T, H/p, D]: split heads, gather sequence.
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    if use_flash is None:
        use_flash = auto_pallas()
    if use_flash:
        from ray_shuffling_data_loader_tpu.ops.flash_attention import (
            flash_attention,
        )

        out = flash_attention(
            qh, kh, vh, causal=causal, use_pallas=True, interpret=interpret
        )
    else:
        out = blockwise_attention(qh, kh, vh, causal=causal, kv_chunk=kv_chunk)
    # [B, T, H/p, D] -> [B, Tl, H, D]: back to sequence shards.
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


@functools.lru_cache(maxsize=None)
def make_ulysses_attention(
    mesh: Mesh,
    axis_name: str = "data",
    causal: bool = False,
    kv_chunk: int = 1024,
    batch_axis: Optional[str] = None,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
):
    """All-to-all (Ulysses-style) sequence-parallel attention over
    ``mesh``'s ``axis_name`` — the second canonical long-context
    strategy next to :func:`make_ring_attention`, preferable when
    ``heads`` is a multiple of the axis size and per-chunk compute is
    too small to hide ``p`` ring hops (each device must fit the full
    sequence for its head group, though — the ring has no such bound).
    Same contract: ``fn(q, k, v) -> out`` on ``[batch, seq, heads,
    head_dim]`` arrays sharded along ``seq``; both ``seq`` and ``heads``
    must be divisible BY the axis size. Memoized like
    :func:`make_ring_attention`."""
    return _seq_parallel_jit(
        mesh,
        axis_name,
        functools.partial(
            _ulysses_local,
            axis_name=axis_name,
            causal=causal,
            kv_chunk=kv_chunk,
            use_flash=use_flash,
            interpret=interpret,
        ),
        batch_axis=batch_axis,
    )
