"""Selective scan: the recurrence of a Mamba layer, forward and backward.

For every sequence, channel ``d`` of ``d_inner`` and state ``n`` of ``N``:

    h_t[d, n] = exp(delta_t[d] * A[d, n]) * h_{t-1}[d, n]
                + delta_t[d] * u_t[d] * B_t[n]
    s_t[d]    = sum_n h_t[d, n] * C_t[n] + D[d] * u_t[d]

with ``h_{-1} = 0``, all of it in float32. Every (channel, state) pair is a
recurrence of its own, ``seq`` steps long, with a decay that differs at
every step: no matrix product computes it. It is one exponential and about
six multiply-adds an element a step, on the vector unit, with a sequential
dependence over the whole sequence.

The Pallas kernels (``selective_scan_fwd`` / ``selective_scan_bwd``) hold
the state in VMEM and registers and never write it out but at the chunks'
boundaries (``[seq / chunk, d_inner, N]``: the backward recomputes a
chunk's states from its boundary, so no ``[seq, d_inner, N]`` array exists
in HBM in either pass). Layout: a block of 1,024 channels fills one vector
register, ``[8 sublanes, 128 lanes]``, and a state ``n`` of that block is a
register of its own (16 of them are the block's whole state). ``B_t[n]``
and ``C_t[n]`` are then scalars, read from SMEM: the forward has no
broadcast along lanes or sublanes and no reduction, every operation is a
full-register multiply-add. (With the 16 states on the sublanes instead,
``d_inner`` on the lanes, ``delta_t`` and ``u_t`` need a sublane broadcast,
``B_t`` and ``C_t`` a lane broadcast, and ``s_t`` a sublane reduction a
step.) The price is one relayout of ``u``, ``delta`` and ``s`` in XLA,
``[seq, d_inner] -> [seq, d_inner / 1024, 8, 128]``. The backward's sums
over the channels (the gradients of ``B_t`` and ``C_t``) are added up
elementwise over the channel blocks in VMEM and reduced over sublanes once
a chunk; the last reduction, over the 128 lanes, is XLA's.

``use_pallas=False`` is the same recurrence as a ``lax.scan`` over the
positions: the CPU's path and the kernels' oracle (its backward keeps every
state: small sizes only). ``interpret=True`` runs the kernels in the Pallas
interpreter, for CPU tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_shuffling_data_loader_tpu.ops.placement import (
    DATA_AXIS,
    auto_pallas,
    over_mesh,
)

# Channels a block: one float32 vector register, 8 sublanes of 128 lanes.
SUBLANES, LANES = 8, 128
BLOCK = SUBLANES * LANES
# Positions between two boundary states. The backward holds a chunk's
# states and its two sums over the channels in VMEM: 3 x chunk x 64 KiB.
CHUNK = 64
VMEM_LIMIT = 48 * 1024 * 1024


def selective_scan_reference(u, delta, a, b, c, d):
    """The recurrence as a ``lax.scan`` over the positions, float32."""
    u32, delta = u.astype(jnp.float32), delta.astype(jnp.float32)
    a, b, c = (x.astype(jnp.float32) for x in (a, b, c))

    def step(h, at):
        u_t, delta_t, b_t, c_t = at  # [batch, d], [batch, d], [batch, n] x 2
        h = jnp.exp(delta_t[..., None] * a) * h + (
            (delta_t * u_t)[..., None] * b_t[:, None, :]
        )
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    h0 = jnp.zeros((u.shape[0], *a.shape), jnp.float32)
    _, s = jax.lax.scan(
        step, h0, tuple(jnp.moveaxis(x, 1, 0) for x in (u32, delta, b, c))
    )
    return jnp.moveaxis(s, 0, 1) + d.astype(jnp.float32) * u32


# -- the kernels ---------------------------------------------------------------------


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, s_ref, hs_ref, h_scr, *,
                chunk: int, states: int):
    """Grid (sequence, channel block, chunk), the chunks innermost and in
    order: ``h_scr`` carries the block's state from one to the next, and
    ``hs_ref`` takes the state each chunk starts from."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    hs_ref[...] = h_scr[...]
    a = [a_ref[n] for n in range(states)]

    def step(t, h):
        dt = dt_ref[t]
        x = dt * u_ref[t]
        s = jnp.zeros_like(x)
        new = []
        for n in range(states):
            h_n = jnp.exp(dt * a[n]) * h[n] + x * b_ref[0, t * states + n]
            s = s + h_n * c_ref[0, t * states + n]
            new.append(h_n)
        s_ref[t] = s
        return tuple(new)

    h = jax.lax.fori_loop(
        0, chunk, step, tuple(h_scr[n] for n in range(states))
    )
    for n in range(states):
        h_scr[n] = h[n]


def _bwd_kernel(u_ref, dt_ref, ds_ref, a_ref, b_ref, c_ref, hs_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                g_scr, da_scr, h_buf, pb_buf, pc_buf, *,
                chunk: int, states: int):
    """Grid (sequence, chunk, channel block): the chunks from the last to
    the first (the index maps turn them round), the channel blocks
    innermost, so that a chunk's sums over the channels (``pb_buf``,
    ``pc_buf``) are complete when its last block is done.

    With ``g_t`` the gradient that reaches ``h_t`` and ``a_t = exp(delta_t
    A)``, ``x_t = delta_t u_t``:

        g_t   = C_t (x) ds_t + a_{t+1} * g_{t+1}
        dC_t  = sum_d h_t ds_t          dB_t = sum_d g_t x_t
        dx_t  = sum_n g_t B_t           e_t  = g_t * h_{t-1} * a_t
        ddelta_t = sum_n e_t A + dx_t u_t
        du_t  = dx_t delta_t            dA  += e_t delta_t

    A chunk's states are recomputed from its boundary into ``h_buf`` first.
    """
    from jax.experimental import pallas as pl

    first_chunk = pl.program_id(1) == 0  # the sequence's last positions
    block = pl.program_id(2)

    @pl.when(first_chunk)
    def _start():
        g_scr[block] = jnp.zeros(g_scr.shape[1:], jnp.float32)
        da_scr[block] = jnp.zeros(da_scr.shape[1:], jnp.float32)

    @pl.when(block == 0)
    def _new_chunk():
        pb_buf[...] = jnp.zeros_like(pb_buf)
        pc_buf[...] = jnp.zeros_like(pc_buf)

    def forward(t, h):
        dt = dt_ref[t]
        x = dt * u_ref[t]
        new = []
        for n in range(states):
            h_buf[t, n] = h[n]  # the state BEFORE position t
            new.append(
                jnp.exp(dt * a_ref[n]) * h[n] + x * b_ref[0, t * states + n]
            )
        return tuple(new)

    h_last = jax.lax.fori_loop(
        0, chunk, forward, tuple(hs_ref[n] for n in range(states))
    )

    def backward(i, carry):
        g, h_t = carry
        t = chunk - 1 - i
        dt, u, ds = dt_ref[t], u_ref[t], ds_ref[t]
        x = dt * u
        dx = jnp.zeros_like(x)
        ddt = jnp.zeros_like(x)
        new_g, new_h = [], []
        for n in range(states):
            a_n = a_ref[n]
            h_before = h_buf[t, n]
            decay = jnp.exp(dt * a_n)
            g_n = g[n] + ds * c_ref[0, t * states + n]
            pc_buf[t, n] = pc_buf[t, n] + h_t[n] * ds
            pb_buf[t, n] = pb_buf[t, n] + g_n * x
            dx = dx + g_n * b_ref[0, t * states + n]
            e = g_n * h_before * decay
            ddt = ddt + e * a_n
            da_scr[block, n] = da_scr[block, n] + e * dt
            new_g.append(g_n * decay)
            new_h.append(h_before)
        ddt_ref[t] = ddt + dx * u
        du_ref[t] = dx * dt
        return tuple(new_g), tuple(new_h)

    g, _ = jax.lax.fori_loop(
        0, chunk, backward,
        (tuple(g_scr[block, n] for n in range(states)), h_last),
    )
    for n in range(states):
        g_scr[block, n] = g[n]
    # The block's sum so far; the last chunk's write is the whole of it.
    da_ref[...] = da_scr[block]

    @pl.when(block == pl.num_programs(2) - 1)
    def _chunk_done():
        def reduce(t, _):
            for n in range(states):
                db_ref[t, pl.ds(n, 1), :] = jnp.sum(
                    pb_buf[t, n], axis=0, keepdims=True
                )
                dc_ref[t, pl.ds(n, 1), :] = jnp.sum(
                    pc_buf[t, n], axis=0, keepdims=True
                )
            return 0

        jax.lax.fori_loop(0, chunk, reduce, 0)


# -- layouts and calls ---------------------------------------------------------------


def _blocked(x, t_pad: int, d_pad: int):
    """``[batch, seq, d] -> [batch, t_pad, d_pad / 1024, 8, 128]`` float32,
    zeros past the sequence and the channels."""
    b, t, d = x.shape
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, t_pad - t), (0, d_pad - d)))
    return x.reshape(b, t_pad, d_pad // BLOCK, SUBLANES, LANES)


def _unblocked(x, t: int, d: int):
    b, t_pad = x.shape[:2]
    return x.reshape(b, t_pad, -1)[:, :t, :d]


def _chunks_flat(x, t_pad: int, chunk: int):
    """``[batch, seq, n] -> [batch, chunks, 1, chunk * n]`` float32: a
    chunk's scalars as SMEM holds them, one row."""
    b, t, n = x.shape
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, t_pad - t), (0, 0)))
    return x.reshape(b, t_pad // chunk, 1, chunk * n)


def _specs(chunk: int, states: int, chunks: int, reverse: bool, order):
    """Block specs of the arrays both kernels read, by the grid's
    ``order`` (which grid axis is the sequence, the channel block, the
    chunk); ``reverse`` takes the chunks from the last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def at(*grid):
        seq, block, c = (grid[i] for i in order)
        return seq, block, (chunks - 1 - c if reverse else c)

    def rows(*g):  # u, delta, s and their gradients
        seq, block, c = at(*g)
        return seq, c, block, 0, 0

    def scalars(*g):
        seq, _, c = at(*g)
        return seq, c, 0, 0

    return {
        "at": at,
        "rows": pl.BlockSpec((None, chunk, None, SUBLANES, LANES), rows),
        "a": pl.BlockSpec(
            (None, states, SUBLANES, LANES), lambda *g: (at(*g)[1], 0, 0, 0)
        ),
        "scalars": pl.BlockSpec(
            (None, None, 1, chunk * states), scalars, memory_space=pltpu.SMEM
        ),
        "state": pl.BlockSpec(
            (None, None, None, states, SUBLANES, LANES),
            lambda *g: (at(*g)[0], at(*g)[2], at(*g)[1], 0, 0, 0),
        ),
    }


def _scan_fwd_call(u5, dt5, a5, b2, c2, chunk: int, interpret: bool):
    """``(s5, boundary states)`` of the blocked operands."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, t_pad, blocks = u5.shape[:3]
    states, chunks = a5.shape[1], t_pad // chunk
    spec = _specs(chunk, states, chunks, False, (0, 1, 2))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, states=states),
        grid=(batch, blocks, chunks),
        in_specs=[
            spec["rows"], spec["rows"], spec["a"], spec["scalars"],
            spec["scalars"],
        ],
        out_specs=[spec["rows"], spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct(u5.shape, jnp.float32),
            jax.ShapeDtypeStruct(
                (batch, chunks, blocks, states, SUBLANES, LANES), jnp.float32
            ),
        ],
        scratch_shapes=[pltpu.VMEM((states, SUBLANES, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="selective_scan_fwd",
    )(u5, dt5, a5, b2, c2)


def _scan_bwd_call(u5, dt5, ds5, a5, b2, c2, hs, chunk: int, interpret: bool):
    """``(du5, ddelta5, dA by sequence, dB and dC before their sum over the
    lanes)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, t_pad, blocks = u5.shape[:3]
    states, chunks = a5.shape[1], t_pad // chunk
    spec = _specs(chunk, states, chunks, True, (0, 2, 1))
    at = spec["at"]
    lanes_spec = pl.BlockSpec(
        (None, chunk, states, LANES), lambda *g: (at(*g)[0], at(*g)[2], 0, 0)
    )
    per_state = (states, SUBLANES, LANES)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, states=states),
        grid=(batch, chunks, blocks),
        in_specs=[
            spec["rows"], spec["rows"], spec["rows"], spec["a"],
            spec["scalars"], spec["scalars"], spec["state"],
        ],
        out_specs=[
            spec["rows"], spec["rows"],
            pl.BlockSpec(
                (None, None, *per_state),
                lambda *g: (at(*g)[0], at(*g)[1], 0, 0, 0),
            ),
            lanes_spec, lanes_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u5.shape, jnp.float32),
            jax.ShapeDtypeStruct(u5.shape, jnp.float32),
            jax.ShapeDtypeStruct((batch, blocks, *per_state), jnp.float32),
            jax.ShapeDtypeStruct((batch, t_pad, states, LANES), jnp.float32),
            jax.ShapeDtypeStruct((batch, t_pad, states, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blocks, *per_state), jnp.float32),  # g across chunks
            pltpu.VMEM((blocks, *per_state), jnp.float32),  # dA so far
            pltpu.VMEM((chunk, *per_state), jnp.float32),  # a chunk's states
            pltpu.VMEM((chunk, *per_state), jnp.float32),  # dB over blocks
            pltpu.VMEM((chunk, *per_state), jnp.float32),  # dC over blocks
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="selective_scan_bwd",
    )(u5, dt5, ds5, a5, b2, c2, hs)


_ROWS = (DATA_AXIS, None, None, None, None)  # [batch, seq, blocks, 8, 128]
_SCALARS = (DATA_AXIS, None, None, None)
_A = (None, None, None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_blocked(u5, dt5, a5, b2, c2, chunk, interpret):
    return _scan_blocked_fwd(u5, dt5, a5, b2, c2, chunk, interpret)[0]


def _scan_blocked_fwd(u5, dt5, a5, b2, c2, chunk, interpret):
    s5, hs = over_mesh(
        functools.partial(_scan_fwd_call, chunk=chunk, interpret=interpret),
        in_dims=[_ROWS, _ROWS, _A, _SCALARS, _SCALARS],
        out_dims=[_ROWS, (DATA_AXIS, None, None, None, None, None)],
    )(u5, dt5, a5, b2, c2)
    return s5, (u5, dt5, a5, b2, c2, hs)


def _scan_blocked_bwd(chunk, interpret, res, ds5):
    u5, dt5, a5, b2, c2, hs = res
    states = a5.shape[1]
    lanes = (DATA_AXIS, None, None, None)
    du5, ddt5, da, db, dc = over_mesh(
        functools.partial(_scan_bwd_call, chunk=chunk, interpret=interpret),
        in_dims=[_ROWS, _ROWS, _ROWS, _A, _SCALARS, _SCALARS,
                 (DATA_AXIS, None, None, None, None, None)],
        out_dims=[_ROWS, _ROWS, (DATA_AXIS, None, None, None, None), lanes,
                  lanes],
    )(u5, dt5, ds5, a5, b2, c2, hs)

    def scalars(x):  # [batch, t_pad, states, 128] -> b2's form
        return jnp.sum(x, axis=-1).reshape(x.shape[0], -1, 1, chunk * states)

    return du5, ddt5, jnp.sum(da, axis=0), scalars(db), scalars(dc)


_scan_blocked.defvjp(_scan_blocked_fwd, _scan_blocked_bwd)


def selective_scan(
    u: jax.Array,
    delta: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """``s [batch, seq, d_inner]`` float32 of ``u``, ``delta`` ``[batch,
    seq, d_inner]``, ``a [d_inner, states]``, ``b``, ``c`` ``[batch, seq,
    states]`` and ``d [d_inner]`` (the equations at the top), differentiable
    in all six.

    ``use_pallas=None`` takes the kernels on a TPU backend and the
    ``lax.scan`` elsewhere; ``interpret=True`` runs the kernels in the
    Pallas interpreter (CPU tests only, never derived from the backend).
    Any ``seq`` and ``d_inner``: both are padded, with steps and channels
    that leave the state as it is."""
    if use_pallas is None:
        use_pallas = auto_pallas()
    if not use_pallas:
        return selective_scan_reference(u, delta, a, b, c, d)
    t, channels = u.shape[1], u.shape[2]
    states = a.shape[1]
    chunk = min(CHUNK, -(-t // 8) * 8)
    t_pad = -(-t // chunk) * chunk
    d_pad = -(-channels // BLOCK) * BLOCK
    # [d, n] -> [blocks, n, 8, 128]: a state's decay rates a register.
    a5 = jnp.pad(a.astype(jnp.float32), ((0, d_pad - channels), (0, 0)))
    a5 = jnp.transpose(
        a5.reshape(d_pad // BLOCK, SUBLANES, LANES, states), (0, 3, 1, 2)
    )
    s5 = _scan_blocked(
        _blocked(u, t_pad, d_pad), _blocked(delta, t_pad, d_pad), a5,
        _chunks_flat(b, t_pad, chunk), _chunks_flat(c, t_pad, chunk),
        chunk, interpret,
    )
    return _unblocked(s5, t, channels) + d.astype(jnp.float32) * u.astype(
        jnp.float32
    )
