"""The selective scan (ISSUE 34): the Pallas kernel pair in the interpreter
against the ``lax.scan`` oracle, forward and the gradients of all six inputs,
at shapes that are no multiple of the chunk or of the channel block; what the
kernels keep of the states; their lowering for a TPU. CPU only, toy sizes."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.ops.selective_scan import (
    BLOCK,
    CHUNK,
    selective_scan,
    selective_scan_reference,
)

NAMES = ("u", "delta", "a", "b", "c", "d")


def _inputs(batch, seq, channels, states, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    u = jax.random.normal(k[0], (batch, seq, channels))
    delta = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, channels)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (channels, states)))
    b = jax.random.normal(k[3], (batch, seq, states))
    c = jax.random.normal(k[4], (batch, seq, states))
    d = jax.random.normal(k[5], (channels,))
    weight = jax.random.normal(k[6], (batch, seq, channels))
    return (u, delta, a, b, c, d), weight


def _kernel(chunk):
    """The kernel pair in the interpreter, with ``chunk`` positions between
    two boundary states (the module's constant, 64, is more than a toy
    sequence has)."""
    module = importlib.import_module(selective_scan.__module__)

    def run(*x):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(module, "CHUNK", chunk)
            return selective_scan(*x, use_pallas=True, interpret=True)

    return run


def test_the_oracle_is_the_recurrence_written_out():
    (u, delta, a, b, c, d), _ = _inputs(1, 5, 3, 2)
    h = np.zeros((3, 2))
    want = []
    for t in range(5):
        h = np.exp(np.asarray(delta[0, t])[:, None] * np.asarray(a)) * h + (
            np.asarray(delta[0, t] * u[0, t])[:, None] * np.asarray(b[0, t])[None, :]
        )
        want.append(h @ np.asarray(c[0, t]) + np.asarray(d * u[0, t]))
    got = selective_scan_reference(u, delta, a, b, c, d)
    assert np.allclose(got[0], np.stack(want), rtol=1e-5, atol=1e-5)


# (batch, seq, channels, states, chunk): a sequence that is no multiple of
# the chunk, channels that are no multiple of a block and more than one, one
# chunk, one position more than a chunk.
SHAPES = {
    "seq 37 in chunks of 16": (2, 37, 96, 4, 16),
    "two channel blocks": (1, 24, 1100, 2, 8),
    "one chunk": (1, 16, 64, 16, 64),
    "a chunk and one position": (1, 17, 40, 3, 16),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernels_against_the_oracle_forward_and_all_six_gradients(shape):
    *sizes, chunk = SHAPES[shape]
    inputs, weight = _inputs(*sizes)
    want = selective_scan_reference(*inputs)
    got = _kernel(chunk)(*inputs)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)

    def loss(f):
        return lambda *x: jnp.sum(f(*x) * weight)

    want_g = jax.grad(loss(selective_scan_reference), argnums=range(6))(*inputs)
    got_g = jax.grad(loss(_kernel(chunk)), argnums=range(6))(*inputs)
    for name, w, g in zip(NAMES, want_g, got_g):
        assert g.shape == w.shape, name
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= 2e-5 * scale + 1e-5, name


def test_bfloat16_inputs_are_scanned_in_float32():
    inputs, _ = _inputs(1, 20, 32, 4)
    low = tuple(x.astype(jnp.bfloat16) for x in inputs)
    got = _kernel(8)(*low)
    want = selective_scan_reference(*(x.astype(jnp.float32) for x in low))
    assert got.dtype == jnp.float32
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)


def _pallas_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_only_the_chunks_boundary_states_leave_the_kernels():
    """The forward writes ``s`` and one state a chunk; the backward reads
    them. Nothing of ``[seq, channels, states]`` elements is an operand or a
    result of either call, nor anywhere in the traced gradient."""
    batch, seq, channels, states, chunk = 1, 64, 2048, 4, 16
    inputs, weight = _inputs(batch, seq, channels, states)
    grad = jax.grad(lambda *x: jnp.sum(_kernel(chunk)(*x) * weight), argnums=range(6))
    jaxpr = jax.make_jaxpr(grad)(*inputs)
    calls = {eqn.params["name"]: eqn for eqn in _pallas_calls(jaxpr.jaxpr)}
    assert sorted(calls) == ["selective_scan_bwd", "selective_scan_fwd"]
    every_state = seq * channels * states
    fwd_out = [v.aval.shape for v in calls["selective_scan_fwd"].outvars]
    assert fwd_out == [
        (batch, seq, 2, 8, 128), (batch, seq // chunk, 2, states, 8, 128),
    ]
    for eqn in calls.values():
        for v in (*eqn.invars, *eqn.outvars):
            assert np.prod(v.aval.shape) < every_state, v.aval
    shapes = re.findall(r"f32\[([0-9,]+)\]", str(jaxpr))
    assert shapes
    outside = [s for s in shapes if np.prod([int(n) for n in s.split(",")]) >= every_state]
    # Inside the kernels' bodies everything is a register of [8, 128].
    assert not outside, outside
    # The oracle's backward does keep them: the check can fail.
    oracle = jax.make_jaxpr(
        jax.grad(lambda *x: jnp.sum(selective_scan_reference(*x) * weight))
    )(*inputs)
    assert f"f32[{seq},{batch},{channels},{states}]" in str(oracle)


def test_the_kernels_lower_for_a_tpu_at_the_published_sizes():
    """Mosaic's block-shape rules (a scalar block's last two dimensions,
    the [8, 128] tiles) are checked when the call is lowered."""
    batch, seq, channels, states = 1, 8192, 5120, 16
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    args = (
        shape(batch, seq, channels), shape(batch, seq, channels),
        shape(channels, states), shape(batch, seq, states),
        shape(batch, seq, states), shape(channels),
    )
    f = lambda *x: jnp.sum(selective_scan(*x, use_pallas=True) ** 2)  # noqa: E731
    text = (
        jax.jit(jax.grad(f, argnums=range(6)))
        .trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    )
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    assert BLOCK == 1024 and seq % CHUNK == 0 and channels % BLOCK == 0


def test_without_a_backend_for_them_the_kernels_are_not_taken():
    inputs, _ = _inputs(1, 8, 16, 2)
    # On the CPU the auto policy takes the oracle: bit for bit.
    assert np.array_equal(selective_scan(*inputs), selective_scan_reference(*inputs))
