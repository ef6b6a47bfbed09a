"""What keeps the chip path honest without a chip: the Pallas kernels
lower for TPU, the compile cache goes where it is told, the chip-only
entry points refuse any other platform, and every process the runtime
spawns is pinned to the CPU before it imports anything.

The run on the chip itself is ``chip_smoke.py``.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from ray_shuffling_data_loader_tpu.ops import dot_interaction, flash_attention
from ray_shuffling_data_loader_tpu.utils import platform as rsdl_platform

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- kernels lower for TPU ----------------------------------------------------


def _lowers_for_tpu(fn, *args):
    """Lower ``fn`` for the TPU on this CPU host (no TPU client needed):
    Pallas checks its grid and block specs for Mosaic here, which is where
    a block shape the chip cannot tile is refused."""
    traced = jax.jit(fn).trace(*args)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


def test_interaction_kernel_lowers_for_tpu():
    x = jax.ShapeDtypeStruct((1000, 19, 32), jnp.bfloat16)  # ragged tail tile
    kernel = lambda x: dot_interaction(x, use_pallas=True)
    _lowers_for_tpu(kernel, x)
    # The backward is plain XLA; value_and_grad keeps the kernel's forward
    # in the program beside it.
    _lowers_for_tpu(
        jax.value_and_grad(lambda x: jnp.sum(kernel(x).astype(jnp.float32))), x
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "head_dim,seq,blocks", [(32, 300, (128, 128)), (128, 300, (128, 128)),
                            (64, 64, (32, 32)), (64, 64, (32, 16))],
)
def test_flash_kernels_lower_for_tpu(head_dim, seq, blocks, causal):
    """Forward, dK/dV and dQ kernels at a sequence length (300) that is
    not a multiple of the 128 block, and at the toy models' blocks of 32
    over 64 tokens, under a vreg's 128 lanes and shorter than the
    sequence."""
    q = jax.ShapeDtypeStruct((2, seq, 2, head_dim), jnp.bfloat16)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, use_pallas=True,
        block_q=blocks[0], block_k=blocks[1],
    )
    _lowers_for_tpu(attn, q, q, q)
    text = _lowers_for_tpu(
        jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)),
            (0, 1, 2),
        ),
        q, q, q,
    )
    # Forward (for the residuals), dK/dV and dQ: three Mosaic calls.
    assert text.count("tpu_custom_call") >= 3


def test_kernel_never_interprets_by_itself():
    """``interpret`` is an argument, not a guess from the backend: asked
    for the kernel on the CPU without it, the op raises rather than run
    in the interpreter or on the reference."""
    x = jnp.ones((8, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="Only interpret mode is supported"):
        jax.block_until_ready(dot_interaction(x, use_pallas=True))


# -- compile cache -----------------------------------------------------------


@pytest.fixture
def cache_config():
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    before = {name: getattr(jax.config, name) for name in names}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


def test_compile_cache_honours_the_environment(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert rsdl_platform.enable_compile_cache() == "/some/dir"
    # JAX reads the variable itself; the helper sets no directory.
    assert jax.config.jax_compilation_cache_dir == before
    # Either way no program is too quick to keep.
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_defaults_into_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert rsdl_platform.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # Fixed: a second call, or another process, names the same directory.
    assert rsdl_platform.enable_compile_cache() == want


# -- chip-only entry points -------------------------------------------------


def _run(script, cwd, **env):
    return subprocess.run(
        [sys.executable, script],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def _results(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_chip_entry_points_refuse_the_cpu(script, tmp_path):
    proc = _run(os.path.join(_REPO, script), str(tmp_path))
    assert proc.returncode != 0, proc.stdout
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]
    assert _results(proc.stdout) == [], proc.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds the script and nothing else of the repo
    there is no program to prove anything about."""
    script = tmp_path / "chip_smoke.py"
    script.write_text(open(os.path.join(_REPO, "chip_smoke.py")).read())
    proc = _run(str(script), str(tmp_path), PYTHONPATH="")
    assert proc.returncode != 0, proc.stdout
    assert "ModuleNotFoundError" in proc.stderr, proc.stderr[-2000:]
    assert _results(proc.stdout) == [], proc.stdout


# -- one process per chip ----------------------------------------------------

_SPAWNER = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, os.environ["RSDL_T_REPO"])
    # What this module saw when it was imported: in a spawned child that
    # is before the child's target runs, where `import jax` at the top of
    # a user's __main__ would read it.
    SEEN_AT_IMPORT = os.environ.get("JAX_PLATFORMS")

    def task():
        return SEEN_AT_IMPORT

    class Probe:
        def seen(self):
            return SEEN_AT_IMPORT

    if __name__ == "__main__":
        from ray_shuffling_data_loader_tpu import runtime

        assert SEEN_AT_IMPORT == "parent-value"
        ctx = runtime.init(num_workers=2)
        worker = ctx.pool.submit(task).result()
        grown = ctx.pool.add_workers(1)
        actor = runtime.spawn_actor(Probe).call("seen")
        runtime.shutdown()
        assert os.environ["JAX_PLATFORMS"] == "parent-value"
        print("SEEN", worker, actor, grown)
    """
)


def test_spawned_worker_and_actor_are_pinned_before_any_import(tmp_path):
    script = tmp_path / "spawner.py"
    script.write_text(_SPAWNER)
    proc = _run(
        str(script), str(tmp_path),
        JAX_PLATFORMS="parent-value", RSDL_T_REPO=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SEEN cpu cpu 3" in proc.stdout, proc.stdout
