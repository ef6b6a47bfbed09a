"""Process-level JAX placement: where compiled programs are cached, and
which platform a spawned child may touch.

One process per chip. A TPU belongs to the first process that initializes
a JAX backend on it; a second one fails or hangs. The driver process owns
the chips, so every child the runtime spawns (pool workers, actors) gets
``JAX_PLATFORMS=cpu`` in its environment *before* it starts — see
:func:`spawn_environ`.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Iterator

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_environ_lock = threading.Lock()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no directory is set here. Otherwise the cache is
    ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because the
    path is part of what makes a later run find the entries. Call before
    the first ``jit``.

    Either way every program is kept, however quickly it compiled: under
    JAX's default floor of one second a program that compiles in about
    that time is written by some runs and not by others, so a run on a
    warm cache could still find something to compile."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def spawn_environ(env: Dict[str, str]) -> Iterator[None]:
    """``os.environ`` updated with ``env`` while children are started.

    ``multiprocessing``'s spawn hands the child a copy of the parent's
    environment as it stands at ``Process.start()``, and the child
    re-imports ``__main__`` before it runs its target: a variable applied
    from inside the target (``os.environ.update``) comes too late for any
    module ``__main__`` imports, ``jax`` included. The parent's own
    environment is restored on exit; starts are serialized so that two
    spawners cannot restore each other's values."""
    with _environ_lock:
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            yield
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
