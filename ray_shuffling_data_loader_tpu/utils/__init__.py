"""Shared utilities: JAX process placement, wall-clock timing, path kinds."""

import os

from ray_shuffling_data_loader_tpu.utils.platform import (  # noqa: F401
    enable_compile_cache,
    spawn_environ,
)
from ray_shuffling_data_loader_tpu.utils.timing import timer  # noqa: F401


def decode_use_threads(num_concurrent_tasks: int) -> bool:
    """Should one Parquet decode task use Arrow's internal thread pool?

    Parallelism normally comes from the worker POOL (one decode task per
    file); per-task Arrow threads only help when the host has idle cores
    beyond the concurrently-decoding tasks — e.g. a ~120-core TPU-VM
    host decoding a 16-file dataset leaves >100 cores idle without them.
    On a saturated host they oversubscribe instead (measured 5x slower,
    see ``shuffle.read_parquet_columns``). Heuristic: engage when the
    host has at least twice as many cores as concurrent decode tasks.
    ``RSDL_DECODE_THREADS=on|off`` overrides.
    """
    env = os.environ.get("RSDL_DECODE_THREADS", "").lower()
    if env in ("on", "1", "true"):
        return True
    if env in ("off", "0", "false"):
        return False
    return (os.cpu_count() or 1) >= 2 * max(1, num_concurrent_tasks)


def arrow_decode_threads(stage_tasks: int) -> bool:
    """Worker-side decision + pool cap for one decode task.

    Called INSIDE the pool worker that is about to decode (so the core
    count consulted is the core count of the host actually doing the
    work — the driver that submitted the stage may have a different
    shape). ``stage_tasks`` is how many decode tasks the stage submitted
    cluster-wide; concurrency on THIS host can't exceed
    ``min(stage_tasks, local cores)``.

    When threads engage, Arrow's process-global thread pool is CAPPED to
    this task's fair share of the host (``cores // concurrent``) —
    Arrow's default pool is cpu_count-sized PER PROCESS, so N concurrent
    uncapped readers would run N x cores threads, re-creating the
    oversubscription the pool-parallel design avoids. A pool worker runs
    one task at a time, so setting the cap here is race-free.
    """
    cores = os.cpu_count() or 1
    concurrent = min(max(1, stage_tasks), cores)
    if not decode_use_threads(concurrent):
        return False
    try:
        import pyarrow as pa

        pa.set_cpu_count(max(2, cores // concurrent))
    except Exception:
        return False
    return True


def decode_rowgroup_threads(stage_tasks: int) -> int:
    """Row-group decode parallelism for ONE Parquet decode task — the
    ``RSDL_DECODE_ROWGROUPS`` gate plus the same fair-share logic as
    :func:`arrow_decode_threads`, returning a thread COUNT instead of
    arming Arrow's pool (the row-group plan owns its threads and reads
    each range with ``use_threads=False``, so the two parallelism
    sources never stack).

    * unset / ``off`` — 1 (single-shot decode; the zero-overhead
      default: no decode pool thread ever exists);
    * ``auto`` — the task's fair share of the host
      (``cores // concurrent``) when idle cores exist, else 1 — the
      exact condition :func:`decode_use_threads` applies to Arrow's
      pool, so ``auto`` can never oversubscribe a saturated host;
    * ``on`` — fair share, floored at 2 (engage even on a host with no
      idle cores — the operator asked);
    * an integer — that many threads, verbatim (CI forces ``2`` on the
      2-core host so the parallel assembly path is exercised).
    """
    env = os.environ.get("RSDL_DECODE_ROWGROUPS", "").strip().lower()
    if env in ("", "off", "0", "false"):
        return 1
    cores = os.cpu_count() or 1
    concurrent = min(max(1, stage_tasks), cores)
    fair = max(1, cores // concurrent)
    if env == "auto":
        return fair if cores >= 2 * concurrent else 1
    if env in ("on", "true"):
        return max(2, fair)
    try:
        return max(1, int(env))
    except ValueError:
        return fair if cores >= 2 * concurrent else 1


def shuffle_plan_spec():
    """The ONE parser of ``RSDL_SHUFFLE_PLAN`` — the seeded plan FAMILY
    every schedule partitions with (ISSUE 12): ``("rowwise", 0)`` or
    ``("block", G)``.

    * unset / ``rowwise`` — the per-row uniform assignment (every row
      draws its reducer independently). Maximal dispersion, but every
      row group holds rows for every reducer, so per-reducer row-group
      pruning can never engage (BENCHLOG r11's honest limit).
    * ``block`` / ``block:G`` — row-group-aligned blocks of ``G``
      consecutive row groups (default 1) are assigned to reducers by a
      seeded permutation; rows inside a block travel together and the
      reduce-side full permutation supplies within-reducer randomness
      (RINAS, PAPERS.md). Per-reducer selections become DISJOINT by
      construction, so the selective schedule decodes each group
      exactly once per epoch.

    A malformed value raises: the plan family determines the delivered
    stream, and silently falling back to a different family would be a
    reproducibility bug, not a tolerable default. Parsed driver-side
    before any task is submitted, so the raise is early and loud."""
    env = os.environ.get("RSDL_SHUFFLE_PLAN", "").strip().lower()
    if env in ("", "rowwise", "row", "off"):
        return ("rowwise", 0)
    if env == "block":
        return ("block", 1)
    if env.startswith("block:"):
        try:
            g = int(env.split(":", 1)[1])
        except ValueError:
            g = 0
        if g >= 1:
            return ("block", g)
    raise ValueError(
        f"RSDL_SHUFFLE_PLAN={env!r}: expected 'rowwise', 'block', or "
        "'block:<G>' with integer G >= 1 (row groups per block)"
    )


def shuffle_plan_label() -> str:
    """The plan family as a metric-label value (``rowwise`` or
    ``block:G``) — the vocabulary the ``{schedule,plan}``-labeled decode
    counters and the audit quality gauges share."""
    family, g = shuffle_plan_spec()
    return family if family == "rowwise" else f"block:{g}"


def is_remote_path(path: str) -> bool:
    """True for URI-style paths (gs://, s3://, ...) that route through a
    non-local filesystem — one definition, shared by Parquet decode and
    the fsspec stats writers."""
    return "://" in path


# Schemes pyarrow's native C++ filesystems resolve directly — preferred
# over fsspec (no extra python deps, zero-copy reads). Everything else
# with a scheme goes through fsspec (file://, memory://, http://, ...).
_PYARROW_NATIVE_SCHEMES = ("s3", "gs", "gcs", "hdfs", "viewfs")


def parquet_filesystem(path: str):
    """Resolve a dataset path to ``(filesystem, relative_path)`` for
    pyarrow readers (``pq.read_table(..., filesystem=fs)`` /
    ``pq.ParquetFile(..., filesystem=fs)``).

    Local paths return ``(None, path)`` (pyarrow mmap-reads them
    directly). The reference only ever reads local NVMe
    (``/root/reference/ray_shuffling_data_loader/shuffle.py:151`` via
    ``pd.read_parquet`` of plain paths); TPU-VM pods routinely read
    training data from object storage instead, so every Parquet input
    site here routes through this resolver.
    """
    if not is_remote_path(path):
        return None, path
    from pyarrow import fs as pafs

    scheme = path.split("://", 1)[0]
    if scheme in _PYARROW_NATIVE_SCHEMES:
        return pafs.FileSystem.from_uri(path)
    import fsspec

    fs, rel = fsspec.core.url_to_fs(path)
    return pafs.PyFileSystem(pafs.FSSpecHandler(fs)), rel


__all__ = [
    "arrow_decode_threads",
    "decode_rowgroup_threads",
    "decode_use_threads",
    "enable_compile_cache",
    "is_remote_path",
    "parquet_filesystem",
    "shuffle_plan_label",
    "shuffle_plan_spec",
    "spawn_environ",
    "timer",
]
