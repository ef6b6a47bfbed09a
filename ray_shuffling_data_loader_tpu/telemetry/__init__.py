"""End-to-end tracing + live metrics for the shuffle/delivery pipeline.

Two halves, both env-gated off by default (zero overhead when disabled):

* :mod:`.trace` — ``trace_span()`` spans with per-process buffered
  recording, trace-context (trial/epoch/task) propagation through the
  runtime's task and actor layers, and a Chrome-trace/Perfetto exporter
  (:func:`trace_export`). Enable with ``RSDL_TRACE=1`` (+
  ``RSDL_TRACE_DIR=<spool>`` for cross-process collection) or
  :func:`enable` before ``runtime.init()``; the trainer's process also
  records while a JAX profiler session runs (:func:`active`), with its
  live spans in the profiler's own trace.
* :mod:`.metrics` — counters/gauges/histograms with cross-process
  sources, a sampled timeline, a JSON snapshot dump, a Prometheus
  text-format exporter (:func:`metrics.to_prometheus_text`), and a
  human-readable progress line. Sampled by
  ``stats.ObjectStoreStatsCollector`` and fed into ``TrialStatsCollector``
  so CSVs and live metrics share one source of truth.

A third half-sibling, :mod:`.audit` (``RSDL_AUDIT=1``), proves the *data*
rather than the time: exactly-once coverage digests across
map/reduce/delivery/consumption, per-epoch shuffle-quality metrics, and
deterministic delivered-stream digests. See docs/observability.md and
``tools/audit_report.py``.

The cluster-wide plane on top (ISSUE 4): :mod:`.export` spools every
process's registry snapshot (role/host/pid-stamped) to the runtime dir
and aggregates them with per-kind merge semantics, and
:mod:`.obs_server` (env-gated ``RSDL_OBS_PORT``; lazily imported by
``runtime.init()``) serves the aggregate live at ``/metrics`` plus
``/healthz`` and ``/status``. ``tools/epoch_report.py`` turns the trace
+ stats artifacts into per-epoch critical-path reports.

See docs/observability.md for the span/metric vocabulary and how to open
a trace in Perfetto. ``chipbench/run.py --trace 1`` traces a benchmark
run's whole window (program spans and the device's, on one clock).
"""

from ray_shuffling_data_loader_tpu.telemetry import metrics  # noqa: F401

# NOTE: every gated plane — trace, audit, export, obs_server (the
# /metrics //healthz //status endpoint), the temporal plane (events /
# timeseries / stragglers, ISSUE 7), and the decision plane (capacity /
# critical / slo, ISSUE 9) — is resolved LAZILY through the PEP 562
# ``__getattr__`` below (ISSUE 14's gate-integrity invariant, enforced
# by tools/rsdl_lint.py): importing this facade executes only the
# metrics gate. The runtime contract is two-tiered: the HEAVY planes
# (obs_server, temporal, decision, journal, elastic) are never imported
# at all while their gates are off (runtime.init gates obs_server on
# RSDL_OBS_PORT; emit_event below, the task-done flush in
# runtime/tasks.py, the store's ledger hook, and the sampler tick all
# check metrics.enabled() BEFORE importing), and the LIGHT stdlib-only
# modules (trace / audit / export / phases / faults) defer their import
# to the first instrumented use — disabled hot paths gate on
# sys.modules / env flags first (see runtime/tasks.py
# _flush_telemetry_spools and runtime/actor.py _trace_ctx), so a fully
# disabled run imports none of them on the dispatch/task-done paths;
# worker DATA paths (shuffle's _audit/_phases proxies) may still import
# a light module once per process, by design — one cheap import, then
# one cached boolean per site.

# Names re-exported from telemetry.trace, resolved on first touch and
# then cached in this module's globals (so the second access is a plain
# attribute lookup, same cost as the old eager import).
_TRACE_NAMES = frozenset(
    (
        "ENV_TRACE",
        "ENV_TRACE_DIR",
        "Span",
        "active",
        "clock_sync",
        "context",
        "current_context",
        "disable",
        "dropped_events",
        "enable",
        "enabled",
        "flush",
        "instant",
        "local_spans",
        "name_thread_track",
        "outbound_context",
        "propagated_span",
        "record_span",
        "refresh_active",
        "refresh_from_env",
        "reset_state",
        "safe_flush",
        "set_context",
        "set_process_name",
        "spool_dir",
        "trace_export",
        "trace_span",
    )
)

# Submodules legal to resolve as facade attributes (``telemetry.audit``
# etc.). After the first import the package attribute exists for real
# (the import system binds submodules onto the parent), so __getattr__
# is never consulted again for them.
_LAZY_SUBMODULES = frozenset(
    (
        "trace",
        "audit",
        "export",
        "events",
        "stragglers",
        "timeseries",
        "capacity",
        "critical",
        "slo",
        "obs_server",
        "phases",
        "profiler",
    )
)


def __getattr__(name):
    if name in _TRACE_NAMES:
        from ray_shuffling_data_loader_tpu.telemetry import trace

        value = getattr(trace, name)
        globals()[name] = value  # cache: next access skips __getattr__
        return value
    if name in _LAZY_SUBMODULES:
        import importlib

        return importlib.import_module(
            f"ray_shuffling_data_loader_tpu.telemetry.{name}"
        )
    if name in ("metrics_snapshot", "metrics_dump"):
        value = (
            metrics.global_snapshot
            if name == "metrics_snapshot"
            else metrics.dump_json
        )
        globals()[name] = value
        return value
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )


def emit_event(kind: str, _flush: bool = False, **fields) -> None:
    """Record one structured event (:mod:`.events`) — the lazy facade
    every wiring site calls: when ``RSDL_METRICS`` is off this is one
    cached boolean check and the events module is never imported.
    ``_flush=True`` drains the buffer to the spool right away — used at
    trial/epoch boundaries so a long-lived driver's lifecycle events
    are durable (and joinable by a post-hoc epoch report) without
    waiting for the buffer high-water mark or atexit. Never raises
    into the caller's data path."""
    if not metrics.enabled():
        return
    try:
        from ray_shuffling_data_loader_tpu.telemetry import events

        events.emit(kind, **fields)
        if _flush:
            events.safe_flush()
    except Exception:
        pass
