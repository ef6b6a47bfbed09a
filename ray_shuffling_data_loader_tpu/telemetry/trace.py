"""Per-process buffered span recording with a Chrome-trace/Perfetto export.

The tracing half of the telemetry subsystem (ISSUE 1; the metrics half is
:mod:`.metrics`). Design constraints, in order:

* **Zero overhead when disabled.** Tracing is off unless ``RSDL_TRACE`` is
  truthy or a JAX profiler session runs in this process (:func:`active`);
  every instrumentation site goes through :func:`trace_span` /
  :func:`record_span`, which reduce to one cached boolean check and a
  shared no-op object when disabled. Nothing is allocated, no clock is
  read.
* **One clock with the profiler.** Under a session every live span also
  enters a ``jax.profiler.TraceAnnotation``, so it lands in the xplane's
  host plane on the thread that ran it; :func:`clock_sync` marks one
  instant on both clocks, and ``trace_export(xplane=...)`` shifts the
  xplane's device and host lines onto the wall clock of the buffers.
* **Per-process buffering, no collection daemon.** The pipeline spans four
  process kinds (driver, spawned task workers, actor processes, trainer
  ranks). Each process appends events to an in-memory buffer and drains it
  to its own ``trace-<pid>.jsonl`` file under the shared spool directory
  (``RSDL_TRACE_DIR`` — inherited through the environment by every spawned
  child, which is why :func:`enable` must run before ``runtime.init()``).
  :func:`trace_export` merges the spool into one Chrome-trace JSON that
  ``chrome://tracing`` / https://ui.perfetto.dev open directly.
* **Context propagation is explicit.** ``(trial, epoch, ...)`` trace
  context lives in a thread-local stack (:func:`context` /
  :func:`current_context`); the runtime's task and actor layers ship the
  caller's context across the process boundary (``runtime/tasks.py``
  pickles it next to the task, ``runtime/actor.py`` appends it to the call
  frame) and re-enter it around execution via :func:`propagated_span`, so
  a reducer's span on a pool worker carries the driver's trial id without
  any global registry.

Timestamps are wall-clock microseconds (``time.time()``), comparable
across processes on one host; durations come from ``perf_counter`` deltas.
:func:`local_spans` is what the loader folds into the per-layer counts of
``HostToDeviceStats.as_dict()["layers"]``.
"""

from __future__ import annotations

import atexit
import contextvars
import json
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from ray_shuffling_data_loader_tpu.telemetry import _env

ENV_TRACE = "RSDL_TRACE"
ENV_TRACE_DIR = "RSDL_TRACE_DIR"
ENV_TRACE_BUFFER = "RSDL_TRACE_BUFFER"

# Flush policy for root spans: drain the buffer to the spool file when it
# holds this many events or this much time has passed — frequent enough
# that short-lived work is exportable promptly, rare enough that hot actor
# dispatch loops do not pay a file append per call. (Task workers
# additionally flush after every task, before reporting it done, so a
# task's spans are always on disk by the time its caller can observe the
# result — see runtime/tasks.py.)
_FLUSH_EVENTS = 256
_FLUSH_INTERVAL_S = 1.0

_lock = threading.RLock()
_enabled: Optional[bool] = None  # tri-state: None = not yet read from env
# ``jax.profiler.TraceAnnotation`` while a profiler session was running at
# the last :func:`refresh_active`, else None: the session half of
# :func:`active`, and what live spans enter.
_annotation = None
_events: List[dict] = []
_dropped = 0
_last_flush = 0.0
_atexit_registered = False
_process_name: Optional[str] = None
_process_meta_emitted = False
_threads_named: set = set()
_base_ctx: Dict[str, Any] = {}
# Context rides in a contextvar, NOT a thread-local: actor dispatches
# interleave as asyncio tasks on one event-loop thread, and each task gets
# its own copy of the contextvars Context — so a dispatch blocked for
# minutes inside context(epoch=N) cannot leak epoch=N into the spans of
# dispatches interleaved on the same thread. Plain threads see their own
# (initially empty) context, matching the old thread-local semantics.
_ctx_stack_var: "contextvars.ContextVar[Tuple[Dict[str, Any], ...]]" = (
    # rsdl-lint: disable=vocabulary-drift -- contextvar debug name,
    # not a Prometheus alias; never appears on a scrape
    contextvars.ContextVar("rsdl_trace_ctx", default=())
)
# The live spans, innermost last: behind ``parent``. A contextvar for the
# same reason, and a span leaves it by identity, not by position, so an
# exit out of order (a span handed to another task) takes only itself.
_live_var: "contextvars.ContextVar[Tuple[Span, ...]]" = (
    # rsdl-lint: disable=vocabulary-drift -- contextvar debug name
    contextvars.ContextVar("rsdl_trace_live", default=())
)


def enabled() -> bool:
    """Is tracing on in this process? Cached after the first env read."""
    global _enabled
    if _enabled is None:
        _enabled = _env.read_flag(ENV_TRACE)
    return _enabled


def active() -> bool:
    """Is any span recorded in this process: ``RSDL_TRACE``, or a JAX
    profiler session seen at the last :func:`refresh_active`? What every
    site reads; it never looks for a session itself (and never imports
    ``jax``)."""
    return _annotation is not None or enabled()


def refresh_active() -> bool:
    """Look for a JAX profiler session and set the process's flag: called
    at the trainer's epoch boundaries and nowhere else, so the stager, the
    shuffle driver and the pool's collector only ever read one cached
    value. ``jax`` is looked up in ``sys.modules``, never imported. While a
    session runs every call also marks the two clocks (:func:`clock_sync`),
    so a session started between two epochs is tied at the next."""
    global _annotation
    profiler = sys.modules.get("jax.profiler")
    annotation = getattr(profiler, "TraceAnnotation", None)
    session = annotation is not None and bool(annotation.is_enabled())
    _annotation = annotation if session else None
    if session:
        _register_atexit()
        clock_sync()
    return active()


def clock_sync() -> None:
    """One mark on both clocks: a ``clock.sync`` annotation in the
    profiler's host plane (``start_ns`` on the session's clock) whose
    ``wall_ns`` is ``time.time_ns()`` at its entry, and the same
    ``wall_ns`` as an instant in this buffer. The difference of the two is
    what ``trace_export(xplane=...)`` shifts the xplane by."""
    annotation = _annotation
    if annotation is None:
        return
    wall_ns = time.time_ns()
    with annotation("clock.sync", wall_ns=wall_ns):
        pass
    _record(
        {
            "name": "clock.sync",
            "cat": "clock",
            "ph": "i",
            "s": "p",
            "ts": wall_ns / 1e3,
            "pid": os.getpid(),
            "tid": _tid(),
            "args": {"wall_ns": wall_ns},
        }
    )


def enable(spool_dir: Optional[str] = None) -> None:
    """Turn tracing on for this process AND (via the environment) every
    process spawned after this call — call before ``runtime.init()`` so
    pool workers and actors inherit it. ``spool_dir`` is where each
    process drains its event buffer; without one, events stay in this
    process's memory and the export covers only this process."""
    global _enabled
    os.environ[ENV_TRACE] = "1"
    if spool_dir:
        os.makedirs(spool_dir, exist_ok=True)
        os.environ[ENV_TRACE_DIR] = spool_dir
    _enabled = True
    _register_atexit()


def disable() -> None:
    global _enabled
    os.environ.pop(ENV_TRACE, None)
    _enabled = False


def refresh_from_env() -> None:
    """Forget the cached enabled state and buffer limit; the next check
    re-reads the env (test harness hook — fixtures restore the env then
    call this)."""
    global _enabled, _max_events_cached, _service_armed_cached, _annotation
    _enabled = None
    _annotation = None
    _max_events_cached = None
    _service_armed_cached = None


def spool_dir() -> Optional[str]:
    return os.environ.get(ENV_TRACE_DIR) or None


_max_events_cached: Optional[int] = None


def _max_events() -> int:
    # Cached like the enabled flag: _record() calls this per event while
    # holding the lock, and an env read + int parse per span is real cost
    # on hot paths (actor dispatch, per-batch staging).
    global _max_events_cached
    if _max_events_cached is None:
        try:
            _max_events_cached = int(
                os.environ.get(ENV_TRACE_BUFFER, "200000")
            )
        except ValueError:
            _max_events_cached = 200_000
    return _max_events_cached


def dropped_events() -> int:
    return _dropped


def set_process_name(name: str) -> None:
    """Label this process in the exported trace (Perfetto's track group
    name). Re-emitted with the next recorded event."""
    global _process_name, _process_meta_emitted
    _process_name = name
    _process_meta_emitted = False


def reset_state() -> None:
    """Drop all buffered events, names, and base context (tests only)."""
    global _dropped, _process_meta_emitted
    with _lock:
        _events.clear()
        _deferred.clear()
        _threads_named.clear()
        _dropped = 0
        _process_meta_emitted = False
        _base_ctx.clear()


# ---------------------------------------------------------------------------
# Trace context (thread-local stack + process-wide base)
# ---------------------------------------------------------------------------


def current_context() -> Dict[str, Any]:
    """The merged trace context visible here: process-wide base
    (:func:`set_context`) overlaid by the :func:`context` stack of the
    current thread / asyncio task."""
    out = dict(_base_ctx)
    for entry in _ctx_stack_var.get():
        out.update(entry)
    return out


def set_context(**kv: Any) -> None:
    """Set process-wide base context (e.g. ``trial=0`` once per run).
    Written under ``_lock`` (rare, boundary-time call); readers snapshot
    without it — a torn read across two keys is harmless context, not
    data."""
    with _lock:
        _base_ctx.update(kv)


_service_armed_cached: Optional[bool] = None


def _service_armed() -> bool:
    """Is the multi-job service plane armed (``RSDL_SERVICE``)? One
    cached env read — NOT an import of the service module: context
    propagation must stay import-free on its hot path."""
    global _service_armed_cached
    if _service_armed_cached is None:
        raw = os.environ.get("RSDL_SERVICE", "").strip().lower()
        _service_armed_cached = raw not in ("", "off", "0", "false", "no")
    return _service_armed_cached


def outbound_context() -> Optional[Dict[str, Any]]:
    """The context to ship with a cross-process call, or None when there
    is nothing to ship (both telemetry halves off, or the merged context
    is empty) — the ONE definition of what crosses task/actor/cluster
    boundaries. The METRICS half needs (trial, epoch) identity too —
    task-duration records, the event log, and the capacity ledger all
    attribute by epoch (ISSUE 7/9) — so context ships whenever either
    half is on; with both off this stays one cached boolean check.
    The service plane (ISSUE 15) ships it too even with telemetry off:
    worker-side audit digests attribute to a job only through this
    context, and a multi-job audit without job identity would fold
    every tenant into one verdict."""
    if not enabled():
        from ray_shuffling_data_loader_tpu.telemetry import (
            metrics as _metrics,
        )

        if not _metrics.enabled() and not _service_armed():
            return None
    return current_context() or None


@contextmanager
def context(**kv: Any):
    """Push context keys for the dynamic extent of the block. Spans opened
    inside (on this thread) merge these into their args; the task/actor
    layers forward them across process boundaries."""
    if not kv:
        yield
        return
    entry = dict(kv)
    token = _ctx_stack_var.set(_ctx_stack_var.get() + (entry,))
    try:
        yield
    finally:
        try:
            _ctx_stack_var.reset(token)
        except ValueError:
            # Token minted in a different Context (a generator migrated
            # across tasks); drop the entry by identity instead.
            _ctx_stack_var.set(
                tuple(e for e in _ctx_stack_var.get() if e is not entry)
            )


# ---------------------------------------------------------------------------
# Recording
# ---------------------------------------------------------------------------


def _tid() -> int:
    return threading.get_native_id()


def _ensure_meta_locked(tid: int) -> None:
    global _process_meta_emitted
    pid = os.getpid()
    if not _process_meta_emitted:
        _events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": _process_name or f"py-{pid}"},
            }
        )
        _process_meta_emitted = True
    if tid not in _threads_named:
        _threads_named.add(tid)
        _events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": threading.current_thread().name},
            }
        )


def _record(event: dict) -> None:
    global _dropped
    with _lock:
        if len(_events) >= _max_events():
            _dropped += 1
            return
        _ensure_meta_locked(event["tid"])
        _events.append(event)


def caused_context() -> Dict[str, Any]:
    """:func:`current_context` plus ``parent``, the name of the innermost
    live span of this thread / asyncio task: what a span recorded (or a
    task submitted) here and now carries about what caused it."""
    merged = current_context()
    live = _live_var.get()
    if live:
        merged["parent"] = live[-1].name
    return merged


def _annotation_args(args: Dict[str, Any]) -> Dict[str, Any]:
    """The span's scalar args, as the profiler's annotation takes them."""
    return {
        k: v for k, v in args.items() if isinstance(v, (str, int, float))
    }


def record_span(
    name: str,
    start_s: float,
    dur_s: float,
    cat: str = "rsdl",
    **args: Any,
) -> None:
    """Record a span retroactively from a wall-clock start and duration —
    for sites that already measured the interval (e.g. the consumer-stall
    accounting in ``jax_dataset``) and for spans that end on another
    thread than they began (``pool:<fn>``, ``stage:transfer``). ``parent``
    is the span that caused it: the caller's, else the live span of this
    thread. Retroactive spans cannot enter the profiler's trace; they stay
    in the buffer."""
    if not active():
        return
    merged = caused_context()
    merged.update(args)
    _record(
        {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": start_s * 1e6,
            "dur": max(0.0, dur_s) * 1e6,
            "pid": os.getpid(),
            "tid": _tid(),
            "args": merged,
        }
    )


# Spans whose arguments are still on a device: ``(name, start_s, cat,
# values, fold)``, resolved when the buffer is read.
_deferred: List[Tuple[str, float, str, Any, Any]] = []


def defer_span(name: str, start_s: float, values, fold, cat: str = "rsdl") -> None:
    """Record a zero-length span whose arguments ``fold(*numpy values)``
    come from ``values``, arrays that may still be on a device (the
    counters a compiled step returned): nothing waits for them here. They
    are fetched, and the span recorded, when the buffer is next read
    (:func:`local_spans`, and so :func:`trace_export`), never by a
    periodic flush. ``numpy.asarray`` does the fetching: this module never
    imports ``jax``."""
    if not active():
        return
    with _lock:
        if len(_deferred) + len(_events) < _max_events():
            _deferred.append((name, start_s, cat, list(values), fold))


def _resolve_deferred() -> None:
    import numpy as np

    with _lock:
        pending, _deferred[:] = list(_deferred), []
    for name, start_s, cat, values, fold in pending:
        record_span(
            name, start_s, 0.0, cat=cat, **fold(*map(np.asarray, values))
        )


def instant(name: str, cat: str = "rsdl", **args: Any) -> None:
    """Record an instant marker (a vertical tick on the timeline)."""
    if not active():
        return
    merged = current_context()
    merged.update(args)
    _record(
        {
            "name": name,
            "cat": cat,
            "ph": "i",
            "s": "t",
            "ts": time.time() * 1e6,
            "pid": os.getpid(),
            "tid": _tid(),
            "args": merged,
        }
    )


class Span:
    """A live span; use via ``with trace_span(...) as sp``. ``sp.set(k=v)``
    attaches attrs discovered mid-span. ``tid`` overrides the recorded
    thread id — for virtual tracks where slices on one real thread can
    overlap without nesting (asyncio-interleaved actor dispatches), which
    the Chrome-trace viewers cannot render on a single track."""

    __slots__ = ("name", "cat", "args", "_ts", "_t0", "_tid", "_ann")

    def __init__(self, name: str, cat: str, args: Dict[str, Any],
                 tid: Optional[int] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self._tid = tid

    def set(self, **kv: Any) -> None:
        self.args.update(kv)

    def __enter__(self) -> "Span":
        merged = caused_context()
        merged.update(self.args)
        self.args = merged
        _live_var.set(_live_var.get() + (self,))
        # Under a profiler session the span also lands in the xplane's
        # host plane, on this thread, beside the device's operations.
        annotation = _annotation
        if annotation is not None:
            self._ann = annotation(self.name, **_annotation_args(merged))
            self._ann.__enter__()
        else:
            self._ann = None
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _live_var.set(tuple(s for s in _live_var.get() if s is not self))
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        _record(
            {
                "name": self.name,
                "cat": self.cat,
                "ph": "X",
                "ts": self._ts * 1e6,
                "dur": dur * 1e6,
                "pid": os.getpid(),
                "tid": self._tid if self._tid is not None else _tid(),
                "args": self.args,
            }
        )
        # Flush on ANY close (rate-limited inside _maybe_flush), not only
        # at depth 0: an async actor serving interleaved dispatches —
        # e.g. the batch queue under the PR-3 supervised consumer, which
        # keeps a get_batch dispatch span open almost continuously — may
        # never reach depth 0 mid-run, and gating on quiescence starved
        # its spool flushes until process exit (trace_export would miss
        # every span since the last lull). Events are only appended at
        # span close, so flushing mid-stack is always safe.
        _maybe_flush()
        return False


class _NullSpan:
    """Shared no-op stand-in returned while tracing is disabled."""

    __slots__ = ()

    def set(self, **kv: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


def trace_span(name: str, cat: str = "rsdl", tid: Optional[int] = None,
               **args: Any):
    """Open a span covering the ``with`` block. When tracing is disabled
    this returns a shared no-op object — the disabled cost is one cached
    boolean check."""
    if not active():
        return _NULL
    _register_atexit()
    return Span(name, cat, args, tid=tid)


def name_thread_track(tid: int, name: str) -> None:
    """Label a (possibly virtual) thread track in the exported trace.
    First call per tid wins; later automatic naming is skipped."""
    if not active():
        return
    with _lock:
        if tid in _threads_named:
            return
        _threads_named.add(tid)
        _events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": os.getpid(),
                "tid": tid,
                "args": {"name": name},
            }
        )


@contextmanager
def propagated_span(name: str, ctx: Optional[Dict[str, Any]],
                    cat: str = "task", tid: Optional[int] = None):
    """Re-enter a remote caller's trace context and open a span — the
    receive side of cross-process propagation (task workers, actor
    dispatch). With tracing disabled no span opens, but a shipped
    context is still re-entered when present (the metrics half ships
    one for epoch attribution — see :func:`outbound_context`); with
    nothing shipped this is a no-op."""
    if not active():
        if ctx:
            with context(**ctx):
                yield
        else:
            yield
        return
    with context(**(ctx or {})):
        with trace_span(name, cat=cat, tid=tid):
            yield


# ---------------------------------------------------------------------------
# Flushing and export
# ---------------------------------------------------------------------------


def _register_atexit() -> None:
    global _atexit_registered
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(flush)


def flush() -> None:
    """Drain this process's buffer to its spool file. No-op without a
    spool directory (events then stay in memory for a local export)."""
    global _last_flush
    directory = spool_dir()
    if not directory:
        return
    with _lock:
        if not _events:
            return
        drained = list(_events)
        _events.clear()
        _last_flush = time.monotonic()
    try:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"trace-{os.getpid()}.jsonl")
        with open(path, "a") as f:
            for event in drained:
                f.write(json.dumps(event) + "\n")
    except OSError:
        # Telemetry must never sink the run; the drained events are lost.
        pass


def safe_flush() -> None:
    """Guarded flush for process-teardown paths (task done, actor exit):
    no-op when tracing is off, never raises — telemetry must not sink
    the exiting process."""
    if not enabled():
        return
    try:
        flush()
    except Exception:
        pass


def _maybe_flush() -> None:
    if spool_dir() is None:
        return
    with _lock:
        due = len(_events) >= _FLUSH_EVENTS or (
            _events
            and time.monotonic() - _last_flush > _FLUSH_INTERVAL_S
        )
    if due:
        flush()


def _read_spool(path: str) -> List[dict]:
    events: List[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue  # torn concurrent append; skip
    except OSError:
        pass
    return events


# Synthetic process ids of the xplane's planes in a merged export: above
# any pid the kernel hands out, so they cannot collide with a spool's.
_XPLANE_PID0 = 1 << 30
_XPLANE_DEVICE_LINES = ("XLA Modules", "XLA Ops")


def _mark_offset_ns(planes) -> Optional[int]:
    """``wall_ns - start_ns`` of the ``clock.sync`` marks: the largest,
    since ``wall_ns`` is read just before a mark is entered and whatever
    delays the entry (another thread holding the interpreter) only makes
    the difference smaller."""
    offsets = [
        int(wall_ns) - int(ev.start_ns)
        for _, lines in planes
        for _, line_events in lines
        for ev in line_events
        if ev.name == "clock.sync"
        for wall_ns in [dict(ev.stats).get("wall_ns")]
        if wall_ns is not None
    ]
    return max(offsets, default=None)


def _xplane_events(
    path: str, span_names: set, op_names: Dict[str, str]
) -> List[dict]:
    """The xplane's device programs and operations and its host
    annotations as Chrome-trace events on the wall clock: every event is
    shifted by ``wall_ns - start_ns`` of the file's ``clock.sync`` marks
    (:func:`clock_sync`). Of the host plane, the lines (threads) that
    hold a span named in ``span_names`` or a mark are kept, whole: the
    profiler's own runtime events on those threads come with them, the
    backend's thread pools do not. A device operation whose own name is in
    ``op_names`` (a train step's ``step:ops`` table) carries that
    ``op_name`` in its ``args``: the scope of ``fusion.661``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [
        (plane.name, [(line.name, list(line.events)) for line in plane.lines])
        for plane in data.planes
    ]
    offset_ns = _mark_offset_ns(planes)
    if offset_ns is None:
        raise ValueError(
            f"no clock.sync mark in {path}: refresh_active() saw no "
            "profiler session while it was recorded"
        )
    events: List[dict] = []
    for index, (plane_name, lines) in enumerate(planes):
        device = plane_name.startswith("/device:")
        if not device and not plane_name.startswith("/host:CPU"):
            continue
        pid = _XPLANE_PID0 + index
        named = False
        for tid, (line_name, line_events) in enumerate(lines):
            if not line_events:
                continue
            if device and line_name not in _XPLANE_DEVICE_LINES:
                continue
            if not device and not any(
                ev.name in span_names for ev in line_events
            ):
                continue
            if not named:
                named = True
                events.append(
                    {
                        "name": "process_name", "ph": "M", "pid": pid,
                        "tid": 0, "args": {"name": f"xplane {plane_name}"},
                    }
                )
            events.append(
                {
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": line_name},
                }
            )
            for ev in line_events:
                # An operation's name is its whole HLO instruction; keep
                # what stands before " = ".
                own = ev.name.split(" = ")[0].lstrip("%")
                op_name = op_names.get(own) if device else None
                events.append(
                    {
                        "name": own[:120],
                        "cat": "xplane",
                        "ph": "X",
                        "ts": (int(ev.start_ns) + offset_ns) / 1e3,
                        "dur": int(ev.duration_ns) / 1e3,
                        "pid": pid,
                        "tid": tid,
                        "args": {"op_name": op_name} if op_name else {},
                    }
                )
    return events


def trace_export(path: str, xplane: Optional[str] = None) -> str:
    """Merge this process's buffer and every spool file into ONE Chrome
    trace JSON at ``path`` (open with chrome://tracing or
    https://ui.perfetto.dev). With ``xplane`` (an ``.xplane.pb`` the JAX
    profiler wrote while :func:`refresh_active` saw its session), the
    device's ``XLA Modules`` / ``XLA Ops`` lines and the host plane's
    annotations go into the same file, shifted onto the wall clock by the
    ``clock.sync`` mark, and a device operation that a ``step:ops`` span of
    the buffer names carries its ``op_name``. Returns ``path``."""
    _resolve_deferred()
    flush()
    events: List[dict] = []
    directory = spool_dir()
    if directory and os.path.isdir(directory):
        for fname in sorted(os.listdir(directory)):
            if fname.startswith("trace-") and fname.endswith(".jsonl"):
                events.extend(_read_spool(os.path.join(directory, fname)))
    with _lock:
        events.extend(_events)  # no-spool mode: the local buffer
    if xplane:
        span_names = {e["name"] for e in events if e.get("ph") == "X"}
        op_names: Dict[str, str] = {}
        for e in events:
            if e["name"] == "step:ops" and e.get("ph") == "X":
                op_names.update(e["args"]["table"])
        events.extend(
            _xplane_events(xplane, span_names | {"clock.sync"}, op_names)
        )
    # Metadata first, then chronological — what the viewers expect.
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def local_spans() -> List[dict]:
    """Every complete span this process recorded: its buffer, and what it
    already drained to its own spool file (the ``RSDL_TRACE_DIR`` route)."""
    _resolve_deferred()
    events: List[dict] = []
    directory = spool_dir()
    if directory:
        events.extend(
            _read_spool(
                os.path.join(directory, f"trace-{os.getpid()}.jsonl")
            )
        )
    with _lock:
        events.extend(_events)
    return [e for e in events if e.get("ph") == "X"]
