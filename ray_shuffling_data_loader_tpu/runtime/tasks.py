"""Task execution: a process pool with futures and a ``wait`` primitive.

Replaces Ray's ``@ray.remote`` task layer that the reference uses for its
shuffle map/reduce stages (``shuffle.py:129,171``) and data generation
(``data_generation.py:30``). Tasks are plain importable functions; arguments
and results that are bulk data travel through the shared-memory
:mod:`.store` as :class:`~.store.ObjectRef` — the worker pool only moves
pickled control messages.

Workers are **spawned** (fresh interpreters): they never inherit JAX/TPU
state from the driver, so shuffle CPU work cannot corrupt the TPU client.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ray_shuffling_data_loader_tpu import telemetry
from ray_shuffling_data_loader_tpu._lazy import lazy_module
from ray_shuffling_data_loader_tpu.utils.platform import spawn_environ

# Fault-injection plane (ISSUE 14 gate-integrity): lazy proxy — the
# plane's module body runs only when a worker actually starts, never
# when this module is imported.
faults = lazy_module("ray_shuffling_data_loader_tpu.runtime.faults")


class TaskError(Exception):
    """A task raised; carries the remote traceback plus structured
    fields the recovery layer keys on: ``error_type`` (the remote
    exception class name) and ``lost_object_id`` (set when the task died
    on an :class:`~.store.ObjectLostError`, so the shuffle driver can
    re-materialize that exact object from lineage instead of guessing
    from traceback text)."""

    def __init__(
        self,
        message: str,
        error_type: Optional[str] = None,
        lost_object_id: Optional[str] = None,
    ):
        super().__init__(message)
        self.error_type = error_type
        self.lost_object_id = lost_object_id

    def __reduce__(self):
        # Crosses the actor wire (HostAgent.submit re-raises it to the
        # remote driver); the default reduce would drop the structured
        # fields.
        return (
            TaskError,
            (self.args[0] if self.args else "", self.error_type,
             self.lost_object_id),
        )


class TaskFuture:
    def __init__(self, task_id: int):
        self.task_id = task_id
        self._event = threading.Event()
        self._result = None
        self._error: Optional[str] = None
        self._waiters_lock = threading.Lock()
        self._waiters: List[threading.Event] = []

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(f"task {self.task_id} not done after {timeout}s")
        if self._error is not None:
            # Workers report structured {"tb", "type", "lost"} errors;
            # pool-level failures (worker died, pool shut down) remain
            # plain strings.
            if isinstance(self._error, dict):
                raise TaskError(
                    self._error.get("tb", ""),
                    error_type=self._error.get("type"),
                    lost_object_id=self._error.get("lost"),
                )
            raise TaskError(self._error)
        return self._result

    def _add_waiter(self, event: threading.Event) -> None:
        with self._waiters_lock:
            if self._event.is_set():
                event.set()
            else:
                self._waiters.append(event)

    def _remove_waiter(self, event: threading.Event) -> None:
        with self._waiters_lock:
            try:
                self._waiters.remove(event)
            except ValueError:
                pass

    def _fulfill(self, result, error):
        self._result = result
        self._error = error
        self._event.set()
        with self._waiters_lock:
            waiters, self._waiters = self._waiters, []
        for w in waiters:
            w.set()


def wait(
    futures: Sequence[TaskFuture],
    num_returns: int = 1,
    timeout: Optional[float] = None,
) -> Tuple[List[TaskFuture], List[TaskFuture]]:
    """``ray.wait`` analog: block until ``num_returns`` futures complete;
    return (done, pending) preserving submission order.

    Event-driven: completions notify a shared event, so waiting burns no
    CPU (futures without waiter support — e.g. bare concurrent futures —
    fall back to a coarse poll).
    """
    import time as _time

    deadline = None if timeout is None else _time.monotonic() + timeout
    notify = threading.Event()
    subscribed = []
    for f in futures:
        add = getattr(f, "_add_waiter", None)
        if add is not None:
            add(notify)
            subscribed.append(f)
    pollable = len(subscribed) < len(futures)
    try:
        while True:
            # Clear BEFORE checking: a completion racing this loop either
            # lands before the check (seen via done()) or after (re-sets
            # the event, so the next wait() returns immediately).
            notify.clear()
            done = [f for f in futures if f.done()]
            if len(done) >= num_returns:
                break
            if deadline is not None and _time.monotonic() > deadline:
                break
            remaining = (
                None if deadline is None else deadline - _time.monotonic()
            )
            if pollable:
                remaining = 0.01 if remaining is None else min(remaining, 0.01)
            if remaining is not None and remaining <= 0:
                continue
            notify.wait(remaining)
    finally:
        for f in subscribed:
            f._remove_waiter(notify)
    # One snapshot, done first: a future completing between two separate
    # scans would otherwise land in BOTH lists.
    done_set = {id(f) for f in futures if f.done()}
    done = [f for f in futures if id(f) in done_set]
    pending = [f for f in futures if id(f) not in done_set]
    return done, pending


def _record_task_done(fn, duration_s: float, trace_ctx) -> None:
    """Feed the straggler detector one completed-task record
    (ISSUE 7). Metrics-gated BEFORE the import so the disabled path
    never loads the stragglers module; never raises."""
    if not telemetry.metrics.enabled():
        return
    try:
        from ray_shuffling_data_loader_tpu.telemetry import stragglers

        ctx = trace_ctx or {}
        stragglers.record_task(
            getattr(fn, "__name__", "task"), duration_s,
            epoch=ctx.get("epoch"), job=ctx.get("job"),
        )
    except Exception:
        pass


def _outbound_ctx():
    """The submitter's trace context to pickle next to the task, or
    None with no facade touch when nothing can have produced one —
    context lives in telemetry.trace (never imported ⇒ empty) and the
    metrics half ships identity through the same path only when
    enabled. The service plane's job identity (ISSUE 15) also rides
    this context, but a job can only be ambient after the shuffle
    driver entered telemetry.context — which loads the trace module —
    so the sys.modules check below already covers it. Mirrors
    runtime/actor.py's _trace_ctx (ISSUE 14: the disabled submit path
    stays import-free)."""
    import sys as _sys

    if (
        _sys.modules.get("ray_shuffling_data_loader_tpu.telemetry.trace")
        is None
        and not telemetry.metrics.enabled()
    ):
        return None
    return telemetry.outbound_context()


_TRACE_MODULE = "ray_shuffling_data_loader_tpu.telemetry.trace"


def _active_trace():
    """``telemetry.trace`` while it records in this process, else None:
    looked up in ``sys.modules`` (never imported ⇒ never active), then
    one cached flag."""
    trace = sys.modules.get(_TRACE_MODULE)
    return trace if trace is not None and trace.active() else None


def _flush_telemetry_spools() -> None:
    """The task-done spool barrier: trace, audit, metrics registry,
    plus (metrics-gated, lazily imported) the event log and straggler
    task records. Trace/audit flush via ``sys.modules`` — a module
    never imported has nothing buffered, and touching the facade
    attribute instead would import it just to no-op (ISSUE 14: the
    disabled path stays import-free, not merely cheap)."""
    import sys as _sys

    for _name in ("trace", "audit", "profiler"):
        _mod = _sys.modules.get(
            f"ray_shuffling_data_loader_tpu.telemetry.{_name}"
        )
        if _mod is not None:
            _mod.safe_flush()
    if telemetry.metrics.enabled():
        telemetry.export.safe_flush()
        try:
            from ray_shuffling_data_loader_tpu.telemetry import (
                capacity,
                events,
                stragglers,
            )

            events.safe_flush()
            stragglers.safe_flush()
            capacity.safe_flush()
        except Exception:
            pass
    # Flush-then-SHIP (ISSUE 19): with the federation plane armed, wake
    # this host's relay shipper so a remote worker's records are durable
    # at the driver at the same task-done barrier local ones are.
    # Env-gated BEFORE the import — relay off stays import-free.
    _mode = os.environ.get("RSDL_RELAY", "").strip().lower()
    if _mode and _mode not in ("off", "0", "false"):
        try:
            from ray_shuffling_data_loader_tpu.telemetry import relay

            relay.kick()
        except Exception:
            pass


def _worker_main(task_q, result_q):
    import pickle

    pid = os.getpid()
    # Unconditional: the role tag is process IDENTITY — the telemetry
    # spools (events/metrics source records) stamp it, not just
    # /task-filtered fault rules — so it must be set even with the
    # fault plane unarmed. (One cheap stdlib import per worker, at
    # worker start, never at module import — the gate invariant.)
    faults.set_role("task")
    # Entrypoint-equivalent of telemetry.enabled(): a freshly spawned
    # worker can only have tracing on via env, and the flag read skips
    # importing the trace module when off (ISSUE 14: the disabled path
    # stays import-free at runtime, not just at import time).
    from ray_shuffling_data_loader_tpu.telemetry import _env

    trace_on = _env.read_flag("RSDL_TRACE")
    if trace_on:
        telemetry.set_process_name(f"task-worker-{pid}")
    instrumented = trace_on or telemetry.metrics.enabled()
    # The continuous profiler (ISSUE 17) samples THIS worker too — env-
    # gated before the import, same contract as the trace flag above.
    if _env.read_flag("RSDL_PROFILE"):
        try:
            from ray_shuffling_data_loader_tpu.telemetry import profiler

            profiler.start()
        except Exception:
            pass
    # Orphan self-destruct: if the pool owner dies without shutdown (e.g.
    # SIGKILL), exit rather than linger holding inherited pipes/fds.
    parent = os.getppid()

    def _watch_parent():
        import time

        while True:
            time.sleep(1.0)
            if os.getppid() != parent:
                os._exit(0)

    threading.Thread(target=_watch_parent, daemon=True).start()
    import time as _time

    while True:
        item = task_q.get()
        if item is None:
            break
        # Announce task start so the driver can attribute in-flight tasks
        # to this worker if it dies mid-task.
        task_id, blob = item
        result_q.put(("start", task_id, pid))
        try:
            # Blob carries the submitter's trace context; the span + the
            # re-entered context give every task a runtime-layer span and
            # make in-task spans inherit (trial, epoch, ...).
            fn, args, kwargs, trace_ctx = pickle.loads(blob)
            t0 = _time.perf_counter()
            if instrumented or trace_ctx is not None:
                with telemetry.propagated_span(
                    f"task:{getattr(fn, '__name__', 'task')}", trace_ctx
                ):
                    result = fn(*args, **kwargs)
            else:
                # Fully disabled: don't resolve the facade span (it
                # would import telemetry.trace just to no-op).
                result = fn(*args, **kwargs)
            _record_task_done(fn, _time.perf_counter() - t0, trace_ctx)
            # Flush BEFORE reporting done: by the time the caller can
            # observe the result, this task's spans, audit digest
            # records, event-log + task-duration records, AND
            # metrics-registry snapshot are on their spools (the
            # driver's reconciler, the cluster metrics aggregator, and
            # the straggler detector all rely on this ordering — all
            # futures resolved implies all worker-side records visible;
            # without the metrics flush, worker counters died with the
            # pool).
            _flush_telemetry_spools()
            result_q.put(("done", task_id, result, None))
        except Exception as exc:
            _flush_telemetry_spools()
            result_q.put(
                (
                    "done",
                    task_id,
                    None,
                    {
                        "tb": traceback.format_exc(),
                        "type": type(exc).__name__,
                        # ObjectLostError carries the id of the missing
                        # segment; the driver's lineage recovery needs it
                        # structured, not buried in traceback text.
                        "lost": getattr(exc, "object_id", None),
                    },
                )
            )


class WorkerPool:
    """Pool of spawned worker processes with a shared task queue.

    Sized at construction, but elastic (ISSUE 10): :meth:`add_workers`
    spawns more processes onto the shared queue mid-run, and
    :meth:`retire_workers` retires workers *gracefully* — a retiring
    worker finishes its current task, takes no more (the pill is just
    the next queue item it dequeues), and exits cleanly; the watchdog
    reaps clean exits without failing anyone's futures.
    """

    def __init__(self, num_workers: int, env: Optional[Dict[str, str]] = None):
        self.num_workers = num_workers
        self.width = num_workers  # scheduler-duck-typed capacity surface
        ctx = mp.get_context("spawn")
        self._mp_ctx = ctx
        self._task_q = ctx.Queue()
        self._result_q = ctx.Queue()
        # Workers are CPU-side shuffle executors and the driver owns the
        # chip: JAX_PLATFORMS=cpu, like the rest of ``env``, is in each
        # worker's environment from its first instruction.
        self._env = {**(env or {}), "JAX_PLATFORMS": "cpu"}
        self._procs_lock = threading.Lock()
        self._procs = self._start_workers(num_workers)
        self._futures: Dict[int, TaskFuture] = {}
        self._futures_lock = threading.Lock()
        self._running_on: Dict[int, int] = {}  # task_id -> worker pid
        self._task_names: Dict[int, str] = {}  # task_id -> fn name
        self._started: Dict[int, float] = {}  # task_id -> start monotonic
        # While tracing is active: task_id -> [submit wall s, submitter's
        # trace context and live span, start wall s], for the ``pool:<fn>``
        # span the collector records when the task is done.
        self._traced: Dict[int, list] = {}
        self._next_id = 0
        self._closed = False
        self._collector = threading.Thread(target=self._collect, daemon=True)
        self._collector.start()
        self._watchdog = threading.Thread(target=self._watch, daemon=True)
        self._watchdog.start()
        # Publish the live in-flight view to the straggler detector
        # (ISSUE 7): which task functions started when, on which worker
        # pid — the feed the wedged-worker flag needs. Metrics-gated
        # before the import, like every temporal-plane touchpoint.
        self._inflight_name = f"pool-{id(self)}"
        if telemetry.metrics.enabled():
            try:
                from ray_shuffling_data_loader_tpu.telemetry import (
                    stragglers,
                )

                stragglers.register_inflight_provider(
                    self._inflight_name, self.in_flight
                )
            except Exception:
                pass

    def _start_workers(self, n: int) -> list:
        procs = [
            self._mp_ctx.Process(
                target=_worker_main,
                args=(self._task_q, self._result_q),
                daemon=True,
            )
            for _ in range(n)
        ]
        with spawn_environ(self._env):
            for p in procs:
                p.start()
        return procs

    def _collect(self):
        while True:
            try:
                item = self._result_q.get()
            except (EOFError, OSError):
                break
            if item is None:
                break
            if item[0] == "start":
                _, task_id, pid = item
                with self._futures_lock:
                    self._running_on[task_id] = pid
                    self._started[task_id] = time.monotonic()
                    traced = self._traced.get(task_id)
                    if traced is not None:
                        traced[2] = time.time()
                continue
            _, task_id, result, error = item
            with self._futures_lock:
                fut = self._futures.pop(task_id, None)
                pid = self._running_on.pop(task_id, None)
                self._started.pop(task_id, None)
                name = self._task_names.pop(task_id, None)
                traced = self._traced.pop(task_id, None)
            if traced is not None:
                self._record_pool_span(name, pid, traced, error)
            if fut is not None:
                fut._fulfill(result, error)

    @staticmethod
    def _record_pool_span(name, pid, traced, error) -> None:
        """The driver-side span of one task, submit to done, with the
        time it waited for a worker (``wait_ns``: submit to the worker's
        start message). Recorded before the future resolves, so whoever
        awaited the task finds it in the buffer."""
        trace = _active_trace()
        if trace is None:
            return
        submit_s, args, start_s = traced
        done_s = time.time()
        if isinstance(error, dict):
            args["error"] = error.get("type") or "TaskError"
        elif error is not None:
            args["error"] = "WorkerLost"
        try:
            trace.record_span(
                f"pool:{name or 'task'}",
                submit_s,
                done_s - submit_s,
                cat="runtime",
                wait_ns=int(1e9 * max(0.0, (start_s or done_s) - submit_s)),
                pid=pid,
                **args,
            )
        except Exception:
            pass  # telemetry never fails a task

    def _watch(self):
        # Fail in-flight tasks whose worker died (e.g. OOM-killed) so
        # callers get a TaskError instead of hanging forever.
        import time as _time

        while not self._closed:
            _time.sleep(0.5)
            with self._procs_lock:
                procs = list(self._procs)
            # Reap gracefully-retired workers (clean exit after a retire
            # pill): membership shrinks without failing any futures.
            clean = [
                p for p in procs if not p.is_alive() and not p.exitcode
            ]
            if clean and not self._closed:
                with self._procs_lock:
                    for p in clean:
                        if p in self._procs:
                            p.join(timeout=0.1)
                            self._procs.remove(p)
                    self.num_workers = self.width = len(self._procs)
            dead = [
                p.pid for p in procs if not p.is_alive() and p.exitcode
            ]
            if not dead:
                continue
            with self._futures_lock:
                lost = [
                    (tid, pid)
                    for tid, pid in self._running_on.items()
                    if pid in dead
                ]
                futs = []
                for tid, pid in lost:
                    fut = self._futures.pop(tid, None)
                    self._running_on.pop(tid, None)
                    self._started.pop(tid, None)
                    name = self._task_names.pop(tid, None)
                    traced = self._traced.pop(tid, None)
                    if fut is not None:
                        futs.append((fut, pid, name, traced))
            for fut, pid, name, traced in futs:
                error = f"worker process {pid} died while running this task"
                if traced is not None:
                    self._record_pool_span(name, pid, traced, error)
                fut._fulfill(None, error)

    # -- elastic membership (ISSUE 10) ---------------------------------------

    def add_workers(self, n: int) -> int:
        """Spawn ``n`` more workers onto the shared task queue (the
        single-host scale-up actuator). Returns the new pool size."""
        if self._closed or n <= 0:
            return self.num_workers
        procs = self._start_workers(int(n))
        with self._procs_lock:
            self._procs.extend(procs)
            self.num_workers = self.width = len(self._procs)
            return self.num_workers

    def retire_workers(
        self, n: int, deadline_s: float = 10.0
    ) -> List[int]:
        """Gracefully retire ``n`` workers (never below one): each pill
        is consumed by SOME worker as its next queue item — it finishes
        its current task, drains nothing further, and exits cleanly.
        Pills queue behind already-submitted tasks, so retirement is
        drain-aware by construction: capacity drops only after the
        backlog ahead of the pill is done. Waits up to ``deadline_s``
        for the exits; stragglers are reaped later by the watchdog (a
        busy worker holding a long task is exactly who we must not
        kill). Returns the pids that exited within the deadline."""
        with self._procs_lock:
            before = {p.pid for p in self._procs}
            n = min(int(n), len(before) - 1)
        if self._closed or n <= 0:
            return []
        for _ in range(n):
            self._task_q.put(None)
        # Membership shrink is the truth, not who reaped: the watchdog's
        # clean-exit reaper races this loop, and a retiree it collects
        # first must still count toward n (pid-set difference), or the
        # call would spin out its whole deadline on a success.
        deadline = time.monotonic() + max(0.0, deadline_s)
        while True:
            with self._procs_lock:
                done = [
                    p
                    for p in self._procs
                    if not p.is_alive() and not p.exitcode
                ]
                for p in done:
                    p.join(timeout=0.1)
                    self._procs.remove(p)
                self.num_workers = self.width = len(self._procs)
                current = {p.pid for p in self._procs}
            retired = sorted(before - current)
            if len(retired) >= n or time.monotonic() >= deadline:
                return retired
            time.sleep(0.05)

    def in_flight(self) -> List[Dict[str, Any]]:
        """The live in-flight task view the straggler detector folds:
        one entry per started-but-unfinished task with its function
        name, worker pid, and age."""
        now = time.monotonic()
        with self._futures_lock:
            return [
                {
                    "stage": self._task_names.get(tid, "task"),
                    "pid": pid,
                    "age_s": now - self._started[tid],
                }
                for tid, pid in self._running_on.items()
                if tid in self._started
            ]

    def submit_local_to(self, refs, fn: Callable, *args, **kwargs):
        """Locality-aware submit surface shared with the cluster scheduler;
        a single-host pool has exactly one locality, so the hint is moot."""
        return self.submit(fn, *args, **kwargs)

    def submit(self, fn: Callable, *args, **kwargs) -> TaskFuture:
        import pickle

        if self._closed:
            raise RuntimeError("worker pool is shut down")
        # Pickle eagerly: mp.Queue pickles in a background feeder thread
        # where a PicklingError would be swallowed and the future never
        # fulfilled; raising here puts the error in the caller's lap.
        # The submitter's trace context rides along so the worker-side
        # span carries (trial, epoch, ...) without changing task args.
        blob = pickle.dumps(
            (fn, args, kwargs, _outbound_ctx())
        )
        trace = _active_trace()
        with self._futures_lock:
            task_id = self._next_id
            self._next_id += 1
            fut = TaskFuture(task_id)
            self._futures[task_id] = fut
            self._task_names[task_id] = getattr(fn, "__name__", "task")
            if trace is not None:
                self._traced[task_id] = [
                    time.time(), trace.caused_context(), None
                ]
        self._task_q.put((task_id, blob))
        return fut

    def shutdown(self):
        if self._closed:
            return
        self._closed = True
        # Unregister only if the module was ever loaded — shutdown on a
        # telemetry-off run must not import the temporal plane.
        import sys as _sys

        stragglers = _sys.modules.get(
            "ray_shuffling_data_loader_tpu.telemetry.stragglers"
        )
        if stragglers is not None:
            try:
                stragglers.unregister_inflight_provider(self._inflight_name)
            except Exception:
                pass
        with self._procs_lock:
            procs = list(self._procs)
        for _ in procs:
            try:
                self._task_q.put(None)
            except Exception:
                pass
        for p in procs:
            p.join(timeout=2)
            if p.is_alive():
                p.terminate()
        for p in procs:
            # SIGKILL stragglers: a worker that survives SIGTERM (e.g. one
            # wedged mid-syscall) would otherwise hang the interpreter's
            # multiprocessing atexit join forever.
            p.join(timeout=2)
            if p.is_alive():
                p.kill()
                p.join()
        try:
            self._result_q.put(None)
        except Exception:
            pass
        # Fail any outstanding futures so waiters don't hang forever.
        with self._futures_lock:
            for fut in self._futures.values():
                fut._fulfill(None, "worker pool shut down")
            self._futures.clear()
