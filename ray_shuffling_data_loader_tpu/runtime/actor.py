"""Actor runtime: named async endpoints in their own processes.

The TPU-native replacement for Ray's named-actor machinery that the reference
builds its delivery layer on: ``ray.remote(_QueueActor).options(name=...)``
(reference ``batch_queue.py:63-65``) and ``ray.get_actor(name)`` discovery
with exponential-backoff retry (``batch_queue.py:358-380``).

Model:

* ``spawn_actor(cls, *args, name=..)`` starts a **spawned** process hosting one
  instance of ``cls`` behind an asyncio socket server. ``async def`` methods
  run as event-loop tasks, so a blocked ``get`` never stalls a concurrent
  ``put`` — the same single-threaded-asyncio concurrency model as a Ray async
  actor (reference ``batch_queue.py:383-509``).
* Named actors register a JSON record (address + pid) in the session registry
  directory; ``connect_actor(name)`` resolves it with exponential backoff.
* Clients hold one blocking connection per calling thread. Fire-and-forget
  calls (``oneway=True``) get no reply — the analog of not ``ray.get``-ing a
  Ray call (reference ``batch_queue.py:94,108``).

The wire protocol is scheme-agnostic (unix socket on-host, TCP across hosts),
so the same actor code serves as the multi-host control plane over DCN.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import multiprocessing as mp
import os
import secrets
import signal
import sys
import threading
import time
import traceback
import weakref
from typing import Any, Dict, Optional

from ray_shuffling_data_loader_tpu import telemetry

from . import transport

# Fault-injection plane (ISSUE 14 gate-integrity): lazy proxy — never
# imported by merely importing the actor layer.
from ray_shuffling_data_loader_tpu._lazy import lazy_module
from ray_shuffling_data_loader_tpu.telemetry import _env
from ray_shuffling_data_loader_tpu.utils.platform import spawn_environ

faults = lazy_module("ray_shuffling_data_loader_tpu.runtime.faults")
from .retry import call_policy, connect_policy
from .transport import Address

logger = logging.getLogger(__name__)


# The caller's trace context to ship with a request frame, or None when
# tracing is off (the common case). A def, not a module-level
# ``telemetry.outbound_context`` binding: binding the facade attribute
# at import time would eagerly pull telemetry.trace into every process
# that imports the actor layer (gate-integrity, ISSUE 14). The
# sys.modules gate keeps the disabled path import-free at CALL time
# too: context can only be non-empty if something already imported
# trace (set_context/context/enable live there), and the metrics half
# ships identity through the same outbound path only when enabled.
def _trace_ctx():
    if (
        sys.modules.get("ray_shuffling_data_loader_tpu.telemetry.trace")
        is None
        and not telemetry.metrics.enabled()
    ):
        return None
    return telemetry.outbound_context()


def _flush_telemetry_spools(maybe: bool = False) -> None:
    """Actor-host spool barrier (quiescence + exit): flush trace only
    if its module is already loaded (never imported ⇒ nothing buffered
    ⇒ nothing to import just to no-op), export only when metrics are on
    (its spool is metrics-gated). Keeps the disabled path import-free
    at runtime, matching the structural gate (ISSUE 14)."""
    for _name in ("trace", "profiler"):
        mod = sys.modules.get(
            f"ray_shuffling_data_loader_tpu.telemetry.{_name}"
        )
        if mod is not None:
            mod.safe_flush()
    if telemetry.metrics.enabled():
        if maybe:
            telemetry.export.maybe_flush()
        else:
            telemetry.export.safe_flush()
    # Flush-then-SHIP (ISSUE 19): with the federation plane armed, wake
    # this host's relay shipper so the records just flushed reach the
    # driver at the same barrier. Env-gated BEFORE the import — relay
    # off means the module is never loaded here.
    _mode = os.environ.get("RSDL_RELAY", "").strip().lower()
    if _mode and _mode not in ("off", "0", "false"):
        try:
            from ray_shuffling_data_loader_tpu.telemetry import relay

            relay.kick()
        except Exception:
            pass


# Virtual thread ids for traced dispatches: concurrent dispatches all run
# on the one event-loop thread, so their spans can overlap WITHOUT
# nesting — which a single Chrome-trace thread track cannot render. Each
# in-flight traced dispatch borrows a virtual tid from a free list (ids
# are reused, keeping the track count = peak concurrency, not dispatch
# count).
_VTID_BASE = 1 << 20
_vtid_lock = threading.Lock()
_vtid_free: list = []
_vtid_high = 0


def _acquire_vtid() -> int:
    global _vtid_high
    with _vtid_lock:
        if _vtid_free:
            return _vtid_free.pop()
        _vtid_high += 1
        tid = _VTID_BASE + _vtid_high
    telemetry.name_thread_track(tid, f"dispatch-{tid - _VTID_BASE}")
    return tid


def _release_vtid(tid: int) -> None:
    with _vtid_lock:
        _vtid_free.append(tid)


class ActorDiedError(Exception):
    """Raised when calling an actor whose process has exited."""


class RemoteError(Exception):
    """An exception raised inside an actor method, re-raised at the caller.

    Picklable exceptions are re-raised directly (so callers can except
    concrete types); ``RemoteError`` is the fallback carrying the remote
    traceback text when the original instance could not cross the wire."""


def _registry_dir(runtime_dir: str) -> str:
    return os.path.join(runtime_dir, "actors")


def _registry_path(runtime_dir: str, name: str) -> str:
    return os.path.join(_registry_dir(runtime_dir), f"{name}.json")


# ---------------------------------------------------------------------------
# Server side
# ---------------------------------------------------------------------------


class _ActorHost:
    """Runs inside the actor process: serves method calls on an asyncio loop."""

    def __init__(self, instance, address: Address):
        self.instance = instance
        self.address = address
        self._shutdown = None  # asyncio.Event, created on the loop
        self._inflight = 0  # dispatches in flight (loop-thread only)
        # Per-connection reply locks: OutOfBand payloads are written by
        # an executor thread on the RAW socket (see _send_out_of_band),
        # so every reply on that connection must serialize against it —
        # and so must the connection CLOSE (writer.close() while an
        # executor send is mid-flight would free the fd under it; a
        # reused fd number would then receive another connection's
        # bytes). Weak-keyed: entries vanish with their writer, so a
        # dispatch outliving its connection can't leak a lock entry.
        self._write_locks: "weakref.WeakKeyDictionary" = (
            weakref.WeakKeyDictionary()
        )

    def _writer_lock(self, writer) -> asyncio.Lock:
        lock = self._write_locks.get(writer)
        if lock is None:
            lock = self._write_locks[writer] = asyncio.Lock()
        return lock

    async def _send_out_of_band(self, writer, req_id, oob) -> None:
        """Write a vectored reply with the bulk payload sent from an
        EXECUTOR thread straight on the raw socket (``sendmsg`` releases
        the GIL). The asyncio loop single-threads every transport write;
        with striped fetches (``RSDL_TCP_STREAMS``) serving N concurrent
        window stripes, that one thread was the measured server-side
        bottleneck — per-core sends are the point of striping. The
        per-connection lock plus a drained transport buffer guarantee
        the raw-socket bytes cannot interleave with loop-side writes."""
        sock = writer.get_extra_info("socket")
        if sock is None:
            transport.write_frame_vectored(
                writer, (req_id, "okv", oob.meta), oob.buffers
            )
            await writer.drain()
            return
        frames = transport.vectored_frames(
            (req_id, "okv", oob.meta), oob.buffers
        )
        tr = writer.transport
        # The transport buffer must be EMPTY (not merely below the high
        # water mark, which is all drain() guarantees) before raw-socket
        # bytes go out, or they would overtake loop-buffered ones. A
        # yield-first spin keeps the common case (already empty) free;
        # a stalled peer backs off to millisecond sleeps, a closed
        # transport aborts, and a half-open peer that simply stops
        # reading hits the same 120 s bound as the raw send path —
        # without it this loop would hold the connection's reply lock
        # forever.
        spins = 0
        deadline = time.monotonic() + 120.0
        while tr.get_write_buffer_size() > 0:
            if tr.is_closing():
                raise ConnectionError("connection closed mid-reply")
            if time.monotonic() > deadline:
                raise ConnectionError(
                    "peer stalled a buffered reply > 120s"
                )
            await asyncio.sleep(0 if spins < 16 else 0.001)
            spins += 1

        def _send():
            # asyncio hands out a TransportSocket, which has no send
            # methods: dup() is a real socket on the same connection.
            with sock.dup() as raw:
                transport.sendmsg_all(raw, frames)

        await asyncio.get_running_loop().run_in_executor(None, _send)

    async def _handle_client(self, reader, writer):
        try:
            while True:
                try:
                    frame = await transport.read_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                # Frames are 5-tuples, or 6 with the caller's trace context
                # appended (tracing enabled caller-side; see _trace_ctx).
                req_id, method, args, kwargs, oneway = frame[:5]
                trace_ctx = frame[5] if len(frame) > 5 else None
                # Dispatch as a task: requests on one connection must not
                # head-of-line-block each other (a blocked queue.get would
                # otherwise deadlock the producer's puts).
                asyncio.get_running_loop().create_task(
                    self._dispatch(
                        writer, req_id, method, args, kwargs, oneway,
                        trace_ctx,
                    )
                )
        finally:
            # Close UNDER the reply lock: an executor-thread OutOfBand
            # send still writing this fd must finish (or fail on its
            # own) before the fd is released for reuse.
            async with self._writer_lock(writer):
                try:
                    writer.close()
                except Exception:
                    pass

    async def _dispatch(self, writer, req_id, method, args, kwargs, oneway,
                        trace_ctx=None):
        self._inflight += 1
        try:
            if method == "__ping__":
                result = "pong"
            elif method == "__terminate__":
                result = None
                self._shutdown.set()
            else:
                if faults.enabled():
                    # Liveness faults: `kill` exits the process abruptly
                    # (no teardown — supervision must cope with SIGKILL
                    # semantics); `wedge` blocks the EVENT LOOP (a
                    # time.sleep on the loop thread), so the actor stops
                    # answering pings — the alive-but-unresponsive case.
                    faults.fire(f"actor.{type(self.instance).__name__}")
                # With a propagated trace context, re-enter it and span
                # the whole dispatch, awaits included — for the queue
                # actor that IS the interesting number (e.g. how long
                # new_epoch blocked on the admission window). Dispatches
                # interleave on this one event-loop thread, but each runs
                # as its own asyncio task with its own contextvars
                # Context, so a context held across an await cannot leak
                # into other dispatches' spans; the virtual tid gives
                # each concurrent dispatch its own renderable track (see
                # _acquire_vtid).
                fn = getattr(self.instance, method)
                vtid = _acquire_vtid() if trace_ctx is not None else None
                try:
                    with telemetry.propagated_span(
                        f"actor:{method}", trace_ctx, cat="actor", tid=vtid
                    ) if vtid is not None else contextlib.nullcontext():
                        result = fn(*args, **kwargs)
                        if asyncio.iscoroutine(result):
                            result = await result
                finally:
                    if vtid is not None:
                        _release_vtid(vtid)
            if not oneway:
                if isinstance(result, transport.OutOfBand):
                    # Zero-copy reply: meta in the pickle header, bulk
                    # payload streamed verbatim after it (StoreServer
                    # fetch_vec path) by an executor thread — concurrent
                    # stripe replies ride different cores. The sync
                    # caller reads it with call_vectored/recv_frame.
                    try:
                        async with self._writer_lock(writer):
                            await self._send_out_of_band(
                                writer, req_id, result
                            )
                    except Exception:
                        # The vectored frame may have PARTIALLY hit the
                        # wire: the connection's framing is gone, and an
                        # err reply on it would be consumed as payload
                        # bytes by a blocked reader. Tear the connection
                        # down so the client fails into its
                        # ActorDiedError ladder instead of hanging.
                        # The client sees only "connection closed": the
                        # cause is logged here or it is lost.
                        logger.exception(
                            "out-of-band reply to %s failed; closing the "
                            "connection", method,
                        )
                        try:
                            writer.close()
                        except Exception:
                            pass
                        return
                    result = None  # release buffer keepalives promptly
                else:
                    async with self._writer_lock(writer):
                        transport.write_frame(writer, (req_id, "ok", result))
                        await writer.drain()
        except Exception as exc:  # noqa: BLE001 — propagate to caller
            if not oneway:
                tb = traceback.format_exc()
                try:
                    async with self._writer_lock(writer):
                        transport.write_frame(writer, (req_id, "err", (exc, tb)))
                        await writer.drain()
                except Exception:
                    # The exception itself didn't pickle; the caller still
                    # needs a reply frame or it blocks forever. Send just
                    # the traceback text.
                    try:
                        async with self._writer_lock(writer):
                            transport.write_frame(
                                writer, (req_id, "err", (None, tb))
                            )
                            await writer.drain()
                    except Exception:
                        pass
        finally:
            # Quiescence flush: when the last in-flight dispatch ends,
            # drain buffered spans to the spool. Async actors can run for
            # whole epochs without a depth-0 moment on the loop thread,
            # so relying on the span-close heuristic alone leaves their
            # spans invisible to a concurrent trace_export until process
            # exit. Event-driven and cheap: no-ops when telemetry is off
            # or the buffer is empty. The metrics-registry snapshot
            # spools on the same trigger (rate-limited inside
            # maybe_flush) so this actor's counters/gauges stay visible
            # to the driver's live aggregation mid-run.
            self._inflight -= 1
            if self._inflight == 0:
                _flush_telemetry_spools(maybe=True)

    async def start(self):
        """Bind the server socket; returns once the actor is reachable.
        TCP with port 0 binds an OS-chosen port and rewrites ``address`` —
        the child owns port selection, so there is no bind-race with other
        spawners."""
        self._shutdown = asyncio.Event()
        self._server = await transport.start_server(
            self.address, self._handle_client
        )
        if self.address[0] == "tcp" and self.address[2] == 0:
            port = self._server.sockets[0].getsockname()[1]
            self.address = ("tcp", self.address[1], port)
        setup = getattr(self.instance, "setup", None)
        if setup is not None:
            result = setup()
            if asyncio.iscoroutine(result):
                await result

    async def wait_shutdown(self):
        async with self._server:
            await self._shutdown.wait()
        # Graceful resource teardown before process exit (e.g. the cluster
        # HostAgent reaping its worker pool — a SIGKILLed agent would orphan
        # the pool, and orphans holding the spawner's resource-tracker pipe
        # hang that process's interpreter exit).
        teardown = getattr(self.instance, "teardown", None)
        if teardown is not None:
            result = teardown()
            if asyncio.iscoroutine(result):
                await result


def _actor_main(
    cls, args, kwargs, address: Address, registry_path, ready_q,
    watch_parent: Optional[int] = None,
):
    # Child process entrypoint (spawned: fresh interpreter, no inherited
    # TPU/JAX state).
    if watch_parent is not None:
        # Daemonic children die with a cleanly-exiting parent but NOT with
        # a SIGKILLed one (preemption), and non-daemon actors (those that
        # spawn their own children, e.g. the HostAgent's worker pool)
        # never do; poll the parent pid and exit when orphaned.
        def _watch():
            while True:
                time.sleep(1.0)
                if not _pid_alive(watch_parent):
                    os._exit(0)

        threading.Thread(target=_watch, daemon=True).start()
    # Unconditional: the role tag is process IDENTITY (telemetry spool
    # source records stamp it), not just /actor-filtered fault rules.
    faults.set_role("actor")
    # The continuous profiler (ISSUE 17) samples this host too — env-
    # gated before the import, same contract as the trace flag below.
    if _env.read_flag("RSDL_PROFILE"):
        try:
            from ray_shuffling_data_loader_tpu.telemetry import profiler

            profiler.start()
        except Exception:
            pass
    if _env.read_flag("RSDL_TRACE"):
        # Entrypoint-equivalent of telemetry.enabled(): a freshly
        # spawned process can only have been enabled via env, and the
        # flag read skips importing the trace module when off.
        telemetry.set_process_name(f"actor:{cls.__name__}-{os.getpid()}")
    try:
        instance = cls(*args, **kwargs)
        host = _ActorHost(instance, address)
    except Exception:
        ready_q.put(("err", traceback.format_exc()))
        return

    async def run():
        # Bind strictly before announcing readiness: callers may issue a
        # method call the moment spawn_actor returns.
        await host.start()
        if registry_path is not None:
            tmp = registry_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(
                    {"address": list(host.address), "pid": os.getpid()}, f
                )
            os.replace(tmp, registry_path)
        # The bound address travels back (it differs from the requested one
        # for tcp port 0).
        ready_q.put(("ok", list(host.address)))
        await host.wait_shutdown()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    finally:
        # Graceful terminate reaches here; drain this actor's spans and
        # final metrics snapshot to their spools before the process
        # exits (atexit also fires on clean exits, but not on the
        # SIGKILL escalation path).
        _flush_telemetry_spools()
        if registry_path is not None:
            try:
                os.unlink(registry_path)
            except FileNotFoundError:
                pass
        if address[0] == "unix":
            try:
                os.unlink(address[1])
            except FileNotFoundError:
                pass


# ---------------------------------------------------------------------------
# Client side
# ---------------------------------------------------------------------------


class ActorHandle:
    """Client-side proxy. ``handle.call("method", ...)`` blocks for the
    result; ``call_oneway`` is fire-and-forget; ``call_async`` awaits on an
    asyncio loop."""

    def __init__(self, address: Address, pid: Optional[int] = None, name=None):
        self.address = tuple(address)
        self.pid = pid
        self.name = name
        self._local = threading.local()
        self._async_clients: Dict[Any, "_AsyncActorClient"] = {}
        self._req_counter = 0
        self._counter_lock = threading.Lock()

    # pickling: handles travel inside task args across processes
    def __getstate__(self):
        return {"address": self.address, "pid": self.pid, "name": self.name}

    def __setstate__(self, state):
        self.__init__(state["address"], state["pid"], state["name"])

    def _conn(self) -> transport.Connection:
        conn = getattr(self._local, "conn", None)
        if conn is None:
            try:
                conn = transport.Connection(self.address)
            except (ConnectionError, FileNotFoundError, OSError) as e:
                raise ActorDiedError(
                    f"cannot connect to actor {self.name or self.address}: {e}"
                ) from e
            self._local.conn = conn
        return conn

    def _next_id(self) -> int:
        with self._counter_lock:
            self._req_counter += 1
            return self._req_counter

    def _send_with_retry(self, req_id, method, args, kwargs, oneway):
        """Connect + send one request frame, retrying transient
        connection failures with bounded backoff (``call_policy``).

        Only the PRE-response window retries: a connect refusal or a
        send-time reset means the request never dispatched (a partial
        frame is dropped by the server's framing loop without
        executing), so a retry cannot double-execute. Failures after the
        frame is fully sent — recv errors — are ambiguous (the method
        may have run) and are NOT retried here; those stay
        ``ActorDiedError`` for callers' existing death handling."""
        policy = call_policy()
        last: Optional[Exception] = None
        for attempt, handle in policy.attempts(site="actor.send"):
            try:
                conn = self._conn()
                conn.send(
                    (req_id, method, args, kwargs, oneway, _trace_ctx())
                )
                return conn
            except (ActorDiedError, ConnectionError, OSError) as e:
                self._local.conn = None
                last = e
                if attempt >= policy.max_attempts:
                    break
                handle.backoff(str(e))
        raise ActorDiedError(
            f"cannot reach actor {self.name or self.address} "
            f"after {policy.max_attempts} attempts: {last}"
        ) from last

    def call(self, method: str, *args, **kwargs):
        # One response tail for plain AND vectored calls (a vectored
        # reply to a plain call is consumed into a throwaway buffer —
        # methods that return OutOfBand are only ever invoked through
        # call_vectored, which hands the payload back). ``into`` is a
        # RESERVED kwarg name on this client (the vectored allocator);
        # passing explicit into=None here makes a remote-method kwarg
        # named ``into`` fail loudly (duplicate keyword) instead of
        # being silently consumed as the allocator.
        return self.call_vectored(method, *args, into=None, **kwargs)[0]

    def call_oneway(self, method: str, *args, **kwargs) -> None:
        self._send_with_retry(
            self._next_id(), method, args, kwargs, True
        )

    def call_vectored(self, method: str, *args, into=None, **kwargs):
        """Call a method whose reply may be a :class:`transport.OutOfBand`
        vectored frame. Returns ``(meta, payload_view)``; the payload is
        landed via ``recv_into`` in the buffer ``into(total_bytes)``
        returns (the zero-copy fetch path mmaps the destination cache
        file), or ``(result, None)`` when the method replied plainly.

        An allocator with a truthy ``wants_meta`` attribute is called
        ``into(total_bytes, reply_meta)`` — the striped fetch needs the
        reply's stripe range before it can hand out the destination
        window (see :meth:`transport.Connection.recv_frame`)."""
        req_id = self._next_id()
        if into is not None and getattr(into, "wants_meta", False):
            user_into = into

            def _shim(total, frame):
                # frame is the raw (req_id, status, meta) reply tuple at
                # the transport layer; hand the caller just the meta.
                return user_into(total, frame[2])

            _shim.wants_meta = True
            into = _shim
        conn = self._send_with_retry(req_id, method, args, kwargs, False)
        try:
            while True:
                frame, payload = conn.recv_frame(into=into)
                resp_id, status, meta = frame
                if resp_id == req_id:
                    break
        except (ConnectionError, OSError) as e:
            self._local.conn = None
            raise ActorDiedError(
                f"actor {self.name or self.address} died mid-call: {e}"
            ) from e
        if status == "okv":
            return meta, payload
        if status == "ok":
            return meta, None
        exc, tb = meta
        if isinstance(exc, Exception):
            raise exc
        raise RemoteError(f"remote call {method} failed:\n{tb}")

    async def call_async(self, method: str, *args, **kwargs):
        loop = asyncio.get_running_loop()
        client = self._async_clients.get(loop)
        if client is None or client.closed:
            client = _AsyncActorClient(self.address)
            await client.connect()
            self._async_clients[loop] = client
        return await client.call(method, *args, **kwargs)

    def call_with_timeout(self, method: str, *args, timeout: float = 30.0,
                          **kwargs):
        """One-shot call on a dedicated timed connection.

        The per-thread connection deliberately has no socket timeout
        (streaming gets block indefinitely by design); control-plane calls
        that must not wedge on a half-dead host — placement, remote spawn —
        use this instead. Raises :class:`ActorDiedError` on timeout or
        connection failure, so callers' existing died-actor fallbacks fire.
        """
        try:
            conn = transport.Connection(self.address, timeout=timeout)
        except (ConnectionError, FileNotFoundError, OSError) as e:
            raise ActorDiedError(
                f"actor {self.name or self.address} unreachable: {e}"
            ) from e
        try:
            conn.send((0, method, args, kwargs, False, _trace_ctx()))
            while True:
                resp_id, status, payload = conn.recv()
                if resp_id == 0:
                    break
        except (ConnectionError, OSError) as e:
            raise ActorDiedError(
                f"actor {self.name or self.address} did not answer "
                f"{method} within {timeout}s: {e}"
            ) from e
        finally:
            conn.close()
        if status == "ok":
            return payload
        exc, tb = payload
        if isinstance(exc, Exception):
            raise exc
        raise RemoteError(f"remote call {method} failed:\n{tb}")

    def ping(self, timeout: float = None) -> bool:
        # A dedicated short-lived connection with a socket timeout: the
        # regular per-thread connection has no timeout, and a wedged (alive
        # but non-responsive) actor must not hang wait_ready's deadline.
        try:
            conn = transport.Connection(self.address, timeout=timeout)
        except (ConnectionError, FileNotFoundError, OSError):
            return False
        try:
            conn.send((0, "__ping__", (), {}, False))
            _, status, payload = conn.recv()
            return status == "ok" and payload == "pong"
        except Exception:
            return False
        finally:
            conn.close()

    def wait_ready(self, timeout: float = 30.0) -> None:
        """Block until the actor answers a ping (reference
        ``BatchQueue.ready``, ``batch_queue.py:67-71``)."""
        deadline = time.monotonic() + timeout
        delay = 0.005
        while True:
            if self.ping(timeout=min(2.0, timeout)):
                return
            if time.monotonic() > deadline:
                raise ActorDiedError(
                    f"actor {self.name or self.address} not ready "
                    f"after {timeout}s"
                )
            time.sleep(delay)
            delay = min(delay * 2, 0.25)

    def terminate(self, force: bool = False, grace_period_s: float = 5.0):
        """Graceful-then-forceful shutdown (reference
        ``BatchQueue.shutdown``, ``batch_queue.py:333-355``)."""
        if not force:
            try:
                self.call("__terminate__")
            except (ActorDiedError, RemoteError, ConnectionError):
                pass
            deadline = time.monotonic() + grace_period_s
            while time.monotonic() < deadline:
                if self.pid is None or not _pid_alive(self.pid):
                    return
                time.sleep(0.02)
        if self.pid is not None and _pid_alive(self.pid):
            try:
                os.kill(self.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


class _AsyncActorClient:
    """Asyncio client with request/response demultiplexing."""

    def __init__(self, address: Address):
        self.address = address
        self._pending: Dict[int, asyncio.Future] = {}
        self._req = 0
        self.closed = False
        self._reader = self._writer = self._reader_task = None

    async def connect(self):
        self._reader, self._writer = await transport.open_connection(
            self.address
        )
        self._reader_task = asyncio.get_running_loop().create_task(
            self._read_loop()
        )

    async def _read_loop(self):
        try:
            while True:
                resp_id, status, payload = await transport.read_frame(
                    self._reader
                )
                fut = self._pending.pop(resp_id, None)
                if fut is None or fut.done():
                    continue
                if status == "ok":
                    fut.set_result(payload)
                else:
                    exc, tb = payload
                    fut.set_exception(
                        exc
                        if isinstance(exc, Exception)
                        else RemoteError(f"remote failure:\n{tb}")
                    )
        except (asyncio.IncompleteReadError, ConnectionError, OSError) as e:
            self.closed = True
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(ActorDiedError(f"actor died: {e}"))
            self._pending.clear()

    async def call(self, method, *args, **kwargs):
        self._req += 1
        req_id = self._req
        fut = asyncio.get_running_loop().create_future()
        self._pending[req_id] = fut
        transport.write_frame(
            self._writer, (req_id, method, args, kwargs, False, _trace_ctx())
        )
        await self._writer.drain()
        return await fut


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


# ---------------------------------------------------------------------------
# Spawning and discovery
# ---------------------------------------------------------------------------


def spawn_actor(
    cls,
    *args,
    name: Optional[str] = None,
    runtime_dir: str,
    host: Optional[str] = None,
    port: int = 0,
    daemon: bool = True,
    **kwargs,
) -> ActorHandle:
    """Start an actor process and return a connected handle.

    With ``host`` set, the actor listens on TCP (multi-host control plane);
    otherwise on a unix socket under ``runtime_dir``. ``daemon=False`` is
    for actors that must spawn child processes themselves (multiprocessing
    forbids daemonic parents); they get a parent-death watchdog instead.
    """
    os.makedirs(_registry_dir(runtime_dir), exist_ok=True)
    token = secrets.token_hex(4)
    if host is not None:
        # port 0: the child binds an OS-chosen port and reports it back.
        address: Address = ("tcp", host, port)
    else:
        address = ("unix", os.path.join(runtime_dir, f"a-{token}.sock"))
    registry_path = (
        _registry_path(runtime_dir, name) if name is not None else None
    )
    if registry_path is not None and os.path.exists(registry_path):
        # A SIGKILLed actor never unlinks its record; a live holder is a
        # real conflict, a dead one is evicted and the name reclaimed
        # (same policy as the cluster registry's register_named_actor).
        # Liveness is judged by the record's PID first — local records
        # always carry one, and a pid probe cannot false-negative on a
        # loaded host the way a short ping can (evicting a live-but-busy
        # actor would spawn a same-name duplicate: split-brain). Only a
        # pid-less record falls back to pings, escalating like the
        # cluster scheduler's ladder before concluding death.
        stale = resolve_actor(name, runtime_dir)
        holder_alive = False
        if stale is not None:
            if stale.pid is not None:
                holder_alive = _pid_alive(stale.pid)
            else:
                holder_alive = any(
                    stale.ping(timeout=t) for t in (2.0, 5.0, 10.0)
                )
        if holder_alive:
            raise ValueError(f"actor name {name!r} already registered")
        try:
            # rsdl-lint: disable=barrier-order -- evicting a DEAD
            # foreign actor's stale record, not self-deregistration:
            # the dead holder's spools were flushed (or lost) with it,
            # this process has nothing to flush on its behalf
            os.unlink(registry_path)
        except FileNotFoundError:
            pass

    ctx = mp.get_context("spawn")
    ready_q = ctx.Queue()
    proc = ctx.Process(
        target=_actor_main,
        args=(
            cls, args, kwargs, address, registry_path, ready_q,
            os.getpid(),
        ),
        daemon=daemon,
    )
    # Actors are host-side services; the process that spawns them owns
    # the chip, so none may initialize a TPU backend.
    with spawn_environ({"JAX_PLATFORMS": "cpu"}):
        proc.start()
    # Readiness handshake with two escapes beyond the mp.Queue message:
    # (a) the registry file the child atomically writes just before its
    #     ready_q.put — observed once (2026-07-31): the child was up and
    #     serving while the queue's feeder thread wedged on a futex, so
    #     the message never arrived and the old loop polled forever;
    # (b) an overall deadline (generous: the actor ctor runs before
    #     readiness and a first-touch jax init can legitimately take
    #     minutes) that kills the child and fails cleanly instead of
    #     wedging the spawner.
    ready_timeout = float(
        os.environ.get("RSDL_SPAWN_READY_TIMEOUT_S", "600")
    )
    deadline = time.monotonic() + ready_timeout
    status = payload = None
    while True:
        try:
            status, payload = ready_q.get(timeout=0.2)
            break
        except Exception:  # queue.Empty
            if not proc.is_alive():
                raise RuntimeError(
                    f"actor {cls.__name__} process exited during startup "
                    f"(exitcode={proc.exitcode})"
                ) from None
            if registry_path is not None and os.path.exists(registry_path):
                try:
                    with open(registry_path) as f:
                        record = json.load(f)
                    status, payload = "ok", record["address"]
                    break
                except (json.JSONDecodeError, KeyError, OSError):
                    pass  # mid-replace; next poll sees it whole
            if time.monotonic() > deadline:
                proc.terminate()
                proc.join(5)
                raise RuntimeError(
                    f"actor {cls.__name__} did not announce readiness "
                    f"within {ready_timeout:.0f}s (child alive; ready-"
                    "queue handshake lost?)"
                )
    if status != "ok":
        raise RuntimeError(f"actor {cls.__name__} failed to start:\n{payload}")
    handle = ActorHandle(tuple(payload), pid=proc.pid, name=name)
    handle._process = proc  # keep a reference for join/cleanup by the owner
    return handle


def resolve_actor(name: str, runtime_dir: str) -> Optional[ActorHandle]:
    path = _registry_path(runtime_dir, name)
    try:
        with open(path) as f:
            record = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return None
    return ActorHandle(
        tuple(record["address"]), pid=record.get("pid"), name=name
    )


def connect_actor(
    name: str,
    runtime_dir: str,
    num_retries: int = 5,
    fallback_resolver=None,
) -> ActorHandle:
    """Discover a named actor, retrying with capped, jittered
    exponential backoff via the shared :class:`~.retry.RetryPolicy`
    (parity with reference ``connect_queue_actor``,
    ``batch_queue.py:358-380``; the old loop doubled its sleep without a
    cap or jitter, so N trainers reconnecting after a queue-actor
    restart thundering-herded in lockstep).

    ``fallback_resolver(name) -> Optional[ActorHandle]`` is consulted when
    the local session registry misses (cluster mode: the head's registry).
    """
    policy = connect_policy(num_retries)
    last_exc: Optional[Exception] = None
    for attempt, backoff in policy.attempts(site="connect_actor"):
        handle = resolve_actor(name, runtime_dir)
        if handle is None and fallback_resolver is not None:
            handle = fallback_resolver(name)
        if handle is not None and handle.ping():
            return handle
        last_exc = ActorDiedError(f"no live actor registered as {name!r}")
        if attempt < policy.max_attempts:
            backoff.backoff(str(last_exc))
    raise ValueError(
        f"Unable to connect to actor {name} after {num_retries} retries. "
        f"Last error: {last_exc!s}"
    )
