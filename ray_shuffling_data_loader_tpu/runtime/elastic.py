"""Elastic control plane: autoscaler + graceful drain + tiered evictor.

ROADMAP item 5, closing the loop PR 9 opened: the decision plane can
*say* where the bottleneck is (``/critical`` sole-active shares), who
is wedged (straggler attribution), and whose bytes are resident where
(the capacity ledger) — this module is the driver-side control loop
that *acts* on those verdicts, with three actuators:

* **Autoscaler** (:meth:`ElasticController.autoscale_once`): when the
  live critical-path verdict lands on a shuffle stage with a dominant
  sole-active share (or a worker is wedged), add capacity — more
  :class:`~.tasks.WorkerPool` workers single-host, a fresh
  :class:`~.cluster.HostAgent` admitted via
  ``ClusterScheduler.add_agent`` in cluster mode. When the shuffle
  stages fall off the critical path, shed what this controller added,
  through the graceful-drain path, never a kill.
* **Graceful drain** (:meth:`ElasticController.drain_host`): the
  *planned*-migration half of the robustness story. ``retire_agent``
  marks the agent draining (dispatch stops placing new tasks there),
  the controller waits out its in-flight tasks under a bounded
  deadline (``RSDL_DRAIN_DEADLINE_S``), re-homes the host's live store
  segments to the session owner (recorded as capacity-ledger
  ``transition`` ops), then completes the retirement with
  ``remove_agent`` + registry ``unregister_host`` (which sweeps the
  host's actor names). Anything the deadline cuts off — including the
  agent crashing mid-drain — degrades into the fault plane's
  ``_drop_agent``/lineage re-execution machinery (PR 3): a drain ends
  in either a clean handover or the already-chaos-proven failover,
  never a hang.
* **Tiered evictor** (:meth:`ElasticController.evict_once`): under
  ``RSDL_STORE_CAPACITY_BYTES`` pressure (watermarked on the ledger's
  ``shm_used_frac``), demote cold epochs' segments shm→spill
  (``ObjectStore.demote`` — readable in place, ledger ``transition``)
  and drop spill segments past the age rung (``drop_segments`` —
  readers re-materialize from lineage on the next touch). Eviction of
  an epoch still inside the in-flight window is forbidden by
  construction: candidates are fenced on
  ``shuffle.protected_epochs()`` and unknown-epoch segments are never
  touched.

Lifecycle: ``runtime.init()``'s session-owner bring-up calls
:func:`maybe_start` iff ``RSDL_ELASTIC`` is ``auto``/``on`` (and
metrics are on — the loop is blind without its input planes); the loop
ticks at the sampler cadence (``RSDL_ELASTIC_PERIOD_S``, default the
timeseries period). Zero overhead when off: ``RSDL_ELASTIC`` unset
means this module is never imported, no thread runs, and no
``transition`` ledger record is ever produced (fresh-interpreter
tested).

Surfacing: structured ``scale.*`` / ``evict.*`` events on ``/events``,
``elastic.*`` counters/gauges (``rsdl_elastic_*`` on a scrape — the
``headroom_low`` / ``drain_stuck`` default SLO rules key on
``elastic.shm_headroom_frac`` / ``elastic.drain_age_seconds``), the
``cluster`` membership section on ``/status``, and
``scale_events`` / ``evicted_gb`` / ``drains`` from :func:`summary`
(tests read it; nothing in the program does).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_shuffling_data_loader_tpu import telemetry
from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

ENV_ELASTIC = "RSDL_ELASTIC"
ENV_PERIOD_S = "RSDL_ELASTIC_PERIOD_S"
ENV_MIN_WORKERS = "RSDL_ELASTIC_MIN_WORKERS"
ENV_MAX_WORKERS = "RSDL_ELASTIC_MAX_WORKERS"
ENV_UP_THRESHOLD = "RSDL_ELASTIC_UP_THRESHOLD"
ENV_DOWN_THRESHOLD = "RSDL_ELASTIC_DOWN_THRESHOLD"
ENV_COOLDOWN_S = "RSDL_ELASTIC_COOLDOWN_S"
ENV_DRAIN_DEADLINE_S = "RSDL_DRAIN_DEADLINE_S"
ENV_EVICT_HIGH = "RSDL_EVICT_HIGH_WATERMARK"
ENV_EVICT_LOW = "RSDL_EVICT_LOW_WATERMARK"
ENV_EVICT_COOLDOWN_S = "RSDL_EVICT_COOLDOWN_S"
ENV_EVICT_DROP_AGE_S = "RSDL_EVICT_DROP_AGE_S"

# The live-verdict stage names that mean "the shuffle plane is the
# bottleneck" (critical.STAGE_ORDER vocabulary minus the consumer side).
SHUFFLE_STAGES = (
    "map", "plan", "reduce", "gather-reduce", "selective-reduce"
)

_UNKNOWN_EPOCH = "-"


def mode() -> str:
    return os.environ.get(ENV_ELASTIC, "").strip().lower()


def enabled() -> bool:
    """Is the elastic plane requested? (``auto``/``on``/``1``; default
    off — the caller gates the *import* on this same env var, so the
    off path never even loads this module.)"""
    return mode() not in ("", "off", "0", "false")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class ElasticController:
    """One driver-side controller instance: policy knobs + the three
    actuators. Constructed by :func:`start` (the env-gated loop) or
    directly by tests/operators for forced ticks."""

    def __init__(self, ctx=None):
        if ctx is None:
            from ray_shuffling_data_loader_tpu import runtime

            ctx = runtime.get_context()
        self._ctx = ctx
        self.min_workers = max(1, int(_env_float(ENV_MIN_WORKERS, 1)))
        self.max_workers = max(
            self.min_workers,
            int(_env_float(ENV_MAX_WORKERS, 2 * (os.cpu_count() or 1))),
        )
        self.up_threshold = _env_float(ENV_UP_THRESHOLD, 0.5)
        self.down_threshold = _env_float(ENV_DOWN_THRESHOLD, 0.1)
        self.cooldown_s = _env_float(ENV_COOLDOWN_S, 30.0)
        self.drain_deadline_s = _env_float(ENV_DRAIN_DEADLINE_S, 30.0)
        self.evict_high = _env_float(ENV_EVICT_HIGH, 0.85)
        self.evict_low = _env_float(ENV_EVICT_LOW, 0.6)
        self.evict_cooldown_s = _env_float(ENV_EVICT_COOLDOWN_S, 5.0)
        self.drop_age_s = _env_float(ENV_EVICT_DROP_AGE_S, 300.0)
        self._lock = threading.Lock()
        self._last_scale_ts = float("-inf")
        self._last_evict_ts = float("-inf")
        # Agents THIS controller added (cluster mode): the only ones
        # scale-down may drain — the bootstrap hosts belong to the
        # operator, not the policy.
        self._added_agents: List[Tuple[str, Any]] = []  # (host_id, handle)
        self._drain_started: Dict[Tuple, float] = {}  # address -> mono ts
        # Lifetime totals (summary()).
        self.scale_events = 0
        self.evicted_bytes = 0
        self.drains = 0

    # -- shared signal reads -------------------------------------------------

    def _protected_epochs(self) -> set:
        """The in-flight eviction fence, via ``sys.modules`` so a
        controller on a non-shuffling process never imports the shuffle
        driver."""
        shuffle_mod = sys.modules.get(
            "ray_shuffling_data_loader_tpu.shuffle"
        )
        if shuffle_mod is None:
            return set()
        try:
            return {int(e) for e in shuffle_mod.protected_epochs()}
        except Exception:
            return set()

    def _trial_in_flight(self) -> bool:
        shuffle_mod = sys.modules.get(
            "ray_shuffling_data_loader_tpu.shuffle"
        )
        if shuffle_mod is None:
            return False
        try:
            return bool(shuffle_mod.live_status().get("running"))
        except Exception:
            return False

    def _shm_frac(self, view: Dict[str, Any]) -> Optional[float]:
        """Used fraction of the shm budget. Prefer this controller's
        OWN store budget over the view's (``capacity.view`` only knows
        the budget when a full runtime session is live — a controller
        driving a bare store must not read tmpfs-relative numbers)."""
        budget = getattr(self._ctx.store, "capacity_bytes", None)
        if budget:
            return self._shm_resident(view) / budget
        frac = view.get("shm_used_frac")
        return None if frac is None else float(frac)

    @staticmethod
    def _shm_resident(view: Dict[str, Any]) -> int:
        """Bytes physically occupying shm (shm + logical cache tier) —
        delegates to capacity's one definition so the evictor's
        watermark math and ``shm_used_frac`` can never drift."""
        from ray_shuffling_data_loader_tpu.telemetry import capacity

        return capacity.shm_resident_bytes(view.get("totals", {}))

    def _shm_budget(self, view: Dict[str, Any]) -> Optional[int]:
        budget = getattr(self._ctx.store, "capacity_bytes", None)
        if budget:
            return int(budget)
        budget = (view.get("host") or {}).get("capacity_bytes")
        return int(budget) if budget else None

    def publish_gauges(self, now: Optional[float] = None) -> None:
        """The gauges the SLO default rules key on, refreshed per tick:
        ``elastic.shm_headroom_frac`` (1 - used fraction of the shm
        budget; the ``headroom_low`` input), ``elastic.drain_age_seconds``
        (age of the oldest still-active drain, 0 when none; the
        ``drain_stuck`` input), ``elastic.workers``, and
        ``elastic.draining_agents``. Never raises."""
        if not _metrics.enabled():
            return
        now = time.monotonic() if now is None else now
        try:
            from ray_shuffling_data_loader_tpu.telemetry import capacity

            frac = self._shm_frac(capacity.view())
            if frac is not None:
                _metrics.registry.gauge("elastic.shm_headroom_frac").set(
                    max(0.0, 1.0 - float(frac))
                )
        except Exception:
            pass
        self._publish_drain_gauges(now)
        try:
            _metrics.registry.gauge("elastic.workers").set(
                float(self._sched_width())
            )
        except Exception:
            pass

    def _publish_drain_gauges(self, now: Optional[float] = None) -> None:
        """Just the drain-age/count gauges — cheap enough for the drain
        wait loop's poll cadence (the full :meth:`publish_gauges` folds
        the whole capacity ledger and belongs on the tick)."""
        if not _metrics.enabled():
            return
        now = time.monotonic() if now is None else now
        try:
            with self._lock:
                started = list(self._drain_started.values())
            age = max((now - t for t in started), default=0.0)
            _metrics.registry.gauge("elastic.drain_age_seconds").set(age)
            _metrics.registry.gauge("elastic.draining_agents").set(
                len(started)
            )
        except Exception:
            pass

    # -- autoscaler ----------------------------------------------------------

    def autoscale_once(self, now: Optional[float] = None) -> Optional[str]:
        """One policy decision from the live verdicts: returns ``"up"``,
        ``"down"``, or ``None``. Only acts mid-trial (between trials
        there is no critical path to read), under a cooldown so one
        slow epoch cannot thrash membership."""
        now = time.monotonic() if now is None else now
        if not self._trial_in_flight():
            return None
        with self._lock:
            if now - self._last_scale_ts < self.cooldown_s:
                return None
        try:
            from ray_shuffling_data_loader_tpu.telemetry import critical

            current = critical.analyze().get("current") or {}
        except Exception:
            return None
        stage = current.get("critical_path")
        shares = current.get("sole_share") or {}
        shuffle_share = sum(
            float(shares.get(s, 0.0)) for s in SHUFFLE_STAGES
        )
        wedged = 0
        try:
            from ray_shuffling_data_loader_tpu.telemetry import stragglers

            wedged = len(stragglers.analyze().get("wedged") or [])
        except Exception:
            pass
        if (
            stage in SHUFFLE_STAGES
            and float(shares.get(stage, 0.0)) >= self.up_threshold
        ) or wedged:
            if self._scale_up(
                reason="wedged-worker" if wedged else f"critical:{stage}",
                share=round(float(shares.get(stage, 0.0)), 4),
            ):
                with self._lock:
                    self._last_scale_ts = now
                return "up"
            return None
        if shuffle_share <= self.down_threshold and not wedged:
            if self._scale_down(share=round(shuffle_share, 4)):
                with self._lock:
                    self._last_scale_ts = now
                return "down"
        return None

    def _sched_width(self) -> int:
        """Current scheduler capacity WITHOUT side effects: on a
        RuntimeContext whose worker pool is still lazy, reading the
        ``scheduler`` property would spawn the pool just to count it —
        report the configured size instead."""
        ctx = self._ctx
        if (
            getattr(ctx, "cluster", None) is None
            and hasattr(ctx, "_pool")
            and ctx._pool is None
        ):
            return int(getattr(ctx, "_num_workers", 0) or 0)
        return int(getattr(ctx.scheduler, "width", 0) or 0)

    def _workers_now(self) -> int:
        return self._sched_width()

    def _scale_up(self, reason: str, **fields) -> bool:
        sched = self._ctx.scheduler
        if self._workers_now() >= self.max_workers:
            return False
        if hasattr(sched, "add_workers"):  # single-host WorkerPool
            before = sched.num_workers
            after = sched.add_workers(1)
            if after <= before:
                return False
            detail = {"workers": after}
        elif hasattr(sched, "add_agent"):  # ClusterScheduler
            detail = self._spawn_scale_agent()
            if detail is None:
                return False
        else:
            return False
        with self._lock:
            self.scale_events += 1
        _metrics.safe_inc("elastic.scale_events_total", direction="up")
        telemetry.emit_event(
            "scale.up", _flush=True, reason=reason, **detail, **fields
        )
        return True

    def _spawn_scale_agent(self) -> Optional[Dict[str, Any]]:
        """Cluster-mode scale-up: spawn a fresh HostAgent (one worker)
        on this host, register it as a synthetic cluster host (so
        scheduler rebuilds keep it), and admit it to the rotation."""
        from .actor import spawn_actor
        from .cluster import HostAgent

        ctx = self._ctx
        advertise = (
            getattr(ctx.cluster, "advertise_host", None)
            if ctx.cluster is not None
            else None
        )
        try:
            # host= makes the agent bind TCP on the advertise address
            # (the canonical start_host_services spawn does the same):
            # its address is published cluster-wide below, and a unix
            # socket would be unreachable from every other host.
            agent = spawn_actor(
                HostAgent,
                ctx.runtime_dir,
                1,
                advertise,
                runtime_dir=ctx.runtime_dir,
                host=advertise,
                daemon=False,
            )
        except Exception:
            return None
        host_id = f"elastic-{agent.pid}:{ctx.session}"
        cluster = ctx.cluster
        if cluster is not None and hasattr(cluster, "registry"):
            try:
                cluster.registry.call(
                    "register_host",
                    host_id,
                    list(agent.address),
                    list(cluster.store_address),
                    1,
                )
            except Exception:
                pass
        sched = ctx.scheduler
        if hasattr(sched, "add_agent"):
            sched.add_agent(agent, num_workers=1)
        with self._lock:
            self._added_agents.append((host_id, agent))
        return {"agent": str(agent.address), "host_id": host_id}

    def _scale_down(self, **fields) -> bool:
        sched = self._ctx.scheduler
        if hasattr(sched, "retire_workers"):  # single-host WorkerPool
            if sched.num_workers <= self.min_workers:
                return False
            retired = sched.retire_workers(1)
            with self._lock:
                self.scale_events += 1
            _metrics.safe_inc(
                "elastic.scale_events_total", direction="down"
            )
            telemetry.emit_event(
                "scale.down", _flush=True,
                workers=sched.num_workers, retired_pids=retired, **fields,
            )
            return True
        with self._lock:
            added = list(self._added_agents)
        if not added:
            return False  # never drain a bootstrap host on policy alone
        host_id, agent = added[-1]
        outcome = self.drain_host(agent, host_id=host_id)
        if outcome is None:
            return False
        with self._lock:
            self.scale_events += 1
            self._added_agents = [
                (h, a) for h, a in self._added_agents if h != host_id
            ]
        _metrics.safe_inc("elastic.scale_events_total", direction="down")
        telemetry.emit_event(
            "scale.down", _flush=True, agent=str(agent.address),
            host_id=host_id, outcome=outcome, **fields,
        )
        return True

    # -- graceful drain ------------------------------------------------------

    def drain_host(
        self,
        agent_or_address,
        host_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
        store_handle=None,
    ) -> Optional[str]:
        """Planned migration of one host agent out of the cluster.

        Protocol: ``retire_agent`` (dispatch stops placing new tasks) →
        wait for its in-flight tasks under ``deadline_s`` (pinging the
        agent each poll — a crash mid-drain is detected, not waited
        out) → re-home its live store segments to this host (ledger
        ``transition`` ops) → ``remove_agent`` + registry
        ``unregister_host`` (sweeping its actor names). A blown
        deadline, a mid-drain crash, or a failed re-home falls back to
        ``_drop_agent``: the chaos-proven failover/lineage machinery
        owns whatever the planned path could not hand over.

        Returns ``"drained"`` (clean), ``"backstop"`` (degraded to
        failover), or ``None`` (not a cluster scheduler / unknown
        agent)."""
        sched = self._ctx.scheduler
        if not hasattr(sched, "retire_agent"):
            return None
        agent = sched.retire_agent(agent_or_address)
        if agent is None:
            return None
        deadline_s = (
            self.drain_deadline_s if deadline_s is None else deadline_s
        )
        address = tuple(agent.address)
        started = time.monotonic()
        with self._lock:
            self.drains += 1
            self._drain_started[address] = started
        _metrics.safe_inc("elastic.drains_total")
        telemetry.emit_event(
            "scale.drain", _flush=True, agent=str(agent.address),
            host_id=host_id, deadline_s=deadline_s,
        )
        alive = True
        try:
            deadline = started + max(0.0, deadline_s)
            while sched.in_flight_on(address) > 0:
                self._publish_drain_gauges()
                if time.monotonic() >= deadline:
                    break
                if not agent.ping(timeout=2.0):
                    # Crash mid-drain: no point waiting out the window.
                    alive = False
                    break
                time.sleep(0.05)
            drained = alive and sched.in_flight_on(address) == 0
            if drained:
                try:
                    self._rehome_segments(agent, store_handle=store_handle)
                except Exception:
                    drained = False
            if drained:
                sched.remove_agent(address)
                self._unregister_host(host_id, address)
                telemetry.emit_event(
                    "scale.drain_done", _flush=True,
                    agent=str(agent.address), host_id=host_id,
                    waited_s=round(time.monotonic() - started, 3),
                )
                return "drained"
            # Backstop: the fault plane's failover path. _drop_agent
            # fires the agent.evicted event + on_agent_dead membership
            # eviction; in-flight tasks fail over and lost segments
            # re-materialize from lineage — precisely the chaos-proven
            # degradation a drain must collapse into, never a hang.
            _metrics.safe_inc("elastic.drain_backstops_total")
            telemetry.emit_event(
                "scale.drain_backstop", _flush=True,
                agent=str(agent.address), host_id=host_id,
                agent_alive=alive,
                in_flight=sched.in_flight_on(address),
            )
            sched._drop_agent(agent)
            self._unregister_host(host_id, address)
            return "backstop"
        finally:
            with self._lock:
                self._drain_started.pop(address, None)
            self.publish_gauges()

    def _unregister_host(self, host_id: Optional[str], address) -> None:
        cluster = getattr(self._ctx, "cluster", None)
        if cluster is None or not hasattr(cluster, "registry"):
            return
        try:
            hosts = cluster.registry.call("hosts")
        except Exception:
            return
        for hid, info in hosts.items():
            if hid == host_id or tuple(info.get("agent") or ()) == tuple(
                address
            ):
                try:
                    # unregister_host also sweeps the host's actor-name
                    # records, so post-drain lookups fail fast.
                    cluster.registry.call_oneway("unregister_host", hid)
                except Exception:
                    pass

    def _rehome_segments(self, agent, store_handle=None) -> int:
        """Adopt the draining host's live segments into this host's
        store (same object ids — local readers resolve them without a
        fetch; remote readers that still dial the dead owner degrade to
        lineage re-execution, the backstop). Segments already visible
        here (shared-filesystem same-machine hosts) move nothing but
        still count as accounted-for. Each adopted segment lands a
        ledger ``transition`` op (same tier — a host move, not a tier
        move, so per-tier residency stays exact)."""
        store = self._ctx.store
        cluster = getattr(self._ctx, "cluster", None)
        if store_handle is None and cluster is not None:
            try:
                hosts = cluster.registry.call("hosts")
                for info in hosts.values():
                    if tuple(info.get("agent") or ()) == tuple(
                        agent.address
                    ):
                        store_handle = cluster._peer_store(
                            tuple(info["store"])
                        )
                        break
            except Exception:
                store_handle = None
        if store_handle is None:
            return 0
        prefix = f"{store.session}-"
        moved = 0
        try:
            segments = store_handle.call("list_segments", prefix)
        except Exception:
            return 0
        for object_id, nbytes in segments:
            if store._find_segment(object_id) is not None:
                continue
            data = store_handle.call("fetch", object_id)
            path = os.path.join(
                store._placement_dir(len(data)), object_id
            )
            tmp = f"{path}.rehome-{os.getpid()}.tmp"
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                os.rename(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except FileNotFoundError:
                    pass
                raise
            moved += len(data)
            self._ledger_transition(object_id, len(data), store.tier_of(path))
        if moved:
            telemetry.emit_event(
                "scale.rehomed", nbytes=moved, agent=str(agent.address)
            )
        return moved

    @staticmethod
    def _ledger_transition(object_id: str, nbytes: int, tier: str) -> None:
        if not _metrics.enabled():
            return
        try:
            from ray_shuffling_data_loader_tpu.telemetry import capacity

            capacity.note("transition", object_id, nbytes=nbytes, tier=tier)
        except Exception:
            pass

    # -- tiered evictor ------------------------------------------------------

    @staticmethod
    def _last_touch(seg: Dict[str, Any]) -> float:
        return float(seg.get("last_touch") or seg["ts"])

    def _candidates(self, tier: str) -> List[Dict[str, Any]]:
        """Live ledger segments on ``tier`` eligible for eviction:
        epoch known (unknown-epoch segments are never touched — we
        cannot prove them cold) and outside the in-flight window.

        Ordering is by LAST ACCESS, not creation age (ISSUE 11): the
        coldest epoch — the one whose segments were read least recently
        per the ledger's ``touch`` ops — evicts first, then segments
        within it least-recently-touched first. An old epoch a resumed
        reader is actively re-reading stays warm; under the old
        creation-age order it was always the first casualty."""
        from ray_shuffling_data_loader_tpu.telemetry import capacity

        protected = self._protected_epochs()
        claimed: set = set()
        if tier == "cache" and os.environ.get("RSDL_SERVICE"):
            # Service plane (ISSUE 15): shared decode-cache segments a
            # LIVE job claims are in active cross-job use — dropping
            # one would silently un-share a hot dataset mid-run. The
            # claim set is refcounted per job and released at job end,
            # so unclaimed segments stay ordinary candidates.
            try:
                from ray_shuffling_data_loader_tpu.runtime.service import (
                    claimed_cache_ids,
                )

                claimed = claimed_cache_ids()
            except Exception:
                claimed = set()
        live = capacity.live_segments()
        # Epoch warmth across ALL tiers: a spill read keeps the epoch's
        # shm segments warm too — the epoch is demonstrably in use.
        epoch_touch: Dict[str, float] = {}
        for seg in live:
            key = seg["epoch"]
            epoch_touch[key] = max(
                epoch_touch.get(key, 0.0), self._last_touch(seg)
            )
        out = []
        for seg in live:
            if seg["tier"] != tier or seg["epoch"] == _UNKNOWN_EPOCH:
                continue
            try:
                epoch = int(seg["epoch"])
            except (TypeError, ValueError):
                continue
            if epoch in protected:
                continue
            if claimed and (
                seg["id"] in claimed
                or any(i in claimed for i in (seg["ids"] or []))
            ):
                continue
            out.append(seg)
        out.sort(
            key=lambda s: (
                epoch_touch.get(s["epoch"], 0.0),
                int(s["epoch"]),
                self._last_touch(s),
                s["ts"],
            )
        )
        return out

    def evict_once(
        self,
        now: Optional[float] = None,
        force: bool = False,
        force_drop: bool = False,
    ) -> Dict[str, int]:
        """One eviction pass. Under shm pressure (used fraction >= the
        high watermark; or ``force``) demote cold epochs' segments
        oldest-first until residency projects under the low watermark,
        then drop spill segments older than the drop-age rung
        (``force_drop`` ignores the age — the operator's/test's
        explicit last rung). Returns the pass's stats (also accumulated
        into the lifetime totals)."""
        now = time.time() if now is None else float(now)
        stats = {
            "demoted": 0, "demoted_bytes": 0,
            "dropped": 0, "dropped_bytes": 0,
        }
        if not _metrics.enabled():
            return stats
        from ray_shuffling_data_loader_tpu.telemetry import capacity

        view = capacity.view(now=now)
        frac = self._shm_frac(view)
        pressured = frac is not None and float(frac) >= self.evict_high
        mono = time.monotonic()
        with self._lock:
            cooled = mono - self._last_evict_ts >= self.evict_cooldown_s
        if not (force or force_drop) and not (pressured and cooled):
            self.publish_gauges()
            return stats
        with self._lock:
            self._last_evict_ts = mono
        store = self._ctx.store
        budget = self._shm_budget(view)
        resident = self._shm_resident(view)
        target = self.evict_low * budget if budget else None
        demoted_epochs: set = set()
        dropped_epochs: set = set()
        if force or pressured:
            # First rung: shed shared decode-cache segments (logical
            # "cache" tier, ISSUE 11), coldest-last-touch first. They
            # are the cheapest bytes to lose — lineage re-materializes
            # them from Parquet on the next claim (the chaos-proven
            # _recover_lost_cache path), no epoch state is at risk.
            for seg in self._candidates("cache"):
                if (
                    not force
                    and target is not None
                    and resident <= target
                ):
                    break
                freed = store.drop_segments(seg["ids"] or [seg["id"]])
                if freed:
                    stats["dropped"] += 1
                    stats["dropped_bytes"] += freed
                    resident -= freed
                    dropped_epochs.add(seg["epoch"])
            for seg in self._candidates("shm"):
                if (
                    not force
                    and target is not None
                    and resident <= target
                ):
                    break
                moved = store.demote(seg["ids"] or [seg["id"]])
                if moved:
                    stats["demoted"] += 1
                    stats["demoted_bytes"] += moved
                    resident -= moved
                    demoted_epochs.add(seg["epoch"])
        for seg in self._candidates("spill"):
            # The age rung keys on last ACCESS, not creation: a spill
            # segment a reader touched recently is demonstrably needed.
            if (
                not force_drop
                and now - self._last_touch(seg) < self.drop_age_s
            ):
                continue
            freed = store.drop_segments(seg["ids"] or [seg["id"]])
            if freed:
                stats["dropped"] += 1
                stats["dropped_bytes"] += freed
                dropped_epochs.add(seg["epoch"])
        with self._lock:
            self.evicted_bytes += (
                stats["demoted_bytes"] + stats["dropped_bytes"]
            )
        if stats["demoted"]:
            _metrics.safe_inc(
                "elastic.evicted_bytes_total",
                float(stats["demoted_bytes"]), action="demote",
            )
            telemetry.emit_event(
                "evict.demote", _flush=True,
                segments=stats["demoted"],
                nbytes=stats["demoted_bytes"],
                epochs=sorted(demoted_epochs),
            )
        if stats["dropped"]:
            _metrics.safe_inc(
                "elastic.evicted_bytes_total",
                float(stats["dropped_bytes"]), action="drop",
            )
            telemetry.emit_event(
                "evict.drop", _flush=True,
                segments=stats["dropped"],
                nbytes=stats["dropped_bytes"],
                epochs=sorted(dropped_epochs),
            )
        self.publish_gauges()
        return stats

    # -- the loop ------------------------------------------------------------

    def tick(self, now: Optional[float] = None) -> None:
        """One control-loop iteration: refresh gauges, run the
        autoscaler policy, run the evictor pass. Never raises."""
        try:
            self.publish_gauges()
        except Exception:
            pass
        try:
            self.autoscale_once()
        except Exception:
            pass
        try:
            self.evict_once(now=now)
        except Exception:
            pass

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "scale_events": self.scale_events,
                "evicted_gb": round(self.evicted_bytes / 2**30, 6),
                "drains": self.drains,
            }


# ---------------------------------------------------------------------------
# Module lifecycle (the env-gated loop runtime.init brings up)
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_controller: Optional[ElasticController] = None
_thread: Optional[threading.Thread] = None
_stop_event: Optional[threading.Event] = None


def controller() -> Optional[ElasticController]:
    return _controller


def period_s() -> float:
    """Control-loop cadence: ``RSDL_ELASTIC_PERIOD_S``, defaulting to
    the timeseries sampler period so verdicts and actions share a
    clock."""
    env = os.environ.get(ENV_PERIOD_S, "").strip()
    if env:
        try:
            return max(0.1, float(env))
        except ValueError:
            pass
    try:
        from ray_shuffling_data_loader_tpu.telemetry import timeseries

        return timeseries.period_s()
    except Exception:
        return 2.0


def running() -> bool:
    return _thread is not None and _thread.is_alive()


def start(ctx=None, period: Optional[float] = None) -> None:
    """Start the control loop (idempotent; session owner only — one
    controller per session, like the obs server and sampler)."""
    global _controller, _thread, _stop_event
    if not _metrics.enabled():
        return
    interval = period_s() if period is None else max(0.1, float(period))
    with _lock:
        if _thread is not None and _thread.is_alive():
            return
        _controller = ElasticController(ctx)
        stop_event = threading.Event()
        _stop_event = stop_event
        ctl = _controller

        def _loop():
            while not stop_event.wait(interval):
                ctl.tick()

        _thread = threading.Thread(
            target=_loop, name="rsdl-elastic", daemon=True
        )
        _thread.start()


def maybe_start(ctx=None) -> bool:
    """Start iff ``RSDL_ELASTIC`` requests it AND metrics are on (the
    loop's inputs — critical path, stragglers, capacity — are all
    metrics-plane folds; without them the policy would be guessing)."""
    if not enabled():
        return False
    if not _metrics.enabled():
        import logging

        logging.getLogger(__name__).warning(
            "%s=%s requested but RSDL_METRICS is off — the elastic "
            "loop needs the decision plane's signals; not starting",
            ENV_ELASTIC, mode(),
        )
        return False
    start(ctx)
    return True


def stop() -> None:
    """Stop the loop and join its thread (session shutdown, tests)."""
    global _thread, _stop_event, _controller
    with _lock:
        thread, _thread = _thread, None
        stop_event, _stop_event = _stop_event, None
        _controller = None
    if stop_event is not None:
        stop_event.set()
    if thread is not None:
        thread.join(timeout=5.0)


def summary() -> Dict[str, Any]:
    """Lifetime totals (empty when no controller ever ran in this
    process)."""
    ctl = _controller
    return ctl.summary() if ctl is not None else {}
