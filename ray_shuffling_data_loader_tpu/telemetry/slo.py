"""Declarative SLO alert engine over the aggregated metrics registry.

The obs plane could show every number but could not *say anything*:
"this run is unhealthy" lived in the operator's head (or in a post-hoc
report's exit code). This module closes that gap with a small,
declarative rule engine evaluated by the timeseries sampler tick —
the decision half the autoscaler/evictor loop (ROADMAP item 5) and a
paging pipeline both consume.

**Rules** are flat JSON objects::

    {"name": "wedged_worker",          # unique; replaces a default
     "kind": "threshold",              # threshold | rate | absence
     "metric": "straggler.wedged_tasks",  # registry key, base name,
                                          # or rsdl_ Prometheus alias
     "op": ">", "value": 0,            # predicate vs the observed value
     "window_s": 60,                   # rate: trailing ring window;
                                       # absence: staleness bound
     "for_s": 0,                       # condition must HOLD this long
     "only_in_flight": false,          # evaluate only mid-trial
     "per_job": false,                 # expand per live tenant
     "per_job_metric": null,           # per-job instances' metric
                                       # (default: "metric")
     "field": "rate",                  # rate rules: ring point field
                                       # ("rate" | "window_mean")
     "severity": "warn"}               # free-form label

* ``threshold`` — predicate over the *current aggregated value*
  (:func:`.export.aggregate`; keys matching a base name are summed, so
  ``stall_seconds`` covers every ``cause=`` series at once).
* ``rate`` — predicate over the mean per-second rate across the
  trailing ``window_s`` of the timeseries ring (:mod:`.timeseries` —
  counter deltas already turned into rates, reset-safe).
* ``absence`` — fires when the metric is missing from the aggregate
  entirely, or (with ``window_s``) when the ring has no point for it
  within the window: the "the thing that should be reporting is not"
  predicate a dead producer or wedged spool shows up as.

**Tenant scope (ISSUE 16).** A rule with ``per_job: true`` expands into
one independent ok → pending → firing → resolved instance per *live
job* each tick (the service registry when armed, the shuffle live-trial
tracker otherwise, falling back to the ``job=`` labels present in the
aggregate so external registries still work). Each instance evaluates
``per_job_metric`` (default: the rule's ``metric``) restricted to that
tenant's ``job=``-labeled series — one stalled tenant pages as
``alert.active{rule,job}`` without dragging its neighbors into the
blast radius, and its ``alert.fired``/``alert.resolved`` events carry
the job id. With no live jobs a per-job rule degrades to the single
global instance, so service-off runs behave exactly as before.

**Sources.** ``RSDL_SLO_RULES`` is either inline JSON (a list of rule
objects) or a path to a JSON rules file. User rules merge over the
**default pack** by name (same name replaces; ``"disabled": true``
removes); the defaults ship the alerts every run wants: producer
stalled, stall share over budget, capacity near limit, wedged worker,
audit mismatch.

**Lifecycle.** :func:`evaluate` runs each sampler tick: a rule whose
condition holds for ``for_s`` transitions to *firing* — emitting an
``alert.fired`` structured event (:mod:`.events`), incrementing
``alert.fired_total{rule=}``, and raising ``alert.active{rule=}`` to 1
(``rsdl_alert_active`` on a scrape) — and back to *resolved* (an
``alert.resolved`` event, gauge 0) when it clears. ``/alerts``
(:mod:`.obs_server`) serves every rule's live state plus the recent
transition history.

Zero-overhead contract: evaluated only from the sampler tick (which
exists only when metrics are on); never imported on a disabled run.
Pure folds — no RPCs, safe on error paths.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_shuffling_data_loader_tpu.telemetry import export as _export
from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics
from ray_shuffling_data_loader_tpu.telemetry import timeseries as _timeseries

ENV_SLO_RULES = "RSDL_SLO_RULES"

_OPS = {
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
}

# The default rule pack (docs/observability.md). Conservative windows:
# a rule that cries wolf is worse than none. Override or disable by
# name via RSDL_SLO_RULES.
DEFAULT_RULES: List[Dict[str, Any]] = [
    {
        # No reducer produced a row for a sustained window while a
        # trial is mid-flight: the producer plane is stalled (dead
        # producer, wedged window, exhausted retries). Per-job
        # instances watch each tenant's delivered-bytes counter (the
        # deliver path stamps job= explicitly), so one tenant's stall
        # pages that tenant alone.
        "name": "producer_stalled",
        "kind": "rate",
        "metric": "shuffle.reduce_rows",
        "per_job": True,
        "per_job_metric": "service.delivered_bytes",
        "op": "==", "value": 0.0,
        "window_s": 30.0, "for_s": 15.0,
        "only_in_flight": True,
        "severity": "page",
    },
    {
        # Some consumer spent more than half its recent wall-clock
        # stalled (both causes summed within each source process;
        # "max-source" takes the worst consumer — a cluster-wide sum
        # would scale with trainer count, not health). Per-job
        # instances key on the spool's job-stamped source series, so a
        # stalled tenant is named rather than averaged away.
        "name": "stall_over_budget",
        "kind": "rate",
        "metric": "stall_seconds",
        "per_job": True,
        "fold": "max-source",
        "op": ">", "value": 0.5,
        "window_s": 60.0, "for_s": 10.0,
        "only_in_flight": True,
        "severity": "warn",
    },
    {
        # The shm tier is near its session budget: the next segments
        # spill to disk — the evictor's (ROADMAP 5) wake-up signal.
        # Per-job instances watch each tenant's share of the used
        # budget (capacity.job_shm_frac), so the tenant actually
        # holding the memory is the one named.
        "name": "capacity_near_limit",
        "kind": "threshold",
        "metric": "capacity.shm_used_frac",
        "per_job": True,
        "per_job_metric": "capacity.job_shm_frac",
        "op": ">", "value": 0.9,
        "for_s": 0.0,
        "severity": "warn",
    },
    {
        # The straggler detector flags an in-flight task over its
        # wedge budget right now.
        "name": "wedged_worker",
        "kind": "threshold",
        "metric": "straggler.wedged_tasks",
        "op": ">", "value": 0.0,
        "for_s": 0.0,
        "severity": "page",
    },
    {
        # The exactly-once reconciler found a digest mismatch: data
        # loss or duplication — never a warning.
        "name": "audit_mismatch",
        "kind": "threshold",
        "metric": "audit.digest_mismatch",
        "op": ">", "value": 0.0,
        "for_s": 0.0,
        "severity": "page",
    },
    {
        # The elastic plane's shm headroom (1 - used fraction of the
        # store budget, published by the control loop / evictor each
        # tick — runtime/elastic.py) is nearly exhausted: the evictor
        # is losing to the ingest rate, the next segments spill.
        "name": "headroom_low",
        "kind": "threshold",
        "metric": "elastic.shm_headroom_frac",
        "op": "<", "value": 0.1,
        "for_s": 0.0,
        "severity": "warn",
    },
    {
        # A graceful drain (planned migration) has been waiting out a
        # host's in-flight window longer than any healthy drain should:
        # the host is likely wedged and the drain is about to (or
        # should) degrade into the failover backstop.
        "name": "drain_stuck",
        "kind": "threshold",
        "metric": "elastic.drain_age_seconds",
        "op": ">", "value": 30.0,
        "for_s": 0.0,
        "severity": "page",
    },
    {
        # A journal resume started (recovery.resume_in_progress set to
        # 1 at resume start; cleared at the resumed run's FIRST
        # delivery — runtime/journal.py) but no batch has reached the
        # consumer for a sustained window: the re-attach/re-execution
        # path is wedged, not recovering.
        "name": "resume_stalled",
        "kind": "threshold",
        "metric": "recovery.resume_in_progress",
        "op": ">", "value": 0.0,
        "for_s": 60.0,
        "severity": "page",
    },
    {
        # A tenant's epoch windows are spending a long time queued at
        # the capacity admission gate (service.admit_epoch): the mean
        # wait across its recent admissions is over budget. Windowed
        # histogram mean — one historic spike does not page forever,
        # and an idle tenant (no new admissions) resolves naturally.
        "name": "admission_wait_long",
        "kind": "rate",
        "metric": "service.admission_wait_seconds",
        "field": "window_mean",
        "op": ">", "value": 5.0,
        "window_s": 120.0, "for_s": 0.0,
        "per_job": True,
        "only_in_flight": True,
        "severity": "warn",
    },
    {
        # The fair-share dispatcher's virtual clock for this tenant
        # trails the most-advanced active clock by a sustained margin
        # while the tenant still has queued tasks: the job is starved
        # (weight misconfiguration, or a neighbor monopolizing
        # dispatch).
        "name": "fair_share_starved",
        "kind": "threshold",
        "metric": "service.dispatch_vtime_lag",
        "op": ">", "value": 8.0,
        "for_s": 10.0,
        "per_job": True,
        "only_in_flight": True,
        "severity": "warn",
    },
    {
        # A relay shipper is falling behind its local spools by a
        # sustained margin (telemetry.relay publishes its backlog as
        # relay.lag_bytes): the driver's federated view is going stale
        # — and past RSDL_RELAY_MAX_LAG_BYTES records start being
        # dropped. Threshold rules never fire on a missing metric, so
        # relay-off sessions are untouched.
        "name": "relay_lagging",
        "kind": "threshold",
        "metric": "relay.lag_bytes",
        "op": ">", "value": 8.0 * 1024 * 1024,
        "for_s": 10.0,
        "severity": "warn",
    },
]

_HISTORY_CAP = 64

_lock = threading.Lock()
_rules_cache: Optional[List[Dict[str, Any]]] = None
# Instance state, keyed by rule name for global instances and
# ``"{rule}|{job}"`` for per-job ones (the instance's job id also lives
# at state["job"]).
_states: Dict[str, Dict[str, Any]] = {}
# Lifetime fire counts per instance key — kept apart from _states so a
# departed tenant's counts survive its instance cleanup (the run
# ledger reads these at run end, after jobs have ended).
_fired_totals: Dict[str, int] = {}
_history: List[Dict[str, Any]] = []


def reset() -> None:
    """Drop rule cache, per-rule state, and history (tests and run
    boundaries); the next evaluate re-reads ``RSDL_SLO_RULES``."""
    global _rules_cache
    with _lock:
        _rules_cache = None
        _states.clear()
        _fired_totals.clear()
        _history.clear()


def _load_user_rules() -> List[Dict[str, Any]]:
    raw = os.environ.get(ENV_SLO_RULES, "").strip()
    if not raw:
        return []
    try:
        if raw.startswith("[") or raw.startswith("{"):
            parsed = json.loads(raw)
        else:
            with open(raw) as f:
                parsed = json.load(f)
    except (OSError, ValueError):
        import logging

        logging.getLogger(__name__).warning(
            "slo: cannot parse %s=%r; using the default rule pack only",
            ENV_SLO_RULES, raw[:120],
        )
        return []
    if isinstance(parsed, dict):
        parsed = [parsed]
    return [r for r in parsed if isinstance(r, dict) and r.get("name")]


def rules() -> List[Dict[str, Any]]:
    """The effective rule list: default pack merged (by name) with the
    ``RSDL_SLO_RULES`` rules — user wins, ``"disabled": true`` drops."""
    global _rules_cache
    with _lock:
        if _rules_cache is not None:
            return list(_rules_cache)
    merged: Dict[str, Dict[str, Any]] = {
        r["name"]: dict(r) for r in DEFAULT_RULES
    }
    for rule in _load_user_rules():
        merged[str(rule["name"])] = dict(rule)
    out = [r for r in merged.values() if not r.get("disabled")]
    with _lock:
        _rules_cache = out
    return list(out)


# ---------------------------------------------------------------------------
# Predicate evaluation
# ---------------------------------------------------------------------------


def _split_key(key: str) -> Tuple[str, Dict[str, str], str]:
    """``(base, labels, suffix)`` of a flat aggregated key: labels
    parsed from the ``{k=v,...}`` segment, ``suffix`` the flattened-
    histogram component trailing the label block —
    ``stall_seconds{cause=staging,source=t-1}`` →
    ``("stall_seconds", {...}, "")``, ``h{job=a}_sum`` →
    ``("h", {"job": "a"}, "_sum")``."""
    brace, close = key.find("{"), key.rfind("}")
    if not (0 <= brace < close):
        return key, {}, ""
    labels: Dict[str, str] = {}
    for part in key[brace + 1:close].split(","):
        k, _, v = part.partition("=")
        labels[k] = v
    return key[:brace], labels, key[close + 1:]


def _metric_matches(key: str, name: str) -> bool:
    base, _labels, suffix = _split_key(key)
    if name in (key, base, base + suffix):
        return True
    # Accept the Prometheus alias so rules can use scrape names; with
    # the suffix so a rule can pin one flattened-histogram component
    # (rsdl_x_max) instead of summing all four.
    if name == _timeseries._prom_name(base):
        return True
    return bool(suffix) and name == _timeseries._prom_name(base + suffix)


def _aggregate_value(
    name: str,
    flat: Optional[Dict[str, float]] = None,
    job: Optional[str] = None,
) -> Optional[float]:
    """Sum of every aggregated key matching ``name`` (exact key, base
    name, or rsdl_ alias); None when nothing matches. ``job`` keeps
    only that tenant's ``job=``-labeled series. Per-source breakdown
    keys are excluded (they would double-count the cluster-merged
    series) — except as the fallback for a job filter, where a metric
    may carry its tenant only through the spool's job-stamped source
    keys and no merged ``job=`` series exists."""
    if flat is None:
        try:
            flat = _export.aggregate(per_source=job is not None)
        except Exception:
            return None
    total: Optional[float] = None
    from_sources: Optional[float] = None
    for key, value in flat.items():
        if not _metric_matches(key, name):
            continue
        _base, labels, _suffix = _split_key(key)
        if job is not None and labels.get("job") != job:
            continue
        if "source" in labels:
            if job is not None:
                from_sources = (from_sources or 0.0) + float(value)
            continue
        total = (total or 0.0) + float(value)
    return total if total is not None else from_sources


def _source_of(key: str) -> Optional[str]:
    brace, close = key.find("{"), key.rfind("}")
    if not (0 <= brace < close):
        return None
    for part in key[brace + 1:close].split(","):
        k, _, v = part.partition("=")
        if k == "source":
            return v
    return None


def _window_rate(name: str, window_s: float,
                 now: Optional[float] = None,
                 fold: str = "sum",
                 job: Optional[str] = None,
                 field: str = "rate") -> Optional[float]:
    """Mean of a ring point field for ``name`` over the trailing
    window. ``fold="sum"`` (default): per sample, matching keys fold
    cluster-wide, then samples average. ``fold="max-source"``: the same
    mean computed per source process, returning the WORST source — the
    right shape for share-of-wall-clock budgets like stall
    seconds/second, where a cluster-wide sum scales with the consumer
    count instead of measuring any one consumer's health. ``job``
    keeps only that tenant's ``job=``-labeled series (merged series
    preferred; job-stamped source series back-fill when none exist).
    ``field`` picks the sampled point field: ``"rate"`` folds by sum,
    anything else (``"window_mean"`` — a histogram's per-observation
    mean over new observations) folds by max. None when the ring holds
    no such point for the metric (unknown — a rule must not fire on
    ignorance)."""
    per_source = fold == "max-source"
    series = _timeseries.series(
        name=name, window_s=window_s, now=now,
        include_sources=per_source or job is not None,
        job=job,
    )
    # {group: {ts: folded value}} — merged keys under "", plus one
    # group per source label.
    base_groups: Dict[str, Dict[float, float]] = {}
    src_groups: Dict[str, Dict[float, float]] = {}
    for key, points in series.items():
        src = _source_of(key)
        by_ts = (
            src_groups.setdefault(src, {})
            if src is not None
            else base_groups.setdefault("", {})
        )
        for p in points:
            if p.get(field) is None:
                continue
            ts = float(p["ts"])
            val = float(p[field])
            if field == "rate":
                by_ts[ts] = by_ts.get(ts, 0.0) + val
            else:
                by_ts[ts] = max(by_ts.get(ts, val), val)
    if per_source:
        groups = src_groups
    elif base_groups or job is None:
        # Merged series win; without a job filter, source series are
        # per-process copies of them and would double-count.
        groups = base_groups
    else:
        # The job filter matched only job-stamped source series: fold
        # them into one logical group so the tenant still gets a value.
        merged: Dict[float, float] = {}
        for by_ts in src_groups.values():
            for ts, val in by_ts.items():
                if field == "rate":
                    merged[ts] = merged.get(ts, 0.0) + val
                else:
                    merged[ts] = max(merged.get(ts, val), val)
        groups = {"": merged} if merged else {}
    means = [
        sum(by_ts.values()) / len(by_ts)
        for by_ts in groups.values()
        if by_ts
    ]
    if not means:
        return None
    return max(means) if per_source else means[0]


def _metric_fresh_in_ring(name: str, window_s: float,
                          now: Optional[float] = None,
                          job: Optional[str] = None) -> bool:
    series = _timeseries.series(
        name=name, window_s=window_s, now=now,
        include_sources=job is not None, job=job,
    )
    return any(points for points in series.values())


def _trial_in_flight(job: Optional[str] = None) -> bool:
    """Whether a shuffle trial is mid-flight — for ``job``, THAT
    tenant's trial specifically (a registered-but-idle job must not
    trip only_in_flight rules; a job this process cannot see stays
    False rather than borrowing the global state)."""
    import sys as _sys

    shuffle_mod = _sys.modules.get("ray_shuffling_data_loader_tpu.shuffle")
    if shuffle_mod is None:
        return False
    try:
        status = shuffle_mod.live_status()
        if job is None:
            return bool(status.get("running"))
        jobs = status.get("jobs") or {}
        if job in jobs:
            return bool(jobs[job].get("running"))
        return False
    except Exception:
        return False


def _live_job_ids(flat: Dict[str, float]) -> List[str]:
    """The tenant set a ``per_job`` rule expands over. The service
    plane's liveness-checked registry wins when armed; the shuffle
    live-trial tracker is next; with neither loaded (unit tests,
    external metric registries) the ``job=`` labels present in the
    aggregate. Empty means "no tenants": per-job rules degrade to
    their global instance."""
    import sys as _sys

    svc = _sys.modules.get("ray_shuffling_data_loader_tpu.runtime.service")
    if svc is not None:
        try:
            if svc.enabled():
                return sorted(
                    str(rec.get("job_id"))
                    for rec in svc.jobs_snapshot()
                    if rec.get("job_id") and svc._record_live(rec)
                )
        except Exception:
            pass
    shuffle_mod = _sys.modules.get("ray_shuffling_data_loader_tpu.shuffle")
    if shuffle_mod is not None:
        try:
            jobs = shuffle_mod.live_status().get("jobs") or {}
            ids = sorted(
                j for j, st in jobs.items()
                if st.get("running") and j != "_default"
            )
            if ids:
                return ids
        except Exception:
            pass
    ids = set()
    for key, value in flat.items():
        base, labels, _suffix = _split_key(key)
        if base.startswith("alert."):
            continue  # our own job-labeled gauges must not keep a
            # departed tenant alive
        jid = labels.get("job")
        if jid and "source" not in labels and value:
            ids.add(jid)
    return sorted(ids)


def _condition(
    rule: Dict[str, Any],
    flat: Optional[Dict[str, float]],
    now: float,
    job: Optional[str] = None,
) -> Tuple[Optional[bool], Optional[float]]:
    """(condition, observed value) for one rule instance; condition
    None means "unknown" (no data) — treated as not-firing for
    threshold/rate. A per-job instance evaluates ``per_job_metric``
    (default: the rule's ``metric``) restricted to that tenant."""
    kind = str(rule.get("kind", "threshold"))
    if job is not None:
        metric = str(rule.get("per_job_metric") or rule.get("metric", ""))
    else:
        metric = str(rule.get("metric", ""))
    op = _OPS.get(str(rule.get("op", ">")))
    target = float(rule.get("value", 0.0))
    if kind == "absence":
        window_s = rule.get("window_s")
        value = _aggregate_value(metric, flat, job=job)
        if value is None:
            return True, None
        if window_s and not _metric_fresh_in_ring(
            metric, float(window_s), now=now, job=job
        ):
            return True, value
        return False, value
    if op is None or not metric:
        return None, None
    if kind == "rate":
        rate = _window_rate(
            metric, float(rule.get("window_s", 60.0)), now=now,
            fold=str(rule.get("fold", "sum")),
            job=job,
            field=str(rule.get("field", "rate")),
        )
        if rate is None:
            return None, None
        return op(rate, target), rate
    value = _aggregate_value(metric, flat, job=job)
    if value is None:
        return None, None
    return op(value, target), value


# ---------------------------------------------------------------------------
# State machine
# ---------------------------------------------------------------------------


def _rule_row(rule: Dict[str, Any], state: Dict[str, Any]) -> Dict[str, Any]:
    """The one ``/alerts`` row shape — shared by :func:`evaluate` and
    :func:`alerts_body` so the page served mid-tick and between ticks
    cannot drift."""
    job = state.get("job")
    metric = rule.get("metric")
    if job is not None:
        metric = rule.get("per_job_metric") or metric
    return {
        "name": str(rule["name"]),
        "kind": rule.get("kind", "threshold"),
        "metric": metric,
        "job": job,
        "op": rule.get("op"),
        "threshold": rule.get("value"),
        "severity": rule.get("severity", "warn"),
        "state": state.get("state", "ok"),
        "active": state.get("state") == "firing",
        "value": state.get("value"),
        "since": state.get("since"),
        "fired_ts": state.get("fired_ts"),
        "resolved_ts": state.get("resolved_ts"),
        "fired_count": state.get("fired_count", 0),
    }


def _active_name(row: Dict[str, Any]) -> str:
    """The ``active`` list entry: the rule name, instance-qualified
    (``rule|job``) for per-job instances."""
    job = row.get("job")
    return f"{row['name']}|{job}" if job else str(row["name"])


def _emit(kind: str, rule: Dict[str, Any], state: Dict[str, Any]) -> None:
    try:
        from ray_shuffling_data_loader_tpu import telemetry as _t

        metric = rule.get("metric")
        extra: Dict[str, Any] = {}
        if state.get("job"):
            extra["job"] = state["job"]
            metric = rule.get("per_job_metric") or metric
        _t.emit_event(
            kind,
            _flush=True,
            rule=rule["name"],
            severity=rule.get("severity", "warn"),
            metric=metric,
            value=state.get("value"),
            threshold=rule.get("value"),
            **extra,
        )
    except Exception:
        pass


def evaluate(now: Optional[float] = None) -> Dict[str, Any]:
    """One engine tick: evaluate every rule against the aggregated
    registry + timeseries ring, advance the ok → pending → firing →
    resolved state machine, emit fire/resolve events + gauges. A
    ``per_job`` rule expands into one independent instance per live
    job (state key ``rule|job``, gauge ``alert.active{rule,job}``,
    job-stamped events); with no live jobs it degrades to the single
    global instance. Called by the sampler tick; returns the
    ``/alerts`` body. Never raises."""
    now = time.time() if now is None else float(now)
    try:
        flat = _export.aggregate(per_source=True)
    except Exception:
        flat = {}
    in_flight = _trial_in_flight()
    jobs = _live_job_ids(flat)
    reg = _metrics.registry if _metrics.enabled() else None
    rows: List[Dict[str, Any]] = []
    seen_keys = set()
    for rule in rules():
        name = str(rule["name"])
        if rule.get("per_job") and jobs:
            instances: List[Tuple[str, Optional[str]]] = [
                (f"{name}|{j}", j) for j in jobs
            ]
        else:
            instances = [(name, None)]
        for skey, job in instances:
            seen_keys.add(skey)
            with _lock:
                state = _states.setdefault(
                    skey, {"state": "ok", "since": None, "fired_count": 0}
                )
                if job is not None:
                    state["job"] = job
            try:
                if rule.get("only_in_flight") and not (
                    in_flight if job is None else _trial_in_flight(job)
                ):
                    cond, value = False, None
                else:
                    cond, value = _condition(rule, flat, now, job=job)
            except Exception:
                cond, value = None, None
            labels = {"rule": name}
            if job is not None:
                labels["job"] = job
            with _lock:
                state["value"] = value
                for_s = float(rule.get("for_s", 0.0))
                st = state["state"]
                if cond:
                    if st == "ok":
                        state["state"] = "pending"
                        state["since"] = now
                        st = "pending"
                    if st == "pending" and now - state["since"] >= for_s:
                        state["state"] = "firing"
                        state["fired_ts"] = now
                        state["fired_count"] += 1
                        _fired_totals[skey] = _fired_totals.get(skey, 0) + 1
                        entry = {"ts": now, "rule": name, "event": "fired",
                                 "value": value}
                        if job is not None:
                            entry["job"] = job
                        _history.append(entry)
                        del _history[:-_HISTORY_CAP]
                        _emit("alert.fired", rule, state)
                        if reg is not None:
                            reg.counter("alert.fired_total", **labels).inc()
                else:
                    if st == "firing":
                        state["state"] = "ok"
                        state["since"] = None
                        state["resolved_ts"] = now
                        entry = {"ts": now, "rule": name,
                                 "event": "resolved", "value": value}
                        if job is not None:
                            entry["job"] = job
                        _history.append(entry)
                        del _history[:-_HISTORY_CAP]
                        _emit("alert.resolved", rule, state)
                    elif st == "pending":
                        state["state"] = "ok"
                        state["since"] = None
                if reg is not None:
                    reg.gauge("alert.active", **labels).set(
                        1.0 if state["state"] == "firing" else 0.0
                    )
                rows.append(_rule_row(rule, state))
    _drop_stale_instances(seen_keys, now, reg)
    with _lock:
        history = list(_history)
    return {
        "ts": now,
        "trial_in_flight": in_flight,
        "jobs": jobs,
        "rules": rows,
        "active": [_active_name(r) for r in rows if r["active"]],
        "history": history,
    }


def _drop_stale_instances(seen_keys, now, reg) -> None:
    """Retire state for instances the tick no longer evaluates — a
    per-job instance whose tenant left the live set, or a global
    instance superseded by per-job expansion. A firing one resolves on
    the way out (gauge to 0, event emitted): a departed tenant must
    not hold a page open forever. Lifetime fire counts survive in
    ``_fired_totals``."""
    with _lock:
        stale = [(k, _states.pop(k)) for k in list(_states)
                 if k not in seen_keys]
    by_name = {str(r["name"]): r for r in rules()}
    for key, state in stale:
        rname = key.split("|", 1)[0]
        labels = {"rule": rname}
        if state.get("job"):
            labels["job"] = state["job"]
        if state.get("state") == "firing":
            state["state"] = "ok"
            state["resolved_ts"] = now
            entry = {"ts": now, "rule": rname, "event": "resolved",
                     "value": state.get("value")}
            if state.get("job"):
                entry["job"] = state["job"]
            with _lock:
                _history.append(entry)
                del _history[:-_HISTORY_CAP]
            _emit("alert.resolved", by_name.get(rname, {"name": rname}),
                  state)
        if reg is not None:
            try:
                reg.gauge("alert.active", **labels).set(0.0)
            except Exception:
                pass


def alerts_body() -> Dict[str, Any]:
    """The ``/alerts`` page: the last evaluated state WITHOUT forcing
    an evaluation (cadence belongs to the sampler tick); evaluates
    once if the engine has never run (e.g. headless one-shot use)."""
    with _lock:
        evaluated = bool(_states)
        history = list(_history)
    if not evaluated:
        return evaluate()
    rows: List[Dict[str, Any]] = []
    for rule in rules():
        name = str(rule["name"])
        with _lock:
            keys = sorted(
                k for k in _states
                if k == name or k.startswith(name + "|")
            ) or [name]
            states = [dict(_states.get(k) or {}) for k in keys]
        for state in states:
            rows.append(_rule_row(rule, state))
    return {
        "ts": time.time(),
        "rules": rows,
        "active": [_active_name(r) for r in rows if r["active"]],
        "history": history,
    }


def fired_counts() -> Dict[str, int]:
    """``{rule or rule|job: times fired}`` over this engine's lifetime
    (kept apart from instance state, so a departed tenant's counts
    survive its cleanup) — what the run ledger records."""
    with _lock:
        return {key: int(n) for key, n in _fired_totals.items() if n}


def active_alerts_by_job() -> Dict[str, List[str]]:
    """``{job_id: [firing rule names]}`` over the per-job instances —
    the fleet view's (``/jobs``) alert column."""
    out: Dict[str, List[str]] = {}
    with _lock:
        for key, state in _states.items():
            job = state.get("job")
            if job and state.get("state") == "firing":
                out.setdefault(job, []).append(key.split("|", 1)[0])
    return {job: sorted(names) for job, names in out.items()}


def status_section() -> Dict[str, Any]:
    """The trimmed view ``/status`` embeds (the full one lives at
    ``/alerts``)."""
    body = alerts_body()
    return {
        "active": body["active"],
        "fired_counts": fired_counts(),
        "rules": len(body["rules"]),
    }
