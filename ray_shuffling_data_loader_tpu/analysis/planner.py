"""Cost-based shuffle-plan compiler + between-epoch re-planner (ISSUE 20).

The repo grew ~60 ``RSDL_*`` knobs whose correct settings are
shape-dependent (ROADMAP item 3): blocks/file >= 2R for block-plan
quality, selective-vs-materialized by prunability *and* cache fit,
decode threads vs cores, fetch-window depth vs the store budget.
PRs 16-19 built the telemetry that can choose them; this module closes
the loop. :func:`compile_plan` runs once per ``shuffle()`` on the
driver: a **footer-stats pass** (row-group counts and sizes plus
schema column widths — the same no-data-read inputs ``_group_owners``
already plans from) feeds a small explicit cost model that resolves
every planner-owned knob into a
:class:`~ray_shuffling_data_loader_tpu.runtime.plan.ResolvedPlan`.

Override semantics (the refactor's contract): an env-set knob **pins**
its term — the planner records the env value with ``source="env"`` and
never touches it; an unset knob gets the planned default. The driver
threads effective values through stage-task *arguments* (workers' env
snapshots date from pool spawn — the PR 12 lesson), so planned and
hand-set runs execute identically for identical terms.

:func:`replan` is the second half: at each epoch boundary the driver
feeds it the live ``/critical`` + ``/capacity`` + timeseries signals
(the elastic loop proved signal->actuator at this cadence) and it
adjusts the *mutable-mid-run* subset — fetch-window depth, decode
row-group threads, selective engagement — emitting one
``plan.replanned`` event per adjustment with before/after terms so
``tools/epoch_report.py`` and the run ledger can attribute throughput
deltas to decisions. Env-pinned terms are never re-planned.

Gate: ``RSDL_PLAN=auto|on`` (``shuffle.py`` checks the env *before*
importing this plane; ``GATED_PLANES`` entry, fresh-interpreter
zero-overhead test in ``tests/test_planner.py``).
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_shuffling_data_loader_tpu.runtime.plan import (
    MUTABLE_TERMS,
    PlanTerm,
    ResolvedPlan,
    SOURCE_ENV,
    SOURCE_PLANNED,
    SOURCE_REPLANNED,
)

# Term -> knob mapping: every planner-emitted term names the registry
# knob it owns. rsdl_lint's knob-registry checker cross-checks this
# literal against the ``planned=True`` entries in
# analysis/knob_registry.py — drift between cost model and registry is
# a lint failure, in both directions.
TERM_KNOBS = {
    "plan": "RSDL_SHUFFLE_PLAN",
    "selective": "RSDL_SELECTIVE_READS",
    "columns": "RSDL_DECODE_PUSHDOWN",
    "decode_rowgroup_threads": "RSDL_DECODE_ROWGROUPS",
    "fetch_window_depth": "RSDL_FETCH_WINDOW_DEPTH",
    "native_threads": "RSDL_NATIVE_THREADS",
}

# Cost-model constants. Each is a *measured* anchor, not a free
# parameter: the quality bound and window clamps come from BENCHLOG
# r11/r12 and the r7 fetch-depth sweep; the budget fraction mirrors
# the decode-cache auto policy's "fits comfortably" margin.
QUALITY_BLOCKS_PER_FILE = 2  # blocks/file >= 2R (ROADMAP item 3)
WINDOW_BUDGET_FRAC = 0.25  # in-flight windows' share of the store budget
WINDOW_DEPTH_MIN = 1
WINDOW_DEPTH_MAX = 8  # measured flat 2..8 on loopback (BENCHLOG r7)
WINDOW_DEPTH_DEFAULT = 4
FOOTER_SAMPLE_CAP = 64  # strided footer sample for huge file lists
DECODED_HEADROOM = 1.15  # same planning headroom as _est_decoded_bytes
SHM_HIGH_WATER = 0.85  # matches RSDL_EVICT_HIGH_WATERMARK's default
SHM_HEADROOM = 0.5  # below this, deepening windows is safe
NATIVE_THREADS_CAP = 8  # gathers saturate DRAM past this (native/__init__)


def _env_set(name: str) -> bool:
    return bool((os.environ.get(name) or "").strip())


def _cores() -> int:
    return os.cpu_count() or 1


# -- footer-stats pass -------------------------------------------------------


def footer_stats(
    filenames: Sequence[str],
    columns: Optional[Sequence[str]] = None,
    narrow_to_32: bool = False,
) -> Dict[str, Any]:
    """No-data-read dataset shape from Parquet footers: per-file
    row-group counts, total rows, and a decoded-bytes estimate from
    schema column widths (narrowed widths when the run narrows).
    Footers are process-cached (``file_row_group_sizes``); a strided
    sample caps the sweep on huge file lists — group counts and schema
    are uniform across a generated dataset, so the sample generalizes.
    OSError from an unreadable footer degrades to unknown (None
    fields): every downstream term has a safe default."""
    import importlib

    # The package __init__ re-exports the shuffle FUNCTION over the
    # module name, so attribute-style imports resolve to the function.
    _shuffle = importlib.import_module(
        "ray_shuffling_data_loader_tpu.shuffle"
    )

    files = list(filenames)
    stride = max(1, len(files) // FOOTER_SAMPLE_CAP)
    sampled = files[::stride][:FOOTER_SAMPLE_CAP]
    groups: List[int] = []
    rows_sampled = 0
    try:
        for f in sampled:
            sizes = _shuffle.file_row_group_sizes(f)
            groups.append(len(sizes))
            rows_sampled += int(sum(sizes))
    except OSError:
        return {"files": len(files), "groups_min": None, "rows": None,
                "bytes_per_row": None, "est_decoded_bytes": None}
    rows_total = int(rows_sampled * (len(files) / max(1, len(sampled))))
    bytes_per_row: Optional[float] = None
    try:
        pf, _, _ = _shuffle._open_parquet_file(sampled[0])
        schema = pf.schema_arrow
        want = {str(c) for c in columns} if columns else None
        width = 0
        for fld in schema:
            if want is not None and fld.name not in want:
                continue
            dt = _shuffle._np_dtype_of(fld)
            if dt is None:
                continue
            itemsize = dt.itemsize
            if narrow_to_32 and itemsize == 8:
                itemsize = 4
            for w in _shuffle._row_shape_of(fld):
                itemsize *= w
            width += itemsize
        if width:
            bytes_per_row = float(width)
    except Exception:
        bytes_per_row = None
    est = (
        rows_total * bytes_per_row * DECODED_HEADROOM
        if bytes_per_row is not None
        else None
    )
    return {
        "files": len(files),
        "files_sampled": len(sampled),
        "groups_min": min(groups) if groups else None,
        "groups_max": max(groups) if groups else None,
        "rows": rows_total,
        "bytes_per_row": bytes_per_row,
        "est_decoded_bytes": est,
    }


def _store_budget() -> Optional[int]:
    """The store's capacity budget (bytes) — the same number the
    capacity ledger watermarks against. None when budgeting is off."""
    try:
        from ray_shuffling_data_loader_tpu import runtime as _runtime

        return _runtime.get_context().store.capacity_bytes
    except Exception:
        return None


# -- the cost model ----------------------------------------------------------


def compile_plan(
    filenames: Sequence[str],
    *,
    num_reducers: int,
    num_trainers: int = 1,
    num_epochs: int = 1,
    start_epoch: int = 0,
    columns: Optional[Sequence[str]] = None,
    device_layout: Optional[dict] = None,
    narrow_to_32: bool = False,
    cache_decoded: bool = True,
) -> ResolvedPlan:
    """Resolve every planner-owned knob once, driver-side.

    Terms and their models (each lands verbatim in ``plan.chosen``):

    * **plan** — ``block:G`` with ``G = groups_min // (2R)`` whenever
      the quality bound ``blocks/file >= 2R`` is satisfiable
      (``ceil(g/G) >= 2R`` holds for that G by construction); a
      dataset whose files carry fewer than ``2R`` row groups cannot
      meet the bound at any granularity, so it stays ``rowwise``.
    * **selective** — engage only when the plan is prunable (block)
      AND the run will NOT ride the cross-epoch decode cache (the
      run's ``cache_decoded`` argument gating ``_decode_cache_auto``;
      cache off means nothing amortizes): with a hot cache the
      materialized/index path amortizes one decode across epochs,
      which beats re-decoding selections every epoch; without it,
      selective's zero map materialization wins (BENCHLOG r11/r12).
    * **columns** — project to the staging layout's column set when
      the layout proves the touchable set and neither the caller nor
      ``RSDL_DECODE_PUSHDOWN`` said otherwise (audit-key append stays
      with ``_pushdown_columns``).
    * **decode_rowgroup_threads** — the fair-share rule
      (``cores // concurrent`` when idle cores exist, else 1) computed
      over the *wider* of the two decode stages (map files vs
      selective reducers), so neither site oversubscribes.
    * **fetch_window_depth** — deepest window pipeline whose total
      in-flight residency (``R`` concurrent reducers x depth windows
      of ``est_bytes/(F*R)``) stays under ``WINDOW_BUDGET_FRAC`` of
      the store budget, clamped to the measured-flat [1, 8] range.
    * **native_threads** — kernel threads fair-shared across the
      reducers that gather concurrently, capped at the DRAM-saturation
      point (8).
    """
    files = list(filenames)
    R = max(1, int(num_reducers))
    cores = _cores()
    stats = footer_stats(files, columns=columns, narrow_to_32=narrow_to_32)
    budget = _store_budget()
    terms: Dict[str, PlanTerm] = {}

    def term(name, value, source, why):
        terms[name] = PlanTerm(
            name=name, knob=TERM_KNOBS[name], value=value,
            source=source, why=why,
        )

    from ray_shuffling_data_loader_tpu.utils import shuffle_plan_spec

    # plan family / granularity
    if _env_set("RSDL_SHUFFLE_PLAN"):
        plan = shuffle_plan_spec()
        term("plan", plan, SOURCE_ENV, "pinned by RSDL_SHUFFLE_PLAN")
    else:
        g = stats.get("groups_min")
        bound = QUALITY_BLOCKS_PER_FILE * R
        if g is not None and g >= bound:
            G = max(1, g // bound)
            plan = ("block", G)
            term(
                "plan", plan, SOURCE_PLANNED,
                f"block:{G}: blocks/file {-(-g // G)} >= 2R={bound} "
                f"(min {g} groups/file)",
            )
        else:
            plan = ("rowwise", 0)
            term(
                "plan", plan, SOURCE_PLANNED,
                f"rowwise: min {g} groups/file cannot meet "
                f"blocks/file >= 2R={bound} at any granularity",
            )

    # selective engagement
    import importlib

    # The package __init__ re-exports the shuffle FUNCTION over the
    # module name, so attribute-style imports resolve to the function.
    _shuffle = importlib.import_module(
        "ray_shuffling_data_loader_tpu.shuffle"
    )

    if _env_set("RSDL_SELECTIVE_READS"):
        engaged, reason = _shuffle.selective_reads_decision(plan)
        term("selective", bool(engaged), SOURCE_ENV, reason)
    else:
        prunable = plan[0] == "block"
        # The cache-amortization argument only exists when the run's
        # decode cache is ON (``cache_decoded`` is a shuffle() call
        # argument, not a knob): with it off, a "cache-friendly" size
        # amortizes nothing and selective wins on any prunable plan.
        cache_friendly = False
        if prunable and cache_decoded:
            try:
                cache_friendly = _shuffle._decode_cache_auto(
                    files, num_epochs - start_epoch, narrow_to_32, columns
                )
            except Exception:
                cache_friendly = False
        if not prunable:
            why = "declined: rowwise plan is not prunable (selective " \
                  "would re-read every group ~R times)"
        elif cache_friendly:
            why = "declined: decoded dataset fits the cross-epoch " \
                  "decode cache — one decode amortized beats per-epoch " \
                  "selective re-reads"
        else:
            why = "engaged: block plan prunes for real and the decoded " \
                  "dataset will not be cache-resident"
        term("selective", prunable and not cache_friendly,
             SOURCE_PLANNED, why)

    # column projection
    projection: Optional[List[str]] = None
    if _env_set("RSDL_DECODE_PUSHDOWN"):
        term("columns", None, SOURCE_ENV, "pinned by RSDL_DECODE_PUSHDOWN")
    elif columns is not None:
        term("columns", [str(c) for c in columns], SOURCE_ENV,
             "caller-provided projection")
    elif device_layout is not None and device_layout.get("columns"):
        projection = [str(c) for c in device_layout["columns"]]
        term("columns", list(projection), SOURCE_PLANNED,
             "staging layout proves the touchable column set")
    else:
        term("columns", None, SOURCE_PLANNED,
             "full decode: no layout or caller projection to prove "
             "the touchable set")

    # decode row-group threads (fair share over the wider decode stage)
    from ray_shuffling_data_loader_tpu.utils import decode_rowgroup_threads

    decode_conc = min(cores, max(1, max(len(files), R)))
    if _env_set("RSDL_DECODE_ROWGROUPS"):
        value = decode_rowgroup_threads(decode_conc)
        term("decode_rowgroup_threads", value, SOURCE_ENV,
             "pinned by RSDL_DECODE_ROWGROUPS")
    else:
        value = cores // decode_conc if cores >= 2 * decode_conc else 1
        term(
            "decode_rowgroup_threads", max(1, value), SOURCE_PLANNED,
            f"fair share: {cores} cores / {decode_conc} concurrent "
            "decode tasks",
        )

    # reduce fetch-window depth vs the store budget
    if _env_set("RSDL_FETCH_WINDOW_DEPTH"):
        from ray_shuffling_data_loader_tpu.runtime.store import (
            fetch_window_depth,
        )

        term("fetch_window_depth", fetch_window_depth(default=4),
             SOURCE_ENV, "pinned by RSDL_FETCH_WINDOW_DEPTH")
    else:
        est = stats.get("est_decoded_bytes")
        if est and budget and files:
            window_bytes = max(1.0, est / (len(files) * R))
            conc_reducers = min(R, cores)
            depth = int(
                (WINDOW_BUDGET_FRAC * budget)
                / (window_bytes * max(1, conc_reducers))
            )
            depth = max(WINDOW_DEPTH_MIN, min(WINDOW_DEPTH_MAX, depth))
            term(
                "fetch_window_depth", depth, SOURCE_PLANNED,
                f"{conc_reducers} reducers x depth windows of "
                f"~{int(window_bytes)}B within "
                f"{WINDOW_BUDGET_FRAC:.0%} of the {budget}B budget",
            )
        else:
            term("fetch_window_depth", WINDOW_DEPTH_DEFAULT,
                 SOURCE_PLANNED,
                 "default: dataset size or store budget unknown")

    # native kernel threads
    if _env_set("RSDL_NATIVE_THREADS"):
        from ray_shuffling_data_loader_tpu import native as _native

        term("native_threads", _native.num_threads(), SOURCE_ENV,
             "pinned by RSDL_NATIVE_THREADS")
    else:
        conc_reducers = max(1, min(R, cores))
        value = max(1, min(NATIVE_THREADS_CAP, cores // conc_reducers))
        term(
            "native_threads", value, SOURCE_PLANNED,
            f"fair share: {cores} cores / {conc_reducers} concurrent "
            f"reducers, capped at {NATIVE_THREADS_CAP}",
        )

    model = {
        "num_reducers": R,
        "num_trainers": int(num_trainers),
        "num_epochs": int(num_epochs),
        "cores": cores,
        "store_budget_bytes": budget,
        "stats": stats,
    }
    return ResolvedPlan(
        plan=plan, projection=projection, terms=terms, model=model
    )


# -- between-epoch re-planner ------------------------------------------------


def _live_signals() -> Dict[str, Any]:
    """Live signals from whichever telemetry planes are armed —
    ``sys.modules`` only (the re-planner must never be the reason a
    dark plane loads; same rule as the run ledger). Absent planes
    simply contribute nothing and the re-planner holds."""
    out: Dict[str, Any] = {}
    pkg = "ray_shuffling_data_loader_tpu."
    capacity = sys.modules.get(pkg + "telemetry.capacity")
    if capacity is not None:
        try:
            out["shm_used_frac"] = (capacity.view() or {}).get(
                "shm_used_frac"
            )
        except Exception:
            pass
    critical = sys.modules.get(pkg + "telemetry.critical")
    if critical is not None:
        try:
            analysis = critical.analyze()
            current = analysis.get("current") or {}
            out["critical_path"] = current.get("critical_path")
            out["sole_share"] = current.get("sole_share")
            stalls = analysis.get("stall_by_cause") or {}
            if stalls:
                out["stall_by_cause"] = stalls
        except Exception:
            pass
    timeseries = sys.modules.get(pkg + "telemetry.timeseries")
    if timeseries is not None:
        try:
            rates = getattr(timeseries, "rates", None)
            if callable(rates):
                out["rates"] = rates()
        except Exception:
            pass
    return out


def replan(rplan: ResolvedPlan, *, epoch: int) -> List[Dict[str, Any]]:
    """Adjust the mutable-mid-run terms between epochs from live
    signals. Rules (each bounded, each an explicit ``plan.replanned``
    event with before/after so the ledger can attribute the delta):

    * shm over the high watermark -> halve the fetch-window depth
      (windows are the in-flight residency the planner sized), and
      engage selective on a prunable plan (drops the materialized
      map's store footprint entirely);
    * reduce-dominant epoch with shm headroom -> double the window
      depth (the reduce is starving on fetches, and residency has
      room), up to the measured-flat cap;
    * map(decode)-dominant epoch -> double decode row-group threads
      up to the core count (the planner's fair share assumed every
      stage task runs at once; a decode-bound run has idle cores).

    Env-pinned terms are never touched — the operator's pin outranks
    the re-planner exactly as it outranks the compiler."""
    signals = _live_signals()
    if not signals:
        return []
    changes: List[Dict[str, Any]] = []

    def mutate(name: str, value: Any, reason: str) -> None:
        t = rplan.terms.get(name)
        if (
            t is None
            or name not in MUTABLE_TERMS
            or t.source == SOURCE_ENV
            or t.value == value
        ):
            return
        changes.append(
            {"term": name, "before": t.value, "after": value,
             "reason": reason}
        )
        t.value = value
        t.source = SOURCE_REPLANNED
        t.why = reason

    shm = signals.get("shm_used_frac")
    path = signals.get("critical_path")
    depth = rplan.term_value("fetch_window_depth")
    if shm is not None and shm >= SHM_HIGH_WATER:
        if isinstance(depth, int) and depth > WINDOW_DEPTH_MIN:
            mutate(
                "fetch_window_depth", max(WINDOW_DEPTH_MIN, depth // 2),
                f"shm {shm:.0%} >= {SHM_HIGH_WATER:.0%} watermark: "
                "shed in-flight window residency",
            )
        if rplan.plan[0] == "block" and not rplan.term_value("selective"):
            mutate(
                "selective", True,
                f"shm {shm:.0%} >= {SHM_HIGH_WATER:.0%} watermark: "
                "selective schedule drops map materialization",
            )
    elif path == "reduce" and (shm is None or shm < SHM_HEADROOM):
        if isinstance(depth, int) and depth < WINDOW_DEPTH_MAX:
            mutate(
                "fetch_window_depth", min(WINDOW_DEPTH_MAX, depth * 2),
                "reduce-dominant epoch with shm headroom: deepen the "
                "fetch pipeline",
            )
    if path == "map":
        threads = rplan.term_value("decode_rowgroup_threads")
        cores = _cores()
        if isinstance(threads, int) and threads < cores:
            mutate(
                "decode_rowgroup_threads", min(cores, threads * 2),
                "map(decode)-dominant epoch: grant decode more of the "
                "idle cores",
            )
    if changes:
        rplan.replans += len(changes)
        from ray_shuffling_data_loader_tpu import telemetry as _telemetry
        from ray_shuffling_data_loader_tpu.telemetry import (
            metrics as _metrics,
        )

        for change in changes:
            _telemetry.emit_event(
                "plan.replanned", epoch=epoch, term=change["term"],
                before=str(change["before"]), after=str(change["after"]),
                reason=change["reason"],
            )
            _metrics.safe_inc("plan.replans", term=change["term"])
    return changes
