"""Telemetry subsystem tests: span nesting + context propagation (incl.
across the runtime actor/task process boundaries), Chrome-trace JSON
schema validity, metrics snapshot round-trip, and the end-to-end
acceptance run — a CPU-backend shuffle whose exported trace shows map,
reduce, queue-admission, and staging spans for two overlapping epochs,
plus a metrics JSON with queue-depth and stall-by-cause series."""

import json
import os

import pytest

from ray_shuffling_data_loader_tpu import runtime, telemetry
from ray_shuffling_data_loader_tpu.telemetry import metrics


_TELEMETRY_ENV = ("RSDL_TRACE", "RSDL_METRICS", "RSDL_TRACE_DIR")


@pytest.fixture
def telemetry_on(tmp_path):
    """Tracing + metrics on, spooling to a per-test dir; fully unwound on
    teardown (env popped, cached enabled-state and buffers cleared) so
    the rest of the suite keeps its telemetry-off default."""
    saved = {k: os.environ.get(k) for k in _TELEMETRY_ENV}
    spool = str(tmp_path / "spool")
    os.environ["RSDL_TRACE"] = "1"
    os.environ["RSDL_METRICS"] = "1"
    os.environ["RSDL_TRACE_DIR"] = spool
    telemetry.refresh_from_env()
    metrics.refresh_from_env()
    telemetry.reset_state()
    metrics.reset()
    yield spool
    telemetry.reset_state()
    metrics.reset()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    telemetry.refresh_from_env()
    metrics.refresh_from_env()


@pytest.fixture
def traced_runtime(telemetry_on):
    """A runtime session created AFTER telemetry was enabled, so spawned
    workers and actors inherit the trace env."""
    ctx = runtime.init(num_workers=2)
    yield ctx
    runtime.shutdown()


def _load_trace(path):
    with open(path) as f:
        payload = json.load(f)
    assert set(payload) >= {"traceEvents"}
    events = payload["traceEvents"]
    assert isinstance(events, list)
    for e in events:
        # Chrome-trace required fields per event phase.
        assert {"name", "ph", "pid", "tid"} <= set(e), e
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e and e["dur"] >= 0, e
    return events


def _spans(events, name=None, cat=None):
    out = [e for e in events if e["ph"] == "X"]
    if name is not None:
        out = [e for e in out if e["name"] == name]
    if cat is not None:
        out = [e for e in out if e.get("cat") == cat]
    return out


# ---------------------------------------------------------------------------
# Tracing core
# ---------------------------------------------------------------------------


def test_disabled_tracing_is_noop(tmp_path):
    # Point at a fresh empty spool and clear any buffered state so this
    # test holds even when the suite itself runs with telemetry on
    # globally (the run_ci_tests.sh telemetry-on lane).
    saved = {k: os.environ.get(k) for k in _TELEMETRY_ENV}
    os.environ["RSDL_TRACE_DIR"] = str(tmp_path / "empty-spool")
    telemetry.disable()
    metrics.disable()
    telemetry.reset_state()
    try:
        # The disabled path hands back one shared null object — no
        # allocation, no clock read.
        assert telemetry.trace_span("a") is telemetry.trace_span("b")
        with telemetry.trace_span("a") as sp:
            sp.set(x=1)
        telemetry.record_span("late", 0.0, 1.0)
        telemetry.instant("tick")
        out = telemetry.trace_export(str(tmp_path / "t.json"))
        assert _load_trace(out) == []
        assert not metrics.enabled()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        telemetry.refresh_from_env()
        metrics.refresh_from_env()


def test_span_nesting_context_and_schema(telemetry_on, tmp_path):
    with telemetry.context(trial=1):
        with telemetry.trace_span("outer", cat="t"):
            with telemetry.context(epoch=2):
                with telemetry.trace_span("inner", cat="t", extra="x"):
                    pass
    telemetry.record_span("retro", 100.0, 0.25, cat="t", epoch=9)
    telemetry.instant("tick", cat="t")
    out = telemetry.trace_export(str(tmp_path / "trace.json"))
    events = _load_trace(out)

    (outer,) = _spans(events, "outer")
    (inner,) = _spans(events, "inner")
    (retro,) = _spans(events, "retro")
    # Context stack merges into span args; inner sees both frames.
    assert outer["args"]["trial"] == 1 and "epoch" not in outer["args"]
    # ... and names the live span that caused it.
    assert inner["args"] == {
        "trial": 1, "epoch": 2, "extra": "x", "parent": "outer",
    }
    assert "parent" not in outer["args"]
    # Nesting: inner lies within outer on the same thread track.
    assert inner["tid"] == outer["tid"]
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    # Retroactive spans convert seconds to microseconds.
    assert retro["ts"] == pytest.approx(100.0 * 1e6)
    assert retro["dur"] == pytest.approx(0.25 * 1e6)
    # Process/thread metadata events come first (viewer convention).
    assert events[0]["ph"] == "M"
    assert any(e["ph"] == "i" and e["name"] == "tick" for e in events)


def test_span_error_attr_and_buffer_cap(telemetry_on, tmp_path):
    with pytest.raises(ValueError):
        with telemetry.trace_span("fails"):
            raise ValueError("boom")
    os.environ["RSDL_TRACE_BUFFER"] = "4"
    telemetry.refresh_from_env()  # the buffer limit is cached per process
    try:
        for i in range(32):
            telemetry.record_span(f"s{i}", 0.0, 0.1)
        assert telemetry.dropped_events() > 0
    finally:
        os.environ.pop("RSDL_TRACE_BUFFER", None)
        telemetry.refresh_from_env()
    events = _load_trace(telemetry.trace_export(str(tmp_path / "t.json")))
    (failed,) = _spans(events, "fails")
    assert failed["args"]["error"] == "ValueError"


class _ProbeActor:
    def work(self, tag):
        with telemetry.trace_span("probe:inner", tag=tag):
            return dict(telemetry.current_context())


def _probe_task(tag):
    with telemetry.trace_span("probe:task-inner", tag=tag):
        return dict(telemetry.current_context())


def test_context_propagates_across_actor_boundary(traced_runtime, tmp_path):
    h = runtime.spawn_actor(_ProbeActor)
    try:
        with telemetry.context(trial=7, epoch=3):
            remote_ctx = h.call("work", "t1")
    finally:
        h.terminate(grace_period_s=5.0)  # flushes the actor's spool file
    # The caller's context crossed the process boundary and was live
    # inside the actor method.
    assert remote_ctx["trial"] == 7 and remote_ctx["epoch"] == 3

    events = _load_trace(telemetry.trace_export(str(tmp_path / "t.json")))
    (dispatch,) = _spans(events, "actor:work")
    (inner,) = _spans(events, "probe:inner")
    assert dispatch["args"]["trial"] == 7
    assert inner["args"]["trial"] == 7 and inner["args"]["epoch"] == 3
    # Both recorded in the ACTOR process, not the driver.
    assert dispatch["pid"] != os.getpid()
    assert inner["pid"] == dispatch["pid"]


def test_context_propagates_across_task_boundary(traced_runtime, tmp_path):
    with telemetry.context(trial=5, epoch=1):
        remote_ctx = runtime.submit(_probe_task, "t2").result()
    assert remote_ctx["trial"] == 5 and remote_ctx["epoch"] == 1

    events = _load_trace(telemetry.trace_export(str(tmp_path / "t.json")))
    (wrapper,) = _spans(events, "task:_probe_task")
    (inner,) = _spans(events, "probe:task-inner")
    assert wrapper["args"]["trial"] == 5
    assert inner["args"]["epoch"] == 1
    assert wrapper["pid"] != os.getpid()  # ran in a pool worker


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_metrics_snapshot_roundtrip(telemetry_on, tmp_path):
    reg = metrics.registry
    reg.counter("h2d.bytes").inc(100)
    reg.counter("h2d.bytes").inc(28)  # same instrument re-resolved
    reg.gauge("queue.depth", epoch=0, rank=1).set(4)
    reg.histogram("h2d.dispatch_seconds").observe(0.5)
    reg.histogram("h2d.dispatch_seconds").observe(1.5)
    metrics.register_source("ext", lambda: {"ext.value": 9.0})

    snap = metrics.global_snapshot()
    assert snap["h2d.bytes"] == 128.0
    assert snap[metrics.format_key("queue.depth", {"epoch": 0, "rank": 1})] == 4.0
    assert snap["h2d.dispatch_seconds_count"] == 2.0
    assert snap["h2d.dispatch_seconds_sum"] == 2.0
    assert snap["h2d.dispatch_seconds_min"] == 0.5
    assert snap["h2d.dispatch_seconds_max"] == 1.5
    assert snap["ext.value"] == 9.0

    metrics.record_sample(snap, ts=123.0)
    path = metrics.dump_json(str(tmp_path / "metrics.json"))
    with open(path) as f:
        payload = json.load(f)
    assert payload["samples"][0]["ts"] == 123.0
    assert payload["samples"][0]["values"]["h2d.bytes"] == 128.0
    assert payload["final"]["ext.value"] == 9.0
    # The progress line renders without error from a real snapshot.
    assert "shm=" in metrics.progress_line(snap)


def test_metrics_dead_source_dropped(telemetry_on):
    calls = []

    def dead():
        calls.append(1)
        raise RuntimeError("actor died")

    metrics.register_source("dead", dead)
    for _ in range(5):
        metrics.global_snapshot()
    # Dropped after the failure limit; not polled forever.
    assert len(calls) == 3


def test_type_conflict_rejected(telemetry_on):
    metrics.registry.counter("x.bytes")
    with pytest.raises(TypeError):
        metrics.registry.gauge("x.bytes")


def test_histogram_zero_observations(telemetry_on):
    """A registered-but-never-observed histogram snapshots count/sum only
    — no _min/_max keys (their inf sentinels must never leak into
    artifacts or the Prometheus export)."""
    metrics.registry.histogram("empty.hist")
    snap = metrics.registry.snapshot()
    assert snap["empty.hist_count"] == 0.0
    assert snap["empty.hist_sum"] == 0.0
    assert "empty.hist_min" not in snap
    assert "empty.hist_max" not in snap
    # And the export renders it without inf/nan.
    text = metrics.to_prometheus_text(snap)
    assert "inf" not in text and "nan" not in text


def test_register_source_name_collision_replaces(telemetry_on):
    """Re-using a source name replaces the previous callable (the
    documented semantics) — and resets its failure count, so a re-pointed
    source isn't dropped for its predecessor's sins."""
    metrics.register_source("s", lambda: {"v": 1.0})
    assert metrics.global_snapshot()["v"] == 1.0

    def dying():
        raise RuntimeError("old actor died")

    metrics.register_source("s", dying)
    metrics.global_snapshot()
    metrics.global_snapshot()  # two failures accrued on the replacement
    metrics.register_source("s", lambda: {"v": 3.0})
    # Fresh failure budget: polls keep succeeding well past the old limit.
    for _ in range(5):
        assert metrics.global_snapshot()["v"] == 3.0


def test_refresh_from_env_toggles_midrun(telemetry_on):
    """refresh_from_env re-reads RSDL_METRICS: flipping the env mid-run
    takes effect at the next enabled() check (the cached-boolean gate)."""
    assert metrics.enabled()
    os.environ.pop("RSDL_METRICS", None)
    metrics.refresh_from_env()
    assert not metrics.enabled()
    os.environ["RSDL_METRICS"] = "1"
    # Stale cache until refreshed — that IS the zero-overhead contract.
    assert not metrics.enabled()
    metrics.refresh_from_env()
    assert metrics.enabled()


def test_to_prometheus_text_format(telemetry_on):
    reg = metrics.registry
    reg.counter("h2d.bytes").inc(128)
    reg.counter("big.rows").inc(1_234_567)
    reg.gauge("queue.depth", epoch=0, rank=1).set(4)
    reg.histogram("h2d.dispatch_seconds").observe(0.5)
    reg.histogram("queue.wait", epoch=2).observe(1.0)
    text = metrics.to_prometheus_text(metrics.global_snapshot())
    lines = text.splitlines()
    assert lines[0].startswith("#")
    # Names sanitized to the Prometheus charset and prefixed rsdl_ (own
    # namespace, no relabeling needed); labels quoted; our key syntax
    # maps 1:1.
    assert "rsdl_h2d_bytes 128" in text
    assert 'rsdl_queue_depth{epoch="0",rank="1"} 4' in text
    assert "rsdl_h2d_dispatch_seconds_count 1" in text
    assert "rsdl_h2d_dispatch_seconds_sum 0.5" in text
    # Counters render exactly (%g would truncate to 6 significant digits).
    assert "rsdl_big_rows 1234567\n" in text
    # A labeled histogram's "_count" suffix belongs to the NAME, with the
    # labels preserved — not mangled into the sanitized name.
    assert 'rsdl_queue_wait_count{epoch="2"} 1' in text
    # HELP/TYPE headers per metric name, typed from the registry's kind
    # map (histogram count/sum scrape as counters, min/max as gauges),
    # each emitted immediately before its samples.
    assert "# HELP rsdl_h2d_bytes " in text
    assert "# TYPE rsdl_h2d_bytes counter" in text
    assert "# TYPE rsdl_queue_depth gauge" in text
    assert "# TYPE rsdl_h2d_dispatch_seconds_count counter" in text
    assert "# TYPE rsdl_h2d_dispatch_seconds_min gauge" in text
    assert 'rsdl_queue_wait_count{epoch="2"}' in text
    idx = lines.index("# TYPE rsdl_h2d_bytes counter")
    assert lines[idx + 1].startswith("rsdl_h2d_bytes ")
    # Non-finite values render as Prometheus literals, not a crash.
    assert metrics.to_prometheus_text(
        {"weird": float("nan"), "hot": float("inf")}
    ).count("NaN") == 1
    # Deterministic output: metric groups sorted by name, samples sorted
    # within each group.
    names = [ln.split(" ", 2)[2].split(" ")[0]
             for ln in lines if ln.startswith("# TYPE ")]
    assert names == sorted(names)


# ---------------------------------------------------------------------------
# End-to-end acceptance: CPU-backend shuffle -> trace + metrics artifacts
# ---------------------------------------------------------------------------


def test_e2e_shuffle_trace_and_metrics(traced_runtime, tmp_path):
    """ISSUE 1 acceptance: a small CPU-backend run produces a valid
    Chrome trace with map, reduce, queue-admission, and staging spans for
    >= 2 overlapping epochs, and a metrics JSON snapshot with queue-depth
    and stall-by-cause series (sampled through ObjectStoreStatsCollector
    and fed into TrialStatsCollector)."""
    from ray_shuffling_data_loader_tpu.data_generation import (
        LABEL_COLUMN,
        generate_data,
    )
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.parallel import make_mesh
    from ray_shuffling_data_loader_tpu.stats import (
        ObjectStoreStatsCollector,
        TrialStatsCollector,
    )

    filenames, _ = generate_data(
        num_rows=4096,
        num_files=2,
        num_row_groups_per_file=1,
        max_row_group_skew=0.0,
        data_dir=str(tmp_path / "data"),
    )
    stats_actor = runtime.spawn_actor(TrialStatsCollector, 2, 2, 2)
    telemetry.set_context(trial=0)
    ds = JaxShufflingDataset(
        filenames,
        num_epochs=2,
        num_trainers=1,
        batch_size=512,
        rank=0,
        feature_columns=["key"],
        label_column=LABEL_COLUMN,
        num_reducers=2,
        max_concurrent_epochs=2,
        mesh=make_mesh(model_parallelism=1),
        queue_name="q-telemetry-e2e",
        seed=3,
    )
    with ObjectStoreStatsCollector(stats_actor, sample_period_s=0.05):
        for epoch in range(2):
            ds.set_epoch(epoch)
            for _features, _label in ds:
                pass

    trace_path = telemetry.trace_export(str(tmp_path / "trace.json"))
    events = _load_trace(trace_path)

    # One shared timeline: map + reduce (worker processes), queue
    # admission (driver), H2D staging (trainer thread) — each tagged with
    # a consistent epoch id, present for BOTH pipelined epochs.
    for name in ("map", "reduce", "stage:h2d"):
        epochs = {s["args"]["epoch"] for s in _spans(events, name)}
        assert {0, 1} <= epochs, (name, epochs)
    admissions = _spans(events, "epoch:admission")
    assert {s["args"]["epoch"] for s in admissions} == {0, 1}
    # The queue actor's dispatch spans carry the caller's epoch context
    # across the process boundary.
    actor_new_epochs = _spans(events, "actor:new_epoch")
    assert {s["args"]["epoch"] for s in actor_new_epochs} == {0, 1}
    # Map/reduce spans were recorded in worker processes, admission in
    # the driver: the export really merged multiple process spools.
    assert {s["pid"] for s in _spans(events, "map")} != {os.getpid()}
    assert {s["pid"] for s in admissions} == {os.getpid()}
    # Epoch pipelining is visible on the merged timeline: epoch 1 shuffle
    # work begins before epoch 0's last staging span ends (the window is
    # max_concurrent_epochs=2, so the epochs overlap).
    e0_stage_end = max(
        s["ts"] + s["dur"]
        for s in _spans(events, "stage:h2d")
        if s["args"]["epoch"] == 0
    )
    e1_map_start = min(
        s["ts"] for s in _spans(events, "map") if s["args"]["epoch"] == 1
    )
    assert e1_map_start < e0_stage_end

    # Metrics artifact: queue-depth and stall-by-cause series.
    metrics_path = metrics.dump_json(str(tmp_path / "metrics.json"))
    with open(metrics_path) as f:
        payload = json.load(f)
    final = payload["final"]
    assert "queue.depth.total" in final
    up = metrics.format_key("stall_seconds", {"cause": "upstream"})
    staging = metrics.format_key("stall_seconds", {"cause": "staging"})
    assert up in final and staging in final
    assert final["h2d.batches"] >= 14  # 2 epochs x 7+ full batches
    assert final["h2d.bytes"] > 0
    assert payload["samples"], "sampler recorded no timeline points"
    assert any(
        "queue.depth.total" in s["values"] for s in payload["samples"]
    )
    # The same series landed in the TrialStatsCollector (one source of
    # truth for CSV stats and live metrics).
    collected = stats_actor.call("snapshot").metrics_samples
    assert collected and "queue.depth.total" in collected[-1]["values"]
