"""Multi-job shuffle-service tests (ISSUE 15).

The service contract, proven end to end:

* two concurrent ``shuffle()`` jobs in one session each deliver
  exactly-once with per-job STRICT audit verdicts, and each job's
  ``delivered_seq`` digests are bit-identical to a solo same-seed
  service-OFF run (isolation by construction, and zero-overhead-off's
  "digests unchanged" in one stroke);
* two jobs using the SAME logical batch-queue name coexist (the
  job-id-suffix fix for the latent named-actor race);
* a crashed reducer in one job recovers without touching the other
  job's epochs (chaos leg, strict audit on both);
* fair-share dispatch interleaves queued tasks across jobs by
  weighted share (deterministic unit on a fake pool);
* epoch admission keys on the capacity ledger's shm fraction, bounded,
  with the no-window/sole-tenant progress guarantees;
* content-identity decode-cache sharing makes a second job over the
  same files cache-hot from its first epoch;
* ``RSDL_SERVICE`` unset: the service module is never imported
  (fresh-interpreter subprocess).

Function-scoped runtimes where faults are armed (schedules parse once
per worker process — the PR 3 lesson).
"""

import collections
import os
import subprocess
import sys
import threading

import pytest

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.batch_queue import BatchQueue
from ray_shuffling_data_loader_tpu.data_generation import generate_file
from ray_shuffling_data_loader_tpu.runtime import faults, service
from ray_shuffling_data_loader_tpu.shuffle import (
    BatchConsumer,
    live_status,
    protected_epochs,
    shuffle,
)
from ray_shuffling_data_loader_tpu.telemetry import audit as _audit
from ray_shuffling_data_loader_tpu.telemetry import metrics as _metrics

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUM_FILES = 4
ROWS_PER_FILE = 400
TOTAL_ROWS = NUM_FILES * ROWS_PER_FILE
EPOCHS = 2


@pytest.fixture(scope="module")
def svc_files(tmp_path_factory):
    """Small Parquet dataset written IN-PROCESS (no worker pool): the
    per-test runtimes below must spawn their pools after the service /
    fault env is armed."""
    data_dir = tmp_path_factory.mktemp("service-data")
    files = []
    for i in range(NUM_FILES):
        fname, _ = generate_file(
            i, i * ROWS_PER_FILE, ROWS_PER_FILE, 1, str(data_dir)
        )
        files.append(fname)
    return files


@pytest.fixture
def svc_env(monkeypatch, tmp_path):
    """Arm the service plane (+ audit strict + metrics, optionally a
    fault schedule), then bring up a fresh runtime whose workers
    inherit everything via the environment."""
    started = []

    def arm(faults_spec=None, seed: int = 0, extra_env=None,
            audit: bool = True):
        spool = tmp_path / "audit-spool"
        spool.mkdir(exist_ok=True)
        monkeypatch.setenv("RSDL_SERVICE", "auto")
        if audit:
            monkeypatch.setenv("RSDL_AUDIT", "1")
            monkeypatch.setenv("RSDL_AUDIT_STRICT", "1")
            monkeypatch.setenv("RSDL_AUDIT_DIR", str(spool))
        monkeypatch.setenv("RSDL_METRICS", "1")
        if faults_spec:
            monkeypatch.setenv("RSDL_FAULTS", faults_spec)
            monkeypatch.setenv("RSDL_FAULTS_SEED", str(seed))
        elif faults_spec == "":
            # Explicitly fault-free: for tests asserting SCHEDULE
            # choices (a recovered cache publisher legitimately
            # degrades an epoch to the materialized path — correct,
            # but not what a schedule assertion wants to see).
            monkeypatch.delenv("RSDL_FAULTS", raising=False)
        # else None: any ambient schedule (the CI service lane's
        # low-prob xN-capped one) rides into the spawned workers —
        # recovery is exactly-once, so digests must not notice.
        for k, v in (extra_env or {}).items():
            monkeypatch.setenv(k, v)
        _audit.refresh_from_env()
        _metrics.refresh_from_env()
        _metrics.registry.clear()
        faults.refresh_from_env()
        ctx = runtime.init(num_workers=2)
        started.append(ctx)
        return ctx

    yield arm
    runtime.shutdown()
    service.reset_state()
    monkeypatch.undo()
    _audit.reset()
    _audit.refresh_from_env()
    _metrics.refresh_from_env()
    faults.refresh_from_env()


class CollectingConsumer(BatchConsumer):
    def __init__(self):
        self.keys = collections.defaultdict(list)
        self.done = collections.defaultdict(bool)

    def consume(self, rank, epoch, batches):
        store = runtime.get_context().store
        for ref in batches:
            cb = store.get_columns(ref)
            self.keys[(epoch, rank)].extend(cb["key"].tolist())
            store.free(ref)

    def producer_done(self, rank, epoch):
        self.done[(epoch, rank)] = True

    def wait_until_ready(self, epoch):
        pass

    def wait_until_all_epochs_done(self):
        pass


def _assert_exactly_once(consumer, epoch):
    assert consumer.done[(epoch, 0)]
    assert sorted(consumer.keys[(epoch, 0)]) == list(range(TOTAL_ROWS))


def _run_job(name, files, seed, results, errors, **kw):
    job = service.register_job(name=name)
    try:
        with service.job_context(job):
            consumer = CollectingConsumer()
            shuffle(
                files, consumer, num_epochs=EPOCHS, num_reducers=4,
                num_trainers=1, seed=seed, **kw,
            )
            results[name] = (job, consumer)
    except BaseException as exc:  # surfaced by the test
        errors[name] = exc
    finally:
        service.end_job(job)


# ---------------------------------------------------------------------------
# Units: mode, scoping, fair share, admission
# ---------------------------------------------------------------------------


def test_mode_parsing(monkeypatch):
    monkeypatch.delenv("RSDL_SERVICE", raising=False)
    assert service.mode() == "off" and not service.enabled()
    monkeypatch.setenv("RSDL_SERVICE", "off")
    assert not service.enabled()
    monkeypatch.setenv("RSDL_SERVICE", "auto")
    assert service.enabled() and service.mode() == "auto"


def test_scoped_name(monkeypatch):
    monkeypatch.setenv("RSDL_SERVICE", "auto")
    assert service.current_job() is None
    assert service.scoped_name("Q") == "Q"  # no ambient job: identity
    job = service.Job("j-1-0", "j", 1.0)
    with service.job_context(job):
        scoped = service.scoped_name("Q")
        assert scoped == "Q--j-1-0"
        # Idempotent: an already-scoped name never double-suffixes.
        assert service.scoped_name(scoped) == scoped
    assert service.scoped_name("Q") == "Q"  # context restored


class _FakeFuture:
    """Inner-future stand-in with manual completion."""

    def __init__(self, tag):
        self.tag = tag
        self._event = threading.Event()
        self._waiters = []
        self._lock = threading.Lock()

    def complete(self):
        with self._lock:
            self._event.set()
            waiters, self._waiters = self._waiters, []
        for w in waiters:
            w.set()

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        assert self._event.wait(timeout)
        return self.tag

    def _add_waiter(self, event):
        with self._lock:
            if self._event.is_set():
                event.set()
            else:
                self._waiters.append(event)

    def _remove_waiter(self, event):
        with self._lock:
            if event in self._waiters:
                self._waiters.remove(event)


class _FakePool:
    width = 2

    def __init__(self):
        self.order = []

    def submit(self, fn, *args, **kwargs):
        fut = _FakeFuture(fn)
        self.order.append(fn)
        return fut

    def submit_local_to(self, refs, fn, *args, **kwargs):
        return self.submit(fn, *args, **kwargs)


def test_fair_share_interleaves_jobs(monkeypatch):
    """Two REGISTERED jobs, width 2: job A's flood is capped at the
    pool width, and the first slot a completion frees goes to job B
    (smaller virtual time / fewer in flight) instead of B waiting
    behind A's whole backlog (FIFO starvation)."""
    monkeypatch.setenv("RSDL_SERVICE", "auto")
    pool = _FakePool()
    sched = service.FairShareScheduler(pool)
    job_a = service.register_job(name="A")
    job_b = service.register_job(name="B")
    try:
        with service.job_context(job_a):
            futs_a = [sched.submit(f"a{i}") for i in range(4)]
        # Two running jobs exist: the cap applies from the very first
        # submissions -- only `width` of A's tasks reach the pool.
        assert pool.order == ["a0", "a1"]
        with service.job_context(job_b):
            futs_b = [sched.submit(f"b{i}") for i in range(2)]
        assert pool.order == ["a0", "a1"]
        # One completion frees one slot: B wins it (vtime tie, fewer
        # in flight) -- no starvation behind A's backlog.
        next(
            inner
            for inner, _j, _p in list(sched._released)
            if inner.tag == "a0"
        ).complete()
        pause = threading.Event()
        for _ in range(100):
            if len(pool.order) > 2:
                break
            pause.wait(0.05)
        assert pool.order[2] == "b0", pool.order
        # Drain everything so the watcher resolves every proxy.
        for _ in range(200):
            for inner, _j, _p in list(sched._released):
                inner.complete()
            if all(f.done() for f in futs_a + futs_b):
                break
            pause.wait(0.05)
        assert all(f.done() for f in futs_a + futs_b)
        assert sorted(pool.order) == sorted(
            [f"a{i}" for i in range(4)] + [f"b{i}" for i in range(2)]
        )
    finally:
        sched.stop()
        service.end_job(job_a)
        service.end_job(job_b)


def test_fair_share_sole_tenant_floods(monkeypatch):
    """One job alone gets the service-off behavior: every task goes
    straight to the pool, no cap, no dispatcher deferral."""
    monkeypatch.setenv("RSDL_SERVICE", "auto")
    pool = _FakePool()
    sched = service.FairShareScheduler(pool)
    job = service.register_job(name="S")
    try:
        with service.job_context(job):
            futs = [sched.submit(f"s{i}") for i in range(5)]
        assert pool.order == [f"s{i}" for i in range(5)]
        for inner, _job, _proxy in list(sched._released):
            inner.complete()
        pause = threading.Event()
        for _ in range(100):
            if all(f.done() for f in futs):
                break
            pause.wait(0.05)
        assert all(f.done() for f in futs)
    finally:
        sched.stop()
        service.end_job(job)


def test_admission_progress_guarantees(monkeypatch):
    """No window in flight, or a sole tenant => admitted immediately;
    under pressure with two active jobs the wait is bounded by the
    timeout knob."""
    monkeypatch.setenv("RSDL_SERVICE", "auto")
    monkeypatch.setenv("RSDL_METRICS", "1")
    monkeypatch.setenv("RSDL_SERVICE_ADMIT_TIMEOUT_S", "0.4")
    _metrics.refresh_from_env()
    from ray_shuffling_data_loader_tpu.telemetry import capacity

    monkeypatch.setattr(
        capacity, "view", lambda *a, **k: {"shm_used_frac": 0.99}
    )
    job_a = service.register_job(name="adm-a")
    try:
        # Sole tenant: admitted even over the watermark.
        assert service.admit_epoch(job_a, 0, in_flight=2) == 0.0
        job_b = service.register_job(name="adm-b")
        try:
            # No window in flight: progress guarantee.
            assert service.admit_epoch(job_a, 0, in_flight=0) == 0.0
            # In flight + pressure + a second tenant: bounded wait.
            waited = service.admit_epoch(job_a, 1, in_flight=1)
            assert 0.3 <= waited <= 2.0
            monkeypatch.setattr(
                capacity, "view", lambda *a, **k: {"shm_used_frac": 0.1}
            )
            assert service.admit_epoch(job_a, 2, in_flight=1) < 0.3
        finally:
            service.end_job(job_b)
    finally:
        service.end_job(job_a)
        _metrics.refresh_from_env()


# ---------------------------------------------------------------------------
# Regression: two same-name jobs coexist (the named-actor race)
# ---------------------------------------------------------------------------


def test_two_same_name_queues_coexist(svc_env):
    """Two jobs creating a batch queue under the SAME logical name get
    two distinct actors (job-id suffix), and each queue carries only
    its own job's items — before ISSUE 15 the second spawn raced the
    first on one registry record."""
    svc_env(audit=False)
    job_a = service.register_job(name="qa")
    job_b = service.register_job(name="qb")
    try:
        with service.job_context(job_a):
            qa = BatchQueue(1, 1, 1, name="svc-queue")
            qa.ready()
        with service.job_context(job_b):
            qb = BatchQueue(1, 1, 1, name="svc-queue")
            qb.ready()
        assert qa.actor.address != qb.actor.address
        qa.new_epoch(0)
        qb.new_epoch(0)
        qa.put_nowait(0, 0, "from-a")
        qb.put_nowait(0, 0, "from-b")
        assert qa.get(0, 0, timeout=5) == "from-a"
        assert qb.get(0, 0, timeout=5) == "from-b"
        # Connecting under job A's context resolves A's actor.
        with service.job_context(job_a):
            handle = runtime.connect_actor("svc-queue")
            assert handle.address == qa.actor.address
        qa.shutdown(force=True)
        qb.shutdown(force=True)
    finally:
        service.end_job(job_a)
        service.end_job(job_b)


# ---------------------------------------------------------------------------
# Two-job concurrency: strict audit + digest-identical to solo runs
# ---------------------------------------------------------------------------


def test_two_jobs_concurrent_audit_isolated(svc_env, svc_files, monkeypatch):
    """The ISSUE 15 acceptance core: two concurrent jobs (same files,
    different seeds) each pass STRICT per-job audit, deliver
    exactly-once, and their per-epoch ``delivered_seq`` digests are
    BIT-IDENTICAL to solo same-seed runs with the service plane off —
    isolation by construction, and the zero-overhead "digests
    unchanged" criterion in the same breath."""
    # Solo reference runs, service OFF.
    monkeypatch.delenv("RSDL_SERVICE", raising=False)
    spool = os.path.join(os.path.dirname(svc_files[0]), "solo-spool")
    monkeypatch.setenv("RSDL_AUDIT", "1")
    monkeypatch.setenv("RSDL_AUDIT_STRICT", "1")
    monkeypatch.setenv("RSDL_AUDIT_DIR", spool)
    _audit.refresh_from_env()
    runtime.init(num_workers=2)
    solo_seq = {}
    for name, seed in (("ja", 7), ("jb", 9)):
        consumer = CollectingConsumer()
        shuffle(
            svc_files, consumer, num_epochs=EPOCHS, num_reducers=4,
            num_trainers=1, seed=seed,
        )
        verdicts = _audit.reconcile(range(EPOCHS))
        assert all(v["ok"] for v in verdicts)
        solo_seq[name] = [v["delivered_seq"] for v in verdicts]
        for e in range(EPOCHS):
            _assert_exactly_once(consumer, e)
    runtime.shutdown()
    _audit.reset(clear_spool=True)

    # Concurrent runs, service ON (fresh runtime; workers inherit env).
    svc_env()
    results, errors = {}, {}
    threads = [
        threading.Thread(
            target=_run_job,
            args=("ja", svc_files, 7, results, errors),
        ),
        threading.Thread(
            target=_run_job,
            args=("jb", svc_files, 9, results, errors),
        ),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors, errors
    assert set(results) == {"ja", "jb"}
    for name in ("ja", "jb"):
        job, consumer = results[name]
        for e in range(EPOCHS):
            _assert_exactly_once(consumer, e)
        verdicts = _audit.reconcile(range(EPOCHS), job=job.job_id)
        assert [v["ok"] for v in verdicts] == [True] * EPOCHS
        assert [
            v["delivered_seq"] for v in verdicts
        ] == solo_seq[name], f"{name}: concurrent digests != solo"
    # The two jobs' streams are genuinely different (different seeds):
    # identical digests across jobs would mean the filter is broken.
    assert solo_seq["ja"] != solo_seq["jb"]


def test_two_jobs_status_and_fence(svc_env, svc_files):
    """While two jobs run, /status's shuffle view carries both jobs and
    the eviction fence is the union of their windows; after both end,
    nothing stays fenced."""
    svc_env(audit=False)
    gate = threading.Event()

    class GatedConsumer(CollectingConsumer):
        def wait_until_ready(self, epoch):
            if epoch > 0:
                gate.wait(timeout=60)

    seen = {}

    def run(name, seed):
        job = service.register_job(name=name)
        try:
            with service.job_context(job):
                consumer = GatedConsumer()
                shuffle(
                    svc_files, consumer, num_epochs=EPOCHS,
                    num_reducers=4, num_trainers=1, seed=seed,
                )
                seen[name] = consumer
        finally:
            service.end_job(job)

    threads = [
        threading.Thread(target=run, args=("sa", 3)),
        threading.Thread(target=run, args=("sb", 4)),
    ]
    for t in threads:
        t.start()
    # Both jobs hold epoch 1 at the gate; epoch 0 flows.
    for _ in range(200):
        st = live_status()
        jobs = st.get("jobs") or {}
        if len(jobs) >= 2 and st.get("running"):
            break
        threading.Event().wait(0.05)
    st = live_status()
    assert len(st.get("jobs") or {}) >= 2
    assert st["running"]
    assert protected_epochs() <= {0, 1}
    gate.set()
    for t in threads:
        t.join(timeout=120)
    assert set(seen) == {"sa", "sb"}
    for consumer in seen.values():
        for e in range(EPOCHS):
            _assert_exactly_once(consumer, e)
    assert protected_epochs() == set()


# ---------------------------------------------------------------------------
# Chaos: one job's reducer crashes; the other job is unaffected
# ---------------------------------------------------------------------------


def test_chaos_reducer_crash_isolated(svc_env, svc_files):
    """A capped crash schedule kills the first reduce attempts (either
    job may be hit): the struck job recovers via the stage budget, the
    neighbor never notices, and BOTH end with strict per-job audit
    ok=true and exactly-once delivery."""
    svc_env("task.reduce/task:crash-exit:1x2", seed=23)
    results, errors = {}, {}
    threads = [
        threading.Thread(
            target=_run_job,
            args=("ca", svc_files, 11, results, errors),
        ),
        threading.Thread(
            target=_run_job,
            args=("cb", svc_files, 13, results, errors),
        ),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errors, errors
    for name in ("ca", "cb"):
        job, consumer = results[name]
        for e in range(EPOCHS):
            _assert_exactly_once(consumer, e)
        verdicts = _audit.reconcile(range(EPOCHS), job=job.job_id)
        assert [v["ok"] for v in verdicts] == [True] * EPOCHS


# ---------------------------------------------------------------------------
# Cross-job hot-dataset sharing
# ---------------------------------------------------------------------------


def test_cross_job_cache_hot(svc_env, svc_files, index_schedule_pinned):
    """Job 2 over the same files rides job 1's decoded segments from
    its FIRST epoch (index schedule at epoch 0 — the Parquet decode is
    skipped entirely), while claims fence the segments and release at
    job end. Explicitly fault-free: a recovered crashed publisher
    legitimately degrades an epoch's schedule, which is not what this
    assertion is about."""
    svc_env(faults_spec="", audit=False)
    service.cache_registry_clear()
    log1, log2 = [], []
    job1 = service.register_job(name="warm")
    with service.job_context(job1):
        c1 = CollectingConsumer()
        shuffle(
            svc_files, c1, num_epochs=EPOCHS, num_reducers=4,
            num_trainers=1, seed=7, cache_decoded=True,
            schedule_log=log1,
        )
    assert dict(log1)[0] == "mapreduce"
    assert dict(log1)[1] == "index"
    # Claims held by the live job fence the segments.
    assert service.claimed_cache_ids()
    job2 = service.register_job(name="rider")
    with service.job_context(job2):
        c2 = CollectingConsumer()
        shuffle(
            svc_files, c2, num_epochs=1, num_reducers=4,
            num_trainers=1, seed=7, cache_decoded=True,
            schedule_log=log2,
        )
    assert dict(log2)[0] == "index", (
        "job 2 should be cache-hot from epoch 0"
    )
    # Same seed => identical stream, via the shared segments.
    assert c2.keys[(0, 0)] == c1.keys[(0, 0)]
    service.end_job(job1)
    service.end_job(job2)
    # Both jobs ended: every claim is released.
    assert service.claimed_cache_ids() == set()


def test_dead_job_claims_do_not_fence(svc_env, svc_files):
    """A SIGKILLed driver never runs end_job: its on-disk record stays
    ``running`` forever, but its claims must NOT fence cache segments
    from the evictor — liveness is record + pid-alive, and a dead pid
    retires the claim."""
    svc_env(faults_spec="", audit=False)
    import json as _json

    ctx = runtime.get_context()
    jobs_dir = os.path.join(ctx.runtime_dir, "service", "jobs")
    os.makedirs(jobs_dir, exist_ok=True)
    # Fabricate a crashed driver's record: running, dead pid.
    dead = {
        "job_id": "ghost-999999-0", "name": "ghost", "weight": 1.0,
        "pid": 999999, "created_ts": 0.0, "ended_ts": None,
        "running": True,
    }
    with open(os.path.join(jobs_dir, "ghost-999999-0.json"), "w") as f:
        _json.dump(dead, f)
    service.cache_registry_clear()
    store = runtime.get_context().store
    import numpy as np

    ref = store.put_columns({"key": np.arange(10, dtype=np.int64)})
    key = service.cache_key(svc_files[0], None, False)
    service.cache_publish(key, ref, job=None)
    with service._registry_locked() as data:
        data[key]["claims"] = {"ghost-999999-0": 0.0}
    assert service.claimed_cache_ids() == set(), (
        "a dead job's claims must not fence segments"
    )
    # A LIVE job's claim (this process) does fence.
    job = service.register_job(name="fence")
    try:
        service.claim_cache(key, job)
        assert ref.object_id in service.claimed_cache_ids()
    finally:
        service.end_job(job)
    assert service.claimed_cache_ids() == set()
    store.free(ref)
    # Cross-process multi-tenancy: the ghost is dead, so this process's
    # sole job must still count as a sole tenant for admission.
    job2 = service.register_job(name="solo-count")
    try:
        assert service.live_jobs_count() == 1
    finally:
        service.end_job(job2)


def test_audit_reconcile_folds_resume_chain(monkeypatch, tmp_path):
    """Job ids change across restarts: a journaled service resume
    reconciles with the whole chain of attempt ids (threaded through
    the journal identity's ``audit_jobs``), so the preempted attempt's
    carried records fold instead of reporting a false mismatch."""
    import numpy as np
    from ray_shuffling_data_loader_tpu import telemetry

    monkeypatch.setenv("RSDL_AUDIT", "1")
    monkeypatch.setenv("RSDL_AUDIT_DIR", str(tmp_path / "spool"))
    _audit.refresh_from_env()
    _audit.reset(clear_spool=True)
    keys = np.arange(100, dtype=np.int64)
    try:
        # Attempt 1 (old id): map + reduce before the "preemption".
        with telemetry.context(job="t-1-0"):
            _audit.record_map(0, 0, {"key": keys})
            _audit.record_reduce(0, 0, {"key": keys})
        # Attempt 2 (new id): delivery of the same epoch.
        with telemetry.context(job="t-2-0"):
            _audit.record_deliver(0, 0, 0, {"key": keys}, offset=0)
        # Newest id alone: the old attempt's worker records are
        # invisible -> incomplete, not ok.
        (v_new,) = _audit.reconcile([0], job="t-2-0")
        assert v_new["ok"] is not True
        # The chain folds both attempts: exactly-once reconciles, and
        # the verdict carries the NEWEST attempt's id.
        (v_chain,) = _audit.reconcile([0], job=["t-1-0", "t-2-0"])
        assert v_chain["ok"] is True
        assert v_chain["job"] == "t-2-0"
        assert v_chain["rows_mapped"] == 100
        assert v_chain["rows_delivered"] == 100
    finally:
        _audit.reset(clear_spool=True)
        _audit.refresh_from_env()


def test_cache_key_content_identity(svc_files, tmp_path):
    """The content key fingerprints path + size + mtime + projection +
    narrowing: same file/same shape agree across calls; a different
    projection (or file) never collides."""
    k1 = service.cache_key(svc_files[0], None, False)
    assert k1 == service.cache_key(svc_files[0], None, False)
    assert k1 != service.cache_key(svc_files[0], ["key"], False)
    assert k1 != service.cache_key(svc_files[0], None, True)
    assert k1 != service.cache_key(svc_files[1], None, False)


# ---------------------------------------------------------------------------
# Fleet observability (ISSUE 16): per-job SLO isolation + /jobs
# ---------------------------------------------------------------------------


def test_two_jobs_slo_fire_and_resolve_isolated(
    svc_env, svc_files, monkeypatch
):
    """The ISSUE 16 SLO acceptance: job A's delivery stalls behind a
    gated consumer — the (short-window) per-job ``producer_stalled``
    instance fires for A ALONE (``alert.active{job,rule}`` gauge up,
    job-stamped fire event), job B never leaves ok, and releasing the
    gate resolves A's instance — with both jobs still ending
    strict-audit ok=true."""
    import json as _json
    import time as _time

    from ray_shuffling_data_loader_tpu.telemetry import events as _events
    from ray_shuffling_data_loader_tpu.telemetry import slo as _slo
    from ray_shuffling_data_loader_tpu.telemetry import (
        timeseries as _timeseries,
    )

    # Shorten producer_stalled so a held consumer gate (not a 30 s
    # production outage) trips it: all-zero delivered-bytes rate across
    # 8 s, held 2 s. Job B's continuous delivery keeps a non-zero
    # sample inside any 8 s window, so B cannot trip it.
    monkeypatch.setenv("RSDL_SLO_RULES", _json.dumps([
        {"name": "producer_stalled", "kind": "rate",
         "metric": "shuffle.reduce_rows",
         "per_job": True, "per_job_metric": "service.delivered_bytes",
         "op": "==", "value": 0.0, "window_s": 8.0, "for_s": 2.0,
         "only_in_flight": True, "severity": "page"},
    ]))
    svc_env()
    _events.reset()
    _timeseries.reset()
    _slo.reset()
    gate = threading.Event()

    class GatedConsumer(CollectingConsumer):
        def wait_until_ready(self, epoch):
            if epoch > 0:
                assert gate.wait(timeout=180)

    results, errors, ids = {}, {}, {}

    def run(name, seed, consumer_cls):
        job = service.register_job(name=name)
        ids[name] = job.job_id
        try:
            with service.job_context(job):
                consumer = consumer_cls()
                shuffle(
                    svc_files, consumer, num_epochs=EPOCHS,
                    num_reducers=4, num_trainers=1, seed=seed,
                )
                results[name] = (job, consumer)
        except BaseException as exc:
            errors[name] = exc
        finally:
            service.end_job(job)

    threads = [
        threading.Thread(target=run, args=("sa", 7, GatedConsumer)),
        threading.Thread(target=run, args=("sb", 9, CollectingConsumer)),
    ]
    for t in threads:
        t.start()
    try:
        # Drive the sampler tick by hand (sample, then evaluate — the
        # engine reads the fresh ring) until A's instance fires.
        saw_both = False
        fired_key = None
        deadline = _time.time() + 150
        while _time.time() < deadline and fired_key is None:
            _timeseries.sample_now()
            out = _slo.evaluate()
            saw_both = saw_both or set(ids.values()) <= set(out["jobs"])
            for active in out["active"]:
                if active.startswith("producer_stalled|"):
                    fired_key = active
            _time.sleep(0.2)
        assert fired_key == f"producer_stalled|{ids['sa']}", (
            fired_key, ids,
        )
        assert saw_both, "both tenants never live in one tick"
        snap = _metrics.registry.snapshot()
        assert snap[
            f"alert.active{{job={ids['sa']},rule=producer_stalled}}"
        ] == 1.0
        assert _slo.active_alerts_by_job().get(ids["sa"]) == [
            "producer_stalled"
        ]
        assert ids["sb"] not in _slo.active_alerts_by_job()
        # Release the gate: delivery resumes and A's instance resolves
        # (rate recovers, or the trial drains — either clears it).
        gate.set()
        resolved = False
        deadline = _time.time() + 150
        while _time.time() < deadline and not resolved:
            _timeseries.sample_now()
            out = _slo.evaluate()
            resolved = fired_key not in out["active"]
            _time.sleep(0.2)
        assert resolved, "producer_stalled|sa never resolved"
    finally:
        gate.set()
        for t in threads:
            t.join(timeout=240)
    assert not errors, errors
    assert set(results) == {"sa", "sb"}
    for name in ("sa", "sb"):
        job, consumer = results[name]
        for e in range(EPOCHS):
            _assert_exactly_once(consumer, e)
        verdicts = _audit.reconcile(range(EPOCHS), job=job.job_id)
        assert [v["ok"] for v in verdicts] == [True] * EPOCHS
    fired = [r for r in _events.load() if r.get("kind") == "alert.fired"]
    assert any(
        r.get("job") == ids["sa"] and r.get("rule") == "producer_stalled"
        for r in fired
    ), fired
    assert not [r for r in fired if r.get("job") == ids["sb"]], (
        "job B fired an alert"
    )
    assert [
        r for r in _events.load()
        if r.get("kind") == "alert.resolved" and r.get("job") == ids["sa"]
    ]
    counts = _slo.fired_counts()
    assert counts.get(f"producer_stalled|{ids['sa']}", 0) >= 1
    assert not [k for k in counts if ids["sb"] in k]


def test_jobs_endpoint_lists_both_tenants(svc_env, svc_files, monkeypatch):
    """``/jobs`` (ISSUE 16): with two tenants gated mid-flight the
    fleet view serves one row each — service identity, trial shape,
    and the default alert/claims columns — and ``/status`` carries the
    running set in its ``fleet`` section; after both end neither row
    shows running."""
    import json as _json
    import urllib.request

    from ray_shuffling_data_loader_tpu.telemetry import obs_server

    svc_env(audit=False)
    port = obs_server.start(0)
    # shuffle() registers its live-trial provider only when the obs
    # endpoint is configured; point the gate at the bound port.
    monkeypatch.setenv("RSDL_OBS_PORT", str(port))

    def get(path):
        url = f"http://127.0.0.1:{port}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return _json.loads(resp.read().decode())

    gate = threading.Event()

    class GatedConsumer(CollectingConsumer):
        def wait_until_ready(self, epoch):
            if epoch > 0:
                assert gate.wait(timeout=180)

    results, errors, ids = {}, {}, {}

    def run(name, seed):
        job = service.register_job(name=name)
        ids[name] = job.job_id
        try:
            with service.job_context(job):
                consumer = GatedConsumer()
                shuffle(
                    svc_files, consumer, num_epochs=EPOCHS,
                    num_reducers=4, num_trainers=1, seed=seed,
                )
                results[name] = consumer
        except BaseException as exc:
            errors[name] = exc
        finally:
            service.end_job(job)

    threads = [
        threading.Thread(target=run, args=("fa", 3)),
        threading.Thread(target=run, args=("fb", 4)),
    ]
    for t in threads:
        t.start()
    try:
        import time as _time

        body = None
        deadline = _time.time() + 120
        while _time.time() < deadline:
            body = get("/jobs")
            rows = {
                r["job_id"]: r for r in body["jobs"] if r.get("running")
            }
            if set(ids.values()) <= set(rows) and all(
                rows[j].get("num_epochs") for j in ids.values()
            ):
                break
            _time.sleep(0.2)
        assert body and body["service_mode"] == "auto"
        rows = {r["job_id"]: r for r in body["jobs"]}
        assert set(ids.values()) <= set(rows), (ids, list(rows))
        for name, jid in ids.items():
            row = rows[jid]
            assert row["name"] == name
            assert row["running"] is True
            assert row["pid"] == os.getpid()
            assert row["weight"] == 1.0
            assert row["num_epochs"] == EPOCHS
            assert row["num_reducers"] == 4
            assert row["active_alerts"] == []
            assert "cache_claims" in row
        # /status mirrors the running set in its fleet section.
        fleet = get("/status").get("fleet") or {}
        running_ids = {r["job_id"] for r in fleet.get("running", [])}
        assert set(ids.values()) <= running_ids, fleet
    finally:
        gate.set()
        for t in threads:
            t.join(timeout=240)
        obs_server.stop()
    assert not errors, errors
    assert set(results) == {"fa", "fb"}


# ---------------------------------------------------------------------------
# Zero-overhead off
# ---------------------------------------------------------------------------


def test_service_off_never_imports_plane():
    """RSDL_SERVICE unset: a fresh interpreter exercising the gate
    points (runtime init + scheduler property, batch queue, the shuffle
    module, shared-cache parser) never loads the service module and
    starts no fair-share thread."""
    code = """
import os, sys, threading
for k in list(os.environ):
    if k.startswith("RSDL_"):
        del os.environ[k]
os.environ["JAX_PLATFORMS"] = "cpu"
import importlib
from ray_shuffling_data_loader_tpu import runtime
# importlib, not `import ... as`: the package exports a `shuffle`
# FUNCTION attribute that shadows the module on as-binding.
sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
ctx = runtime.init(num_workers=1)
_ = ctx.scheduler  # the wrap point
assert not sh.shared_decode_cache_enabled()
from ray_shuffling_data_loader_tpu.batch_queue import BatchQueue
q = BatchQueue(1, 1, 1, name="zq")
q.ready()
q.shutdown(force=True)
runtime.shutdown()
assert "ray_shuffling_data_loader_tpu.runtime.service" not in sys.modules, (
    "service plane imported on a service-off run")
assert not [t for t in threading.enumerate() if "fair-share" in t.name]
print("ZERO_OVERHEAD_OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=180,
        env={**os.environ, "PYTHONPATH": _REPO},
        cwd=_REPO,
    )
    assert out.returncode == 0, out.stderr
    assert "ZERO_OVERHEAD_OK" in out.stdout
