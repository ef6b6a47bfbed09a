"""The program's own spans (ISSUE 25): the switch that follows a profiler
session, the spans of every layer in the profiler's trace and on its clock,
the ``layers`` counts folded from them, the names the trace readers go by,
the runtime's ``pool:`` spans, and (ISSUE 36) the train step that keeps the
program it compiled and says what is in it. All on the CPU; what the spans
cost and read on the chip is PERF.md's."""

import asyncio
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu import runtime, telemetry
from ray_shuffling_data_loader_tpu.data_generation import (
    LABEL_COLUMN,
    generate_data,
)
from ray_shuffling_data_loader_tpu.jax_dataset import (
    JaxShufflingDataset,
    layer_counts,
)
from ray_shuffling_data_loader_tpu.resident import (
    DeviceResidentShufflingDataset,
)
from ray_shuffling_data_loader_tpu.runtime.tasks import TaskError
from ray_shuffling_data_loader_tpu.telemetry import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402

ROWS, BATCH = 4096, 512


@pytest.fixture(autouse=True)
def clean_trace(monkeypatch):
    """Every test starts and ends with tracing off and an empty buffer."""
    monkeypatch.delenv("RSDL_TRACE", raising=False)
    monkeypatch.delenv("RSDL_TRACE_DIR", raising=False)
    trace.refresh_from_env()
    trace.reset_state()
    yield
    monkeypatch.undo()
    trace.refresh_from_env()
    trace.reset_state()


@pytest.fixture(scope="module")
def files(local_runtime, tmp_path_factory):
    data_dir = tmp_path_factory.mktemp("spans-data")
    filenames, _ = generate_data(ROWS, 2, 1, 0.0, str(data_dir))
    return filenames


def _stream(files, queue_name, epochs):
    return JaxShufflingDataset(
        files,
        num_epochs=epochs,
        num_trainers=1,
        batch_size=BATCH,
        rank=0,
        feature_columns=["key"],
        label_column=LABEL_COLUMN,
        num_reducers=2,
        # One epoch in flight: an epoch's shuffle begins when the one
        # before it has been consumed, so the second traced epoch's
        # ``shuffle:epoch`` begins inside the session.
        max_concurrent_epochs=1,
        queue_name=queue_name,
    )


def _resident(files, epochs):
    return DeviceResidentShufflingDataset(
        files,
        num_epochs=epochs,
        batch_size=BATCH,
        feature_columns=["key"],
        label_column=LABEL_COLUMN,
        seed=1,
    )


def _drain(ds, epoch):
    ds.set_epoch(epoch)
    return [np.asarray(features["key"]) for features, _ in ds]


def _inside(child, parents):
    _, start, dur = child
    return any(s <= start and start + dur <= s + d for _, s, d in parents)


# -- (a) under a profiler session ---------------------------------------------


def test_spans_land_in_the_profilers_trace_on_its_clock(files, tmp_path):
    stream = _stream(files, "q-spans-on", 3)
    _drain(stream, 0)  # before the session: nothing is recorded
    assert not trace.active() and "layers" not in stream.stats.as_dict()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for epoch in (1, 2):
            keys = np.sort(np.concatenate(_drain(stream, epoch)))
            assert np.array_equal(keys, np.arange(ROWS))
        assert trace.active()
        resident = _resident(files, 2)
        for epoch in (0, 1):
            keys = np.sort(np.concatenate(_drain(resident, epoch)))
            assert np.array_equal(keys, np.arange(ROWS))
    finally:
        jax.profiler.stop_trace()

    xplane = trace_reduce.find_xplane(str(tmp_path))
    planes = trace_reduce.load(xplane)
    host = [
        ev
        for name, lines in planes.items()
        if name.startswith("/host:")
        for events in lines.values()
        for ev in events
    ]
    by_name = {}
    for ev in host:
        by_name.setdefault(ev[0], []).append(ev)
    for name in (
        "stage:epoch", "stage:h2d", "queue:get", "stage:ring-put",
        "staging:device_put", "resident:handover", "resident:dispatch",
        "clock.sync",
    ):
        assert name in by_name, (name, sorted(by_name))
    per_epoch = ROWS // BATCH
    assert len(by_name["stage:epoch"]) == 2
    assert len(by_name["stage:h2d"]) == 2 * per_epoch
    assert len(by_name["resident:handover"]) == 2
    assert len(by_name["resident:dispatch"]) == 2 * per_epoch
    # Each child inside its parent.
    for child, parent in (
        ("stage:h2d", "stage:epoch"),
        ("queue:get", "stage:epoch"),
        ("stage:ring-put", "stage:epoch"),
        ("staging:device_put", "stage:h2d"),
    ):
        assert all(
            _inside(ev, by_name[parent]) for ev in by_name[child]
        ), (child, parent)
    for handover in by_name["resident:handover"]:
        assert sum(
            _inside(ev, [handover]) for ev in by_name["resident:dispatch"]
        ) == 1

    # One mark on both clocks, and a later one agrees with it.
    with trace._lock:
        walls = [
            e["args"]["wall_ns"]
            for e in trace._events
            if e["name"] == "clock.sync"
        ]
    starts = sorted(ev[1] for ev in by_name["clock.sync"])
    assert len(walls) == len(starts) == 4  # one at each traced epoch
    # ``wall_ns`` is read just before a mark is entered, so a mark that
    # was held up reads a smaller offset: the two largest are the sound
    # ones, and they agree.
    offsets = sorted(w - s for w, s in zip(sorted(walls), starts))
    assert offsets[-1] - offsets[-2] < 1_000_000, offsets

    # The layers' counts, from the spans.
    spans = trace.local_spans()
    transfers = [s for s in spans if s["name"] == "stage:transfer"]
    assert len(transfers) == 2 * per_epoch
    for s in transfers:
        assert s["args"]["bytes"] == BATCH * 2 * 4
        assert s["args"]["parent"] == "stage:h2d"
        assert 0 < s["args"]["put_ns"] <= s["dur"] * 1e3
    assert all(
        s["args"]["parent"] == "stage:epoch"
        for s in spans
        if s["name"] in ("stage:h2d", "stage:ring-put", "queue:get")
    )
    layers = stream.stats.as_dict()["layers"]
    assert set(layers) == {"runtime", "shuffle", "delivery", "staging"}
    assert layers["staging"]["transfers"] == 2 * per_epoch
    assert 0 < layers["staging"]["max_transfer_s"]
    assert 0 < layers["staging"]["ring_put_s"] < layers["staging"]["stager_s"]
    assert layers["delivery"]["gets"] >= 2
    assert 0 < layers["delivery"]["get_wait_s"] < layers["staging"]["stager_s"]
    # Epoch 2's shuffle began inside the session. Epoch 1's begins when
    # epoch 0's last ack reaches the driver: before the flag turned on (it
    # is then not recorded at all, never half), or on a loaded host just
    # after.
    assert len(layers["shuffle"]["epoch_s"]) in (1, 2)
    assert all(s > 0 for s in layers["shuffle"]["epoch_s"])
    # Which schedule made each of them, in the same order: the span says it.
    schedules = layers["shuffle"]["schedules"]
    assert len(schedules) == len(layers["shuffle"]["epoch_s"])
    assert set(schedules) <= {"index", "selective", "mapreduce"}, schedules
    by_fn = layers["runtime"]["by_fn"]
    assert sum(c["tasks"] for c in by_fn.values()) >= 4
    assert any(fn.startswith("shuffle_") for fn in by_fn), sorted(by_fn)

    # One file, one clock: the xplane's copy of a live span stands where
    # the buffer's does.
    out = trace.trace_export(str(tmp_path / "merged.json"), xplane=xplane)
    with open(out) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    ours = sorted(
        e["ts"] for e in events
        if e["name"] == "stage:h2d" and e["cat"] == "staging"
    )
    theirs = sorted(
        e["ts"] for e in events
        if e["name"] == "stage:h2d" and e["cat"] == "xplane"
    )
    assert len(ours) == len(theirs) == 2 * per_epoch
    assert max(abs(a - b) for a, b in zip(ours, theirs)) < 1000.0  # us


def test_export_without_a_mark_says_so(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="clock.sync"):
        trace.trace_export(
            str(tmp_path / "t.json"),
            xplane=trace_reduce.find_xplane(str(tmp_path)),
        )


# -- (b) with tracing off ---------------------------------------------------------


def test_tracing_off_records_nothing_and_starts_no_thread(files):
    stream = _stream(files, "q-spans-off", 1)
    before = set(threading.enumerate())
    stream.set_epoch(0)
    it = iter(stream)
    next(it)
    during = {t.name for t in set(threading.enumerate()) - before}
    assert "hbm-stager" in during and "hbm-transfer-watch" not in during
    for _ in it:
        pass
    resident = _resident(files, 1)
    _drain(resident, 0)
    assert not trace.active()
    assert "layers" not in stream.stats.as_dict()
    assert "layers" not in resident.stats.as_dict()
    with trace._lock:
        assert trace._events == []
    assert layer_counts(trace.local_spans()) == {}


def test_the_switch_never_imports_jax():
    code = (
        "import sys\n"
        "from ray_shuffling_data_loader_tpu.telemetry import trace\n"
        "assert not trace.active() and not trace.refresh_active()\n"
        "with trace.trace_span('x'):\n"
        "    trace.record_span('y', 0.0, 1.0)\n"
        "assert not trace.local_spans()\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "RSDL_TRACE"}
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env, timeout=120,
        capture_output=True, text=True,
    )
    assert done.returncode == 0, done.stderr


def test_the_flag_moves_only_at_a_refresh(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert not trace.active()  # nobody looked yet
        assert trace.refresh_active() and trace.active()
    finally:
        jax.profiler.stop_trace()
    assert trace.active()  # still: the flag is cached
    assert not trace.refresh_active() and not trace.active()


# -- (c) the layers' arithmetic, on a synthetic span list ------------------------


def _span(name, ts_ms, dur_ms, tid=1, **args):
    return {
        "name": name, "ph": "X", "pid": 7, "tid": tid,
        "ts": ts_ms * 1e3, "dur": dur_ms * 1e3, "args": args,
    }


SYNTHETIC = [
    # The stager thread: one epoch of two batches.
    _span("stage:epoch", 0, 1000, epoch=3),
    _span("queue:get", 10, 100, parent="stage:epoch", refs=3),
    _span("stage:h2d", 200, 50, parent="stage:epoch", batch=0),
    _span("staging:device_put", 210, 20, parent="stage:h2d"),
    _span("staging:sync", 230, 10, parent="stage:h2d"),
    _span("stage:ring-put", 250, 300, parent="stage:epoch", batch=0),
    _span("stage:h2d", 600, 40, parent="stage:epoch", batch=1),
    _span("stage:ring-put", 640, 1, parent="stage:epoch", batch=1),
    # The watcher's thread.
    _span("stage:transfer", 210, 30, tid=2, parent="stage:h2d", bytes=1000,
          put_ns=10_000_000),
    _span("stage:transfer", 605, 250, tid=2, parent="stage:h2d", bytes=3000,
          put_ns=30_000_000),
    # The consumer.
    _span("stall", 100, 5, tid=3, cause="upstream"),
    # The pool's collector: overlapping tasks, nobody's children.
    _span("pool:shuffle_map", 0, 100, tid=4, wait_ns=20_000_000, epoch=4),
    _span("pool:shuffle_map", 0, 150, tid=4, wait_ns=50_000_000, epoch=4,
          error="ValueError"),
    _span("pool:shuffle_map", 160, 100, tid=4, wait_ns=0, epoch=4, retry=1),
    _span("pool:shuffle_reduce", 300, 400, tid=4, wait_ns=100_000_000),
    _span("pool:generate_file", 0, 10, tid=4, wait_ns=0),
    # The shuffle driver's threads.
    _span("shuffle:epoch", 0, 3000, tid=5, epoch=4, schedule="mapreduce"),
    _span("shuffle:epoch", 6000, 4000, tid=5, epoch=6, schedule="index"),
    _span("shuffle:epoch", 2000, 5000, tid=6, epoch=5, schedule="index"),
    _span("epoch:admission", 1990, 10, tid=7, epoch=5),
    # The resident loader.
    _span("resident:handover", 0, 100, tid=8, epoch=1),
    _span("resident:dispatch", 90, 10, tid=8, parent="resident:handover"),
    _span("resident:dispatch", 150, 5, tid=8),
]


def test_layers_arithmetic():
    got = layer_counts(SYNTHETIC)
    # Only what a per-layer metric reads; the resident loader's spans and
    # the stall are for the merged trace.
    assert set(got) == {"runtime", "shuffle", "delivery", "staging"}
    by_fn = got["runtime"]["by_fn"]
    assert set(by_fn) == {"shuffle_map", "shuffle_reduce", "generate_file"}
    assert by_fn["shuffle_map"] == {
        "tasks": 3,
        "wait_s": pytest.approx(0.070),
        "run_s": pytest.approx(0.280),
    }
    assert by_fn["shuffle_reduce"] == {
        "tasks": 1,
        "wait_s": pytest.approx(0.100),
        "run_s": pytest.approx(0.300),
    }
    # In the order they began, whatever thread recorded them.
    assert got["shuffle"] == {
        "epoch_s": [3.0, 5.0, 4.0],
        "schedules": ["mapreduce", "index", "index"],
    }
    assert got["delivery"] == {
        "gets": 1, "get_wait_s": pytest.approx(0.100),
    }
    assert got["staging"] == {
        "stager_s": pytest.approx(1.0),
        "ring_put_s": pytest.approx(0.301),
        "transfers": 2,
        "max_transfer_s": pytest.approx(0.250),
    }
    assert layer_counts([]) == {}
    # A layer is absent, not zero, where it recorded nothing.
    assert set(layer_counts(SYNTHETIC[:2])) == {"delivery", "staging"}
    assert layer_counts(
        [s for s in SYNTHETIC if s["name"].startswith("resident:")]
    ) == {}


# -- the span that caused a span -------------------------------------------------


def _parents():
    return {
        s["name"]: s["args"].get("parent") for s in trace.local_spans()
    }


def test_parent_is_the_live_span_of_the_asyncio_task(monkeypatch):
    """Actor dispatches interleave as asyncio tasks on one thread: each
    names its own enclosing span, whatever the others enter and leave in
    between."""
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()

    async def dispatch(name, a_entered, b_entered, a_left):
        if name == "B":
            await a_entered.wait()
        with trace.trace_span(f"actor:{name}"):
            if name == "A":
                a_entered.set()
                await b_entered.wait()  # B enters while A is live
            else:
                b_entered.set()
                await a_left.wait()  # A leaves while B is live
            with trace.trace_span(f"inner:{name}"):
                submitted = trace.caused_context()
            trace.record_span(f"retro:{name}", 0.0, 1.0)
        if name == "A":
            a_left.set()
        return submitted

    async def both():
        events = [asyncio.Event() for _ in range(3)]
        return await asyncio.gather(
            dispatch("A", *events), dispatch("B", *events)
        )

    with trace.trace_span("loop"):
        submitted = asyncio.run(both())
        assert trace.caused_context()["parent"] == "loop"
    assert [c["parent"] for c in submitted] == ["inner:A", "inner:B"]
    assert _parents() == {
        # The tasks were made under ``loop`` and inherit it.
        "actor:A": "loop", "actor:B": "loop",
        "inner:A": "actor:A", "inner:B": "actor:B",
        "retro:A": "actor:A", "retro:B": "actor:B",
        "loop": None,
    }
    assert "parent" not in trace.caused_context()


def test_a_span_leaves_the_live_spans_by_identity(monkeypatch):
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    outer = trace.trace_span("outer")
    inner = trace.trace_span("inner")
    outer.__enter__()
    inner.__enter__()
    outer.__exit__(None, None, None)  # out of order
    assert trace.caused_context()["parent"] == "inner"
    inner.__exit__(None, None, None)
    assert "parent" not in trace.caused_context()
    assert _parents() == {"outer": None, "inner": "outer"}


def test_a_thread_starts_with_no_live_span(monkeypatch):
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    seen = []

    def work():
        seen.append(trace.caused_context().get("parent"))
        with trace.trace_span("theirs"):
            pass

    with trace.trace_span("mine"):
        thread = threading.Thread(target=work)
        thread.start()
        thread.join()
        # A caller that hands work to another thread names the cause
        # itself (``stage:transfer`` does).
        trace.record_span("handed", 0.0, 1.0, parent="stage:h2d")
    assert seen == [None]
    assert _parents() == {
        "theirs": None, "handed": "stage:h2d", "mine": None,
    }


# -- (e) the names the trace's readers go by ---------------------------------------


def _module_name(jitted, *args):
    return jitted.lower(*args).as_text().split("module @", 1)[1].split()[0]


def test_jitted_programs_keep_their_names(files):
    resident = _resident(files, 1)
    perm = resident._perm(0)
    assert _module_name(resident._perm_fn, np.int32(0)) == (
        "jit_epoch_permutation"
    )
    assert _module_name(resident._permute_all, resident._buf, perm) == (
        "jit_permute_all"
    )
    ebuf = resident._epoch_buf(0)
    assert _module_name(
        resident._slice_fn(BATCH), ebuf, np.int32(0)
    ) == "jit_cut"
    stream = _stream(files, "q-spans-names", 1)
    unpack = stream._get_unpack(("key",), ("int32",), "float32")
    assert _module_name(unpack, jnp.zeros((2, BATCH), jnp.int32)) == (
        "jit_unpack"
    )
    _drain(stream, 0)


def test_the_step_names_its_parts_in_the_hlo():
    import optax

    from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
    from ray_shuffling_data_loader_tpu.parallel import (
        init_state,
        make_mesh,
        make_train_step,
    )

    mesh = make_mesh(devices=jax.devices()[:1])
    model = dlrm_for_data_spec(
        embed_dim=8, top_mlp=(16, 8), vocab_cap=64,
        use_pallas_interaction=True, interpret_interaction=True,
    )
    example = {c: jnp.zeros((64,), jnp.int32) for c in model.vocab_sizes}
    optimizer = optax.adam(1e-3)
    state, shardings = init_state(
        model, optimizer, mesh, example, rng=jax.random.key(0)
    )
    step = make_train_step(model, optimizer, mesh, shardings)
    lowered = step.lower(state, example, jnp.zeros((64,), jnp.float32))
    assert lowered.as_text().split("module @", 1)[1].startswith("jit_step_fn")
    hlo = lowered.compile().as_text()
    op_names = set()
    for piece in hlo.split('op_name="')[1:]:
        op_names.add(piece.split('"', 1)[0])
    for scope in ("/jvp(loss)/", "transpose(jvp(loss))", "/optimizer/",
                  "/dot_interaction/", "dot_interaction_fwd", "/embedding/"):
        assert any(scope in n for n in op_names), scope
    # The lookup's gather forward, its scatter-add backward.
    assert any("jvp(loss)" in n and "/embedding/" in n for n in op_names)
    assert any(
        "transpose(jvp(loss))" in n and "/embedding/" in n for n in op_names
    )
    # The optimizer's update is no part of the loss.
    assert not any("loss" in n and "/optimizer/" in n for n in op_names)


@pytest.mark.parametrize(
    "embed_dim, packed_tables, pack", [(32, 19, 4), (128, 0, 1)]
)
def test_step_build_says_how_the_tables_are_read(
    monkeypatch, embed_dim, packed_tables, pack
):
    """``make_train_step`` leaves on ``step:build`` how many of the model's
    tables the step it traced reads through the lane-filled view: a count
    fixed by the shapes, the shipped model's 19 at ``embed_dim`` 32, none
    at 128."""
    import optax

    from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
    from ray_shuffling_data_loader_tpu.parallel import (
        make_mesh,
        make_train_step,
    )

    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    mesh = make_mesh(devices=jax.devices()[:1])
    model = dlrm_for_data_spec(embed_dim=embed_dim)
    make_train_step(model, optax.adam(1e-3), mesh, None)
    (span,) = [s for s in trace.local_spans() if s["name"] == "step:build"]
    assert span["args"]["packed_tables"] == packed_tables
    assert span["args"]["pack"] == pack


# -- (f) the pool's spans ------------------------------------------------------------


def _fails_once(marker):
    if not os.path.exists(marker):
        with open(marker, "w"):
            pass
        raise ValueError("first attempt")
    return "second attempt"


def test_pool_span_counts_a_failure_and_its_retry(
    local_runtime, tmp_path, monkeypatch
):
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    pool = runtime.get_context().pool
    marker = str(tmp_path / "marker")
    with telemetry.context(epoch=9):
        with pytest.raises(TaskError):
            pool.submit(_fails_once, marker).result(timeout=60)
        with telemetry.context(retry=1):
            again = pool.submit(_fails_once, marker)
        assert again.result(timeout=60) == "second attempt"
    spans = [
        s for s in trace.local_spans() if s["name"] == "pool:_fails_once"
    ]
    assert len(spans) == 2
    first, second = sorted(spans, key=lambda s: s["ts"])
    assert first["args"]["error"] == "ValueError"
    assert "error" not in second["args"] and second["args"]["retry"] == 1
    for s in spans:
        assert s["args"]["epoch"] == 9 and s["args"]["pid"] != os.getpid()
        assert 0 <= s["args"]["wait_ns"] <= s["dur"] * 1e3
    # One failure and one retry, on the spans; the layer counts the time.
    assert sum("error" in s["args"] for s in spans) == 1
    assert sum("retry" in s["args"] for s in spans) == 1
    by_fn = layer_counts(spans)["runtime"]["by_fn"]
    assert by_fn["_fails_once"]["tasks"] == 2
    assert by_fn["_fails_once"]["wait_s"] + by_fn["_fails_once"][
        "run_s"
    ] == pytest.approx(sum(s["dur"] for s in spans) / 1e6)
    assert not pool._traced  # nothing is kept once a task is done


def test_pool_keeps_nothing_with_tracing_off(local_runtime, tmp_path):
    pool = runtime.get_context().pool
    marker = str(tmp_path / "marker")
    with open(marker, "w"):
        pass
    fut = pool.submit(_fails_once, marker)
    assert not pool._traced
    assert fut.result(timeout=60) == "second attempt"
    assert trace.local_spans() == []


# -- (g) the train step keeps the program it compiled (ISSUE 36) ---------------------


class _OneMatrix:
    """The smallest model ``make_train_step`` takes: ``logits = x @ w``,
    with something to say when the step is built and of a batch's shape."""

    build_facts = {"model": "one_matrix"}

    def init(self, rng, features):
        return {"w": jnp.full((features["x"].shape[1],), 0.5, jnp.float32)}

    def apply(self, params, features):
        return features["x"] @ params["w"]

    def traced_facts(self, features, labels):
        return {"rows": int(features["x"].shape[0])}


def _batch(rows):
    return {"x": jnp.ones((rows, 4), jnp.float32)}, jnp.zeros((rows,), jnp.float32)


def _kept_step():
    import optax

    from ray_shuffling_data_loader_tpu.parallel import (
        init_state,
        make_mesh,
        make_train_step,
    )

    mesh = make_mesh(devices=jax.devices()[:1])
    model, optimizer = _OneMatrix(), optax.adam(1e-3)
    state, shardings = init_state(model, optimizer, mesh, _batch(8)[0])
    return make_train_step(model, optimizer, mesh, shardings), state


class _Compiles:
    """The backend compilations JAX reports while the block runs."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        self.count += event == self.EVENT

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _train_spans(name):
    return [
        s for s in trace.local_spans()
        if s["name"] == name and s.get("cat") == "train"
    ]


def test_the_step_compiles_once_a_shape_and_keeps_each_program(monkeypatch):
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    step, state = _kept_step()
    assert callable(step.lower)  # the jitted function's, for an abstract batch
    whole, short = _batch(8), _batch(3)  # a loader's short last batch
    with _Compiles() as first:
        for _ in range(4):
            state, metrics = step(state, *whole)
    assert first.count == 1 and np.isfinite(float(metrics["loss"]))
    (eight,) = step._programs
    with _Compiles() as second:
        state, _ = step(state, *short)
        state, _ = step(state, *whole)
        state, _ = step(state, *short)
    assert second.count == 1
    # Kept beside the first, which is still the program of its shape.
    assert len(step._programs) == 2 and eight in step._programs
    assert step._programs[0] is not eight  # the one used last comes first
    with _Compiles() as reading:
        builds, ops = _train_spans("step:build"), _train_spans("step:ops")
    # Saying what is in the programs compiles nothing.
    assert reading.count == 0
    assert [b["args"]["rows"] for b in builds] == [8, 3]
    assert len(ops) == 2
    # A mistaken call is still the jitted function's error, and costs no
    # program.
    with pytest.raises(TypeError):
        step(state)
    assert len(step._programs) == 2


def test_a_step_first_called_untraced_says_its_program_once_tracing_is_on(
    monkeypatch,
):
    step, state = _kept_step()
    for _ in range(2):
        state, _ = step(state, *_batch(8))
    assert trace.local_spans() == []
    # A profiler session that began after warm-up, or the switch: the flag.
    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    assert trace.local_spans() == []  # nothing until a step runs
    for _ in range(3):
        state, _ = step(state, *_batch(8))
    (build,) = _train_spans("step:build")
    (ops,) = _train_spans("step:ops")
    (program,) = step._programs
    memory = program.compiled.memory_analysis()
    assert build["args"]["model"] == "one_matrix" and build["args"]["rows"] == 8
    assert {
        k: build["args"][k]
        for k in ("temp_bytes", "argument_bytes", "output_bytes",
                  "alias_bytes", "code_bytes")
    } == {
        "temp_bytes": memory.temp_size_in_bytes,
        "argument_bytes": memory.argument_size_in_bytes,
        "output_bytes": memory.output_size_in_bytes,
        "alias_bytes": memory.alias_size_in_bytes,
        "code_bytes": memory.generated_code_size_in_bytes,
    }
    assert build["args"]["argument_bytes"] > 0
    # ``step:ops``: the module's name, and the program's own instructions.
    text = program.compiled.as_text()
    assert ops["args"]["program"] == "jit_step_fn"
    assert text.startswith("HloModule jit_step_fn")
    table = ops["args"]["table"]
    entry = text[text.index("\nENTRY "):]
    in_entry = [own for own in table if f"%{own} = " in entry]
    assert in_entry, table
    for own, op_name in table.items():
        # The names are those of the compiled text, and so a trace's.
        assert f"%{own} = " in text, own
        assert f'op_name="{op_name}"' in text, own
    unscoped = {
        own: table[own] for own in in_entry
        if "jvp(loss)" not in table[own]
        and "/optimizer/" not in table[own]
    }
    # All under ``loss`` or ``optimizer`` but the step counter's ``add``.
    assert set(unscoped.values()) <= {"jit(step_fn)/add"}, unscoped
    assert any("transpose(jvp(loss))" in v for v in table.values())
    # No parameter, and nothing of a fused computation.
    assert not any(own.startswith(("param", "state", "Arg_")) for own in table)
    # The loader carries the table through and adds up the build's numbers.
    folded = layer_counts(trace.local_spans())["train step"]
    assert folded["step:ops"]["table"] == table
    assert folded["step:ops"]["program"] == "jit_step_fn"
    assert folded["step:ops"]["spans"] == 1
    assert folded["step:build"]["spans"] == 1
    assert folded["step:build"]["sum"]["temp_bytes"] == memory.temp_size_in_bytes


def test_a_step_never_traced_says_nothing_and_reads_no_text(monkeypatch):
    from ray_shuffling_data_loader_tpu.parallel import train

    asked = []
    monkeypatch.setattr(
        train._Program, "ops", lambda self: asked.append("ops") or {}
    )
    monkeypatch.setattr(
        train._Program, "build", lambda self: asked.append("build") or {}
    )
    step, state = _kept_step()
    for _ in range(3):
        state, _ = step(state, *_batch(8))
    assert trace.local_spans() == [] and asked == []
    assert layer_counts(trace.local_spans()) == {}
    assert not step._programs[0].said


def test_the_newest_table_is_the_one_the_layers_keep():
    def ops(ts_ms, table):
        return {**_span("step:ops", ts_ms, 0, table=table, program="jit_step_fn"),
                "cat": "train"}

    def build(ts_ms, temp):
        return {**_span("step:build", ts_ms, 0, model="m", temp_bytes=temp,
                        shared_from=[1, 2]), "cat": "train"}

    got = layer_counts([
        ops(20, {"fusion.1": "jit(step_fn)/optimizer/add"}),
        ops(10, {"fusion.1": "jit(step_fn)/jvp(loss)/mul"}),
        build(10, 100), build(20, 300),
        # The span of the build itself (``RSDL_TRACE``) is no counter.
        _span("step:build", 0, 5, model="m"),
    ])
    assert set(got) == {"train step"}
    assert got["train step"]["step:ops"] == {
        "spans": 2, "sum": {}, "program": "jit_step_fn",
        "table": {"fusion.1": "jit(step_fn)/optimizer/add"},
    }
    assert got["train step"]["step:build"] == {
        "spans": 2, "sum": {"temp_bytes": 400},
    }


HLO_TEXT = """\
HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(step_fn)/optimizer/add"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %b = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %add.5 = f32[] add(%a, %b), metadata={op_name="jit(step_fn)/jvp(loss)/reduce_sum"}
}

%branch_1 (arg_tuple.0: (f32[4]{0:T(128)}, s32[])) -> f32[4] {
  %arg_tuple.0 = (f32[4]{0:T(128)}, s32[]) parameter(0)
  %get-tuple-element.3 = f32[4]{0:T(128)} get-tuple-element(%arg_tuple.0), index=0, metadata={op_name="jit(step_fn)/jvp(loss)/m/experts/cond/mul"}
  %copy-start.1 = (f32[4]{0:T(128)}, f32[4]{0:T(128)S(1)}, u32[]) copy-start(%get-tuple-element.3)
  %copy-done.1 = f32[4]{0:T(128)S(1)} copy-done(%copy-start.1)
  ROOT %fusion.7 = f32[4]{0:T(128)} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_fn)/jvp(loss)/m/experts/cond/mul"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[] {
  %Arg_0.1 = f32[4]{0:T(128)} parameter(0), metadata={op_name="state.params[\\'w\\']"}
  %constant.2 = s32[] constant(1), metadata={op_name="jit(step_fn)/jvp(loss)/m/experts/cond"}
  %tuple.3 = (f32[4]{0:T(128)}, s32[]) tuple(%Arg_0.1, %constant.2)
  %cond.4 = f32[4]{0:T(128)} conditional(%constant.2, %tuple.3, %tuple.3), branch_computations={%branch_1, %branch_1}, metadata={op_name="jit(step_fn)/jvp(loss)/m/experts/cond"}
  %call.6 = f32[4]{0:T(128)} call(%cond.4), to_apply=%branch_1, metadata={op_name="jit(step_fn)/jvp(loss)/m/call"}
  ROOT %reduce.8 = f32[] reduce(%call.6, %constant.2), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step_fn)/transpose(jvp(loss))/m/reduce_sum"}
}
"""


def test_program_ops_keeps_what_can_be_an_event_of_its_own():
    """On a text as the TPU's compiler writes it: tiled layouts, a tuple
    type, a branch's computation, a fusion's and a reducer's."""
    from ray_shuffling_data_loader_tpu.parallel.train import program_ops

    got = program_ops(HLO_TEXT)
    assert got["program"] == "jit_step_fn"
    assert got["table"] == {
        # A branch's operations are events of their own, the fused and
        # the reducer's are not; copies carry no ``op_name``.
        "fusion.7": "jit(step_fn)/jvp(loss)/m/experts/cond/mul",
        "cond.4": "jit(step_fn)/jvp(loss)/m/experts/cond",
        "call.6": "jit(step_fn)/jvp(loss)/m/call",
        "reduce.8": "jit(step_fn)/transpose(jvp(loss))/m/reduce_sum",
    }
    assert program_ops("") == {"program": "", "table": {}}


def test_the_merged_trace_puts_the_op_name_on_a_device_operation(
    monkeypatch, tmp_path
):
    """``trace_export(xplane=...)``: an operation of the device's ``XLA
    Ops`` line whose own name a ``step:ops`` span of the buffer holds
    carries that ``op_name``; nothing else changes."""
    import types

    monkeypatch.setenv("RSDL_TRACE", "1")
    trace.refresh_from_env()
    trace.record_span(
        "step:ops", 1.0, 0.0, cat="train", program="jit_step_fn",
        table={"fusion.661": "jit(step_fn)/jvp(loss)/m/layer_2/attention/mul"},
    )

    def event(name, start, dur, **stats):
        return types.SimpleNamespace(
            name=name, start_ns=start, duration_ns=dur, stats=list(stats.items())
        )

    def line(name, *events):
        return types.SimpleNamespace(name=name, events=list(events))

    planes = [
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", event("jit_step_fn(123)", 1000, 500)),
            line("XLA Ops",
                 event("%fusion.661 = f32[4]{0} fusion(..)", 1000, 200),
                 event("%copy.3 = f32[4]{0} copy(..)", 1200, 100)),
        ]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("main", event("clock.sync", 900, 1, wall_ns=5_000_000_900)),
        ]),
    ]
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: types.SimpleNamespace(planes=planes)),
    )
    out = trace.trace_export(str(tmp_path / "merged.json"), xplane="an.xplane.pb")
    with open(out) as f:
        events = {
            e["name"]: e for e in json.load(f)["traceEvents"]
            if e.get("cat") == "xplane"
        }
    assert events["fusion.661"]["args"] == {
        "op_name": "jit(step_fn)/jvp(loss)/m/layer_2/attention/mul"
    }
    assert events["copy.3"]["args"] == {}
    assert events["jit_step_fn(123)"]["args"] == {}
    assert events["fusion.661"]["ts"] == pytest.approx(5_000_001.0)
