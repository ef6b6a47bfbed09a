"""One run of one cell: set-up, the window, the comparison, the metrics.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one model family is a file of its own, found by name:
``configs/<name>.json`` (by the entry's ``file`` in ``BENCHMARK.json``),
``traffic/<traffic>.json``, ``layer_metrics/<metric>.py``, and
``families/<family>/`` by the ``family`` the configuration's file names.
This module is the one general consumer loop that reads them:

    runtime.init -> data files from --seed -> the family's state and
    compiled step -> the loader the configuration names -> warm-up on epoch
    0 (the steps the reference follows) -> WINDOW: set_epoch, for batch in
    loader: step, ``steps_in_flight`` steps dispatched ahead of the one
    waited for (seconds of device work, so that the chip stays fed while the
    host stands still), epochs back to back -> when the time is up send
    nothing more, wait for all that was sent, read the clock -> the memory
    peak, free the state -> the comparison.

From the program it takes the system under test (runtime, loaders, mesh;
through the family's ``program.py`` the model, its state and its step) and
nothing of the yardstick. All ``jax`` imports sit inside functions: the
runtime's workers re-import ``__main__``.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import types
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# -- data files: cell, configuration, traffic mix, metric readers, family ------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str, root: str = ROOT):
    """``(cell, configuration, traffic)`` by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    bench_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, cfg, traffic


def metrics_for(bench: dict, kind: str, workload: str) -> List[dict]:
    """The metrics of ``kind`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those that list it, and those that list no cells."""
    return [
        m
        for m in bench[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]


def load_reader(name: str, root: str = ROOT, paths0: str = "chipbench"):
    """The ``read(ctx)`` of ``layer_metrics/<name>.py``."""
    path = os.path.join(root, paths0, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


FAMILY_MODULES = ("counts", "reference", "program")


def load_family(cfg: dict, root: str = ROOT, paths0: str = "chipbench"):
    """The family the configuration names: ``families/<family>/`` as a
    package of its own, with its ``counts``, ``reference`` and ``program``.
    A configuration that names none, or one that is not there, is an error,
    not a default."""
    base = os.path.join(root, paths0, "families")
    found = sorted(
        d for d in (os.listdir(base) if os.path.isdir(base) else [])
        if os.path.isfile(os.path.join(base, d, "__init__.py"))
    )
    name = cfg.get("family")
    if name not in found:
        raise KeyError(
            f"configuration {cfg.get('name')!r} names the family {name!r}; "
            f"families found under {base}: {found}"
        )
    path = os.path.join(base, name)
    package = "chipbench_family_" + name.replace(".", "_").replace("-", "_")
    loaded = sys.modules.get(package)
    if loaded is None or list(loaded.__path__) != [path]:
        # Another root's family of the same name gives way, with its modules.
        for key in [k for k in sys.modules if k.startswith(package + ".")]:
            del sys.modules[key]
        spec = importlib.util.spec_from_file_location(
            package, os.path.join(path, "__init__.py"),
            submodule_search_locations=[path],
        )
        loaded = importlib.util.module_from_spec(spec)
        sys.modules[package] = loaded
        spec.loader.exec_module(loaded)
    return types.SimpleNamespace(
        name=name,
        **{m: importlib.import_module(f"{package}.{m}") for m in FAMILY_MODULES},
    )


# -- the program's side -------------------------------------------------------


def _like(tree, like):
    """``tree``, once it has the shapes and types of the program's own."""
    import jax

    want = jax.tree.map(lambda x: (x.shape, x.dtype), like)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    if want != got:
        raise AssertionError(
            "the program's parameter tree is not the configuration's:\n"
            f"{want}\n{got}"
        )
    return tree


def _make_loader(
    cfg, filenames, mesh, feature_columns, label_column, loader_seed, epochs
):
    """The loader the configuration names, asked for the family's columns."""
    kind = cfg["loader"]
    if kind == "stream":
        from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset

        return JaxShufflingDataset(
            filenames,
            num_epochs=epochs,
            num_trainers=int(cfg["num_trainers"]),
            batch_size=int(cfg["batch_size"]),
            rank=0,
            feature_columns=feature_columns,
            label_column=label_column,
            num_reducers=int(cfg["num_reducers"]),
            max_concurrent_epochs=int(cfg["max_concurrent_epochs"]),
            seed=loader_seed,
            mesh=mesh,
        )
    if kind == "resident":
        from ray_shuffling_data_loader_tpu.resident import (
            DeviceResidentShufflingDataset,
        )

        return DeviceResidentShufflingDataset(
            filenames,
            num_epochs=epochs,
            batch_size=int(cfg["batch_size"]),
            feature_columns=feature_columns,
            label_column=label_column,
            seed=loader_seed,
            mesh=mesh,
            num_rows=int(cfg["num_rows"]),
        )
    raise KeyError(f"unknown loader {kind!r} in the configuration")


class CompileCounter:
    """Counts what JAX compiles or fetches from its cache while armed."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        self.total = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.total += 1
            if self.armed:
                self.count += 1


def host_facts() -> dict:
    facts = {"cpu_count": os.cpu_count()}
    try:
        st = os.statvfs("/dev/shm")
        facts["dev_shm_gb"] = round(st.f_frsize * st.f_blocks / 1e9, 2)
    except OSError:
        facts["dev_shm_gb"] = None
    return facts


class Program:
    """The program's compiled train step with its state, as the family's
    ``program.Side`` built them, started from the benchmark's weights, and
    the readings the comparison takes from its first steps: each step's
    loss, the norm and the sketch of every leaf of the first gradient as the
    optimizer got it (Adam's first moment after one step is (1 - b1) g), and
    the norm of every leaf's change after the steps followed, read before a
    later step consumes the donated state."""

    def __init__(self, cfg, family, mesh, seed, rehearse=False, tamper_step=None):
        import jax

        from ray_shuffling_data_loader_tpu.parallel.mesh import replicated

        from chipbench import check

        self.side = side = family.program.Side(cfg, mesh, seed, rehearse)
        # Both sides start from the benchmark's weights, not the program's.
        self._weights = lambda: family.reference.init_params(
            cfg, seed, sharding=replicated(mesh)
        )
        self.state = side.state._replace(
            params=_like(side.tree(self._weights()), side.state.params)
        )
        self._step = tamper_step(side.step) if tamper_step else side.step
        # The side keeps the columns and the tree's names; the state is
        # this object's alone, since every step donates it.
        side.state = side.step = None
        b1 = float(cfg["optimizer"]["b1"])

        def first_gradient(mu):
            g = {k: v / (1.0 - b1) for k, v in mu.items()}
            return check.norms(g), check.sketches(g)

        self._first_gradient = jax.jit(first_gradient)
        self._change_norms = jax.jit(
            lambda p, p0: check.norms({k: p[k] - p0[k] for k in p})
        )
        self.readings = {"loss": []}

    def step_on(self, features, label):
        """One train step on one batch; returns the loss, on the device."""
        self.state, metrics = self._step(
            self.state, *self.side.inputs(features, label)
        )
        return metrics["loss"]

    def record(self, loss) -> None:
        self.readings["loss"].append(loss)
        if "grad_norm" not in self.readings:
            self.readings["grad_norm"], self.readings["grad_sketch"] = (
                self._first_gradient(
                    self.side.flat(self.state.opt_state[0].mu)
                )
            )

    def record_change(self) -> None:
        self.readings["change_norm"] = self._change_norms(
            self.side.flat(self.state.params), self._weights()
        )

    def fetch_readings(self) -> dict:
        import jax

        r = jax.device_get(self.readings)
        return {
            "loss": [float(x) for x in r["loss"]],
            "grad_norm": {k: float(v) for k, v in r["grad_norm"].items()},
            "grad_sketch": {k: v.tolist() for k, v in r["grad_sketch"].items()},
            "change_norm": {k: float(v) for k, v in r["change_norm"].items()},
        }

    def free(self) -> None:
        self.state = self._step = self.readings = None


# -- the run ------------------------------------------------------------------


def run_cell(
    bench: dict,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    rehearse: bool = False,
    t_start: Optional[float] = None,
    devices=None,
    tamper: Optional[Dict[str, Callable]] = None,
    dump_trace: Optional[str] = None,
    say: Callable[[str], None] = lambda m: print(m, file=sys.stderr, flush=True),
    root: str = ROOT,
) -> dict:
    """Run one cell and return the result object (``correct``,
    ``attempted``, ``failed``, ``metrics``, ``device``, ``compared``).

    ``tamper`` is for the tests that break the timed path underneath:
    ``{"step": f(step) -> step, "batch": f(features, label) -> same}``.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    tamper = tamper or {}
    cell, cfg, traffic = load_cell(bench, workload, root)
    if rehearse:
        cfg = {**cfg, **cfg["rehearsal"]}
    chips = int(cell["chips"])
    family = load_family(cfg, root, bench["paths"][0])

    import jax
    import numpy as np

    from ray_shuffling_data_loader_tpu import runtime
    from ray_shuffling_data_loader_tpu.parallel import make_mesh

    from chipbench import check, datagen, trace_reduce, work

    devices = list(devices if devices is not None else jax.devices()[:chips])
    if len(devices) != chips:
        raise RuntimeError(f"the cell asks for {chips} chips, have {len(devices)}")
    mesh = make_mesh(devices=devices)
    batch = int(cfg["batch_size"])
    num_rows = int(cfg["num_rows"])
    key_col = datagen.KEY_COLUMN
    in_flight = int(traffic["steps_in_flight"])
    warm_steps = int(traffic["warmup_steps"])
    stride = int(traffic["sample_stride"])
    say(f"host: {json.dumps(host_facts())}")
    compiles = CompileCounter()

    runtime.init()
    ctx = runtime.get_context()
    data_dir = tempfile.mkdtemp(prefix="chipbench-data-")
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    ds = None
    try:
        # -- set-up: files, state, step ---------------------------------------
        t0 = time.perf_counter()
        filenames, disk_bytes = datagen.generate(
            ctx.pool.submit,
            cfg["data_spec"],
            num_rows,
            int(cfg["num_files"]),
            int(cfg["row_groups_per_file"]),
            data_dir,
            seed,
        )
        say(
            f"data: {num_rows} rows in {len(filenames)} files, "
            f"{disk_bytes / 1e9:.2f} GB on disk, {time.perf_counter() - t0:.1f} s"
        )
        program = Program(cfg, family, mesh, seed, rehearse, tamper.get("step"))
        say(
            f"model: family {family.name}, "
            f"{family.counts.num_parameters(cfg) / 1e6:.1f} M parameters, "
            f"batch {batch}, mesh {dict(mesh.shape)}"
        )
        # The columns the family asks the loader for, and the key beside them.
        label_col = program.side.label_column
        feature_cols = [*program.side.feature_columns, key_col]

        def delivered(features, label) -> dict:
            """A delivered batch as one ``{column: array}``."""
            return {**features, label_col: label} if label_col else dict(features)

        # -- the loader, and the first batch ----------------------------------
        # More epochs than the fastest plausible window can use; a window
        # that does use them all (a toy rehearsal's) closes there.
        epochs_given = int(traffic["epochs_given"])
        t_loader = time.perf_counter()
        ds = _make_loader(
            cfg, filenames, mesh, feature_cols, label_col,
            seed & 0x7FFFFFFF, epochs_given,
        )

        losses: List = []  # device scalars, fetched after the window

        def consume(features, label):
            """The consumer: one step on one delivered batch."""
            if "batch" in tamper:
                features, label = tamper["batch"](features, label)
            return features, label, program.step_on(features, label)

        # Warm-up on epoch 0: the first steps, which the reference follows,
        # through the window's own feed and call.
        ds.set_epoch(0)
        it = iter(ds)
        warm_batches = []
        first_batch_s = None
        for i in range(warm_steps):
            features, label = next(it)
            if first_batch_s is None:
                jax.block_until_ready((features, label))
                first_batch_s = time.perf_counter() - t_loader
            features, label, loss = consume(features, label)
            warm_batches.append(delivered(features, label))
            program.record(loss)
        program.record_change()
        # Every program of the window is compiled by now; let the rest of
        # the warm-up settle before the clock starts.
        jax.block_until_ready((program.state, program.readings))
        it.close()
        del it
        say(
            f"warm-up: first batch {first_batch_s:.1f} s after the loader's "
            f"constructor was called, {warm_steps} steps done, "
            f"{compiles.total} programs compiled or fetched"
        )

        # -- the window -------------------------------------------------------
        sample_at = int(np.random.default_rng(seed).integers(0, stride))
        epochs_keys: List[List] = []
        whole: List[bool] = []
        samples: List[dict] = []
        iter_s: List[float] = []
        wait_s = 0.0
        attempted = 0
        annotate = jax.profiler.TraceAnnotation
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        setup_s = time.perf_counter() - t_start
        compiles.armed = True
        t_open = time.perf_counter()
        epoch = 1
        done = False
        t_iter = t_open
        while not done and epoch < epochs_given:
            ds.set_epoch(epoch)
            it = iter(ds)
            keys: List = []
            epochs_keys.append(keys)
            whole.append(False)
            while True:
                with annotate("loader.next"):
                    t_wait = time.perf_counter()
                    item = next(it, None)
                    wait_s += time.perf_counter() - t_wait
                if item is None:
                    whole[-1] = True
                    break
                attempted += 1
                with annotate("runahead.block"):
                    if len(losses) >= in_flight:
                        losses[-in_flight].block_until_ready()
                with annotate("step.dispatch"):
                    features, label, loss = consume(*item)
                losses.append(loss)
                keys.append(features[key_col])
                if (attempted - 1) % stride == sample_at:
                    samples.append(delivered(features, label))
                now = time.perf_counter()
                iter_s.append(now - t_iter)
                t_iter = now
                if now - t_open >= seconds:
                    done = True
                    break
            epoch += 1
        jax.block_until_ready(losses[-1] if losses else program.state)
        t_close = time.perf_counter()
        compiles.armed = False
        window_s = t_close - t_open
        if trace:
            jax.profiler.stop_trace()
        say(
            f"window: {window_s:.3f} s, {len(iter_s)} iterations over "
            f"{len(epochs_keys)} epochs ({sum(whole)} whole), "
            f"{len(samples)} batches sampled"
        )
        longest = sorted(range(len(iter_s)), key=iter_s.__getitem__)[-4:]
        say(
            "longest iterations (index: ms): "
            + ", ".join(f"{i}: {1e3 * iter_s[i]:.1f}" for i in reversed(longest))
        )
        if compiles.count:
            raise RuntimeError(
                f"{compiles.count} compilations inside the measured window"
            )
        t_after = time.perf_counter()
        it.close()
        loader_stats = ds.stats.as_dict()
        if hasattr(ds, "close"):
            ds.close()
        ds = None

        # -- memory, then free the program's state ----------------------------
        mem = [d.memory_stats() or {} for d in devices]
        peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
        limit = max((m.get("bytes_limit", 0) for m in mem), default=0)
        say(f"device memory: peak_bytes_in_use {peak}, bytes_limit {limit}")
        loss_values = [float(x) for x in jax.device_get(losses)]
        prog = program.fetch_readings()
        fetch = lambda b: {k: np.asarray(v) for k, v in b.items()}  # noqa: E731
        warm_host = [fetch(b) for b in warm_batches]
        sample_host = [fetch(b) for b in samples]
        epochs_host = [[np.asarray(k) for k in ks] for ks in epochs_keys]
        program.free()
        del losses, warm_batches, samples, epochs_keys, item
        del features, label, loss

        # -- the comparison -----------------------------------------------------
        truth = datagen.read_truth(filenames)
        numbers = check.delivery_numbers(
            num_rows, batch, epochs_host, whole,
            [*warm_host, *sample_host], truth, key_col,
        )
        bad_losses = sum(not np.isfinite(x) for x in loss_values)
        numbers["losses_not_finite"] = bad_losses
        # The reference follows the files' rows of the keys delivered.
        ref_batches = []
        for b in warm_host:
            keys = np.clip(b[key_col].astype(np.int64), 0, num_rows - 1)
            ref_batches.append(
                family.reference.batch_of(
                    cfg, {c: col[keys] for c, col in truth.items()}
                )
            )
        del truth
        ref = family.reference.Reference(cfg).follow(
            lambda: family.reference.init_params(cfg, seed), ref_batches
        )
        training = check.training_numbers(prog, ref)
        for name in check.PRINTED:
            say(f"{name} {training.pop(name)!r}, not compared")
        numbers.update(training)
        limits = {
            **{k: 0 for k in (
                "keys_off", "rows_altered", "epochs_in_same_order",
                "batches_short", "losses_not_finite",
            )},
            **cfg["limits"],
        }
        correct, compared = check.judge(numbers, limits)
        check_s = time.perf_counter() - t_after
        say(f"comparison took {check_s:.1f} s")

        # -- metrics ----------------------------------------------------------
        rows_done = (len(loss_values) - bad_losses) * batch
        kind = devices[0].device_kind
        ctxm = {
            "cfg": cfg,
            "family": family,
            "cell": cell,
            "traffic": traffic,
            "chips": chips,
            "device_kind": kind,
            "peaks": None if rehearse else work.peaks_for(kind),
            "window_s": window_s,
            "rows": rows_done,
            "iter_s": iter_s,
            "wait_s": wait_s,
            "first_batch_s": first_batch_s,
            "loader_stats": loader_stats,
            "trace": None,
        }
        device = {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(devices),
            "memory_peak_bytes": int(peak),
        }
        result = {
            "correct": bool(correct),
            "attempted": attempted,
            "failed": attempted - len(loss_values) + bad_losses,
        }
        if trace:
            planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            if dump_trace:
                with open(dump_trace, "w") as f:
                    json.dump(trace_reduce.summary(planes), f, indent=1)
            tr = reduce_trace(planes, trace_reduce)
            ctxm["trace"] = tr
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            metrics = {}
            for m in metrics_for(bench, "per_layer", workload):
                value = load_reader(m["name"], root, bench["paths"][0])(ctxm)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            result["metrics"] = metrics
            result["device"] = device
            result["breakdown"] = tr["breakdown"]
        else:
            e2e = {
                "rows_per_s": rows_done / window_s / chips,
                "step_p95_ms": 1e3 * percentile(iter_s, 0.95),
                "setup_s": setup_s,
            }
            say(f"step_p95_ms over {len(iter_s)} samples")
            result["metrics"] = {
                m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in metrics_for(bench, "end_to_end", workload)
            }
            result["device"] = device
        result["compared"] = compared
        return result
    finally:
        if ds is not None and hasattr(ds, "close"):
            ds.close()
        runtime.shutdown()
        shutil.rmtree(data_dir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        leaked = [
            f for f in os.listdir(ctx.store.shm_dir)
            if f.startswith(ctx.store.session)
        ]
        if leaked:
            say(f"segments left in {ctx.store.shm_dir}: {leaked[:5]}")


def percentile(values: List[float], q: float) -> float:
    """The ``q`` quantile by linear interpolation between order statistics."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    at = q * (len(xs) - 1)
    lo = int(at)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (at - lo)


def reduce_trace(planes: dict, tr) -> dict:
    """What the per-layer readers need of a trace: the device's operation
    and program events inside the window the benchmark's own host spans
    mark, busy and idle time, and the breakdown."""
    host = [
        ev
        for name, lines in planes.items()
        if not name.startswith(tr.DEVICE_PREFIX)
        for events in lines.values()
        for ev in events
        if ev[0] in ("loader.next", "step.dispatch", "runahead.block")
    ]
    dev_names = tr.device_planes(planes)
    if not dev_names:
        raise RuntimeError(f"no device plane in the trace: {sorted(planes)}")
    ops_all = {p: planes[p].get(tr.OPS_LINE, []) for p in dev_names}
    every = [ev for evs in ops_all.values() for ev in evs]
    if not every:
        raise RuntimeError("no operation ran on the device in the trace")
    # The traced window: from the first host span of the loop to the end
    # of the last device operation.
    lo = min(ev[1] for ev in host) if host else min(ev[1] for ev in every)
    hi = max(ev[1] + ev[2] for ev in every)
    busy = [tr.busy_union_ns(tr.clip(evs, lo, hi)) for evs in ops_all.values()]
    first = dev_names[0]
    ops = tr.clip(ops_all[first], lo, hi)
    modules = tr.clip(planes[first].get(tr.MODULES_LINE, []), lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": statistics.fmean(busy) / 1e9,
        "ops": ops,
        "modules": modules,
        "host": host,
        "breakdown": {
            "device_ops": tr.top_ops(ops),
            "idle_gaps": tr.label_gaps(tr.gaps(ops, lo, hi), host),
        },
    }
