"""Tests of the benchmark's own yardstick (chipbench/). CPU only: toy sizes,
kernels in the Pallas interpreter, no TPU topology described here."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, harness, limits, trace_reduce, work  # noqa: E402

BENCH = harness.load_benchmark()
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


def config(name):
    with open(os.path.join(ROOT, CONFIGS[name]["file"])) as f:
        return json.load(f)


def family_of(cfg):
    return harness.load_family(cfg)


# -- the arithmetic, against hand counts ----------------------------------------


@pytest.mark.parametrize(
    "name,flops,params,state",
    [
        ("dlrm-mlperf-stream", 26_495_232, 377_186_177, 4_526_234_124),
        ("dlrm-shipped-resident", 1_512_000, 93_444_321, 1_121_331_852),
    ],
)
def test_work_from_the_config_file_alone(name, flops, params, state):
    cfg = config(name)
    counts = family_of(cfg).counts
    assert counts.flops_per_row(cfg) == flops
    assert counts.num_parameters(cfg) == params
    assert counts.state_bytes(cfg) == state
    assert sum(counts.vocab_sizes(cfg).values()) == 2_912_607
    # The rehearsal's cap on the tables is the configuration's, not a flag.
    toy = {**cfg, **cfg["rehearsal"]}
    assert max(counts.vocab_sizes(toy).values()) == 5000


def test_packed_and_permute_bytes():
    # 20 feature columns + label = 21 rows of int32, held as 24 sublanes.
    assert work.packed_nbytes(36_000_000, 20) == 3_456_000_000
    assert work.packed_nbytes(1000, 7) == 8 * 4 * 1000
    cfg = config("dlrm-shipped-resident")
    assert work.permute_bytes(cfg) == 2 * 3_456_000_000


def test_interaction_work():
    cfg = config("dlrm-mlperf-stream")
    w = family_of(cfg).counts.interaction_fwd_work(cfg, 250_000)
    assert w["flops"] == 250_000 * 171 * 128 * 2
    assert w["bytes"] == 250_000 * (19 * 128 * 2 + 171 * 2)


def test_peaks_table_knows_the_v5e_and_nothing_else():
    p = work.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9 imaginary")


def test_percentile():
    assert harness.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 0.5) == 3.0
    assert harness.percentile(list(range(101)), 0.95) == 95.0


# -- BENCHMARK.json holds together ----------------------------------------------


def test_benchmark_json_names_files_that_exist():
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for c in BENCH["configs"]:
        cfg = config(c["name"])
        assert cfg["name"] == c["name"]
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        for key in c["reduced"]:
            assert key in cfg and key in cfg["published"], key
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        harness.load_cell(BENCH, w["name"])
        assert len(w["why"]) <= 200
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert callable(harness.load_reader(m["name"]))


def test_a_new_config_mix_cell_and_metric_are_files_and_entries_only(tmp_path):
    """Drop a configuration, a traffic mix, a cell and a per-layer metric
    into a copy of the benchmark as NEW files and entries; the harness
    finds each by name, and no existing file is edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"))
    before = {
        os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
        for dp, _, fs in os.walk(os.path.join(root, "chipbench"))
        for p in fs
    }
    cfg = config("dlrm-shipped-resident")
    cfg["name"] = "dlrm-new"
    cfg["num_rows"] = 1_000_000
    with open(os.path.join(root, "chipbench/configs/dlrm-new.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "chipbench/traffic/one-in-flight.json"), "w") as f:
        json.dump({"steps_in_flight": 1, "warmup_steps": 3,
                   "epochs_given": 8, "sample_stride": 4}, f)
    with open(os.path.join(root, "chipbench/layer_metrics/new.rows.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['rows']) or None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "dlrm-new", "source": "x", "reduced": [],
                             "file": "chipbench/configs/dlrm-new.json", "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "dlrm-new",
                               "traffic": "one-in-flight", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new.rows", "unit": "rows", "better": "higher",
                               "source": "program_counter", "layer": "delivery",
                               "moves": "rows_per_s", "workloads": ["new-cell"]})
    cell, got_cfg, traffic = harness.load_cell(bench, "new-cell", root)
    assert got_cfg["num_rows"] == 1_000_000 and traffic["steps_in_flight"] == 1
    names = [m["name"] for m in harness.metrics_for(bench, "per_layer", "new-cell")]
    assert names == ["new.rows"]
    assert harness.load_reader("new.rows", root)({"rows": 5}) == 5.0
    # A reader that finds nothing returns nothing, never 0.
    assert harness.load_reader("new.rows", root)({"rows": 0}) is None
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


@pytest.mark.parametrize(
    "name", [m["name"] for m in BENCH["per_layer"]]
)
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    cfg = config("dlrm-shipped-resident")
    ctx = {
        "cfg": cfg, "family": family_of(cfg), "cell": {}, "traffic": {},
        "chips": 1, "device_kind": "cpu", "peaks": None, "window_s": 0.0,
        "rows": 0, "iter_s": [], "wait_s": 0.0, "first_batch_s": None,
        "loader_stats": {}, "trace": None,
    }
    assert harness.load_reader(name)(ctx) is None


# -- the trace reduction, on a small synthetic event list -------------------------


def test_trace_reduction_on_synthetic_events():
    ops = [("a", 0, 10), ("b", 5, 10), ("a", 30, 10), ("c", 100, 5)]
    assert trace_reduce.busy_union_ns(ops) == 15 + 10 + 5
    assert trace_reduce.sums_by_name(ops) == {"a": 20, "b": 10, "c": 5}
    assert trace_reduce.clip(ops, 8, 32) == [("a", 8, 2), ("b", 8, 7), ("a", 30, 2)]
    assert trace_reduce.gaps(ops, 0, 60) == [(15, 15), (40, 20)]
    assert trace_reduce.durations_of(ops, "a") == [10, 10]
    spans = [("loader.next", 14, 10), ("runahead.block", 38, 30)]
    assert trace_reduce.label_gaps(trace_reduce.gaps(ops, 0, 60), spans) == [
        ["runahead.block", 20 / 1e9], ["loader.next", 15 / 1e9],
    ]
    assert trace_reduce.top_ops(ops, top=2) == [["a", 20 / 1e9], ["b", 10 / 1e9]]


def test_reduce_trace_window_busy_and_readers():
    dev = "/device:TPU:0"
    planes = {
        dev: {
            "XLA Ops": [("fusion.1", 100, 50), ('%jvp.1 = bf16[8,171] custom-call(), custom_call_target="tpu_custom_call"', 150, 25),
                        ("fusion.1", 300, 50), ("early", 0, 10)],
            "XLA Modules": [("jit_step_fn(1)", 100, 75), ("jit_step_fn(1)", 300, 50),
                            ("jit_permute_all(2)", 200, 40)],
        },
        "/host:CPU": {"python3": [("loader.next", 90, 5), ("step.dispatch", 95, 5),
                                  ("other", 0, 1000)]},
    }
    tr = harness.reduce_trace(planes, trace_reduce)
    assert tr["window_s"] == pytest.approx((350 - 90) / 1e9)
    assert tr["busy_s"] == pytest.approx(125 / 1e9)
    assert [n for n, _ in tr["breakdown"]["device_ops"]][0] == "fusion.1"
    cfg = config("dlrm-shipped-resident")
    ctx = {"trace": tr, "cfg": cfg, "family": family_of(cfg), "chips": 1,
           "peaks": work.peaks_for("TPU v5 lite")}
    assert harness.load_reader("device.idle_pct")(ctx) == pytest.approx(
        100 * (1 - 125 / 260)
    )
    assert harness.load_reader("step.device_ms")(ctx) == pytest.approx(62.5e-6)
    least = work.permute_bytes(ctx["cfg"]) / 819e9
    assert harness.load_reader("resident.permute_roofline")(ctx) == pytest.approx(
        100 * least / 40e-9
    )
    least = family_of(cfg).counts.interaction_fwd_work(cfg, 250_000)["bytes"] / 819e9
    assert harness.load_reader("interaction.fwd_roofline")(ctx) == pytest.approx(
        100 * least / 25e-9
    )
    assert trace_reduce.short_name(planes[dev]["XLA Ops"][1][0]) == "jvp.1[tpu_custom_call]"


# -- the comparison --------------------------------------------------------------


def _ref():
    return {
        "loss": [0.7, 0.69, 0.68],
        "grad_norm": {"a": 1.0, "b": 2.0, "c": 1e-6},
        "grad_sketch": {"a": [0.6, 0.8], "b": [2.0, 0.0], "c": [1e-6, 0.0]},
        "change_norm": {"a": 0.1, "b": 0.2, "c": 0.3},
    }


def test_training_numbers_by_the_worst_leaf():
    ref = _ref()
    prog = json.loads(json.dumps(ref))
    prog["loss"][1] = 0.69 * 1.01
    prog["grad_norm"]["c"] = 0.1  # tiny leaf: measured against the median
    prog["grad_norm"]["a"] = 1.05  # gaps 0.05, 0, 0.1: the median leaf's 0.05
    prog["change_norm"]["c"] = 0.0  # its gradient is nought: left out
    prog["change_norm"]["a"] = 0.0  # has not moved: reads a/median = 0.5
    prog["grad_sketch"]["a"] = [0.6, 0.5]  # differs by 0.3 of a norm of 1
    prog["grad_sketch"]["b"] = [2.0, 0.2]  # by 0.2 of a norm of 2: 0.1
    n = check.training_numbers(prog, ref)
    assert n["grad_diff"] == pytest.approx(0.1)  # the median leaf of 0.3, 0.1, 0
    assert n["loss_gap"] == pytest.approx(0.01)
    assert n["grad_norm_gap"] == pytest.approx(0.1 - 1e-6)
    assert n["grad_norm_mid_gap"] == pytest.approx(0.05)
    assert set(check.PRINTED) == {"loss_gap", "grad_norm_gap"}
    assert n["change_norm_gap"] == pytest.approx(0.1 / 0.2)
    same = check.training_numbers(ref, ref)
    assert set(same.values()) == {0.0}


def test_judge_needs_a_limit_for_every_number():
    ok, compared = check.judge({"x": 0.5, "y": 0}, {"x": 1.0, "y": 0})
    assert ok and compared["x"] == {"value": 0.5, "limit": 1.0, "ok": True}
    ok, _ = check.judge({"x": 1.5}, {"x": 1.0})
    assert not ok
    ok, _ = check.judge({"x": float("nan")}, {"x": 1.0})
    assert not ok
    with pytest.raises(KeyError):
        check.judge({"z": 0}, {})


def test_delivery_numbers_catch_each_broken_guarantee():
    n, b = 12, 4
    truth = {"key": np.arange(n, dtype=np.int32),
             "v": (np.arange(n) * 10).astype(np.int32)}
    e1 = [np.array([3, 1, 2, 0]), np.array([7, 5, 6, 4]), np.array([8, 9, 11, 10])]
    e2 = [np.array([0, 1, 2, 3]), np.array([4, 5, 6, 7]), np.array([8, 9, 10, 11])]
    sample = {"key": e1[0], "v": truth["v"][e1[0]]}
    good = check.delivery_numbers(n, b, [e1, e2, e2[:1]], [True, True, False],
                                  [sample], truth, "key")
    assert good == {"keys_off": 0, "rows_altered": 0,
                    "epochs_in_same_order": 0, "batches_short": 0}
    dup = [e1[0], e1[0], e1[2]]
    assert check.delivery_numbers(n, b, [dup], [True], [], truth, "key")[
        "keys_off"] == 8  # four repeated, four missing
    assert check.delivery_numbers(n, b, [e1, e1], [True, True], [], truth, "key")[
        "epochs_in_same_order"] == 1
    bad = {"key": e1[0], "v": truth["v"][e1[0]] + np.array([0, 1, 0, 0])}
    assert check.delivery_numbers(n, b, [e1], [True], [bad], truth, "key")[
        "rows_altered"] == 1
    assert check.delivery_numbers(n, b, [[e1[0][:3]]], [False], [], truth, "key")[
        "batches_short"] == 1


# -- the plain reference against the program, and its control, at a toy size -------


@pytest.fixture(scope="module")
def toy():
    """The shipped-width configuration at its rehearsal size, one device."""
    import jax

    from ray_shuffling_data_loader_tpu.parallel import make_mesh

    cfg = config("dlrm-shipped-resident")
    cfg = {**cfg, **cfg["rehearsal"]}
    return cfg, family_of(cfg), make_mesh(devices=jax.devices()[:1])


def test_reference_agrees_with_the_program_and_the_control_does_not(toy):
    """The program (bfloat16 compute, Pallas interaction in the
    interpreter) stays inside the configuration's limits against the
    float32 reference; the reference in float8 put in its place, and the
    reference fed half of each batch, do not."""
    cfg, family, mesh = toy
    reference = family.reference
    assert reference.CONTROL == "fp8"
    seed = 12
    rows = limits.generator_batches(cfg, seed, 3)
    batches = [reference.batch_of(cfg, r) for r in rows]
    make = lambda: reference.init_params(cfg, seed)  # noqa: E731
    ref = reference.Reference(cfg).follow(make, batches)
    def judged(side):
        numbers = check.training_numbers(side, ref)
        for name in check.PRINTED:  # printed, not compared
            numbers.pop(name)
        return check.judge(numbers, cfg["limits"])

    ok, compared = judged(
        limits.program_readings(cfg, family, mesh, seed, rows, rehearse=True)
    )
    assert ok, compared
    ok, compared = judged(
        reference.Reference(cfg, quant=reference.CONTROL).follow(make, batches)
    )
    assert not ok and not compared["grad_diff"]["ok"], compared
    ok, compared = judged(
        reference.Reference(cfg).follow(make, batches, rows_used=2048)
    )
    assert not ok and not compared["grad_diff"]["ok"], compared


def test_a_sketch_estimates_the_norm_of_a_difference():
    """Whatever the pattern of the difference: here every element of a
    column is off by the same amount, which a plain fold would add up."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    a = rng.normal(size=(3000, 128)).astype(np.float32)
    b = a + 0.01 * rng.normal(size=(1, 128)).astype(np.float32)
    gap = np.asarray(check.sketch(jnp.asarray(a))) - np.asarray(
        check.sketch(jnp.asarray(b))
    )
    assert np.linalg.norm(gap) == pytest.approx(np.linalg.norm(a - b), rel=0.15)
    assert np.asarray(check.sketch(jnp.ones((5,)))).shape == (256,)


def test_weights_come_from_the_seed(toy):
    cfg, family, _ = toy
    reference = family.reference
    a = reference.init_params(cfg, 2**31 + 5)
    b = reference.init_params(cfg, 2**31 + 5)
    c = reference.init_params(cfg, 5)
    assert len(a) == 19 + 2 * len(family.counts.mlp_shapes(cfg))
    assert a["embed_embeddings_name16"].shape == (5000, 32)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["dense_0.w"], c["dense_0.w"])


def test_datagen_is_the_upstream_arithmetic():
    from ray_shuffling_data_loader_tpu import data_generation

    from chipbench import datagen

    cfg = config("dlrm-mlperf-stream")
    assert {c: (lo, hi) for c, (lo, hi, _) in cfg["data_spec"].items()} == {
        c: (lo, hi) for c, (lo, hi, _) in data_generation.DATA_SPEC.items()
    }
    mine = datagen.generate_row_group(cfg["data_spec"], 2, 1000, 64, 2**31 + 9)
    theirs = data_generation.generate_row_group(2, 1000, 64, 2**31 + 9)
    assert set(mine) == set(theirs)
    assert all(np.array_equal(mine[k], theirs[k]) for k in mine)


# -- whole runs, with the look for a chip skipped ---------------------------------


def _run(workload, seed=5, seconds=1.0, tamper=None, chips=1, say=lambda m: None):
    import jax

    bench = json.loads(json.dumps(BENCH))
    for w in bench["workloads"]:
        w["chips"] = chips
    return harness.run_cell(
        bench, workload, seed, seconds, False, rehearse=True,
        devices=jax.devices()[:chips], tamper=tamper, say=say,
    )


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_a_sound_run_is_correct_and_leaves_nothing(workload):
    said = []
    r = _run(workload, say=said.append)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {
        m["name"] for m in harness.metrics_for(BENCH, "end_to_end", workload)
    }
    assert all(v["value"] > 0 for v in r["metrics"].values())
    # Closed early, with epochs still in flight: the run looks for segments
    # of its own session in /dev/shm when it has shut down, and found none.
    assert any(m.startswith("window:") for m in said)
    assert not [m for m in said if m.startswith("segments left")], said


def test_the_loop_runs_on_four_virtual_devices():
    r = _run("resident-train", chips=4)
    assert r["correct"], r["compared"]
    assert r["device"]["count"] == 4


def _state_unchanged(step):
    import jax

    def broken(state, features, label):
        _, metrics = step(jax.tree.map(jax.numpy.copy, state), features, label)
        return state, metrics

    return broken


def _half_batch(step):
    import jax.numpy as jnp

    def broken(state, features, label):
        half = label.shape[0] // 2
        twice = lambda x: jnp.concatenate([x[:half], x[:half]])  # noqa: E731
        return step(state, {c: twice(v) for c, v in features.items()}, twice(label))

    return broken


def _altered_answer(features, label):
    col = "embeddings_name3"
    return {**features, col: features[col].at[0].add(1)}, label


def _repeated_batch():
    last = []

    def broken(features, label):
        if not last:
            last.append((features, label))
        return last[0]

    return broken


@pytest.mark.parametrize(
    "fault,tamper,number",
    [
        ("state unchanged", {"step": _state_unchanged}, "change_norm_gap"),
        ("half the batch left out", {"step": _half_batch}, "grad_diff"),
        ("an answer altered", {"batch": _altered_answer}, "rows_altered"),
        ("a batch repeated", {"batch": _repeated_batch()}, "keys_off"),
    ],
)
def test_a_broken_timed_path_is_not_correct(fault, tamper, number):
    r = _run("resident-train", tamper=tamper)
    assert not r["correct"], fault
    assert not r["compared"][number]["ok"], (fault, r["compared"])


def test_without_a_tpu_run_py_fails_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "stream-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
