"""A model family and a sample's shape are data (ISSUE 27): the harness
finds a family by the name a configuration gives, as it finds
configurations, mixes and readers, and the generator and the delivery check
take columns of more than one number a row. CPU only, toy sizes."""

import hashlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, datagen, harness  # noqa: E402

BENCH = harness.load_benchmark()

# What is left that knows no model: every module of chipbench/ itself.
GENERAL = sorted(
    f for f in os.listdir(os.path.join(ROOT, "chipbench")) if f.endswith(".py")
)
FAMILY_WORDS = ("dlrm", "embed_", "Dense_", "top_mlp", "embed_dim")


def _config(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def _files(top):
    return {
        os.path.relpath(os.path.join(dp, p), top): open(os.path.join(dp, p), "rb").read()
        for dp, _, fs in os.walk(top)
        for p in fs
        if "__pycache__" not in dp
    }


# -- (a) a family that is not the DLRM is new files and entries only ----------------


@pytest.fixture(scope="module")
def other_family(tmp_path_factory):
    """A copy of the benchmark with the tests' own family, a configuration
    and a cell of it dropped in as NEW files and entries."""
    root = str(tmp_path_factory.mktemp("bench"))
    top = os.path.join(root, "chipbench")
    shutil.copytree(
        os.path.join(ROOT, "chipbench"), top,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    before = _files(top)
    shutil.copytree(
        os.path.join(HERE, "families", "meanpool"),
        os.path.join(top, "families", "meanpool"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(
        os.path.join(HERE, "families", "meanpool-toy.json"),
        os.path.join(top, "configs", "meanpool-toy.json"),
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "meanpool-toy", "source": "x", "reduced": [], "why": "x",
        "file": "chipbench/configs/meanpool-toy.json",
    })
    bench["workloads"].append({
        "name": "meanpool-train", "config": "meanpool-toy",
        "traffic": "per-batch-epochs", "chips": 1, "why": "x",
    })
    for m in bench["per_layer"]:
        if m["name"] == "step.mfu_pct":
            m["workloads"] = [*m["workloads"], "meanpool-train"]
    return root, bench, before


def _run(other_family, tamper=None):
    import jax

    root, bench, _ = other_family
    return harness.run_cell(
        bench, "meanpool-train", 2**31 + 77, 1.0, False, rehearse=True,
        devices=jax.devices()[:1], tamper=tamper, say=lambda m: None, root=root,
    )


def test_another_family_runs_correct_through_the_same_loop(other_family):
    root, bench, before = other_family
    r = _run(other_family)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"rows_per_s", "step_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    # Float32 against float32: far inside limits a bfloat16 program needs.
    assert r["compared"]["grad_diff"]["value"] < 1e-4
    after = _files(os.path.join(root, "chipbench"))
    assert {p: after[p] for p in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/meanpool-toy.json",
        *(f"families/meanpool/{m}.py"
          for m in ("__init__", "counts", "program", "reference")),
    ]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(other_family):
    import jax

    def unchanged(step):
        def broken(state, *inputs):
            _, metrics = step(jax.tree.map(jax.numpy.copy, state), *inputs)
            return state, metrics

        return broken

    r = _run(other_family, tamper={"step": unchanged})
    assert not r["correct"]
    assert r["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_the_readers_divide_the_family_s_own_counts(other_family):
    root, bench, _ = other_family
    _, cfg, _ = harness.load_cell(bench, "meanpool-train", root)
    family = harness.load_family(cfg, root)
    assert family.name == "meanpool"
    flops = 3 * 2 * (16 * 32 + 32)
    assert family.counts.flops_per_row(cfg) == flops
    assert family.counts.num_parameters(cfg) == 357 * 16 + 16 * 32 + 32 + 32 + 1
    ctx = {"cfg": cfg, "family": family, "rows": 1000, "window_s": 2.0,
           "chips": 1, "peaks": {"bf16_flops_per_s": 1e9,
                                 "hbm_bytes_per_s": 1e9},
           "trace": {"ops": [("tpu_custom_call", 0, 10)], "modules": []}}
    assert harness.load_reader("step.mfu_pct", root)(ctx) == pytest.approx(
        100.0 * flops * 1000 / 2.0 / 1e9
    )
    # The other family's kernel is not this family's: nothing to read.
    assert harness.load_reader("interaction.fwd_roofline", root)(ctx) is None
    # The same name under another root is that root's family.
    there = harness.load_family(_config("dlrm-shipped-resident"), root)
    here = harness.load_family(_config("dlrm-shipped-resident"))
    assert there.counts.__file__.startswith(root)
    assert here.counts.__file__.startswith(ROOT)


def test_the_control_of_the_other_family_reads_above_its_program(other_family):
    """Its float32 program against its reference, and the reference in
    bfloat16 put in the program's place."""
    import jax

    from ray_shuffling_data_loader_tpu.parallel import make_mesh

    from chipbench import limits

    root, bench, _ = other_family
    _, cfg, _ = harness.load_cell(bench, "meanpool-train", root)
    family = harness.load_family(cfg, root)
    ref = family.reference
    seed = 31
    batches = limits.generator_batches(cfg, seed, 3)
    ref_batches = [ref.batch_of(cfg, b) for b in batches]
    make = lambda: ref.init_params(cfg, seed)  # noqa: E731
    plain = ref.Reference(cfg).follow(make, ref_batches)

    def judged(side):
        numbers = check.training_numbers(side, plain)
        for name in check.PRINTED:
            numbers.pop(name)
        return check.judge(numbers, cfg["limits"])

    mesh = make_mesh(devices=jax.devices()[:1])
    ok, compared = judged(limits.program_readings(cfg, family, mesh, seed, batches))
    assert ok, compared
    ok, compared = judged(
        ref.Reference(cfg, quant=ref.CONTROL).follow(make, ref_batches)
    )
    assert not ok and not compared["grad_diff"]["ok"], compared


# -- (b) no family, or one that is not there, is an error that lists what is ----------


@pytest.mark.parametrize("named", [None, "transformer-xl"])
def test_a_missing_or_unknown_family_is_an_error_that_lists_the_families(named):
    cfg = _config("dlrm-mlperf-stream")
    cfg.pop("family")
    if named:
        cfg["family"] = named
    with pytest.raises(KeyError) as err:
        harness.load_family(cfg)
    assert "['dlrm']" in str(err.value) and repr(named) in str(err.value)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_names_a_family_that_is_there(name):
    family = harness.load_family(_config(name))
    for need in ("num_parameters", "state_bytes", "flops_per_row"):
        assert callable(getattr(family.counts, need))
    for need in ("init_params", "batch_of", "Reference", "CONTROL"):
        assert hasattr(family.reference, need)
    assert callable(family.program.Side)


# -- (c) no general file names a family, a leaf or a width key of one -----------------


@pytest.mark.parametrize("name", GENERAL)
def test_no_general_file_names_a_family(name):
    assert {"run.py", "harness.py", "check.py", "datagen.py", "limits.py",
            "trace_reduce.py", "work.py", "follow.py"} <= set(GENERAL)
    with open(os.path.join(ROOT, "chipbench", name)) as f:
        text = f.read()
    assert [w for w in FAMILY_WORDS if w in text] == []
    assert "ray_shuffling_data_loader_tpu.models" not in text


def test_counts_and_reference_import_nothing_of_the_program():
    top = os.path.join(ROOT, "chipbench", "families")
    for family in os.listdir(top):
        for module in ("counts.py", "reference.py"):
            path = os.path.join(top, family, module)
            if os.path.isfile(path):
                assert "ray_shuffling_data_loader_tpu" not in open(path).read(), path


# -- (e) a column of more than one number a row ---------------------------------------

WIDE_SPEC = {
    "tokens": [0, 1000, "int32", 8],
    "weight": [0, 1, "float64", 2],
    "flag": [0, 3, "int64"],
}


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("wide"))
    names = [
        datagen.write_file(WIDE_SPEC, i, 40 * i, 40, 2, d, 2**31 + 3)[0]
        for i in range(2)
    ]
    return names, datagen.read_truth(names)


def test_a_wide_column_is_a_fixed_size_list_read_back_as_rows_by_width(wide):
    import pyarrow as pa
    import pyarrow.parquet as pq

    names, truth = wide
    schema = pq.read_schema(names[0])
    assert schema.field("tokens").type == pa.list_(pa.int32(), 8)
    assert schema.field("weight").type == pa.list_(pa.float64(), 2)
    assert schema.field("flag").type == pa.int64()
    assert truth["tokens"].shape == (80, 8) and truth["tokens"].dtype == np.int32
    assert truth["weight"].shape == (80, 2) and truth["weight"].dtype == np.float32
    assert truth["flag"].shape == (80,)
    assert 0 <= truth["tokens"].min() and truth["tokens"].max() < 1000
    assert len(np.unique(truth["tokens"])) > 400  # not one draw repeated
    # The file's rows are the generator's, group by group.
    group = datagen.generate_row_group(WIDE_SPEC, 1, 60, 20, 2**31 + 3)
    assert np.array_equal(truth["tokens"][60:80], group["tokens"])
    assert group["tokens"].dtype == np.int32


def test_delivery_numbers_count_rows_whatever_a_column_s_shape(wide):
    _, truth = wide
    n, b = 80, 20
    order = np.random.default_rng(0).permutation(n).astype(np.int32)
    epoch = [order[i : i + b] for i in range(0, n, b)]
    batch = lambda keys: {c: truth[c][keys] for c in truth}  # noqa: E731
    numbers = lambda epochs, whole, samples: check.delivery_numbers(  # noqa: E731
        n, b, epochs, whole, samples, truth, "key"
    )
    assert numbers([epoch], [True], [batch(k) for k in epoch]) == {
        "keys_off": 0, "rows_altered": 0, "epochs_in_same_order": 0,
        "batches_short": 0,
    }
    # One element of one row, and three elements of another: two rows.
    bad = batch(epoch[1])
    bad["tokens"] = bad["tokens"].copy()
    bad["tokens"][4, 7] += 1
    assert numbers([epoch], [True], [bad])["rows_altered"] == 1
    bad["weight"] = bad["weight"].copy()
    bad["weight"][9, :] += 0.5
    bad["tokens"][9, 0] += 1
    assert numbers([epoch], [True], [bad])["rows_altered"] == 2
    # A short batch and a repeated key count as before.
    short = [epoch[0], epoch[1][:-1], *epoch[2:]]
    got = numbers([short], [True], [])
    assert got["batches_short"] == 1 and got["keys_off"] == 1
    again = [epoch[0], epoch[0], *epoch[2:]]
    assert numbers([again], [True], [])["keys_off"] == 2 * b


# -- (f) a three-element schema writes the bytes it wrote before ----------------------


def test_the_generator_writes_the_parent_s_bytes(tmp_path):
    """Pinned from the generator of commit 2203b56, before a schema entry
    could carry a width: one file of the wide configuration's schema."""
    spec = _config("dlrm-mlperf-stream")["data_spec"]
    assert spec == _config("dlrm-shipped-resident")["data_spec"]
    assert all(len(entry) == 3 for entry in spec.values())
    group = datagen.generate_row_group(spec, 2, 1000, 64, 2**31 + 9)
    h = hashlib.sha256()
    for k in sorted(group):
        h.update(k.encode())
        h.update(np.ascontiguousarray(group[k]).tobytes())
    assert h.hexdigest() == (
        "a7c5a300449d2e19fcf34e4144843b8fdd402534fcdef656803aab56303c7219"
    )
    name, size = datagen.write_file(spec, 3, 3000, 1000, 5, str(tmp_path), 2**31 + 9)
    assert size == 113028
    assert hashlib.sha256(open(name, "rb").read()).hexdigest() == (
        "888f85a22e3f107eb2f6dd45932369d8c3468be895aaf186688f17ff2dec8bea"
    )
