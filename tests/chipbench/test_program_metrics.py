"""The per-layer readers that read the program's own spans and programs
(ISSUE 25), each on a synthetic ``ctx``: what it computes where it finds
something, and nothing (never 0) where the program recorded nothing, as the
parent commit does for every metric that is new here."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

LAYERS = {
    "runtime": {
        "by_fn": {
            "shuffle_map": {"tasks": 8, "wait_s": 0.5, "run_s": 11.5},
            "shuffle_reduce": {"tasks": 4, "wait_s": 0.5, "run_s": 7.5},
            # Not the shuffle's: left out of the share.
            "generate_file": {"tasks": 1, "wait_s": 0.5, "run_s": 1.5},
        },
    },
    "shuffle": {"epoch_s": [6.0, 9.0, 7.0]},
    "delivery": {"gets": 12, "get_wait_s": 0.5},
    "staging": {
        "stager_s": 40.0, "ring_put_s": 30.0, "transfers": 100,
        "max_transfer_s": 0.25,
    },
}
MS = 1_000_000
MODULES = [
    ("jit_unpack(1)", 0, 2 * MS), ("jit_unpack(1)", 10 * MS, 4 * MS),
    ("jit_unpack(1)", 20 * MS, 3 * MS), ("jit_step_fn(2)", 30 * MS, 190 * MS),
    # Two epochs' hand-overs, and a gather whose draw lies before the trace.
    ("jit_permute_all(5)", 300 * MS, 600 * MS),
    ("jit_epoch_permutation(4)", 1000 * MS, 400 * MS),
    ("jit_permute_all(5)", 1400 * MS, 700 * MS),
    ("jit_epoch_permutation(4)", 5000 * MS, 500 * MS),
    ("jit_permute_all(5)", 5500 * MS, 700 * MS),
]


def _ctx(loader, layers=None, modules=None):
    return {
        "cfg": {"loader": loader, "batch_size": 250_000}, "cell": {},
        "traffic": {}, "chips": 1, "device_kind": "cpu", "peaks": None,
        "window_s": 40.0, "rows": 0, "iter_s": [], "wait_s": 0.0,
        "first_batch_s": None,
        "loader_stats": {"batches_staged": 100, **(
            {"layers": layers} if layers is not None else {}
        )},
        "trace": None if modules is None else {
            "window_s": 40.0, "busy_s": 39.0, "ops": [], "modules": modules,
            "host": [],
        },
    }


NEW = {
    "runtime.task_wait_pct": ("stream", 100.0 * 1.0 / 20.0),
    "shuffle.epoch_s": ("stream", 7.0),
    "queue.get_wait_pct": ("stream", 100.0 * 0.5 / 40.0),
    "staging.max_transfer_ms": ("stream", 250.0),
    "staging.unpack_ms": ("stream", 3.0),
    "resident.handover_ms": ("resident", 1150.0),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_reader_reads_the_programs_own_record(name):
    loader, want = NEW[name]
    read = harness.load_reader(name)
    assert read(_ctx(loader, LAYERS, MODULES)) == pytest.approx(want)
    # The parent commit: no ``layers``, no ``epoch_permutation`` program.
    old_modules = [m for m in MODULES if "epoch_permutation" not in m[0]]
    if name not in ("staging.unpack_ms",):
        assert read(_ctx(loader, None, old_modules)) is None
    # A layer that recorded nothing, an empty trace, no trace at all.
    assert read(_ctx(loader, {}, [])) is None
    assert read(_ctx(loader, {"staging": {}, "runtime": {"by_fn": {}}}, [])) is None
    assert read(_ctx(loader, None, None)) is None
    # The other loader's cell has nothing for it.
    other = "resident" if loader == "stream" else "stream"
    if name in ("staging.unpack_ms", "resident.handover_ms"):
        assert read(_ctx(other, LAYERS, MODULES)) is None


def test_the_new_entries_are_the_last_of_per_layer():
    tail = BENCH["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == [
        "runtime.task_wait_pct", "shuffle.epoch_s", "queue.get_wait_pct",
        "staging.max_transfer_ms", "staging.unpack_ms",
        "resident.handover_ms",
    ]
    for m in tail:
        loader, _ = NEW[m["name"]]
        assert m["workloads"] == [f"{loader}-train"], m
        assert set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }
    layers = {m["name"]: m["layer"] for m in tail}
    assert layers["shuffle.epoch_s"] == "host shuffle"
    assert layers["resident.handover_ms"] == "resident loader"
