"""``chipbench/scope_time.py`` and the readers built on it (ISSUE 36), on
synthetic events: an operation inside a ``cond`` or a ``while`` is counted
once (PR 32 read 116.5 ms of expert layers for 63.3 by summing a ``cond``
and its branch's operations both), events outside the step's programs are
left out, the phases and the scopes; every new reader on a synthetic ``ctx``
and with nothing to read; and the pin of what this PR may not touch: every
file the benchmark had at the parent commit byte for byte, every entry it
had in its place. CPU only, no JAX."""

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, scope_time  # noqa: E402

BENCH = harness.load_benchmark()
MS = 1_000_000
LOSS = "jit(step_fn)/jvp(loss)/M.loss_fn/M"
BACK = f"jit(step_fn)/transpose(jvp(loss))/M.loss_fn/M/jvp(loss)/M.loss_fn/M/checkpoint"
BRANCH = f"{BACK}/layer_5/feed_forward/experts/cond/branch_0_fun"

# One step of 100 ms, as the ``XLA Ops`` line shows it: (own name, start,
# duration in ms, op_name). The ``cond`` holds its branch's operations, one
# of them a ``while`` that holds its body's; a copy the compiler put into the
# branch has no ``op_name``.
STEP = [
    ("fusion.1", 0, 10, f"{LOSS}/layer_2/self_attn/attention/mul"),
    ("cond.58", 10, 30, f"{BACK}/layer_5/feed_forward/experts/cond"),
    ("fusion.7", 11, 9, f"{BRANCH}/mul"),
    ("while.3", 20, 18, f"{BRANCH}/while"),
    ("fusion.8", 22, 8, f"{BRANCH}/while/body/dot_general"),
    ("copy.5", 30, 6, None),
    ("fusion.9", 40, 10, "jit(step_fn)/optimizer/add"),
    ("add.2", 50, 1, "jit(step_fn)/add"),
    ("fusion.10", 51, 9, f"{LOSS}/layer_14/mamba/ssm_scan/selective_scan_fwd/pallas_call"),
    ("fusion.11", 60, 5, f"{LOSS}/layer_14/mamba/mul"),
]
# Two milliseconds each of the scopes the other readers name.
OTHER_SCOPES = [
    "attention_window", "router", "dense_ffn", "head", "short_conv",
    "embedding", "dot_interaction",
]
STEP += [
    (f"fusion.{20 + i}", 65 + 2 * i, 2, f"{LOSS}/layer_1/{scope}/mul")
    for i, scope in enumerate(OTHER_SCOPES)
]
TABLE = {own: op for own, _, _, op in STEP if op}
STEPS = 2


def _events(offset_ms):
    return [
        (f"%{own} = f32[4]{{0:T(128)}} fusion(..)", (offset_ms + at) * MS, dur * MS)
        for own, at, dur, _ in STEP
    ]


def _ctx(table=TABLE, program="jit_step_fn", layers=None):
    ops = [
        *_events(0), *_events(100),
        # Another program's operations, of the same names: not the step's.
        ("%fusion.1 = f32[4]{0} fusion(..)", 250 * MS, 10 * MS),
        ("%cond.58 = f32[4]{0} conditional(..)", 260 * MS, 30 * MS),
    ]
    modules = [
        ("jit_step_fn(77)", 0, 100 * MS), ("jit_step_fn(77)", 100 * MS, 100 * MS),
        ("jit_unpack(5)", 240 * MS, 60 * MS),
    ]
    train = {"step:build": {"spans": 1, "sum": {"temp_bytes": 8_359_199_232,
                                                 "layers": 5}}}
    if table is not None:
        train["step:ops"] = {"spans": 1, "sum": {}, "table": table,
                             "program": program}
    return {
        "cfg": {}, "family": None, "cell": {}, "traffic": {}, "chips": 1,
        "device_kind": "cpu", "peaks": None, "window_s": 0.3, "rows": 0,
        "iter_s": [], "wait_s": 0.0, "first_batch_s": None,
        "loader_stats": {"layers": {
            "train step": train,
            "shuffle": {"epoch_s": [4.0, 5.0, 4.5, 4.4],
                        "schedules": ["mapreduce", "index", "index", "index"]},
            **(layers or {}),
        }},
        "trace": {"window_s": 0.3, "busy_s": 0.29, "ops": ops,
                  "modules": modules, "host": []},
    }


def test_a_cond_and_a_while_weigh_what_is_their_own():
    """The line nests; self time counts every nanosecond once."""
    got = {
        scope_time.own_name(text): self_ns / MS
        for text, _, self_ns in scope_time.self_times(_events(0))
    }
    # The cond: 30 ms less its branch's mul (9) and while (18); the while:
    # 18 less its body's dot (8) and the copy (6).
    assert got["cond.58"] == 3 and got["while.3"] == 4
    assert got["fusion.7"] == 9 and got["fusion.8"] == 8 and got["copy.5"] == 6
    assert sum(got.values()) == 65 + 2 * len(OTHER_SCOPES)  # the line's busy time
    # Summed by name, the expert layer reads 65 for the 24 it is.
    under = [
        dur for own, _, dur, op in STEP
        if scope_time.in_scope(op, "experts")
    ]
    assert sum(under) == 65
    assert scope_time.scope_ms(_ctx(), "experts") == 24.0


def test_only_what_begins_inside_a_step_s_program_counts():
    sums, steps = scope_time.by_name(_ctx())
    assert steps == STEPS
    # Twice the step's, nothing of the third ``fusion.1`` and ``cond.58``.
    assert sums["fusion.1"] == 2 * 10 * MS and sums["cond.58"] == 2 * 3 * MS
    assert sum(sums.values()) == STEPS * (65 + 2 * len(OTHER_SCOPES)) * MS
    # A program of another name has no step to read.
    assert scope_time.by_name(_ctx(program="jit_other")) is None


def test_phases():
    assert scope_time.phase(f"{LOSS}/head/while") == "forward"
    # A recomputed layer is traced under the transpose: backward.
    assert scope_time.phase(f"{BACK}/layer_5/short_conv/mul") == "backward"
    assert scope_time.phase("jit(step_fn)/optimizer/jit(_where)/select_n") == "optimizer"
    for op_name in (None, "", "jit(step_fn)/add", "gather",
                    "transpose(jvp(jit(_held_experts)))/jit(_take)/gather"):
        assert scope_time.phase(op_name) == "unscoped", op_name
    ctx = _ctx()
    got = {p: scope_time.phase_ms(ctx, p) for p in scope_time.PHASES}
    assert got == {
        "forward": 10.0 + 9 + 5 + 2 * len(OTHER_SCOPES),
        "backward": 3.0 + 9 + 4 + 8,
        "optimizer": 10.0,
        "unscoped": 6.0 + 1,  # the copy without a name in the table, the add
    }
    # The four add up to the step's busy time.
    assert sum(got.values()) == 65 + 2 * len(OTHER_SCOPES)


def test_a_scope_is_a_part_of_the_path_and_may_exclude_what_follows_it():
    scan = f"{LOSS}/layer_14/mamba/ssm_scan/selective_scan_fwd/pallas_call"
    assert scope_time.in_scope(scan, "mamba")
    assert not scope_time.in_scope(scan, "mamba", ("ssm_scan",))
    assert scope_time.in_scope(f"{LOSS}/layer_14/mamba/mul", "mamba", ("ssm_scan",))
    # A part, not a substring: ``attention_window`` is not ``attention``.
    assert not scope_time.in_scope(f"{LOSS}/layer_1/attention_window/mul", "attention")
    assert not scope_time.in_scope(f"{LOSS}/layer_1/cross_attention/mul", "attention")
    assert not scope_time.in_scope(None, "attention")
    ctx = _ctx()
    assert scope_time.scope_ms(ctx, "mamba") == 14.0
    assert scope_time.scope_ms(ctx, "mamba", ("ssm_scan",)) == 5.0
    # A scope the model lacks is nothing, not 0.
    assert scope_time.scope_ms(ctx, "memory_unit") is None


NEW = {
    "step.forward_ms": 24.0 + 2 * len(OTHER_SCOPES),
    "step.backward_ms": 24.0,
    "step.optimizer_ms": 10.0,
    "step.unscoped_ms": 7.0,
    "scope.attention_ms": 10.0,
    "scope.experts_ms": 24.0,
    "scope.mamba_ms": 5.0,
    **{f"scope.{scope}_ms": 2.0 for scope in OTHER_SCOPES},
    "step.scratch_bytes": 8_359_199_232,
    "shuffle.index_epochs_pct": 75.0,
}


def test_the_new_entries_are_these():
    was = _parent()["benchmark"]["per_layer"]
    assert [m["name"] for m in BENCH["per_layer"][len(was):len(was) + len(NEW)]] == [
        "step.forward_ms", "step.backward_ms", "step.optimizer_ms",
        "step.unscoped_ms", "scope.attention_ms", "scope.attention_window_ms",
        "scope.experts_ms", "scope.router_ms", "scope.dense_ffn_ms",
        "scope.head_ms", "scope.short_conv_ms", "scope.mamba_ms",
        "scope.embedding_ms", "scope.dot_interaction_ms", "step.scratch_bytes",
        "shuffle.index_epochs_pct",
    ]
    assert set(NEW) == {m["name"] for m in BENCH["per_layer"][len(was):len(was) + len(NEW)]}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_reader_reads_the_program_s_table_and_the_trace(name):
    read = harness.load_reader(name)
    assert read(_ctx()) == pytest.approx(NEW[name])
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert entry["moves"] == "rows_per_s" and entry["workloads"]
    # A program that hands over no table, no bytes and no schedule (the
    # parent of this PR, under these files): nothing, never 0.
    bare = _ctx(table=None)
    bare["loader_stats"]["layers"]["train step"].pop("step:build")
    bare["loader_stats"]["layers"]["shuffle"].pop("schedules")
    assert read(bare) is None
    # An untraced run, and a loader that recorded no layer at all.
    assert read({**_ctx(), "trace": None, "loader_stats": {}}) is None


# -- what this PR may not touch ----------------------------------------------------


def _parent():
    with open(os.path.join(HERE, "parent_688a57d.json")) as f:
        return json.load(f)


def test_every_file_the_benchmark_had_is_the_parent_s():
    """Byte for byte: a PR that adds to the benchmark edits no file of it."""
    parent = _parent()
    assert parent["commit"].startswith("688a57d") and len(parent["files"]) == 69
    assert all(p.startswith(("chipbench/", "tests/chipbench/")) for p in parent["files"])
    for path, digest in parent["files"].items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_every_entry_the_parent_had_is_in_its_place(kind):
    """This PR appends metrics and nothing else; stated so that it stays
    true when a later PR appends entries, or cells to an entry's list."""
    was, now = _parent()["benchmark"], BENCH
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == was[key]
    assert [e["name"] for e in now[kind][: len(was[kind])]] == [
        e["name"] for e in was[kind]
    ]
    for old, new in zip(was[kind], now[kind]):
        assert list(new) == list(old), old["name"]
        for key, value in old.items():
            if key == "workloads":
                assert new[key][: len(value)] == value, old["name"]
            else:
                assert new[key] == value, (old["name"], key)
