"""The train step's device time, split by what the program says of its own
operations: the helper of the ``step.*_ms`` and ``scope.*_ms`` readers.

Two records meet here. The trace's ``XLA Ops`` line has one event for every
operation the device ran, named by the operation's own text (``%fusion.661 =
...``), and says nothing of where in the program it came from. The program
says that: its train step keeps the one program it compiled and, in a traced
run, hands over ``step:ops`` (``loader_stats["layers"]["train step"]
["step:ops"]``): ``table``, each operation's own name -> its ``op_name``
(``jit(step_fn)/transpose(jvp(loss))/../layer_2/self_attn/attention/..``),
and ``program``, the module's name as the ``XLA Modules`` line shows it. A
program that hands over no table (the parent of the PR that brought this
file) leaves every reader here with nothing to read.

*Self time.* The line nests: a ``cond``, a ``while`` and a ``call`` are
events that contain the events of the operations they ran. An event's self
time is its duration less that of the events it contains, found from starts
and ends alone (no knowledge of HLO), so a container weighs what is its own
and every nanosecond of the line is counted once. A sum of durations by name
counts a branch's operations twice (PR 32 read 116.5 ms of expert layers
that way, for 63.3).

*Which events.* Those that begin inside an event of the ``XLA Modules`` line
whose name holds the table's ``program`` (``jit_step_fn``: the events
``step.device_ms`` takes the median of). The sums are divided by the number
of those module events: milliseconds a step.

*Phase*, from the ``op_name``: ``optimizer`` under the scope of that name;
under the scope ``loss`` (``jvp(loss)``), ``backward`` where a
``transpose(`` stands in the path and ``forward`` otherwise. A layer
recomputed for the backward pass is traced under ``transpose(jvp(loss))``,
so **backward holds the recomputation** (the rule PERF.md's hand-made tables
followed). ``unscoped``: an own name the table lacks (copies the compiler
put in carry no ``op_name``), or an ``op_name`` under neither scope (the
step counter's ``add``; an operation the compiler rebuilt inside a branch
and named ``gather``). The four add up to the self time of all events.

*Scope.* A reader names the scope it wants as one part of the path
(``attention``) and the scopes that must not follow it (``mamba`` without
``ssm_scan``), so a new model's scope is a new file here and no edit.
"""

from __future__ import annotations

import bisect
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # text, start_ns, duration_ns

PHASES = ("forward", "backward", "optimizer", "unscoped")


def own_name(text: str) -> str:
    """An operation's own name: what its text begins with."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(events: Iterable[Event]) -> List[List]:
    """``[text, start, self_ns]`` of every event, in the order they began:
    the duration less what the events directly inside it cover."""
    out: List[List] = []
    open_: List[Tuple[int, int]] = []  # (end, index into out), outermost first
    for text, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and start >= open_[-1][0]:
            open_.pop()
        if open_:
            end, at = open_[-1]
            out[at][2] -= min(start + dur, end) - start
        out.append([text, start, dur])
        open_.append((start + dur, len(out) - 1))
    return out


def phase(op_name: Optional[str]) -> str:
    if not op_name:
        return "unscoped"
    parts = op_name.split("/")
    if "optimizer" in parts:
        return "optimizer"
    if "jvp(loss)" not in op_name:
        return "unscoped"
    return "backward" if "transpose(" in op_name else "forward"


def in_scope(op_name: Optional[str], scope: str, not_after: Sequence[str] = ()) -> bool:
    """Is ``scope`` a part of the path, with none of ``not_after`` behind it?"""
    if not op_name:
        return False
    parts = op_name.split("/")
    if scope not in parts:
        return False
    behind = parts[parts.index(scope) + 1:]
    return not any(other in behind for other in not_after)


def step_ops(ctx) -> Optional[dict]:
    """The program's ``step:ops`` record, if it handed one over."""
    layers = (ctx.get("loader_stats") or {}).get("layers") or {}
    ops = (layers.get("train step") or {}).get("step:ops") or {}
    return ops if ops.get("table") and ops.get("program") else None


def by_name(ctx) -> Optional[Tuple[Dict[str, int], int]]:
    """``({own name: self_ns summed over the window}, steps)`` of the events
    inside the step's module events; nothing without a trace, a table or a
    step. Made once a trace and kept on it for the other readers."""
    tr = ctx.get("trace")
    ops = step_ops(ctx)
    if not tr or not ops:
        return None
    if "scope_time" not in tr:
        steps = sorted(
            (start, start + dur)
            for name, start, dur in tr["modules"]
            if ops["program"] in name
        )
        starts = [s for s, _ in steps]
        sums: Dict[str, int] = {}
        for text, start, self_ns in self_times(tr["ops"]):
            at = bisect.bisect_right(starts, start) - 1
            if at >= 0 and start < steps[at][1]:
                name = own_name(text)
                sums[name] = sums.get(name, 0) + self_ns
        tr["scope_time"] = (sums, len(steps))
    sums, steps = tr["scope_time"]
    return (sums, steps) if steps and sums else None


def ms_a_step(ctx, keep: Callable[[Optional[str]], bool]) -> Optional[float]:
    """Milliseconds a step of the events whose ``op_name`` (None where the
    table has none) ``keep`` takes; nothing where it takes no event."""
    found = by_name(ctx)
    if found is None:
        return None
    sums, steps = found
    table = step_ops(ctx)["table"]
    kept = [ns for name, ns in sums.items() if keep(table.get(name))]
    return sum(kept) / steps / 1e6 if kept else None


def phase_ms(ctx, which: str) -> Optional[float]:
    return ms_a_step(ctx, lambda op_name: phase(op_name) == which)


def scope_ms(ctx, scope: str, not_after: Sequence[str] = ()) -> Optional[float]:
    """Forward, backward and recomputation alike."""
    return ms_a_step(ctx, lambda op_name: in_scope(op_name, scope, not_after))
