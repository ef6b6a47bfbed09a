"""From a profiler trace to numbers: the reduction, kept with the benchmark.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into plain lists of
``(name, start_ns, duration_ns)`` per plane and line; everything else here
works on such lists, so the arithmetic is tested on a small synthetic one.

On a TPU the device planes are ``/device:TPU:<n>``. Their line ``XLA Ops``
holds one event per executed HLO operation (fusions, custom calls, copies),
``XLA Modules`` one per executed program. Host planes (``/host:CPU``) hold
the benchmark's own ``TraceAnnotation`` spans on the same clock.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane name: {line name: [events]}}`` of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return planes


def device_planes(planes: dict) -> List[str]:
    return sorted(p for p in planes if p.startswith(DEVICE_PREFIX))


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Event]:
    """The parts of ``events`` that lie inside ``[lo, hi)``."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_union_ns(events: Iterable[Event]) -> int:
    """Time covered by at least one event."""
    total = 0
    end = None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if dur <= 0:
            continue
        if end is None or start > end:
            total += dur
            end = start + dur
        elif start + dur > end:
            total += start + dur - end
            end = start + dur
    return total


def sums_by_name(events: Iterable[Event]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for name, _, dur in events:
        out[name] = out.get(name, 0) + dur
    return out


def durations_of(events: Iterable[Event], needle: str) -> List[int]:
    """Durations of the events whose name contains ``needle``."""
    return [dur for name, _, dur in events if needle in name]


def gaps(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Idle intervals ``(start, duration)`` of ``[lo, hi)``."""
    out = []
    at = lo
    for _, start, dur in sorted(clip(events, lo, hi), key=lambda e: e[1]):
        if start > at:
            out.append((at, start - at))
        at = max(at, start + dur)
    if hi > at:
        out.append((at, hi - at))
    return out


def label_gaps(
    idle: Sequence[Tuple[int, int]], spans: Iterable[Event], top: int = 10
) -> List[List]:
    """The longest idle gaps, each named after the host span that covers
    most of it (``host`` where none does), summed by name."""
    spans = sorted(spans, key=lambda e: e[1])
    by_name: Dict[str, int] = {}
    for start, dur in sorted(idle, key=lambda g: -g[1])[:200]:
        best, best_cover = "host", 0
        for name, s, d in spans:
            if s >= start + dur:
                break
            cover = min(s + d, start + dur) - max(s, start)
            if cover > best_cover:
                best, best_cover = name, cover
        by_name[best] = by_name.get(best, 0) + dur
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def short_name(name: str) -> str:
    """An operation's own name: the trace prints the whole HLO instruction
    (``%fusion.1 = f32[..] fusion(..)``); a Mosaic call keeps its mark."""
    short = name.split(" = ")[0].lstrip("%")[:80]
    if "tpu_custom_call" in name:
        short += "[tpu_custom_call]"
    return short


def top_ops(events: Iterable[Event], top: int = 10) -> List[List]:
    ranked = sorted(sums_by_name(events).items(), key=lambda kv: -kv[1])[:top]
    return [[short_name(name), ns / 1e9] for name, ns in ranked]


def summary(planes: dict, top: int = 40) -> dict:
    """What a person looks at first: every plane and line with its event
    count, span and the names that took most time."""
    out = {}
    for pname, lines in planes.items():
        out[pname] = {}
        for lname, events in lines.items():
            if not events:
                continue
            out[pname][lname] = {
                "events": len(events),
                "first_ns": min(e[1] for e in events),
                "last_ns": max(e[1] + e[2] for e in events),
                "top": [
                    [n, s / 1e9, sum(1 for e in events if e[0] == n)]
                    for n, s in sorted(
                        sums_by_name(events).items(), key=lambda kv: -kv[1]
                    )[:top]
                ],
            }
    return out
