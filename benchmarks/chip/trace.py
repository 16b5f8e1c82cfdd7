"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

``load`` turns the file into plain lists: the operations of each device
plane (its "XLA Ops" line), the program executions of each device plane
(its "XLA Modules" line) and the host spans the benchmark itself opened
(names that start with ``bench.``), all on the profiler's one clock in
nanoseconds.  ``reduce`` then computes, inside the traced window:

- busy: the union of the intervals in which an operation ran, per device,
  and its mean over the devices;
- the idle gaps of that union, each attributed to the innermost benchmark
  span that was open on the host at the gap's midpoint (``host:<span>``, or
  ``host:none``);
- the operations that took most time, by name;
- collective time on each device that no other operation overlaps.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter", re.I)

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)


def _events(line) -> List[Interval]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [...]} from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    d["ops"] = _events(line)
                elif line.name == MODULES_LINE:
                    d["modules"] = _events(line)
            if d["ops"]:
                devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e[2].startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": sorted(spans)}


def union(intervals: List[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: List[List[int]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _covered(merged: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


def spans_at(spans: List[Interval], times: List[int]) -> List[str]:
    """For each of the sorted ``times``, the innermost benchmark span open
    at it (spans nest, as the host's ``with`` blocks open them)."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2][len(SPAN_PREFIX):] if stack else "none")
    return out


def window_of(tr: dict, span: str = "bench.window") -> Tuple[int, int]:
    """The traced window: the benchmark's window span if it is in the
    trace, else the extent of all device operations."""
    marks = [(s, e) for s, e, n in tr["spans"] if n == span]
    if marks:
        return marks[0]
    ops = [o for d in tr["devices"].values() for o in d["ops"]]
    return min(o[0] for o in ops), max(o[1] for o in ops)


def reduce(tr: dict, lo: int = None, hi: int = None, top: int = 10) -> dict:
    if lo is None or hi is None:
        lo, hi = window_of(tr)
    window_ns = hi - lo
    busy, gaps, op_time = [], defaultdict(int), defaultdict(int)
    exposed, coll_total, step_gaps = [], [], []
    for d in tr["devices"].values():
        ops = [o for o in d["ops"] if o[1] > lo and o[0] < hi]
        merged = union(ops, lo, hi)
        busy.append(_covered(merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for (a, b), name in zip(idle, spans_at(
                tr["spans"], [(a + b) // 2 for a, b in idle])):
            gaps["host:" + name] += b - a
        for s, e, name in ops:
            op_time[name] += min(e, hi) - max(s, lo)
        coll = [o for o in ops if COLLECTIVE.search(o[2])]
        compute = union([o for o in ops if not COLLECTIVE.search(o[2])],
                        lo, hi)
        c_merged = union(coll, lo, hi)
        coll_total.append(_covered(c_merged))
        exposed.append(_covered(c_merged) - _overlap(c_merged, compute))
        mods = sorted(m for m in d["modules"] if lo <= m[0] and m[1] <= hi)
        step_gaps.append([(b[0] - a[1], a[2], b[2])
                          for a, b in zip(mods, mods[1:])])
    n = max(len(busy), 1)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "collective_s": max(coll_total, default=0) / 1e9,
        "collective_exposed_s": max(exposed, default=0) / 1e9,
        "device_ops": sorted(((k, v / n / 1e9) for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((k, v / n / 1e9) for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
        "module_gaps": step_gaps[0] if step_gaps else [],
    }


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Total length of the intersection of two merged interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot
