#!/usr/bin/env python3
"""Device time by the train step's named scopes, and device idle by the
trainer runtime's spans, from one profiler trace.

The program names its layers with ``jax.named_scope`` (``attention``,
``mlp``, ``lm_head``, ``optimizer``), which the trace keeps in each device
operation's ``tf_op`` stat (``xplane_meta.py``), and opens host spans whose
names start with ``train.`` (``train.feed``, ``train.step``,
``train.end_step``, ``train.end_step.pull``, ``train.checkpoint``).
``load`` reads both beside what ``trace.load`` reads; ``reduce`` gives,
inside the traced window:

- the device self time of each scope over the train-step executions that
  lie wholly inside it (self time: an operation's duration less what its
  nested operations on the same line cover, so a ``while`` and its body
  are not counted twice), the busy time of those executions, and their
  count;
- the device idle time in the gaps between those executions (the gaps
  ``host_gap_ms.train`` averages) by the innermost ``train.`` span open on
  the host over each part of each gap (``none`` where no such span is
  open).  The benchmark's own ``bench.`` spans interleave with these, so
  the two kinds are kept apart.

Run on the chip, it drives a cell as ``run.py --trace 1`` does and prints
one JSON line, in milliseconds per train step:

    python3 benchmarks/chip/scopes.py --workload qwen25-05b.fullft \\
        --seed 12345 --seconds 10

or reduces a trace written by ``python -m repro.launch.train --profile-dir``
(no chip needed to read it):

    python3 benchmarks/chip/scopes.py --trace-file DIR_OR_XPLANE_PB
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
for _p in (str(CHECKOUT), str(CHECKOUT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import trace, xplane_meta  # noqa: E402

SCOPES = ("attention", "mlp", "lm_head", "optimizer")
PROGRAM_PREFIX = "train."
STEP_MODULE = "train_step"
# a path element that is a scope, bare or under transforms: jvp(lm_head)
_SCOPE = re.compile(r"^(?:[\w.]+\()*(%s)\)*$" % "|".join(SCOPES))


def scope_of(tf_op: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an operation's ``tf_op`` path."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    for part in reversed(path.split("/")):
        m = _SCOPE.match(part)
        if m:
            return m.group(1)
    return None


def load(path: str) -> dict:
    """``trace.load``'s dict, with each device's ``scopes`` (operation
    name -> scope) and the host's ``train.`` spans under ``program``."""
    from jax.profiler import ProfileData
    tr = trace.load(path)
    meta = xplane_meta.tf_ops(path)
    for plane, d in tr["devices"].items():
        ops = meta.get(plane, {})
        d["scopes"] = {name: scope_of(ops[name]) for name in ops}
    program: List[trace.Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend(e for e in trace._events(line)
                               if e[2].startswith(PROGRAM_PREFIX))
    tr["program"] = sorted(program)
    return tr


def self_times(ops: List[trace.Interval]) -> List[Tuple[int, str]]:
    """(self time, name) of each operation of one line, where operations
    nest (a loop's event holds its body's)."""
    order = sorted(range(len(ops)), key=lambda k: (ops[k][0], -ops[k][1]))
    own = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for k in order:
        s, e, _ = ops[k]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][1]) - s
        stack.append(k)
    return [(own[k], ops[k][2]) for k in range(len(ops))]


def innermost(spans: List[trace.Interval]) -> List[trace.Interval]:
    """The time line of the (properly nested) ``spans`` cut where any of
    them opens or closes: (start, end, innermost span open) for each piece
    in which one is open."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    out: List[trace.Interval] = []
    open_: List[trace.Interval] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        open_ = [sp for sp in open_ if sp[1] > a]
        while i < len(by_start) and by_start[i][0] <= a:
            if by_start[i][1] > a:
                open_.append(by_start[i])
            i += 1
        if open_:
            out.append((a, b, max(open_, key=lambda sp: (sp[0], -sp[1]))[2]))
    return out


def _split(idle: List[Tuple[int, int]], pieces: List[trace.Interval],
           into: Dict[str, int]):
    """Add to ``into`` the part of the sorted ``idle`` intervals that each
    piece's name covers, and the rest to ``none``."""
    covered, j = 0, 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            part = min(b, pieces[k][1]) - max(a, pieces[k][0])
            into[pieces[k][2]] += part
            covered += part
            k += 1
    rest = sum(b - a for a, b in idle) - covered
    if rest:
        into["none"] += rest


def reduce(tr: dict, lo: int = None, hi: int = None) -> dict:
    """Seconds, averaged over the devices, over the ``steps`` train-step
    executions wholly inside the window: ``scope_s`` (self time by scope,
    ``unscoped`` for the rest) and ``step_busy_s`` within them, and
    ``idle_by_span_s`` in the ``gaps`` between consecutive ones (the gaps
    ``host_gap_ms.train`` averages); with the names of the ``spans_seen``
    in the trace."""
    if lo is None or hi is None:
        lo, hi = trace.window_of(tr)
    scope_ns: Dict[str, int] = defaultdict(int)
    idle_ns: Dict[str, int] = defaultdict(int)
    busy = steps = gaps = 0
    pieces = innermost(tr.get("program", []))
    for d in tr["devices"].values():
        scopes = d.get("scopes", {})
        mods = sorted(m for m in d["modules"]
                      if STEP_MODULE in m[2] and lo <= m[0] and m[1] <= hi)
        steps += len(mods)
        gaps += max(len(mods) - 1, 0)
        ops_by_start = sorted(d["ops"])
        starts = [o[0] for o in ops_by_start]

        def ops_in(a, b):
            return [o for o in ops_by_start[bisect.bisect_left(starts, a):
                                            bisect.bisect_right(starts, b)]
                    if o[1] <= b]

        for ms, me, _ in mods:
            ops = ops_in(ms, me)
            busy += sum(e - s for s, e in trace.union(ops, ms, me))
            for t, name in self_times(ops):
                scope_ns[scopes.get(name) or "unscoped"] += t
        for (_, a, _), (b, _, _) in zip(mods, mods[1:]):
            merged = trace.union([o for o in d["ops"]
                                  if o[1] > a and o[0] < b], a, b)
            edges = [a] + [x for iv in merged for x in iv] + [b]
            _split([(x, y) for x, y in zip(edges[0::2], edges[1::2])
                    if y > x], pieces, idle_ns)
    n = max(len(tr["devices"]), 1)
    return {"steps": steps // n, "gaps": gaps // n,
            "spans_seen": sorted({sp[2] for sp in tr.get("program", [])}),
            "step_busy_s": busy / n / 1e9,
            "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
            "idle_by_span_s": {k: v / n / 1e9 for k, v in idle_ns.items()}}


def per_step_ms(r: dict) -> dict:
    """``reduce``'s result as milliseconds per train step (idle: per gap
    between two steps), under the names of the per-layer metrics they are
    for.  A scope or span the program never named (an older program) gives
    no key, not a zero."""
    n, gaps = r["steps"], r["gaps"]
    if not n:
        return {}
    scope, idle = r["scope_s"], r["idle_by_span_s"]
    out = {f"{k}_ms.train": 1e3 * scope[k] / n
           for k in SCOPES + ("unscoped",) if k in scope}
    for metric, spans in (("feed", ("train.feed",)),
                          ("dispatch", ("train.step",)),
                          ("end_step", ("train.end_step",
                                        "train.end_step.pull"))):
        if gaps and any(sp in r["spans_seen"] for sp in spans):
            out[f"{metric}_idle_ms.train"] = 1e3 * sum(
                idle.get(sp, 0.0) for sp in spans) / gaps
    out["step_busy_ms"] = 1e3 * r["step_busy_s"] / n
    return out


def xplane_in(path: str) -> str:
    p = Path(path)
    if p.is_dir():
        found = sorted(p.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = found[-1]
    return str(p)


def summary(path: str) -> dict:
    """The split and the trace's own reduction, of one trace file; with no
    device plane (a CPU run), only the host spans' total time by name."""
    tr = load(path)
    spans: Dict[str, float] = defaultdict(float)
    for s, e, name in tr["program"]:
        spans[name] += (e - s) / 1e9
    if not tr["devices"]:
        return {"program_s": dict(spans)}
    base = trace.reduce(tr)
    gaps = [g for g, a, b in base["module_gaps"]
            if STEP_MODULE in a and STEP_MODULE in b]
    r = reduce(tr)
    return {"steps": r["steps"], "per_step_ms": per_step_ms(r),
            "idle_by_span_s": r["idle_by_span_s"],
            "host_gap_ms": sum(gaps) / len(gaps) / 1e6 if gaps else None,
            "busy_s": base["busy_s"], "window_s": base["window_s"],
            "program_s": dict(spans),
            "idle_gaps": base["idle_gaps"]}


def split_cell(cell, seed: int, seconds: float, devices,
               keep: Optional[str] = None) -> dict:
    """Drive ``cell``'s window once with the harness's tracer, as a
    ``--trace 1`` run does (no reference check), and split the trace."""
    import shutil
    from benchmarks.chip import harness
    drive = cell.drive().Drive(cell, seed, devices, {}, harness.log)
    span = cell.traffic.get("trace_seconds", seconds)
    tracer = harness.Tracer(True, lead=max(0.0, (seconds - span) / 2),
                            seconds=span, snap=drive.counters)
    rec = drive.window(seconds, tracer)
    tracer.finish()
    path = xplane_in(tracer.dir)
    out = summary(path)
    if keep:
        Path(keep).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(keep) / f"{cell.name}.{seed}.xplane.pb")
    shutil.rmtree(tracer.dir, ignore_errors=True)
    out.update(workload=cell.name, seed=seed,
               device=devices[0].device_kind, end_to_end=rec["end_to_end"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", default="",
                    help="copy the cell's trace into this directory")
    ap.add_argument("--trace-file", default="",
                    help="split this trace (a file or a directory holding "
                         "one) instead of driving a cell")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.trace_file):
        ap.error("give --workload or --trace-file")
    if args.trace_file:
        print(json.dumps(summary(xplane_in(args.trace_file))), flush=True)
        return
    import jax
    from benchmarks.chip import harness
    from repro.launch.compile_cache import enable_compile_cache
    cell = harness.Cell(args.workload)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu":
        sys.exit(f"JAX runs on {devices[0].platform}, not on a TPU")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the scopes are op metadata, which JAX leaves out of the cache's key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    out = split_cell(cell, args.seed, args.seconds, devices, args.keep)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
