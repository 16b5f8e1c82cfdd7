#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python benchmarks/chip/run.py --workload qwen25-05b.fullft \
        --seed 12345 --seconds 10 --trace 0

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of part
of the window.  The numbers that decide ``correct`` are printed beside their
limits as the last lines of standard error and under ``checks`` in the
result line, which is the last line of standard output.

The run needs a TPU and as many chips as the cell asks for; otherwise it
exits non-zero and prints no result.  JAX's persistent compilation cache is
on (``repro.launch.compile_cache``), so only a checkout's first run of a
cell compiles.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def fail(msg: str):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (str(CHECKOUT), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        import repro  # noqa: F401  (the system under test)
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"cannot import the program from {CHECKOUT / 'src'}: {e}")
    from benchmarks.chip import harness
    try:
        cell = harness.Cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(f"unknown workload {args.workload!r}: {e}")

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no accelerator: {e}")
    if devices[0].platform != "tpu":
        fail(f"JAX runs on {devices[0].platform}, not on a TPU")
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
    cache = enable_compile_cache()
    harness.log(f"[bench] {cell.name} seed {args.seed} on {cell.chips} x "
                f"{devices[0].device_kind}; compile cache {cache}; "
                f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}")
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips])
    for k, c in out["checks"].items():
        harness.log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
