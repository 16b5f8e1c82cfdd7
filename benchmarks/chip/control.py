#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not part of a run.

For each seed, the numbers that decide ``correct`` as the program gives them
at the cell's own size, read from the set-up's first training steps.  For
each control seed it also gives the same numbers for the control, the
reference computed in bfloat16 in the program's place, and for the fault
"half of the batch left out, the mean taken over the rest", planted in the
reference put in the program's place.  One JSON line per reading, with the
harness's verdict on it under the cell's committed limits:

    python benchmarks/chip/control.py --workload qwen25-05b.fullft \\
        --seeds 11,12,13 --control-seeds 11,12,13
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def verdict(cell, numbers: dict) -> dict:
    """The numbers beside the cell's limits, and whether a run reading them
    would come out correct."""
    from benchmarks.chip import compare
    checks = compare.with_limits(numbers, cell.limits)
    return {"correct": compare.passed(checks), "checks": checks}


def readings(cell, seed, devices, control, log):
    """[(kind, numbers)] for the program and, on a control seed, for the
    bfloat16 control and the half-batch fault."""
    import jax.numpy as jnp
    from benchmarks.chip import compare
    from benchmarks.chip.drives import train
    drive = cell.drive().Drive(cell, seed, devices, {}, log)
    prog, batches = drive.program, drive.check_batches
    drive.release()
    del drive
    gc.collect()
    c, tr = cell.config, cell.traffic
    ref = train.reference_numbers(c, tr, seed, batches, jnp.float32)
    out = [("program", compare.train_numbers(prog, ref))]
    if control:
        low = train.reference_numbers(c, tr, seed, batches, jnp.bfloat16)
        out.append(("control_bf16", compare.train_numbers(low, ref)))
        half = [{k: v[:len(v) // 2] for k, v in b.items()} for b in batches]
        hb = train.reference_numbers(c, tr, seed, half, jnp.float32)
        out.append(("fault_half_batch", compare.train_numbers(hb, ref)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    for p in (str(CHECKOUT), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from benchmarks.chip import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.Cell(args.workload)
    devices = jax.devices()[:cell.chips]
    for s in args.seeds:
        for kind, numbers in readings(cell, s, devices,
                                      s in args.control_seeds, harness.log):
            print(json.dumps(dict(seed=s, kind=kind, **numbers,
                                  **verdict(cell, numbers))), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
