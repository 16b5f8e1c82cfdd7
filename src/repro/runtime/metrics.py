"""Metrics observer (paper §6.1.2): step, losses, PPL/accuracy, RSS, power.

The paper reads RSS via ``dumpsys procstats`` and power via
``power_profile.xml``; here RSS comes from ``/proc/self/statm`` and power from
the pluggable power model (see core/energy.py) — same observer interface,
host-appropriate sources.  Writes JSONL + CSV; the visualizer renders them.
"""
from __future__ import annotations

import csv
import json
import math
import os
import time
from typing import Any, Dict, List, Optional

import jax


def read_rss_mb() -> float:
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / 1e6
    except Exception:
        return 0.0


class MetricsObserver:
    FIELDS = ("step", "loss", "ppl", "accuracy", "grad_norm", "lr",
              "step_time_s", "rss_mb", "power_w", "energy_kj", "battery",
              "tokens_per_s")

    def __init__(self, out_dir: Optional[str] = None, power_watts: float = 6.0,
                 log_every: int = 1, print_fn=print):
        self.out_dir = out_dir
        self.power_watts = power_watts  # phone-class sustained draw default
        self.log_every = log_every
        self.print_fn = print_fn
        self.rows: List[Dict[str, Any]] = []
        self.energy_kj = 0.0
        self._t0 = None
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)

    def start_step(self):
        self._t0 = time.perf_counter()

    def end_step(self, step: int, metrics: Dict[str, Any],
                 tokens: float = 0.0, battery: float = 1.0):
        dt = (time.perf_counter() - self._t0) if self._t0 else 0.0
        self.energy_kj += self.power_watts * dt / 1000.0
        # the step's metrics come from the device here, one read each
        with jax.profiler.TraceAnnotation("train.end_step.pull"):
            loss = float(metrics.get("loss", float("nan")))
            accuracy = float(metrics.get("accuracy", float("nan")))
            grad_norm = float(metrics.get("grad_norm", float("nan")))
            lr = float(metrics.get("lr", float("nan")))
        row = {
            "step": step,
            "loss": loss,
            "ppl": float(math.exp(min(loss, 30.0))) if loss == loss else None,
            "accuracy": accuracy,
            "grad_norm": grad_norm,
            "lr": lr,
            "step_time_s": dt,
            "rss_mb": read_rss_mb(),
            "power_w": self.power_watts,
            "energy_kj": self.energy_kj,
            "battery": battery,
            "tokens_per_s": tokens / dt if dt > 0 else 0.0,
        }
        self.rows.append(row)
        if self.out_dir:
            with open(os.path.join(self.out_dir, "metrics.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
        if self.print_fn and step % self.log_every == 0:
            self.print_fn(
                f"step {step:5d} | loss {row['loss']:.4f} | "
                f"ppl {row['ppl']:.2f} | {dt*1e3:.0f} ms | "
                f"rss {row['rss_mb']:.0f} MB | energy {self.energy_kj:.2f} kJ")
        return row

    def flush_csv(self):
        if not (self.out_dir and self.rows):
            return None
        path = os.path.join(self.out_dir, "metrics.csv")
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(self.rows[0].keys()))
            w.writeheader()
            w.writerows(self.rows)
        return path

    @property
    def peak_rss_mb(self) -> float:
        return max((r["rss_mb"] for r in self.rows), default=0.0)
