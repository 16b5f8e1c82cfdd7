"""The benchmark harness: one cell, one run, one result line.

A cell is ``<config>.<mix>`` and every part of it is found by name:

- ``BENCHMARK.json`` at the root of the checkout: the cell's chips and
  which metrics it reports;
- ``configs/<config>.json``: the model's sizes as published;
- ``traffic/<mix>.json``: the inputs' parameters and the drive that runs
  them, ``drives/<drive>.py``;
- ``limits/<cell>.json``: the limit of each number that decides
  ``correct``;
- ``metrics/<metric>.py``: one reader per per-layer metric.

A drive builds the system under test from the seed (set-up), runs the
timed window, reads memory, frees the program's state and then compares
what the window produced with ``reference.py``.  The harness times set-up
from the process's start, counts compilations inside the window, traces a
part of the window when asked, and prints the last line.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Optional

HERE = Path(__file__).resolve().parent


def process_age_s() -> float:
    """Seconds since this process started, from /proc."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything the harness knows about one workload, read from files."""

    def __init__(self, name: str, root: Path = HERE,
                 bench: Optional[dict] = None):
        if bench is None:
            bench = load_json(root.parents[1] / "BENCHMARK.json")
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.root = name, root
        self.chips = int(entry["chips"])
        self.config = load_json(root / "configs" / f"{entry['config']}.json")
        self.traffic = load_json(root / "traffic" /
                                 f"{entry['traffic']}.json")
        lim = root / "limits" / f"{name}.json"
        self.limits = load_json(lim)["limits"] if lim.exists() else {}

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]

    def drive(self):
        return load_module(self.root / "drives" /
                           f"{self.traffic['drive']}.py",
                           f"bench_drive_{self.traffic['drive']}")

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        path = self.root / "metrics" / f"{metric}.py"
        return load_module(path, "bench_metric_" +
                           metric.replace(".", "_").replace("-", "_")).read


# ---------------------------------------------------------------------------
# the system under test's configuration
# ---------------------------------------------------------------------------
def model_config(c: dict):
    """A Qwen2-style checkpoint config -> the program's ``ModelConfig``."""
    from repro.config import ModelConfig
    if c.get("model_type") != "qwen2" or c.get("hidden_act") != "silu":
        raise ValueError(f"{c['name']}: only qwen2-style dense decoders are "
                         "mapped onto the program")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], max_seq_len=c["max_position_embeddings"],
        mlp_variant="swiglu", norm_variant="rmsnorm", pos_variant="rope",
        qkv_bias=bool(c.get("attention_bias", False)),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]))


def vocab_rows(cfg) -> int:
    """Rows of the program's token table (it pads the vocabulary)."""
    from repro.models import registry
    return registry.param_specs(cfg)["embed"]["tok"].shape[0]


# ---------------------------------------------------------------------------
# compilations and traces
# ---------------------------------------------------------------------------
class CompileCounter:
    """Backend compilations (a persistent-cache hit included) in this
    process, from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Tracer:
    """Traces ``seconds`` of the window, starting ``lead`` seconds into it,
    into a directory of its own under ``TMPDIR``; the drive calls ``poll``
    after each unit of work and ``snap`` gives the drive's counters at the
    trace's two ends."""

    def __init__(self, on: bool, lead: float, seconds: float,
                 snap: Callable[[], dict]):
        self.lead, self.seconds, self.snap = lead, seconds, snap
        self.t0 = None
        self.state = "idle" if on else "off"
        self.counters: Dict[str, dict] = {}
        self.dir = None
        self.started = 0.0
        self._ann = None

    def begin(self, t0: float):
        self.t0 = t0

    def poll(self, now: float):
        if self.state == "idle" and now - self.t0 >= self.lead:
            import tempfile
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.counters["start"] = self.snap()
            self.started = time.perf_counter()
            self.state = "tracing"
        elif self.state == "tracing" and \
                time.perf_counter() - self.started >= self.seconds:
            self.finish()

    def finish(self):
        if self.state != "tracing":
            return
        import jax
        self.counters["end"] = self.snap()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self) -> Optional[dict]:
        if self.state != "done":
            return None
        import shutil
        from benchmarks.chip.trace import load, reduce
        paths = list(Path(self.dir).rglob("*.xplane.pb"))
        out = reduce(load(str(paths[0]))) if paths else None
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def device_info(devices, chips: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        hooks: Optional[dict] = None, setup_t0: Optional[float] = None
        ) -> dict:
    """Set-up, window, memory, release, check: the result line's dict."""
    import jax
    from benchmarks.chip import compare
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    drive = cell.drive().Drive(cell, seed, devices, hooks or {}, log)
    tr_cfg = cell.traffic
    tracer = Tracer(trace, lead=max(0.0, (seconds - tr_cfg.get(
        "trace_seconds", seconds)) / 2), seconds=tr_cfg.get(
        "trace_seconds", seconds), snap=drive.counters)
    setup_s = process_age_s() if setup_t0 is None else \
        time.perf_counter() - setup_t0
    n0 = counter.n
    rec = drive.window(seconds, tracer)
    tracer.finish()
    log(f"[bench] window: {rec['window_s']:.3f} s, compilations inside it: "
        f"{counter.n - n0}")
    mem = drive.memory()
    log(f"[bench] memory: {json.dumps(mem)}")
    drive.release()
    gc.collect()
    reduced = tracer.reduce()
    t = time.perf_counter()
    checks = drive.check()
    log(f"[bench] reference and comparison: {time.perf_counter() - t:.3f} s")
    e2e = dict(rec["end_to_end"], setup_s=setup_s)
    metrics: Dict[str, dict] = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from benchmarks.chip.peaks import peak
        ctx = {"record": rec, "trace": reduced, "counters": tracer.counters,
               "peak": (hooks or {}).get("peak") or
               peak(devices[0].device_kind), "chips": cell.chips,
               "config": cell.config, "traffic": cell.traffic}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = compare.passed(checks) and rec["failed"] == 0
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics,
           "device": dict(device_info(devices, cell.chips),
                          memory_peak_bytes=mem["memory_peak_bytes"])}
    if trace and reduced is not None:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out
