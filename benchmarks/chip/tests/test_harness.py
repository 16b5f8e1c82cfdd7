"""The harness finds a cell's parts by name, and refuses to run off the chip.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from conftest import CHECKOUT, SMOKE_CONFIG, smoke_cell

from benchmarks.chip import harness

HERE = Path(harness.__file__).resolve().parent


def test_a_new_mix_and_metric_are_found_by_name(tmp_path, cpu_devices):
    """A mix and a per-layer metric that exist only as new files, and the
    entries that name them, run with no edit to any file."""
    root = tmp_path / "benchmarks" / "chip"
    root.mkdir(parents=True)
    for d in ("drives", "metrics", "configs"):
        shutil.copytree(HERE / d, root / d)
    (root / "traffic").mkdir()
    (root / "configs" / "tiny.json").write_text(json.dumps(SMOKE_CONFIG))
    mix = dict(smoke_cell("qwen25-05b.fullft").traffic, seq_len=16)
    (root / "traffic" / "fullft-short.json").write_text(json.dumps(mix))
    (root / "metrics" / "steps_traced.train.py").write_text(
        "def read(ctx):\n    return float(ctx['record']['steps'])\n")
    bench = {"workloads": [{"name": "tiny.fullft-short", "config": "tiny",
                            "traffic": "fullft-short", "chips": 1}],
             "end_to_end": [{"name": "train_tokens_per_s",
                             "unit": "tokens/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": [{"name": "steps_traced.train", "unit": "steps",
                            "workloads": ["tiny.fullft-short"]},
                           {"name": "mfu.other", "unit": "%",
                            "workloads": ["other.cell"]}]}
    cell = harness.Cell("tiny.fullft-short", root=root, bench=bench)
    assert [m["name"] for m in cell.per_layer] == ["steps_traced.train"]
    out = harness.run(cell, 5, 1.0, True, cpu_devices, setup_t0=0.0,
                      hooks={"peak": {"flops": 1e12,
                                      "hbm_bytes_per_s": 1e11}})
    assert out["metrics"]["steps_traced.train"]["value"] >= 1
    out = harness.run(cell, 5, 1.0, False, cpu_devices, setup_t0=0.0)
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen25-05b.fullft", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_accelerator_means_no_result():
    r = _run_py(CHECKOUT)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "not on a TPU" in r.stderr


def test_no_program_means_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""
