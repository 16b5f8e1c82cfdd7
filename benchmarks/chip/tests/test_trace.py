"""The reduction from a trace to busy time, idle gaps and collectives."""
from pathlib import Path

import pytest

from benchmarks.chip import trace

MS = 1_000_000


def _tr(ops, spans=(), modules=()):
    return {"devices": {"/device:TPU:0": {"ops": list(ops),
                                          "modules": list(modules)}},
            "spans": sorted(spans)}


def test_busy_is_the_union_and_gaps_go_to_the_open_span():
    ops = [(0, 4 * MS, "fusion.1"), (2 * MS, 6 * MS, "fusion.2"),
           (8 * MS, 9 * MS, "all-reduce.3")]
    spans = [(0, 10 * MS, "bench.window"), (6 * MS, 8 * MS, "bench.feed")]
    r = trace.reduce(_tr(ops, spans))
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.007)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"host:feed": 0.002, "host:window": 0.001})
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(0.004)
    # the all-reduce overlaps no compute: all of it is exposed
    assert r["collective_exposed_s"] == pytest.approx(0.001)


def test_collective_under_compute_is_not_exposed():
    ops = [(0, 10 * MS, "fusion"), (2 * MS, 5 * MS, "all-gather-start"),
           (9 * MS, 12 * MS, "reduce-scatter")]
    r = trace.reduce(_tr(ops), 0, 12 * MS)
    assert r["collective_s"] == pytest.approx(0.006)
    assert r["collective_exposed_s"] == pytest.approx(0.002)


def test_gaps_between_step_programs():
    mods = [(0, 5 * MS, "jit_train_step"), (7 * MS, 12 * MS,
                                            "jit_train_step"),
            (12 * MS + 500_000, 15 * MS, "jit_train_step")]
    r = trace.reduce(_tr(mods, modules=mods), 0, 15 * MS)
    assert [g for g, _, _ in r["module_gaps"]] == [2 * MS, 500_000]


def test_a_recorded_chip_trace():
    """A trace recorded on one TPU v5 lite: four steps of two small jitted
    programs (a 2048 x 2048 matmul-tanh-matmul, then a sum), each step in a
    ``bench.step`` span followed by a 2 ms sleep in ``bench.feed``.  The
    device's clock runs about 1 ms behind the host's in the file, so the
    first step's programs fall before the window span opens: three steps'
    operations count, 3 x (91.5 + 91.5 + 22.6 + 24.3) us."""
    path = Path(__file__).parent / "data" / "probe.xplane.pb"
    tr = trace.load(str(path))
    assert list(tr["devices"]) == ["/device:TPU:0"]
    assert len(tr["devices"]["/device:TPU:0"]["modules"]) == 8
    r = trace.reduce(tr)
    assert r["window_s"] == pytest.approx(0.01395331)
    assert r["busy_s"] == pytest.approx(690.013e-6, rel=1e-6)
    ops = dict(r["device_ops"])
    tanh = [v for k, v in ops.items()
            if k.startswith("%convolution_tanh_fusion")]
    assert tanh == [pytest.approx(3 * 91.6e-6, rel=1e-2)]
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"host:feed", "host:step"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert gaps["host:feed"] == pytest.approx(7.126014e-3)
