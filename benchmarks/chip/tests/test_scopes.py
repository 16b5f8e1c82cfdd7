"""Device time by named scope and device idle by the trainer runtime's
spans (``scopes.py``), and the ``tf_op`` reader under it."""
from pathlib import Path

import pytest

from benchmarks.chip import scopes, trace, xplane_meta

MS = 1_000_000
DATA = Path(__file__).parent / "data"


def test_tf_op_of_a_recorded_chip_trace():
    ops = xplane_meta.tf_ops(str(DATA / "probe.xplane.pb"))
    assert list(ops) == ["/device:TPU:0"]
    tanh = [v for k, v in ops["/device:TPU:0"].items()
            if k.startswith("%convolution_tanh_fusion")]
    assert tanh == ["jit(<lambda>)/dot_general:"]


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/attention/dot_general:",
     "attention"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general:", "mlp"),
    ("jit(train_step)/transpose(jvp(lm_head))/dot_general:", "lm_head"),
    ("jit(train_step)/optimizer/sqrt:", "optimizer"),
    # the innermost scope wins
    ("jit(f)/attention/closed_call/mlp/add:", "mlp"),
    ("jit(train_step)/transpose(jvp())/while/body/add:", None),
    # a name that merely contains a scope's name is none
    ("jit(train_step)/my_attention_helper/add:", None),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _tr(ops, modules, spans=(), program=(), names=None):
    return {"devices": {"/device:TPU:0": {
                "ops": list(ops), "modules": list(modules),
                "scopes": names or {}}},
            "spans": sorted(spans), "program": sorted(program)}


def test_self_time_by_scope_under_nesting():
    """A loop whose body holds a scoped loop of its own: each level's time
    counts once, at the innermost operation that covers it."""
    ops = [(0, 10 * MS, "while.outer"),
           (1 * MS, 5 * MS, "while.inner"),
           (1 * MS, 2 * MS, "fusion.a"), (3 * MS, 4 * MS, "fusion.a"),
           (6 * MS, 9 * MS, "fusion.m"),
           (11 * MS, 12 * MS, "fusion.o")]
    names = {"while.inner": "attention", "fusion.a": "attention",
             "fusion.m": "mlp", "fusion.o": "optimizer"}
    mods = [(0, 12 * MS, "jit_train_step(1)")]
    r = scopes.reduce(_tr(ops, mods, names=names), 0, 12 * MS)
    assert r["steps"] == 1
    assert r["step_busy_s"] == pytest.approx(0.011)
    assert r["scope_s"] == pytest.approx(
        {"attention": 0.004, "mlp": 0.003, "optimizer": 0.001,
         "unscoped": 0.003})
    assert sum(r["scope_s"].values()) == pytest.approx(r["step_busy_s"])


def test_only_steps_wholly_inside_the_window_count():
    ops = [(0, 4 * MS, "f"), (5 * MS, 9 * MS, "f"), (10 * MS, 14 * MS, "f")]
    mods = [(0, 4 * MS, "jit_train_step"), (5 * MS, 9 * MS,
                                            "jit_train_step"),
            (10 * MS, 14 * MS, "jit_other")]
    r = scopes.reduce(_tr(ops, mods, names={"f": "mlp"}), 2 * MS, 15 * MS)
    assert r["steps"] == 1
    assert r["scope_s"] == pytest.approx({"mlp": 0.004})


def test_idle_goes_to_the_innermost_program_span():
    """The drive's ``bench.`` spans and the runtime's ``train.`` spans
    interleave (``train.step`` opens inside ``bench.feed`` and outlives
    it): each kind is reduced on its own, and each part of the gap between
    two steps goes to the program span open over it."""
    spans = [(0, 40 * MS, "bench.window"), (1 * MS, 13 * MS, "bench.step"),
             (13 * MS, 20 * MS + MS // 2, "bench.end_step"),
             (20 * MS + MS // 2, 25 * MS + MS // 2, "bench.feed"),
             (25 * MS + MS // 2, 40 * MS, "bench.step")]
    program = [(0, 13 * MS, "train.step"),
               (13 * MS, 20 * MS, "train.end_step"),
               (14 * MS, 18 * MS, "train.end_step.pull"),
               (21 * MS, 24 * MS, "train.feed"),
               (25 * MS, 40 * MS, "train.step")]
    mods = [(2 * MS, 12 * MS, "jit_train_step"),
            (28 * MS, 38 * MS, "jit_train_step")]
    tr = _tr(mods, mods, spans, program)
    r = scopes.reduce(tr)
    assert (r["steps"], r["gaps"]) == (2, 1)
    # the gap [12, 28): only the idle between the two steps is split
    assert r["idle_by_span_s"] == pytest.approx(
        {"train.step": 0.004, "train.end_step": 0.003,
         "train.end_step.pull": 0.004, "train.feed": 0.003, "none": 0.002})
    base = trace.reduce(tr)
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(
        sum(g for g, _, _ in base["module_gaps"]) / 1e9)
    # the benchmark's own reduction still reads its own spans, a whole gap
    # of the window to the span open at its midpoint
    assert dict(base["idle_gaps"]) == pytest.approx(
        {"host:step": 0.004, "host:end_step": 0.016})
    ms = scopes.per_step_ms(r)
    assert ms["dispatch_idle_ms.train"] == pytest.approx(4.0)
    assert ms["end_step_idle_ms.train"] == pytest.approx(7.0)
    assert ms["feed_idle_ms.train"] == pytest.approx(3.0)
    assert ms["step_busy_ms"] == pytest.approx(10.0)


def test_innermost_cuts_the_time_line_where_spans_open_and_close():
    spans = [(0, 10, "a"), (2, 5, "b"), (5, 7, "c"), (12, 14, "d")]
    assert scopes.innermost(spans) == [
        (0, 2, "a"), (2, 5, "b"), (5, 7, "c"), (7, 10, "a"), (12, 14, "d")]


def test_a_trace_without_scopes_reads_all_unscoped():
    """A program without named scopes (as before they were added) still
    splits: every operation is unscoped."""
    ops = [(0, 4 * MS, "f"), (1 * MS, 2 * MS, "g")]
    r = scopes.reduce(_tr(ops, [(0, 4 * MS, "jit_train_step")]), 0, 4 * MS)
    assert r["scope_s"] == pytest.approx({"unscoped": 0.004})
    assert r["idle_by_span_s"] == {}
    # the metrics it has no names for are absent, not zero
    assert scopes.per_step_ms(r) == pytest.approx(
        {"unscoped_ms.train": 4.0, "step_busy_ms": 4.0})


def test_split_a_cell_on_the_cpu(cpu_devices):
    """The cell's drive under the harness's tracer, at the smoke size: on
    the CPU the trace has no device plane, so only the runtime's spans are
    read, one of each per step."""
    from conftest import smoke_cell
    cell = smoke_cell("qwen25-05b.fullft", check_steps=1, trace_seconds=1)
    out = scopes.split_cell(cell, 2**31 + 11, 1.0, cpu_devices)
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert set(out["program_s"]) == {"train.feed", "train.step",
                                      "train.end_step", "train.end_step.pull"}


def test_a_recorded_scoped_chip_trace():
    """Recorded on one TPU v5 lite by ``record_probe_scoped.py``: three
    steps of a toy scanned, rematerialised step with the four scopes, fed
    by ``TrainerRuntime`` (``train.`` spans) inside the drive's ``bench.``
    spans.  The toy's device work is a third of a millisecond per step, so
    the host's spans hold mostly idle time; the device's clock runs behind
    the host's, so some of that work falls inside ``train.feed``."""
    tr = scopes.load(str(DATA / "probe_scoped.xplane.pb"))
    r = scopes.reduce(tr)
    assert r["steps"] == 3
    assert r["step_busy_s"] == pytest.approx(997.048e-6, rel=1e-6)
    assert r["scope_s"] == pytest.approx(
        {"attention": 236.847e-6, "mlp": 242.751e-6, "lm_head": 175.781e-6,
         "optimizer": 33.448e-6, "unscoped": 308.222e-6}, rel=1e-6)
    # operations run one at a time: self times add up to the busy time,
    # to the nanosecond the trace rounds each event to
    assert sum(r["scope_s"].values()) == pytest.approx(r["step_busy_s"],
                                                       abs=1e-8)
    # the gaps between the steps are split whole, to the nanosecond
    base = trace.reduce(tr)
    assert r["gaps"] == len(base["module_gaps"]) == 2
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(
        sum(g for g, _, _ in base["module_gaps"]) / 1e9, abs=1e-8)
    assert r["idle_by_span_s"]["train.step"] == pytest.approx(2.388166e-3,
                                                              rel=1e-6)
    assert set(r["idle_by_span_s"]) == {
        "train.feed", "train.step", "train.end_step",
        "train.end_step.pull", "none"}
    names = [n for _, _, n in tr["program"]]
    assert [names.count(n) for n in ("train.feed", "train.step",
                                     "train.end_step",
                                     "train.end_step.pull")] == [3] * 4
