"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (set-up, window, release, check) on the
CPU at a small size, with the committed limits of the real cell, and with
one fault planted in the program's step.  A sound run, the bfloat16
control and the readings the limits were set from are judged the same
way.
"""
import jax
import jax.numpy as jnp
import pytest
from conftest import smoke_cell

from benchmarks.chip import control, harness

SEED = 2 ** 33 + 77


def _run(cell, devices, **hooks):
    return harness.run(cell, SEED, 1.0, False, devices, hooks=hooks,
                       setup_t0=0.0)


def _train_cell():
    return smoke_cell("qwen25-05b.fullft")


def test_sound_training_run_is_correct(cpu_devices):
    out = _run(_train_cell(), cpu_devices)
    assert out["correct"], out["checks"]


def test_state_left_unchanged_fails(cpu_devices):
    from repro.core.step import make_train_step

    def frozen(cfg, tcfg):
        step = make_train_step(cfg, tcfg)

        def train_step(state, batch):
            _, metrics = step(state, batch)
            return jax.tree.map(jnp.copy, state), metrics
        return train_step

    out = _run(_train_cell(), cpu_devices, make_train_step=frozen)
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_fails(cpu_devices):
    from repro.core.step import make_train_step

    def half(cfg, tcfg):
        step = make_train_step(cfg, tcfg)

        def train_step(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return train_step

    out = _run(_train_cell(), cpu_devices, make_train_step=half)
    assert not out["correct"], out["checks"]


def test_bfloat16_control_reads_apart_from_the_program(cpu_devices):
    """``control.py``'s readings on the CPU: the bfloat16 control reads at
    least three times what the program reads on every number, and the
    half-batch fault fails the cell's committed limits.  (At this size the
    control's own gaps stay under those limits, which were set on the chip
    at the cell's size; the next test holds the limits to those readings.)
    """
    rd = dict(control.readings(_train_cell(), SEED, cpu_devices, True,
                               harness.log))
    p, ctl = rd["program"], rd["control_bf16"]
    for k in p:
        assert ctl[k] > 3 * p[k], (k, p[k], ctl[k])
    assert control.verdict(_train_cell(), p)["correct"]
    assert not control.verdict(_train_cell(),
                               rd["fault_half_batch"])["correct"]


def test_committed_limits_fail_the_chip_readings_of_control_and_faults():
    """The verdict a run would give on the readings the limits were set
    from (one TPU v5 lite, the cell's own size): the program's largest
    passes, the control's and each fault's smallest fail."""
    rec = harness.load_json(harness.HERE / "limits" /
                            "qwen25-05b.fullft.json")
    cell = _train_cell()
    assert cell.limits == rec["limits"]
    r = rec["readings"]
    assert control.verdict(cell, r["program_max"])["correct"]
    for kind in ("control_bf16_min", "fault_half_batch_min",
                 "fault_state_unchanged"):
        assert not control.verdict(cell, r[kind])["correct"], kind
