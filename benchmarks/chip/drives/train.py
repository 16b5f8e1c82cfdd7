"""Training drive: the in-memory jitted train step, as ``train_loop`` runs it.

Set-up builds one state from the seed (weights from ``weights.py``, AdamW
state from the program), compiles ``jax.jit(make_train_step(...),
donate_argnums=(0,))`` for the cell's batch and drives it, through the
``TrainerRuntime.steps`` feed with the per-step ``block_until_ready`` and
``end_step`` of ``launch/train.py::train_loop``, over its first
``check_steps`` steps.  It reads the losses, the first gradient (AdamW's
first moment after one step over 1 - beta1) and, after the last of them,
the change of the weights.  The window then goes on with the same state
and the same feed.  After the window the state is freed and the reference
follows the same steps on the same rows.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import compare, reference, weights
from benchmarks.chip.flops import train_flops_per_token
from benchmarks.chip.harness import model_config, vocab_rows
from benchmarks.chip.traffic import PackedRows


def train_config(cell):
    from repro.config import TrainConfig
    tr = cell.traffic
    return TrainConfig(global_batch=tr["global_batch"], seq_len=tr["seq_len"],
                       **tr["train"])


class Drive:
    def __init__(self, cell, seed, devices, hooks, log):
        from repro.core.step import make_train_step
        from repro.optim import adamw_init
        from repro.runtime.trainer import TrainerRuntime
        self.cell, self.seed, self.devices, self.log = cell, seed, devices, log
        c, tr = cell.config, cell.traffic
        self.cfg = model_config(c)
        self.tcfg = train_config(cell)
        self.rows = vocab_rows(self.cfg)
        key = weights.seed_key(seed)
        params = weights.program_weights(key, c, self.rows)
        state = {"params": params, "opt": adamw_init(params),
                 "step": jnp.zeros((), jnp.int32)}
        del params
        ds = PackedRows(tr, seed, c["vocab_size"])
        self.rt = TrainerRuntime(self.cfg, self.tcfg, out_dir=None,
                                 seed=seed, dataset=ds, print_fn=None)
        self.feed = self.rt.steps(0)
        make_step = hooks.get("make_train_step", make_train_step)
        step_fn = jax.jit(make_step(self.cfg, self.tcfg), donate_argnums=(0,))
        step, batch = next(self.feed)
        self.compiled = step_fn.lower(state, batch).compile()
        self.mem = self.compiled.memory_analysis()
        # the first steps, through the window's own call and feed
        n = tr["check_steps"]
        self.check_batches, losses = [], []
        for k in range(n):
            if k:
                step, batch = next(self.feed)
            self.check_batches.append({
                "tokens": np.asarray(batch["tokens"]),
                "labels": np.asarray(batch["labels"])})
            state, metrics = self.compiled(state, batch)
            jax.block_until_ready(metrics["loss"])
            self.rt.end_step(step, metrics)
            losses.append(float(metrics["loss"]))
            if k == 0:
                grad = compare.leaf_norms(state["opt"]["m"],
                                          1.0 / (1.0 - self.tcfg.beta1))
        update = compare.leaf_norms(_change_norms(
            state["params"], key, weights.frozen(c), self.rows))
        self.program = {"losses": losses, "grad": grad, "update": update}
        self.program_state = state
        self.steps = 0

    def counters(self) -> dict:
        return {"steps": self.steps, "t": time.perf_counter()}

    def window(self, seconds, tracer) -> dict:
        state = self.program_state
        self.program_state = None
        b, s = self.tcfg.global_batch, self.tcfg.seq_len
        phases = []     # per step: feed, dispatch, wait, end_step (s)
        t0 = t = time.perf_counter()
        tracer.begin(t0)
        while True:
            with jax.profiler.TraceAnnotation("bench.feed"):
                step, batch = next(self.feed)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                state, metrics = self.compiled(state, batch)
                t2 = time.perf_counter()
                jax.block_until_ready(metrics["loss"])
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.end_step"):
                self.rt.end_step(step, metrics)
            self.steps += 1
            now = time.perf_counter()
            phases.append((t1 - t, t2 - t1, t3 - t2, now - t3))
            tracer.poll(now)
            if now - t0 >= seconds:
                break
            t = time.perf_counter()
        self.program_state = state
        elapsed = now - t0
        ms = 1e3 * np.asarray(phases)
        worst = int(np.argmax(ms.sum(axis=1)))
        self.log("[bench] step phases ms (feed, dispatch, wait, end_step): "
                 f"median {np.round(np.median(ms, axis=0), 3).tolist()}, "
                 f"slowest step {worst} "
                 f"{np.round(ms[worst], 3).tolist()}")
        tokens = self.steps * b * s
        return {"window_s": elapsed, "steps": self.steps, "tokens": tokens,
                "attempted": self.steps, "failed": 0,
                "flops_per_token": train_flops_per_token(self.cell.config, s),
                "end_to_end": {"train_tokens_per_s": tokens / elapsed}}

    def memory(self) -> dict:
        from benchmarks.chip.harness import peak_bytes
        m = self.mem
        program = (m.argument_size_in_bytes + m.output_size_in_bytes
                   - m.alias_size_in_bytes + m.temp_size_in_bytes)
        in_use = peak_bytes(self.devices)
        return {"peak_bytes_in_use": in_use, "step_program_bytes": program,
                "step_temp_bytes": m.temp_size_in_bytes,
                "memory_peak_bytes": max(in_use, program)}

    def release(self):
        """Frees the program's state, its compiled step and its feed."""
        self.program_state = self.compiled = self.rt = self.feed = None

    def check(self) -> dict:
        c, tr = self.cell.config, self.cell.traffic
        ref = reference_numbers(c, tr, self.seed, self.check_batches,
                                jnp.float32)
        numbers = compare.train_numbers(self.program, ref)
        return compare.with_limits(numbers, self.cell.limits)


def reference_numbers(c, tr, seed, batches, dtype) -> dict:
    """The reference's losses and leaf norms over ``batches``, in the
    program's leaves."""
    key = weights.seed_key(seed)
    hp = dict(tr["train"])
    rows = c["vocab_size"]

    def first_grad(g):
        return compare.leaf_norms(weights.to_program(g, c, rows))

    losses, grad, w = reference.train(
        weights.hf_weights(key, c), c, batches, hp, first_grad, dtype)
    update = compare.leaf_norms(_hf_change_norms(w, key, weights.frozen(c)))
    return {"losses": losses, "grad": grad, "update": update}


@partial(jax.jit, static_argnums=(2, 3))
def _change_norms(params, key, c_items, rows):
    """Norms of each leaf's change from the seed's weights, made again
    inside the call, so that no second copy of the weights stays live."""
    p0 = weights.to_program(weights.draw_hf(key, c_items, "float32"),
                            dict(c_items), rows)
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                        params, p0)


@partial(jax.jit, static_argnums=(2,))
def _hf_change_norms(w, key, c_items):
    c = dict(c_items)
    w0 = weights.draw_hf(key, c_items, "float32")
    d = weights.to_program(jax.tree.map(jnp.subtract, w, w0), c,
                           c["vocab_size"])
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), d)
