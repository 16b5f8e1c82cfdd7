"""TrainerRuntime: shared scaffolding for every train-loop variant.

Three loop variants compose this runtime (repro/launch/train.py):

  train_loop           fully in-memory jitted step
  offload_train_loop   in-memory fwd/bwd + segment-streamed optimizer (C1)
  stream_train_loop    layer-streamed fwd/bwd + streamed optimizer (C1, full)

The ~50 lines of setup/teardown they used to mirror live here exactly once:
data pipeline + deterministic skip-ahead on resume, MetricsObserver wiring,
CheckpointStore + SIGTERM preemption flush, energy-governor hook, cadence
checkpointing, and the CSV/dashboard teardown.  Each variant keeps only its
own state construction, resume guard and step body.
"""
from __future__ import annotations

import os
import signal
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint.store import CheckpointStore, latest_step
from repro.config import ModelConfig, TrainConfig
from repro.offload.state import ensure_base_quant_match
from repro.data.corpus import synthetic_wikitext
from repro.data.dataset import LMDataset, packed_batches
from repro.data.tokenizer import ByteTokenizer
from repro.runtime.metrics import MetricsObserver
from repro.runtime.visualizer import write_dashboard


def build_data(cfg: ModelConfig, tcfg: TrainConfig, n_sentences: int = 4000,
               seed: int = 0):
    tok = ByteTokenizer()
    text = synthetic_wikitext(n_sentences, seed=seed)
    ds = LMDataset(text, tok, tcfg.seq_len)
    # token ids must stay inside the model vocab
    assert tok.vocab_size <= cfg.vocab_size, (tok.vocab_size, cfg.vocab_size)
    return ds


class TrainerRuntime:
    """One instance per training run; owns observer, data and checkpoints."""

    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, *,
                 out_dir: Optional[str], seed: int = 0,
                 governor=None, dataset=None, print_fn=print):
        self.cfg, self.tcfg = cfg, tcfg
        self.out_dir, self.seed = out_dir, seed
        self.governor, self.print_fn = governor, print_fn
        self.ds = dataset if dataset is not None else build_data(
            cfg, tcfg, seed=seed)
        self.obs = MetricsObserver(out_dir=out_dir, print_fn=print_fn)
        self.ckdir = (os.path.join(out_dir, "ckpt")
                      if (tcfg.checkpoint_every > 0 and out_dir) else None)
        self.store: Optional[CheckpointStore] = (
            CheckpointStore(self.ckdir, keep=tcfg.keep_checkpoints)
            if self.ckdir else None)
        self.tokens_per_step = tcfg.global_batch * tcfg.seq_len
        self._preempt_signum: Optional[int] = None
        self._preempt_flush: Optional[Callable[[], None]] = None
        self._prev_sigterm = None
        self._step_span = None      # "train.step": hand-off to end_step
        self._profiling = False

    # ------------------------------------------------------------------
    # resume / fault tolerance
    # ------------------------------------------------------------------
    def latest_checkpoint(self) -> Optional[int]:
        return latest_step(self.ckdir) if self.ckdir else None

    def log(self, msg: str):
        if self.print_fn:
            self.print_fn(msg)

    def guard_segment_layout(self, ostate):
        """Reconcile CLI storage flags against an existing segment layout
        (one shared guard for every offload loop variant — this used to be
        mirrored per-loop).  Storage choices are fixed when the layout is
        created: a differing ``--offload-moment-dtype`` is merely ignored
        (warn), but a differing ``--base-quant`` would hand the jitted
        program the wrong encoding, so it hard-errors."""
        tcfg = self.tcfg
        if getattr(ostate, "frozen", False):
            if tcfg.offload_moment_dtype != "float32":
                self.log(f"[warn] --offload-moment-dtype "
                         f"{tcfg.offload_moment_dtype} ignored: the frozen "
                         "base layout stores params only (no m/v segments); "
                         "the adapter's moments live in RAM")
        elif ostate.moment_dtype != tcfg.offload_moment_dtype:
            self.log(f"[warn] --offload-moment-dtype "
                     f"{tcfg.offload_moment_dtype} ignored: the resumed "
                     f"segment files store {ostate.moment_dtype} moments "
                     "(fixed at create time)")
        ensure_base_quant_match(ostate, tcfg.base_quant)

    def install_sigterm(self, flush_fn: Callable[[], None],
                        defer: bool = False):
        """Preemption tolerance: flush a checkpoint on SIGTERM, then exit.

        ``defer=True`` records the signal and lets ``steps()`` run the flush
        at the next step *boundary* instead of inside the handler — required
        for the offload variants, whose segment files are mutated in place
        mid-step (a handler-time snapshot could capture a half-applied
        update sweep with a stale step count).
        """
        if self.store is None:
            return

        if defer:
            def _flush(signum, frame):
                self._preempt_signum = signum
                self._preempt_flush = flush_fn
        else:
            def _flush(signum, frame):
                flush_fn()
                raise SystemExit(128 + signum)
        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM, _flush)
        except ValueError:
            pass  # not the main thread

    def restore_sigterm(self):
        """Hand SIGTERM back to whoever owned it before install_sigterm —
        a deferred handler whose flush only runs inside steps() must never
        outlive the loop (it would swallow termination requests)."""
        if self._prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except ValueError:
                pass
            self._prev_sigterm = None

    # ------------------------------------------------------------------
    # the step scaffold
    # ------------------------------------------------------------------
    def steps(self, start: int) -> Iterator[Tuple[int, dict]]:
        """(step, device batch) pairs from ``start`` to total_steps, with the
        data iterator skipped ahead so resumed runs see the exact same
        order, and the observer's step timer armed.

        Each step leaves three host spans in a profiler trace: ``train.feed``
        (the batch built and uploaded), ``train.step`` (from the hand-off
        to ``end_step``: the caller's dispatch and its wait for the device)
        and ``train.end_step``.  ``tcfg.profile_steps`` (first, last) traces
        those steps into ``tcfg.profile_dir``."""
        batches = packed_batches(self.ds, self.tcfg.global_batch,
                                 seed=self.seed, epochs=10_000)
        for _ in range(start):
            next(batches)  # deterministic data order on resume
        try:
            for step in range(start, self.tcfg.total_steps):
                self._close_step_span()
                self._profile_at(step)
                if self._preempt_signum is not None:  # deferred SIGTERM
                    self._preempt_flush()
                    raise SystemExit(128 + self._preempt_signum)
                with jax.profiler.TraceAnnotation("train.feed"):
                    batch = {k: jnp.asarray(v)
                             for k, v in next(batches).items()}
                self.obs.start_step()
                self._step_span = jax.profiler.TraceAnnotation("train.step")
                self._step_span.__enter__()
                yield step, batch
        finally:
            # also runs when the consuming loop dies on an exception (the
            # generator is closed), so a crashed run stays killable
            self._close_step_span()
            self._stop_profile()
            self.restore_sigterm()

    def _close_step_span(self):
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None

    def _profile_at(self, step: int):
        """Start the profiler before the first of ``tcfg.profile_steps``,
        stop it before the step after the last (so the checkpoint that
        follows a step is inside its trace)."""
        first, last = self.tcfg.profile_steps
        if not self.tcfg.profile_dir:
            return
        if step == first:
            jax.profiler.start_trace(self.tcfg.profile_dir)
            self._profiling = True
        elif step == last + 1:
            self._stop_profile()

    def _stop_profile(self):
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
            self.log(f"[profile] steps {self.tcfg.profile_steps[0]}-"
                     f"{self.tcfg.profile_steps[1]} traced into "
                     f"{self.tcfg.profile_dir}")

    def end_step(self, step: int, metrics) -> dict:
        self._close_step_span()
        with jax.profiler.TraceAnnotation("train.end_step"):
            row = self.obs.end_step(
                step, metrics, tokens=self.tokens_per_step,
                battery=(self.governor.monitor.fraction()
                         if self.governor else 1.0))
            if self.governor is not None:
                self.governor.after_step(step, row["step_time_s"])
        return row

    def checkpoint_due(self, step: int) -> bool:
        return (self.store is not None
                and (step + 1) % self.tcfg.checkpoint_every == 0)

    # ------------------------------------------------------------------
    # teardown
    # ------------------------------------------------------------------
    def finish(self, title: str) -> MetricsObserver:
        self.restore_sigterm()
        if self.store is not None:
            self.store.wait()
        self.obs.flush_csv()
        if self.out_dir:
            write_dashboard(self.obs.rows,
                            os.path.join(self.out_dir, "dashboard.html"),
                            title=title)
        if self._preempt_signum is not None:
            # SIGTERM landed after the last step: the loop's end-of-run save
            # already persisted the final state, so just exit as requested
            raise SystemExit(128 + self._preempt_signum)
        return self.obs
