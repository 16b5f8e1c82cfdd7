"""End-to-end training driver (paper Application layer).

Composes the full resource-aware runtime: data pipeline -> sharded train step
(C1–C4) -> energy governor (C5) -> metrics observer + visualizer (C7) ->
fault-tolerant checkpointing.  Runs on 1 CPU device (paper-scale models) or
any mesh.

Three loop variants compose the shared ``TrainerRuntime`` scaffold
(repro/runtime/trainer.py):

  train_loop           fully in-memory jitted step
  offload_train_loop   in-memory fwd/bwd, segment-streamed optimizer (C1)
  stream_train_loop    layer-streamed fwd/bwd AND optimizer (C1, full depth):
                       peak resident params bounded by a few layer segments

    PYTHONPATH=src python -m repro.launch.train --arch gpt2_124m \
        --steps 200 --batch 8 --seq 128 --lora-rank 8 --out runs/gpt2
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.config import ModelConfig, TrainConfig, dtype_of
from repro.checkpoint.safetensors import save_adapter
from repro.checkpoint.store import (checkpoint_meta, is_adapter_checkpoint,
                                    is_offload_checkpoint,
                                    offload_checkpoint_layout, restore,
                                    restore_offload)
from repro.core.energy import EnergyGovernor, SimulatedBattery
from repro.core.step import (init_adapter_state, init_state, make_grad_step,
                             make_stream_step, make_train_step)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.offload.state import (LAYER_LAYOUT, LayerStreamedState,
                                 OffloadedTrainState, offload_dir_for)
from repro.optim.schedule import lr_schedule
from repro.param import abstract_params, init_params, tree_bytes
from repro.runtime.trainer import TrainerRuntime, build_data  # noqa: F401


def _resume_layout_guard(rt: TrainerRuntime, last: int, expected: str):
    """Refuse to resume a checkpoint written by a different loop variant.

    ``expected`` is the layout this loop can consume: "memory" (in-memory
    jit), "byte" (byte-balanced optimizer offload), "layer" (layer-aligned
    param streaming) or "adapter" (adapter-only, frozen-base streamed LoRA).
    The error names the flag that matches the checkpoint.
    """
    actual = "memory"
    if is_offload_checkpoint(rt.ckdir, last):
        actual = ("layer" if offload_checkpoint_layout(rt.ckdir, last) ==
                  LAYER_LAYOUT else "byte")
    elif is_adapter_checkpoint(rt.ckdir, last):
        actual = "adapter"
    if actual == expected:
        return
    kind = {"memory": "in-memory",
            "byte": "byte-balanced segment-offload",
            "layer": "layer-aligned (param-streaming) segment-offload",
            "adapter": "adapter-only (frozen-base streamed LoRA)"}
    flag = {"memory": "without offload flags",
            "byte": "with --offload-segments N",
            "layer": "with --offload-stream-params",
            "adapter": "with --offload-stream-params --lora-rank N"}
    raise ValueError(
        f"{rt.ckdir} holds {kind[actual]} checkpoints; resume {flag[actual]} "
        f"(or point --out elsewhere)")


def train_loop(cfg: ModelConfig, tcfg: TrainConfig, *, out_dir: Optional[str],
               seed: int = 0, resume: bool = True,
               governor: Optional[EnergyGovernor] = None,
               dataset=None, print_fn=print):
    if tcfg.offload_stream_params:
        loop = (stream_lora_train_loop if tcfg.lora_rank > 0
                else stream_train_loop)
        return loop(cfg, tcfg, out_dir=out_dir, seed=seed,
                    resume=resume, governor=governor,
                    dataset=dataset, print_fn=print_fn)
    if tcfg.offload_segments > 0:
        return offload_train_loop(cfg, tcfg, out_dir=out_dir, seed=seed,
                                  resume=resume, governor=governor,
                                  dataset=dataset, print_fn=print_fn)
    rt = TrainerRuntime(cfg, tcfg, out_dir=out_dir, seed=seed,
                        governor=governor, dataset=dataset, print_fn=print_fn)
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    state = init_state(jax.random.PRNGKey(seed), cfg, tcfg)

    start = 0
    last = rt.latest_checkpoint()
    if resume and last is not None:
        _resume_layout_guard(rt, last, "memory")
        state, start = restore(rt.ckdir, state)
        start = int(start)
        rt.log(f"[resume] from step {start}")
    # defer: mid-step the donated `state` buffers belong to the jit call
    rt.install_sigterm(lambda: rt.store.save_sync(state, int(state["step"])),
                       defer=True)

    for step, batch in rt.steps(start):
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        rt.end_step(step, metrics)
        if rt.checkpoint_due(step):
            rt.store.save_async(state, step + 1)
    if rt.store:
        rt.store.wait()
        rt.store.save_sync(state, int(state["step"]))
    obs = rt.finish(f"{cfg.name} | {'LoRA' if tcfg.lora_rank else 'Full-FT'}")
    return state, obs


def offload_train_loop(cfg: ModelConfig, tcfg: TrainConfig, *,
                       out_dir: Optional[str], seed: int = 0,
                       resume: bool = True,
                       governor: Optional[EnergyGovernor] = None,
                       dataset=None, print_fn=print):
    """Training with segment-wise *optimizer-state* offload (paper §4.1.1
    C1, phone realization — repro/offload/).

    fwd/bwd runs jitted on the full in-memory params; the AdamW update then
    streams the (p, m, v) segments through a small LRU window with
    double-buffered prefetch, so peak resident optimizer state is
    ``offload_resident / offload_segments`` of the whole — decoupled from
    model size.  Checkpoints hardlink the segment files (zero-copy)."""
    rt = TrainerRuntime(cfg, tcfg, out_dir=out_dir, seed=seed,
                        governor=governor, dataset=dataset, print_fn=print_fn)
    grad_fn = jax.jit(make_grad_step(cfg, tcfg))
    work_dir = offload_dir_for(out_dir, tcfg.offload_dir)
    like_params = abstract_params(registry.param_specs(cfg),
                                  dtype=dtype_of(tcfg.param_dtype))

    ostate = None
    last = rt.latest_checkpoint()
    if resume and last is not None:
        _resume_layout_guard(rt, last, "byte")
        ostate, start = restore_offload(
            rt.ckdir, work_dir, like_params, last,
            max_resident=tcfg.offload_resident,
            prefetch=tcfg.offload_prefetch,
            async_writeback=tcfg.offload_async_writeback,
            io_backend=tcfg.offload_io)
        rt.guard_segment_layout(ostate)
        rt.log(f"[resume] offload checkpoint step {start}")
    if ostate is None:
        state = init_state(jax.random.PRNGKey(seed), cfg, tcfg)
        ostate = OffloadedTrainState.create(
            state, work_dir, tcfg.offload_segments,
            max_resident=tcfg.offload_resident,
            prefetch=tcfg.offload_prefetch,
            moment_dtype=tcfg.offload_moment_dtype,
            async_writeback=tcfg.offload_async_writeback,
            io_backend=tcfg.offload_io)
        del state  # from here on the segment files own the optimizer state

    rt.install_sigterm(lambda: rt.store.save_offload(ostate, ostate.step),
                       defer=True)  # segments mutate in place mid-step
    params = ostate.materialize_params()
    for step, batch in rt.steps(ostate.step):
        loss, metrics, grads = grad_fn(params, batch)
        lr = lr_schedule(jnp.asarray(step, jnp.int32),
                         base_lr=tcfg.learning_rate,
                         warmup_steps=tcfg.warmup_steps,
                         total_steps=tcfg.total_steps, kind=tcfg.schedule)
        params = ostate.apply_update(grads, lr=lr, beta1=tcfg.beta1,
                                     beta2=tcfg.beta2, eps=tcfg.eps,
                                     weight_decay=tcfg.weight_decay)
        del grads
        jax.block_until_ready(loss)
        metrics = dict(metrics)
        metrics["lr"] = lr
        rt.end_step(step, metrics)
        if rt.checkpoint_due(step):
            rt.store.save_offload(ostate, step + 1)
    if rt.store:
        rt.store.save_offload(ostate, ostate.step)
    s = ostate.stats()
    rt.log(f"[offload] segments {ostate.store.num_segments} | state "
           f"{s['store_bytes']/1e6:.1f} MB | peak window "
           f"{s['peak_resident_bytes']/1e6:.1f} MB | prefetch hit "
           f"{s['prefetch_hits']}/{s['prefetch_hits']+s['sync_loads']}")
    ostate.close()
    obs = rt.finish(f"{cfg.name} | offload x{ostate.store.num_segments}")
    state = {"params": params, "step": jnp.asarray(ostate.step, jnp.int32),
             "offload": ostate}
    return state, obs


def stream_train_loop(cfg: ModelConfig, tcfg: TrainConfig, *,
                      out_dir: Optional[str], seed: int = 0,
                      resume: bool = True,
                      governor: Optional[EnergyGovernor] = None,
                      dataset=None, print_fn=print):
    """Layer-streamed training (paper §4.1.1 C1, full depth): fwd/bwd pulls
    each block's layer-aligned (p, m, v) segment through the offload window
    (prefetching block i+1 while block i computes), saves only the
    layer-boundary activations, back-propagates block-by-block into a
    gradient scratch store, and streams the AdamW update segment-wise.  Peak
    resident params during compute stay bounded by a few layer segments +
    the head segment — independent of model depth."""
    rt = TrainerRuntime(cfg, tcfg, out_dir=out_dir, seed=seed,
                        governor=governor, dataset=dataset, print_fn=print_fn)
    work_dir = offload_dir_for(out_dir, tcfg.offload_dir)
    like_params = abstract_params(registry.param_specs(cfg),
                                  dtype=dtype_of(tcfg.param_dtype))

    lstate = None
    last = rt.latest_checkpoint()
    if resume and last is not None:
        _resume_layout_guard(rt, last, "layer")
        lstate, start = restore_offload(
            rt.ckdir, work_dir, like_params, last,
            max_resident=tcfg.offload_resident,
            prefetch=tcfg.offload_prefetch,
            async_writeback=tcfg.offload_async_writeback,
            io_backend=tcfg.offload_io)
        rt.guard_segment_layout(lstate)
        rt.log(f"[resume] layer-streamed checkpoint step {start}")
    if lstate is None:
        state = init_state(jax.random.PRNGKey(seed), cfg, tcfg)
        lstate = LayerStreamedState.create(
            state, work_dir, max_resident=tcfg.offload_resident,
            prefetch=tcfg.offload_prefetch,
            moment_dtype=tcfg.offload_moment_dtype,
            async_writeback=tcfg.offload_async_writeback,
            io_backend=tcfg.offload_io)
        del state  # the segment files own params AND optimizer state now

    rt.install_sigterm(lambda: rt.store.save_offload(lstate, lstate.step),
                       defer=True)  # segments mutate in place mid-step
    step_fn = make_stream_step(cfg, tcfg, lstate,
                               grad_dir=os.path.join(work_dir, "grads"))
    for step, batch in rt.steps(lstate.step):
        loss, metrics = step_fn(batch, step)
        rt.end_step(step, metrics)
        if rt.checkpoint_due(step):
            rt.store.save_offload(lstate, step + 1)
    if rt.store:
        rt.store.save_offload(lstate, lstate.step)
    s = step_fn.stats()
    ps = step_fn.pipeline_stats()
    rt.log(f"[stream] {lstate.n_layers} layer segments + head | state "
           f"{s['param_store_bytes']/1e6:.1f} MB | peak param window "
           f"{s['param_peak_resident_bytes']/1e6:.1f} MB | prefetch hit "
           f"{s['param_prefetch_hits']}"
           f"/{s['param_prefetch_hits']+s['param_sync_loads']}")
    rt.log(f"[stream] pipeline: read-blocked {ps['read_block_s']:.2f}s | "
           f"write-blocked {ps['write_block_s']:.2f}s | h2d staging "
           f"{ps['stage_h2d_s']:.2f}s | background write "
           f"{ps['writeback_busy_s']:.2f}s")
    params = lstate.materialize_params()
    step_fn.close()
    lstate.close()
    obs = rt.finish(f"{cfg.name} | layer-streamed x{lstate.n_layers}")
    state = {"params": params, "step": jnp.asarray(lstate.step, jnp.int32),
             "offload": lstate}
    return state, obs


def stream_lora_train_loop(cfg: ModelConfig, tcfg: TrainConfig, *,
                           out_dir: Optional[str], seed: int = 0,
                           resume: bool = True,
                           governor: Optional[EnergyGovernor] = None,
                           dataset=None, print_fn=print):
    """PEFT on the streamed offload engine (paper C6 over C1, full depth):
    the frozen base pages through *read-only* param-only layer segments —
    no m/v segments, no dirty write-back, no gradient scratch — while the
    (tiny) LoRA adapter and its AdamW state stay memory-resident.
    ``merge_lora`` runs per block inside the jitted apply/VJP entry points,
    so merged weights exist one block at a time.  With ``--base-quant int8``
    the frozen segments are additionally per-channel quantized (QLoRA-style)
    and stay int8 in the window — the program dequantizes per block inside
    the jit.  Checkpoints are **adapter-only**: base and adapter init both
    derive deterministically from the seed (crc32 path fold, repro/param.py),
    so resume re-derives (and re-quantizes) the frozen base and restores
    just the adapter tree."""
    rt = TrainerRuntime(cfg, tcfg, out_dir=out_dir, seed=seed,
                        governor=governor, dataset=dataset, print_fn=print_fn)
    work_dir = offload_dir_for(out_dir, tcfg.offload_dir)
    # the frozen base is fully determined by (arch, seed, param dtype) plus
    # its segment quantization; the quant suffix only appears when set so
    # pre-codec fp32 tags (and their checkpoints) stay valid
    base_tag = (f"{cfg.name}|seed{seed}|{tcfg.param_dtype}"
                + (f"|{tcfg.base_quant}" if tcfg.base_quant else ""))
    # adapter init is tiny; the full base only materializes if the frozen
    # segments still need laying out (see below)
    adapter = init_adapter_state(jax.random.PRNGKey(seed), cfg, tcfg)
    # everything the restored adapter is only valid against: base identity
    # (base_tag covers arch/seed/dtype/quant) and the merge hyperparameters
    # — stamped into the checkpoint manifest, validated on resume.  An
    # adapter trained against an int8 base is NOT valid against the fp32
    # base (and vice versa): the adapter learned around the quantization
    # error, so a codec mismatch hard-errors via base_quant/base_tag.
    peft_meta = {"seed": int(seed), "base_tag": base_tag,
                 "base_quant": tcfg.base_quant,
                 "lora_rank": int(tcfg.lora_rank),
                 "lora_alpha": float(tcfg.lora_alpha),
                 "lora_targets": list(tcfg.lora_targets)}

    start = 0
    last = rt.latest_checkpoint()
    if resume and last is not None:
        _resume_layout_guard(rt, last, "adapter")
        stored = checkpoint_meta(rt.ckdir, last)
        bad = {k: (stored[k], v) for k, v in peft_meta.items()
               if k in stored and stored[k] != v}
        if bad:
            raise ValueError(
                f"{rt.ckdir} was written with different PEFT settings: " +
                "; ".join(f"{k} was {was!r}, now {now!r}"
                          for k, (was, now) in sorted(bad.items())) +
                " — the adapter only matches the base/merge it was trained "
                "against (rerun with the original flags, or point --out "
                "elsewhere)")
        adapter, start = restore(rt.ckdir, adapter)
        start = int(start)
        rt.log(f"[resume] adapter-only checkpoint step {start} "
               f"(frozen base re-derived from seed {seed})")
    # the frozen segments are read-only and seed-derived: a matching store
    # left in work_dir by a previous run is reused as-is — no full-base RAM
    # materialization and no parameter-sized rewrite to flash on restart
    like_base = abstract_params(registry.param_specs(cfg),
                                dtype=dtype_of(tcfg.param_dtype))
    lstate = LayerStreamedState.open_frozen_if_matching(
        work_dir, like_base, base_tag=base_tag,
        max_resident=tcfg.offload_resident, prefetch=tcfg.offload_prefetch,
        io_backend=tcfg.offload_io)
    if lstate is not None:
        rt.log("[stream+lora] reusing frozen base segments in "
               f"{work_dir} (tag {base_tag})")
    else:
        # base only — the adapter above is the same tree init_state builds
        base = init_params(jax.random.PRNGKey(seed),
                           registry.param_specs(cfg),
                           dtype=dtype_of(tcfg.param_dtype))
        lstate = LayerStreamedState.create_frozen(
            base, work_dir, base_tag=base_tag,
            max_resident=tcfg.offload_resident,
            prefetch=tcfg.offload_prefetch,
            quant=tcfg.base_quant,
            io_backend=tcfg.offload_io)
        del base  # the read-only segment files own the base from here on
    rt.guard_segment_layout(lstate)

    step_fn = make_stream_step(cfg, tcfg, lstate, grad_dir="",
                               adapter=adapter)
    # defer: the adapter/opt swap inside the update is not atomic mid-step
    rt.install_sigterm(
        lambda: rt.store.save_sync(step_fn.adapter_state(),
                                   int(step_fn.adapter_state()["step"]),
                                   extra_meta=peft_meta),
        defer=True)
    for step, batch in rt.steps(start):
        loss, metrics = step_fn(batch, step)
        rt.end_step(step, metrics)
        if rt.checkpoint_due(step):
            rt.store.save_async(step_fn.adapter_state(), step + 1,
                                extra_meta=peft_meta)
    if rt.store:
        rt.store.wait()
        rt.store.save_sync(step_fn.adapter_state(),
                           int(step_fn.adapter_state()["step"]),
                           extra_meta=peft_meta)
    adapter = step_fn.adapter_state()
    s = step_fn.stats()
    adapter_mb = tree_bytes({"lora": adapter["lora"],
                             "opt": adapter["opt"]}) / 1e6
    quant_note = f" ({tcfg.base_quant})" if tcfg.base_quant else ""
    rt.log(f"[stream+lora] {lstate.n_layers} frozen layer segments + head | "
           f"base {s['param_store_bytes']/1e6:.1f} MB read-only{quant_note} |"
           f" peak param window {s['param_peak_resident_bytes']/1e6:.1f} MB |"
           f" adapter state {adapter_mb:.2f} MB resident | prefetch hit "
           f"{s['param_prefetch_hits']}"
           f"/{s['param_prefetch_hits']+s['param_sync_loads']}")
    ps = step_fn.pipeline_stats()
    rt.log(f"[stream+lora] pipeline: read-blocked {ps['read_block_s']:.2f}s"
           f" | h2d staging {ps['stage_h2d_s']:.2f}s | prefetch hit rate "
           f"{ps['prefetch_hit_rate']:.2f}")
    if out_dir:
        save_adapter(os.path.join(out_dir, "adapter.safetensors"),
                     adapter["lora"], rank=tcfg.lora_rank,
                     alpha=tcfg.lora_alpha, targets=tcfg.lora_targets,
                     base_quant=tcfg.base_quant, base_tag=base_tag)
    # a quantized base materializes dequantized, so the merged export folds
    # the adapter into the same weights the adapter actually trained against
    base = lstate.materialize_params()
    step_fn.close()
    lstate.close()
    obs = rt.finish(f"{cfg.name} | streamed-LoRA r{tcfg.lora_rank} "
                    f"x{lstate.n_layers}{quant_note}")
    state = {"base": base, "lora": adapter["lora"], "opt": adapter["opt"],
             "step": adapter["step"], "offload": lstate}
    return state, obs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2_124m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-5)
    ap.add_argument("--lora-rank", type=int, default=0)
    ap.add_argument("--lora-alpha", type=float, default=None,
                    help="LoRA scaling numerator (effective scale "
                         "alpha/rank; default 32); requires --lora-rank")
    ap.add_argument("--lora-targets", default=None,
                    help="comma-separated leaf names to adapt (default "
                         "wq,wk,wv,wo; use e.g. w_x,w_out for the ssm "
                         "family); requires --lora-rank")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--attention", default="streaming")
    ap.add_argument("--scan-layers", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="lax.scan over the stacked layers (in-memory path); "
                         "--no-scan-layers unrolls them")
    ap.add_argument("--offload-segments", type=int, default=0,
                    help="page (param, m, v) state to N mmap segment files; "
                         "optimizer updates stream segment-by-segment (C1)")
    ap.add_argument("--offload-stream-params", action="store_true",
                    help="layer-streamed fwd/bwd: segments become "
                         "layer-aligned (one per block + head) and params "
                         "page through the window during compute too")
    ap.add_argument("--offload-dir", default="",
                    help="segment-file directory (default <out>/offload)")
    ap.add_argument("--offload-resident", type=int, default=2,
                    help="LRU window size in segments")
    ap.add_argument("--offload-prefetch",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="background double-buffered segment prefetch")
    ap.add_argument("--offload-moment-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="storage dtype of the AdamW m/v segments "
                         "(bfloat16 halves their bytes; update math stays "
                         "fp32 via the bf16 segment codec)")
    ap.add_argument("--offload-async-writeback",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="bounded background dirty-segment writer: eviction "
                         "no longer blocks on encode+msync (flush and "
                         "snapshots stay barriers)")
    ap.add_argument("--offload-staging",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="double-buffered host->device staging of block "
                         "i+1 while block i computes (deferred loss/"
                         "grad-norm syncs are unconditional)")
    ap.add_argument("--base-quant", default="", choices=("", "int8"),
                    help="quantize the frozen base segments of streamed "
                         "LoRA (requires --lora-rank and "
                         "--offload-stream-params): int8 per-channel "
                         "absmax, ~4x less flash and resident window; the "
                         "jitted per-block program dequantizes on the fly")
    ap.add_argument("--offload-activations", action="store_true",
                    help="spill layer-boundary activations to a per-step "
                         "scratch store during the streamed forward sweep "
                         "and re-pull them in reverse order for backward "
                         "(requires --offload-stream-params): resident "
                         "activations stop scaling with depth at long seq")
    ap.add_argument("--activation-codec", default="fp32",
                    choices=("fp32", "bf16", "int8"),
                    help="storage precision of spilled activations: fp32 is "
                         "a bit-exact spill, bf16 halves the bytes, int8 "
                         "quarters them (per-token absmax)")
    ap.add_argument("--offload-io", default="",
                    choices=("", "mmap", "pread", "direct", "uring", "auto"),
                    help="segment read backend: mmap (default, page-cache "
                         "oracle), pread (batched positional reads straight "
                         "into window buffers), direct (O_DIRECT, bypasses "
                         "the page cache), uring (one io_uring SQE batch "
                         "per segment pull), auto (probe uring -> direct -> "
                         "pread).  Unsupported backends fall back to pread "
                         "with a logged note; bytes are bit-identical "
                         "across all of them.  Default '' defers to "
                         "$REPRO_OFFLOAD_IO, else mmap")
    ap.add_argument("--out", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--energy", action="store_true",
                    help="enable the K/mu/rho governor with a simulated battery")
    ap.add_argument("--profile-dir", default="",
                    help="write a jax.profiler trace of --profile-steps "
                         "here (open it with TensorBoard's profile plugin "
                         "or xprof)")
    ap.add_argument("--profile-steps", default="2:4", metavar="A:B",
                    help="the steps A to B (inclusive) that --profile-dir "
                         "traces; the first steps compile, so start later")
    args = ap.parse_args()
    try:
        first, last = (int(v) for v in args.profile_steps.split(":"))
    except ValueError:
        first = last = -1
    if not 0 <= first <= last:
        ap.error(f"--profile-steps {args.profile_steps!r} is not A:B with "
                 "0 <= A <= B")
    # fail at parse time, not deep inside the first step's split_batch
    if args.microbatches < 1:
        ap.error(f"--microbatches must be >= 1, got {args.microbatches}")
    if args.batch % args.microbatches != 0:
        ap.error(f"--batch {args.batch} is not divisible by --microbatches "
                 f"{args.microbatches}; each micro-batch must be equal-sized")
    if args.lora_rank == 0 and (args.lora_alpha is not None
                                or args.lora_targets is not None):
        ap.error("--lora-alpha/--lora-targets have no effect without "
                 "--lora-rank N")
    lora_targets = tuple(
        t.strip() for t in (args.lora_targets or "wq,wk,wv,wo").split(",")
        if t.strip())
    if args.lora_rank > 0 and not lora_targets:
        ap.error("--lora-rank set but --lora-targets is empty")
    if args.base_quant and not (args.lora_rank > 0
                                and args.offload_stream_params):
        ap.error("--base-quant applies to the frozen base of streamed LoRA; "
                 "pass --lora-rank N and --offload-stream-params with it")
    if args.offload_activations and not args.offload_stream_params:
        ap.error("--offload-activations spills the streamed driver's "
                 "boundary activations; pass --offload-stream-params with it")
    from repro.core.remat import POLICIES
    if args.remat not in POLICIES:
        ap.error(f"--remat {args.remat!r} is not a remat policy "
                 f"(choose from {', '.join(POLICIES)})")
    if args.attention not in ("naive", "streaming", "ref", "flash"):
        ap.error(f"--attention {args.attention!r} is not an attention impl "
                 "(choose from naive, streaming, ref, flash)")

    print(f"[compile cache] {enable_compile_cache()}")
    if args.profile_dir:
        # a trace names device time by the scopes in op metadata, which JAX
        # leaves out of the compile cache's key: key by it, or a cached
        # executable could carry another version's names
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    tcfg = TrainConfig(
        global_batch=args.batch, seq_len=args.seq,
        microbatches=args.microbatches, learning_rate=args.lr,
        total_steps=args.steps, warmup_steps=max(args.steps // 20, 1),
        lora_rank=args.lora_rank,
        lora_alpha=((32.0 if args.lora_alpha is None else args.lora_alpha)
                    if args.lora_rank else 0.0),
        lora_targets=lora_targets,
        remat_policy=args.remat, attention_impl=args.attention,
        scan_layers=args.scan_layers,
        compute_dtype="float32", checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.out or "",
        offload_segments=args.offload_segments,
        offload_stream_params=args.offload_stream_params,
        offload_dir=args.offload_dir,
        offload_resident=args.offload_resident,
        offload_prefetch=args.offload_prefetch,
        offload_moment_dtype=args.offload_moment_dtype,
        offload_async_writeback=args.offload_async_writeback,
        offload_staging=args.offload_staging,
        base_quant=args.base_quant,
        offload_activations=args.offload_activations,
        activation_codec=args.activation_codec,
        offload_io=args.offload_io,
        profile_dir=args.profile_dir, profile_steps=(first, last))
    governor = None
    if args.energy:
        governor = EnergyGovernor(monitor=SimulatedBattery(
            level=70.0, drain_per_unit=0.5))
    t0 = time.time()
    state, obs = train_loop(cfg, tcfg, out_dir=args.out, seed=args.seed,
                            governor=governor)
    print(f"done in {time.time()-t0:.1f}s | final loss "
          f"{obs.rows[-1]['loss']:.4f} | peak RSS {obs.peak_rss_mb:.0f} MB")


if __name__ == "__main__":
    main()
