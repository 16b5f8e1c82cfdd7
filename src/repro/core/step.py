"""Composed train / eval / serve steps (paper Application layer).

``make_train_step`` assembles the full resource-aware runtime:
  C1 parameter sharding   — in/out shardings from the rule preset
  C2 grad accumulation    — lax.scan micro-batching (+ optional bf16 grad compression)
  C3 activation ckpt      — remat policy inside the model scan
  C4 ME attention         — TrainConfig.attention_impl
  C6 Full-FT vs LoRA      — lora=True trains only the adapter tree

State pytrees:
  Full-FT: {"params", "opt", "step"}
  LoRA:    {"base", "lora", "opt", "step"}   (opt covers only the adapter)
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig, dtype_of
from repro.core.accumulate import value_and_grad_accumulated
from repro.core.lora import lora_specs, merge_lora
from repro.models import registry
from repro.optim import adamw_init, adamw_update, clip_by_global_norm
from repro.optim.schedule import lr_schedule
from repro.param import init_params


# ----------------------------------------------------------------------------
# State construction
# ----------------------------------------------------------------------------
def _lora_specs_checked(specs, cfg: ModelConfig, tcfg: TrainConfig):
    lspecs = lora_specs(specs, tcfg.lora_targets, tcfg.lora_rank)
    if not lspecs:
        raise ValueError(
            f"lora_targets {tcfg.lora_targets!r} match no leaves of "
            f"{cfg.name} ({cfg.family} family) — the adapter would be "
            "empty and train nothing; pick >=2-D leaf names from the "
            "model's param specs (e.g. wq,wk,wv,wo for attention, "
            "w_x,w_out for the ssm family)")
    return lspecs


def init_adapter_state(rng, cfg: ModelConfig, tcfg: TrainConfig):
    """The adapter-only slice of ``init_state``'s LoRA tree — identical
    {"lora", "opt", "step"} leaves (same key folding) without materializing
    the base.  Used when the frozen base segments already exist on disk."""
    lspecs = _lora_specs_checked(registry.param_specs(cfg), cfg, tcfg)
    lora = init_params(jax.random.fold_in(rng, 1), lspecs,
                       dtype=jnp.float32)
    return {"lora": lora, "opt": adamw_init(lora),
            "step": jnp.zeros((), jnp.int32)}


def init_state(rng, cfg: ModelConfig, tcfg: TrainConfig):
    specs = registry.param_specs(cfg)
    pd = dtype_of(tcfg.param_dtype)
    params = init_params(rng, specs, dtype=pd)
    if tcfg.lora_rank > 0:
        return {"base": params, **init_adapter_state(rng, cfg, tcfg)}
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


def state_specs(cfg: ModelConfig, tcfg: TrainConfig):
    """ParamSpec pytree for the full state (for shardings / abstract AOT)."""
    from repro.param import ParamSpec, spec, tree_map_specs
    specs = registry.param_specs(cfg)
    pd = dtype_of(tcfg.param_dtype)
    pspecs = tree_map_specs(
        lambda s: ParamSpec(s.shape, pd, s.axes, s.init, s.scale), specs)

    def f32(s_tree):
        return tree_map_specs(
            lambda s: ParamSpec(s.shape, jnp.float32, s.axes, "zeros", 1.0),
            s_tree)

    scalar = spec((), (), init="zeros", dtype=jnp.int32)
    if tcfg.lora_rank > 0:
        lspecs = lora_specs(specs, tcfg.lora_targets, tcfg.lora_rank)
        lspecs = f32(lspecs)
        return {"base": pspecs, "lora": lspecs,
                "opt": {"m": f32(lspecs), "v": f32(lspecs), "count": scalar},
                "step": scalar}
    return {"params": pspecs,
            "opt": {"m": f32(pspecs), "v": f32(pspecs), "count": scalar},
            "step": scalar}


# ----------------------------------------------------------------------------
# Train step
# ----------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    model_loss = registry.loss_fn(cfg)
    reduce_dtype = (dtype_of(tcfg.grad_reduce_dtype)
                    if tcfg.grad_reduce_dtype else None)

    def train_step(state, batch):
        lora_mode = "lora" in state

        def loss_of(trainable, mb):
            if lora_mode:
                params = merge_lora(state["base"], trainable,
                                    rank=tcfg.lora_rank, alpha=tcfg.lora_alpha)
            else:
                params = trainable
            return model_loss(params, mb, cfg, tcfg)

        trainable = state["lora"] if lora_mode else state["params"]
        loss, metrics, grads = value_and_grad_accumulated(
            loss_of, trainable, batch, tcfg.microbatches, reduce_dtype)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
            lr = lr_schedule(state["step"], base_lr=tcfg.learning_rate,
                             warmup_steps=tcfg.warmup_steps,
                             total_steps=tcfg.total_steps, kind=tcfg.schedule)
            new_trainable, new_opt = adamw_update(
                grads, state["opt"], trainable, lr=lr, beta1=tcfg.beta1,
                beta2=tcfg.beta2, eps=tcfg.eps,
                weight_decay=tcfg.weight_decay)
        new_state = dict(state)
        if lora_mode:
            new_state["lora"] = new_trainable
        else:
            new_state["params"] = new_trainable
        new_state["opt"] = new_opt
        new_state["step"] = state["step"] + 1
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = lr
        return new_state, metrics

    return train_step


def make_grad_step(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    """Forward/backward only — for the segment-wise offload path (C1 phone
    realization), where the optimizer update runs *outside* jit, streaming
    (p, m, v) segments through an LRU window (see repro/offload/).

    Returns ``grad_step(params, batch) -> (loss, metrics, grads)`` with
    gradients already clipped (same order as ``make_train_step``).
    Full-FT only: LoRA state is adapter-sized and never needs offload.
    """
    if tcfg.lora_rank > 0:
        raise ValueError(
            "byte-balanced optimizer offload supports Full-FT only (the "
            "adapter's optimizer state is tiny); for PEFT on a phone budget "
            "combine --lora-rank with --offload-stream-params (frozen "
            "streamed base + in-memory adapter)")
    model_loss = registry.loss_fn(cfg)
    reduce_dtype = (dtype_of(tcfg.grad_reduce_dtype)
                    if tcfg.grad_reduce_dtype else None)

    def grad_step(params, batch):
        def loss_of(p, mb):
            return model_loss(p, mb, cfg, tcfg)

        loss, metrics, grads = value_and_grad_accumulated(
            loss_of, params, batch, tcfg.microbatches, reduce_dtype)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return loss, metrics, grads

    return grad_step


def make_stream_step(cfg: ModelConfig, tcfg: TrainConfig, lstate,
                     grad_dir: str, adapter=None) -> Callable:
    """Layer-streamed train step (C1 phone realization, full depth): fwd/bwd
    pages block params through the offload window (repro/core/stream.py)
    instead of materializing the whole tree, then streams the AdamW update.

    ``lstate`` is a ``LayerStreamedState``; ``grad_dir`` holds the gradient
    scratch segments.  Returns ``step_fn(batch, step) -> (loss, metrics)``.

    With ``tcfg.lora_rank > 0`` (C6 over the streamed base) ``lstate`` must
    be the frozen param-only layout and ``adapter`` the in-memory trainable
    state {"lora", "opt", "step"}; ``grad_dir`` is unused (adapter grads
    accumulate in memory).
    """
    from repro.core.stream import StreamedTrainStep
    return StreamedTrainStep(cfg, tcfg, lstate, grad_dir, adapter=adapter)


def make_eval_step(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    model_loss = registry.loss_fn(cfg)

    def eval_step(state, batch):
        if "lora" in state:
            params = merge_lora(state["base"], state["lora"],
                                rank=tcfg.lora_rank, alpha=tcfg.lora_alpha,
                                train=False)
        else:
            params = state["params"]
        loss, metrics = model_loss(params, batch, cfg, tcfg)
        return metrics

    return eval_step


def make_serve_step(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    decode = registry.decode_fn(cfg)

    def serve_step(params, cache, tokens, index):
        return decode(params, cache, tokens, index, cfg, tcfg)

    return serve_step
