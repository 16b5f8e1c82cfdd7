"""Intermediate-layer building blocks (paper §3.1 "Intermediate Layer").

Norms, MLP variants, embeddings, RoPE / M-RoPE.  All functions are pure; all
parameters come in as pytrees declared via ParamSpec.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.param import spec


# ----------------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------------
def norm_specs(d_model: int, variant: str):
    if variant == "rmsnorm":
        return {"scale": spec((d_model,), ("norm",), init="ones")}
    return {"scale": spec((d_model,), ("norm",), init="ones"),
            "bias": spec((d_model,), ("norm",), init="zeros")}


def apply_norm(p, x, variant: str, eps: float = 1e-6):
    """Statistics accumulate in fp32 WITHOUT materializing an fp32 copy of x
    (an x.astype(f32) at the scanned-layer entry lets XLA convert the whole
    stacked activation checkpoint to f32 — measured 2x activation memory on
    command-r-104b; see EXPERIMENTS.md §Perf)."""
    d = x.shape[-1]
    if variant == "rmsnorm":
        ms = jnp.einsum("...d,...d->...", x, x,
                        preferred_element_type=jnp.float32) / d
        inv = jax.lax.rsqrt(ms + eps)[..., None].astype(x.dtype)
        return x * inv * p["scale"].astype(x.dtype)
    mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
    ms = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)[..., None] / d
    var = ms - jnp.square(mu)
    inv = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    y = (x - mu.astype(x.dtype)) * inv.astype(x.dtype)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


# ----------------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------------
def mlp_specs(d_model: int, d_ff: int, variant: str, bias: bool = False):
    s = {}
    if variant in ("swiglu", "geglu"):
        s["wi"] = spec((d_model, 2 * d_ff), ("embed", "mlp"))
        s["wo"] = spec((d_ff, d_model), ("mlp", "embed"))
        if bias:
            s["bi"] = spec((2 * d_ff,), ("mlp",), init="zeros")
            s["bo"] = spec((d_model,), ("norm",), init="zeros")
    else:  # gelu | relu2
        s["wi"] = spec((d_model, d_ff), ("embed", "mlp"))
        s["wo"] = spec((d_ff, d_model), ("mlp", "embed"))
        if bias:
            s["bi"] = spec((d_ff,), ("mlp",), init="zeros")
            s["bo"] = spec((d_model,), ("norm",), init="zeros")
    return s


def apply_mlp(p, x, variant: str):
    h = x @ p["wi"].astype(x.dtype)
    if "bi" in p:
        h = h + p["bi"].astype(x.dtype)
    if variant in ("swiglu", "geglu"):
        u, g = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(g) if variant == "swiglu" else jax.nn.gelu(g)
        h = u * act
    elif variant == "relu2":  # minitron/nemotron squared-ReLU
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    y = h @ p["wo"].astype(x.dtype)
    if "bo" in p:
        y = y + p["bo"].astype(x.dtype)
    return y


# ----------------------------------------------------------------------------
# Embeddings (tables padded to padded_vocab for TP divisibility; pad logits
# are masked to -inf so they can never win argmax / affect the softmax)
# ----------------------------------------------------------------------------
def embed_specs(vocab: int, d_model: int, tie: bool, padded_vocab: int = 0):
    pv = padded_vocab or vocab
    s = {"tok": spec((pv, d_model), ("vocab", "embed"), init="embed")}
    if not tie:
        s["unembed"] = spec((d_model, pv), ("embed", "vocab"))
    return s


def embed_tokens(p, tokens, compute_dtype):
    # the table is shared with a tied unembedding: both uses are one layer
    with jax.named_scope("lm_head"):
        return p["tok"].astype(compute_dtype)[tokens]


def unembed(p, x, tie: bool, softcap: float = 0.0, true_vocab: int = 0):
    if tie:
        logits = x @ p["tok"].astype(x.dtype).T
    else:
        logits = x @ p["unembed"].astype(x.dtype)
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    pv = logits.shape[-1]
    if true_vocab and true_vocab < pv:
        mask = jnp.arange(pv) < true_vocab
        logits = jnp.where(mask, logits, -1e30)
    return logits


# ----------------------------------------------------------------------------
# RoPE / M-RoPE
# ----------------------------------------------------------------------------
def _rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, D); positions: broadcastable to (..., S) int32.

    Half-split (GPT-NeoX) rotation: (x1, x2) -> (x1*cos - x2*sin, x2*cos + x1*sin)
    """
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)                       # (d/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, d/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, d/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections: Tuple[int, ...], theta: float = 10000.0):
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, D); positions3: (B, 3, S) — (temporal, height, width) ids.
    The D/2 frequency dims are split into ``sections`` (t, h, w); each section
    takes its angle from the corresponding position stream.
    """
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = _rope_freqs(d, theta)                        # (half,)
    # angle per stream: (B, 3, S, half)
    ang_all = positions3.astype(jnp.float32)[..., None] * freqs
    # select stream per frequency-section via one-hot contraction
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=half)  # (half,)
    onehot = jax.nn.one_hot(sec_id, len(sections), dtype=jnp.float32)   # (half, 3)
    ang = jnp.einsum("bksf,fk->bsf", ang_all, onehot)    # (B, S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_positions(batch: int, seq: int, n_vision: int):
    """Synthetic (t,h,w) position ids: a vision block of n_vision patches laid
    out on a sqrt grid followed by text tokens (all three ids equal)."""
    import math
    side = max(int(math.sqrt(max(n_vision, 1))), 1)
    idx = jnp.arange(seq)
    is_vis = idx < n_vision
    t = jnp.where(is_vis, 0, idx - n_vision + (n_vision > 0) * (side - 1) + 1)
    h = jnp.where(is_vis, idx // side, t)
    w = jnp.where(is_vis, idx % side, t)
    pos = jnp.stack([t, h, w], axis=0).astype(jnp.int32)   # (3, S)
    return jnp.broadcast_to(pos[None], (batch, 3, seq))
