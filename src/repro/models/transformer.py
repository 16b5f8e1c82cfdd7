"""Decoder-only GQA transformer LM — the dense workhorse.

Covers: granite-34b, minitron-8b, command-r-plus-104b, qwen1.5-0.5b, and the
paper's own models (gpt2-*, qwen2.5-0.5b, gemma3-*).  MoE / hybrid / enc-dec /
vlm families reuse the attention block defined here.

Layers are stacked on a leading ``layers`` dim and executed with ``lax.scan``
(+ optional remat per paper C3).  Decode runs against a donated KV cache.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig
from repro.core import attention as attn_mod
from repro.core.attention import attention
from repro.models import layers as L
from repro.param import spec, tree_map_specs
from repro.sharding import constrain


# ----------------------------------------------------------------------------
# Specs
# ----------------------------------------------------------------------------
def attn_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    s = {
        "wq": spec((d, qd), ("embed", "heads")),
        "wk": spec((d, kvd), ("embed", "kv_heads")),
        "wv": spec((d, kvd), ("embed", "kv_heads")),
        "wo": spec((qd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = spec((qd,), ("heads",), init="zeros")
        s["bk"] = spec((kvd,), ("kv_heads",), init="zeros")
        s["bv"] = spec((kvd,), ("kv_heads",), init="zeros")
    if cfg.attn_out_bias:
        s["bo"] = spec((d,), ("norm",), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = spec((cfg.head_dim,), ("norm",), init="ones")
        s["k_norm"] = spec((cfg.head_dim,), ("norm",), init="ones")
    return s


def block_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": L.norm_specs(cfg.d_model, cfg.norm_variant),
        "attn": attn_specs(cfg),
        "ln2": L.norm_specs(cfg.d_model, cfg.norm_variant),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_variant, cfg.mlp_bias),
    }


def stack_specs(specs, n: int):
    """Add a leading scanned ``layers`` dim to every leaf spec."""
    return tree_map_specs(
        lambda s: spec((n,) + s.shape, ("layers",) + s.axes, init=s.init,
                       dtype=s.dtype, scale=s.scale), specs)


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s = {
        "embed": L.embed_specs(cfg.vocab_size, cfg.d_model, cfg.tie_embeddings,
                               cfg.padded_vocab),
        "blocks": stack_specs(block_specs(cfg), cfg.n_layers),
        "ln_f": L.norm_specs(cfg.d_model, cfg.norm_variant),
    }
    if cfg.pos_variant == "learned":
        s["wpe"] = spec((cfg.max_seq_len, cfg.d_model), (None, "embed"),
                        init="embed")
    return s


# ----------------------------------------------------------------------------
# Per-layer sliding-window pattern (hybrid full/SWA schedules)
# ----------------------------------------------------------------------------
def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(L,) int32 — per-layer window size; 0 = full attention."""
    if cfg.sliding_window <= 0:
        return jnp.zeros((cfg.n_layers,), jnp.int32)
    idx = jnp.arange(cfg.n_layers)
    if cfg.global_layer_every > 0:
        is_global = (idx % cfg.global_layer_every) == (cfg.global_layer_every - 1)
    else:
        is_global = jnp.zeros((cfg.n_layers,), bool)
    # first and last layers global for hybrid stability (hymba-style)
    if cfg.family == "hybrid":
        is_global = is_global | (idx == 0) | (idx == cfg.n_layers - 1)
    return jnp.where(is_global, 0, cfg.sliding_window).astype(jnp.int32)


# ----------------------------------------------------------------------------
# Attention sub-block (shared by every family with attention)
# ----------------------------------------------------------------------------
def apply_attention(p, x, cfg: ModelConfig, tcfg: TrainConfig, *,
                    positions, window, kv_cache=None, cache_index=None,
                    kv_positions=None, cross_kv=None, cache_mode="update"):
    """x: (B, S, d).  positions: (B, S) (rope/learned) or (B, 3, S) (mrope).

    kv_cache: optional (ck, cv) with shape (B, Smax, KVH, D) — decode mode;
    the new k/v are written at ``cache_index`` and attention runs against the
    full cache.  With ``cache_mode="append"`` the cache is instead a
    *read-only* gathered view (the paged-KV serving path: each row's pages
    gathered into a contiguous strip): positions at or past ``cache_index``
    in the view are stale page contents and are masked out, the fresh k/v
    are appended after the view with their true positions, and
    ``new_kv_cache`` is just ``(k, v)`` — the caller scatters them into its
    page pool (the view is never written).  cross_kv: cross-attention source
    (whisper): either an encoder-output array (B, S_enc, d) to project k/v
    from, or a precomputed (k, v) tuple (decode).  Returns
    (out, new_kv_cache).
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = x.dtype

    q = (x @ p["wq"].astype(cd)).reshape(b, s, h, hd)
    project_kv = cross_kv is None or not isinstance(cross_kv, tuple)
    if cross_kv is None:
        kv_src, skv = x, s
    elif isinstance(cross_kv, tuple):
        k, v = cross_kv
    else:
        kv_src, skv = cross_kv.astype(cd), cross_kv.shape[1]
    if project_kv:
        k = (kv_src @ p["wk"].astype(cd)).reshape(b, skv, kvh, hd)
        v = (kv_src @ p["wv"].astype(cd)).reshape(b, skv, kvh, hd)
    if "bq" in p:
        q = q + p["bq"].astype(cd).reshape(h, hd)
        if project_kv:
            k = k + p["bk"].astype(cd).reshape(kvh, hd)
            v = v + p["bv"].astype(cd).reshape(kvh, hd)
    if cfg.qk_norm:
        q = L.apply_norm({"scale": p["q_norm"]}, q, "rmsnorm")
        if cross_kv is None:
            k = L.apply_norm({"scale": p["k_norm"]}, k, "rmsnorm")

    if cross_kv is None and cfg.pos_variant == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    elif cross_kv is None and cfg.pos_variant == "mrope":
        q = L.apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = L.apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)

    if kv_cache is not None and cache_mode == "append":
        ck, cv = kv_cache
        cap = ck.shape[1]
        # stale view entries (>= the write head) mask to SENTINEL -> their
        # scores are NEG_INF -> exactly zero weight in fp32, so garbage in
        # unwritten page tail bytes can never perturb the output
        view_pos = jnp.arange(cap, dtype=jnp.int32)[None]
        view_pos = jnp.where(view_pos < cache_index, view_pos,
                             attn_mod.SENTINEL)
        fresh_pos = jnp.arange(s, dtype=jnp.int32)[None] + cache_index
        kv_pos = jnp.broadcast_to(
            jnp.concatenate([view_pos, fresh_pos], axis=1), (b, cap + s))
        q_pos = jnp.broadcast_to(fresh_pos, (b, s))
        out = attention(q, jnp.concatenate([ck.astype(cd), k], axis=1),
                        jnp.concatenate([cv.astype(cd), v], axis=1),
                        q_pos=q_pos, kv_pos=kv_pos, causal=True,
                        window=window, impl=tcfg.attention_impl,
                        chunk=tcfg.attn_chunk)
        new_cache = (k, v)
    elif kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, cache_index, 0, 0))
        q_pos = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None] + cache_index, (b, s))
        smax = ck.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(smax, dtype=jnp.int32)[None],
                                  (b, smax))
        # positions beyond the write head are padding
        kv_pos = jnp.where(kv_pos <= cache_index + s - 1, kv_pos,
                           attn_mod.SENTINEL)
        out = attention(q, ck.astype(cd), cv.astype(cd), q_pos=q_pos,
                        kv_pos=kv_pos, causal=True, window=window,
                        impl=tcfg.attention_impl, chunk=tcfg.attn_chunk)
        new_cache = (ck, cv)
    else:
        if cross_kv is not None:
            out = attention(q, k, v, causal=False, window=0,
                            impl=tcfg.attention_impl, chunk=tcfg.attn_chunk)
        else:
            pos1d = positions if positions.ndim == 2 else positions[:, 0]
            out = attention(q, k, v, q_pos=pos1d, kv_pos=pos1d, causal=True,
                            window=window, impl=tcfg.attention_impl,
                            chunk=tcfg.attn_chunk)
        new_cache = None

    out = out.reshape(b, s, h * hd)
    y = out @ p["wo"].astype(cd)
    if "bo" in p:
        y = y + p["bo"].astype(cd)
    return y, new_cache


def apply_block(p, x, cfg, tcfg, *, positions, window, kv_cache=None,
                cache_index=None, cache_mode="update"):
    # the scope names are what a device trace attributes time by; remat
    # recompute and the backward pass inherit them
    with jax.named_scope("attention"):
        h, cache = apply_attention(
            p["attn"], L.apply_norm(p["ln1"], x, cfg.norm_variant), cfg, tcfg,
            positions=positions, window=window, kv_cache=kv_cache,
            cache_index=cache_index, cache_mode=cache_mode)
    x = x + h
    with jax.named_scope("mlp"):
        h = L.apply_mlp(p["mlp"], L.apply_norm(p["ln2"], x, cfg.norm_variant),
                        cfg.mlp_variant)
    x = x + h
    x = constrain(x, ("batch", "seq", "act_embed"), preset=tcfg.shard_preset)
    return x, cache


def cross_entropy(logits, labels):
    """Mean token NLL over labels >= 0; returns (loss, metrics)."""
    with jax.named_scope("lm_head"):
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   safe[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = nll.sum() / denom
        acc = (jnp.argmax(logits, -1) == labels).astype(jnp.float32) * mask
        return loss, {"loss": loss, "ppl_log": loss,
                      "accuracy": acc.sum() / denom, "tokens": mask.sum()}


# ----------------------------------------------------------------------------
# KV-cache specs (decode / serve_step) — used by the unified lm.py driver
# ----------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    kv = spec((cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
              ("layers", "cache_batch", "cache_seq", "cache_heads", None),
              init="zeros", dtype=dtype)
    return {"k": kv, "v": kv}
