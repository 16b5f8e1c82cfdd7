"""Unified decoder-only LM driver for dense / moe / ssm / hybrid / vlm.

One scan-over-layers driver (with the paper's remat C3 applied to the scanned
body) serving:

  dense   granite-34b, minitron-8b, command-r-plus-104b, qwen1.5-0.5b, paper models
  moe     phi3.5-moe-42b (top-2), dbrx-132b (top-4)
  ssm     mamba2-130m (attention-free SSD)
  hybrid  hymba-1.5b (parallel attention+SSM heads, meta tokens)
  vlm     qwen2-vl-7b backbone (vision-embedding stub + M-RoPE)

``forward`` returns (logits, aux); ``decode_step`` runs one token against a
donated cache pytree whose content depends on the family (kv and/or ssm).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig, dtype_of
from repro.core.remat import maybe_remat
from repro.models import layers as L
from repro.models import mamba2, moe as moe_mod
from repro.models import transformer as T
from repro.models.hymba import apply_hymba_block, hymba_block_specs
from repro.param import spec
from repro.sharding import constrain


# ----------------------------------------------------------------------------
# Specs
# ----------------------------------------------------------------------------
def block_specs(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.family in ("dense", "vlm"):
        return T.block_specs(cfg)
    if cfg.family == "moe":
        return {
            "ln1": L.norm_specs(cfg.d_model, cfg.norm_variant),
            "attn": T.attn_specs(cfg),
            "ln2": L.norm_specs(cfg.d_model, cfg.norm_variant),
            "moe": moe_mod.moe_specs(cfg),
        }
    if cfg.family == "ssm":
        return {
            "ln1": L.norm_specs(cfg.d_model, cfg.norm_variant),
            "mamba": mamba2.mamba_specs(cfg),
        }
    if cfg.family == "hybrid":
        return hymba_block_specs(cfg)
    raise ValueError(f"lm.py does not drive family {cfg.family!r}")


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s = {
        "embed": L.embed_specs(cfg.vocab_size, cfg.d_model, cfg.tie_embeddings,
                               cfg.padded_vocab),
        "blocks": T.stack_specs(block_specs(cfg), cfg.n_layers),
        "ln_f": L.norm_specs(cfg.d_model, cfg.norm_variant),
    }
    if cfg.pos_variant == "learned":
        s["wpe"] = spec((cfg.max_seq_len, cfg.d_model), (None, "embed"),
                        init="embed")
    if cfg.n_meta_tokens > 0:
        s["meta"] = spec((cfg.n_meta_tokens, cfg.d_model), (None, "embed"),
                         init="embed")
    return s


# ----------------------------------------------------------------------------
# Input embedding (+ vision stub merge, + meta tokens)
# ----------------------------------------------------------------------------
def embed_input(params, batch, cfg: ModelConfig, tcfg: TrainConfig):
    cd = dtype_of(tcfg.compute_dtype)
    x = L.embed_tokens(params["embed"], batch["tokens"], cd)
    if cfg.family == "vlm" and "vision" in batch:
        nv = min(batch["vision"].shape[1], x.shape[1])
        x = jnp.concatenate([batch["vision"].astype(cd)[:, :nv], x[:, nv:]],
                            axis=1)
    if cfg.n_meta_tokens > 0:
        meta = jnp.broadcast_to(params["meta"].astype(cd)[None],
                                (x.shape[0],) + params["meta"].shape)
        x = jnp.concatenate([meta, x], axis=1)
    if cfg.pos_variant == "learned":
        x = x + params["wpe"].astype(cd)[None, :x.shape[1]]
    return x


def _positions(cfg: ModelConfig, b: int, s: int):
    if cfg.pos_variant == "mrope":
        return L.mrope_positions(b, s, cfg.n_vision_tokens)
    from repro.core.attention import default_positions
    return default_positions(b, s)


# ----------------------------------------------------------------------------
# Forward (teacher-forced)
# ----------------------------------------------------------------------------
def forward(params, batch, cfg: ModelConfig, tcfg: TrainConfig):
    x = embed_input(params, batch, cfg, tcfg)
    b, s_total, _ = x.shape
    x = constrain(x, ("batch", "seq", "act_embed"), preset=tcfg.shard_preset)
    positions = _positions(cfg, b, s_total)
    windows = T.layer_windows(cfg)
    # full-attention configs carry an all-zero per-layer windows array; the
    # scanned entry arrives as a traced scalar, so pass the zero statically
    # instead — kernel impls (flash) specialize their grid on the window
    full_attn = cfg.sliding_window <= 0
    fam = cfg.family
    bspecs = block_specs(cfg)
    from repro.sharding import constrain_params

    def body(carry, layer):
        x, aux = carry
        if fam == "ssm":
            layer = constrain_params(layer, bspecs, tcfg.shard_preset)
        else:
            layer = (constrain_params(layer[0], bspecs, tcfg.shard_preset),
                     ) + tuple(layer[1:])
        if fam in ("dense", "vlm"):
            lp, win = layer
            x, _ = T.apply_block(lp, x, cfg, tcfg, positions=positions,
                                 window=0 if full_attn else win)
        elif fam == "moe":
            lp, win = layer
            x, _, a = moe_mod.apply_moe_block(
                lp, x, cfg, tcfg, positions=positions,
                window=0 if full_attn else win)
            aux = aux + a
        elif fam == "ssm":
            lp = layer
            h, _ = mamba2.apply_mamba(
                lp["mamba"], L.apply_norm(lp["ln1"], x, cfg.norm_variant),
                cfg, tcfg)
            x = x + h
            x = constrain(x, ("batch", "seq", "act_embed"),
                          preset=tcfg.shard_preset)
        elif fam == "hybrid":
            lp, win = layer
            x, _, _ = apply_hymba_block(lp, x, cfg, tcfg, positions=positions,
                                        window=0 if full_attn else win)
        return (x, aux), None

    body = maybe_remat(body, tcfg.remat_policy)
    xs = params["blocks"] if fam == "ssm" else (params["blocks"], windows)
    aux0 = jnp.zeros((), jnp.float32)
    if tcfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), xs)
    else:
        aux = aux0
        for i in range(cfg.n_layers):
            layer = jax.tree.map(lambda a: a[i], xs)
            (x, aux), _ = body((x, aux), layer)

    return head_logits(params, x, cfg), aux / max(cfg.n_layers, 1)


def head_logits(params, x, cfg: ModelConfig):
    """The final norm and the unembedding of the last block's output."""
    with jax.named_scope("lm_head"):
        if cfg.n_meta_tokens > 0:
            x = x[:, cfg.n_meta_tokens:]
        x = L.apply_norm(params["ln_f"], x, cfg.norm_variant)
        return L.unembed(params["embed"], x.astype(jnp.float32),
                         cfg.tie_embeddings, cfg.logit_softcap,
                         cfg.vocab_size)


def loss_fn(params, batch, cfg: ModelConfig, tcfg: TrainConfig):
    logits, aux = forward(params, batch, cfg, tcfg)
    loss, metrics = T.cross_entropy(logits, batch["labels"])
    metrics["aux_loss"] = aux
    return loss + aux, metrics


# ----------------------------------------------------------------------------
# Layer program (layer-streamed fwd/bwd; repro/core/stream.py)
# ----------------------------------------------------------------------------
class LayerProgram(NamedTuple):
    """Jitted per-stage entry points for the two-sweep streamed driver.

    The monolithic ``loss_fn`` above is re-expressed as an explicit program
    over a head tree (embed/ln_f/wpe/meta) and L single-block trees, so the
    driver can pull one block's params through the offload window at a time:

      embed(head, batch) -> x0
      block(bp, x, window, positions) -> (x, aux)        one transformer block
      block_vjp(bp, x, window, positions, dy, daux)
          -> (dblock, dx)                                recomputes the block
      head_vjp(head, xL, batch, aux_sum)
          -> (loss, metrics, dhead, dxL, daux)           loss + its VJP
      embed_vjp(head, batch, dx0) -> dhead               embed contribution
      head_loss(head, xL, batch, aux_sum)
          -> (loss, metrics)                             eval / loss-only
      positions(b, s) -> position ids for block calls

    When ``tcfg.lora_rank > 0`` the program is built in PEFT mode: every
    entry point takes the (tiny, memory-resident) adapter sub-tree alongside
    the frozen base tree, ``merge_lora`` is applied per block *inside* the
    jit, and the VJPs differentiate with respect to the adapter only — the
    cotangents returned alongside the activation cotangent are adapter
    cotangents, and the base segments are never written.  With
    ``tcfg.base_quant`` the base arguments arrive *encoded* — a
    (codes_tree, scales_tree) pair of int8 codes + per-channel scales — and
    are dequantized as the first op inside each jitted entry point, so fp32
    base weights exist one block at a time, only as XLA transients:

      embed(head, hlora, batch) -> x0
      block(bp, blora, x, window, positions) -> (x, aux)
      block_vjp(bp, blora, x, window, positions, dy, daux) -> (dblora, dx)
      head_vjp(head, hlora, xL, batch, aux_sum)
          -> (loss, metrics, dhlora, dxL, daux)
      embed_vjp(head, hlora, batch, dx0) -> dhlora
      head_loss(head, hlora, xL, batch, aux_sum) -> (loss, metrics)

    Per-step loss/grads match the in-memory jit path up to re-association
    noise (equivalence-tested at 1e-5 on the smoke configs).
    """
    embed: Any
    block: Any
    block_vjp: Any
    head_vjp: Any
    embed_vjp: Any
    head_loss: Any
    positions: Any
    lora: bool = False


def make_layer_program(cfg: ModelConfig, tcfg: TrainConfig) -> LayerProgram:
    """Build the per-layer apply/VJP entry points (all jitted once; every
    block shares shapes, so the whole program compiles L-independently)."""
    if cfg.family == "encdec":
        raise ValueError("layer streaming drives decoder-only families; "
                         "encdec (whisper) keeps the in-memory path")
    fam = cfg.family
    bspecs = block_specs(cfg)
    from repro.sharding import constrain_params

    def embed_fn(head, batch):
        x = embed_input(head, batch, cfg, tcfg)
        return constrain(x, ("batch", "seq", "act_embed"),
                         preset=tcfg.shard_preset)

    def block_fn(bp, x, window, positions):
        bp = constrain_params(bp, bspecs, tcfg.shard_preset)
        if cfg.sliding_window <= 0:
            # the driver feeds the per-layer window as a jit argument, so it
            # is traced here; full-attention configs only ever carry zeros —
            # pin the zero statically so the flash kernel can specialize
            window = 0
        aux = jnp.zeros((), jnp.float32)
        if fam in ("dense", "vlm"):
            x, _ = T.apply_block(bp, x, cfg, tcfg, positions=positions,
                                 window=window)
        elif fam == "moe":
            x, _, aux = moe_mod.apply_moe_block(bp, x, cfg, tcfg,
                                                positions=positions,
                                                window=window)
        elif fam == "ssm":
            h, _ = mamba2.apply_mamba(
                bp["mamba"], L.apply_norm(bp["ln1"], x, cfg.norm_variant),
                cfg, tcfg)
            x = x + h
            x = constrain(x, ("batch", "seq", "act_embed"),
                          preset=tcfg.shard_preset)
        else:  # hybrid
            x, _, _ = apply_hymba_block(bp, x, cfg, tcfg, positions=positions,
                                        window=window)
        return x, aux

    # paper C3 on the streamed path too: the per-block VJPs below close over
    # the remat-wrapped body, so a ``dots``/``full`` policy trades block-
    # internal activation residency for recompute exactly as the in-memory
    # scan body does (validated at parse time in launch/train.py)
    block_fn = maybe_remat(block_fn, tcfg.remat_policy)

    def head_fn(head, x, batch, aux_sum):
        logits = head_logits(head, x, cfg)
        loss, metrics = T.cross_entropy(logits, batch["labels"])
        aux = aux_sum / max(cfg.n_layers, 1)
        metrics["aux_loss"] = aux
        return loss + aux, metrics

    def positions(b, s):
        return _positions(cfg, b, s)

    if tcfg.base_quant and tcfg.lora_rank <= 0:
        raise ValueError(
            "--base-quant applies to the frozen base of streamed LoRA "
            "(--lora-rank N with --offload-stream-params); quantized "
            "Full-FT training would fold quantization error back into the "
            "updated weights every step")

    if tcfg.lora_rank > 0:
        from repro.core.lora import merge_lora
        from repro.offload.codecs import dequant_tree
        rank, alpha = tcfg.lora_rank, tcfg.lora_alpha
        # quantized frozen base: the segments stay int8 in the window and
        # arrive here as (codes, scales) pairs; dequant_tree decodes them
        # inside the jit (a no-op on plain trees), so the fp32 base exists
        # per block only, fused into the merge below
        base_of = dequant_tree if tcfg.base_quant else (lambda t: t)

        # merge_lora(train=True) stop-gradients every base leaf, so even
        # though the VJPs below only differentiate the adapter args, the
        # merged weights W' = sg(W) + (alpha/r) A@B are formed inside the
        # jit — one block's merged copy at a time, never a full tree.
        def lora_block_fn(bp, blp, x, window, positions):
            return block_fn(merge_lora(base_of(bp), blp, rank=rank,
                                       alpha=alpha),
                            x, window, positions)

        def lora_embed_fn(head, hlp, batch):
            return embed_fn(merge_lora(base_of(head), hlp, rank=rank,
                                       alpha=alpha),
                            batch)

        def lora_head_fn(head, hlp, x, batch, aux_sum):
            return head_fn(merge_lora(base_of(head), hlp, rank=rank,
                                      alpha=alpha),
                           x, batch, aux_sum)

        # dy is each block's incoming activation cotangent — produced by the
        # previous VJP and never read again, so its buffer is donated to the
        # call (the backward sweep recycles one cotangent-sized buffer
        # instead of allocating L of them)
        @functools.partial(jax.jit, donate_argnums=(5,))
        def lora_block_vjp(bp, blp, x, window, positions, dy, daux):
            _, f_vjp = jax.vjp(
                lambda lp, xx: lora_block_fn(bp, lp, xx, window, positions),
                blp, x)
            dlp, dx = f_vjp((dy, daux))
            return dlp, dx

        @jax.jit
        def lora_head_vjp(head, hlp, x, batch, aux_sum):
            loss, f_vjp, metrics = jax.vjp(
                lambda lp, xx, a: lora_head_fn(head, lp, xx, batch, a),
                hlp, x, aux_sum, has_aux=True)
            dhlp, dx, daux = f_vjp(jnp.ones((), loss.dtype))
            return loss, metrics, dhlp, dx, daux

        @jax.jit
        def lora_embed_vjp(head, hlp, batch, dx):
            _, f_vjp = jax.vjp(lambda lp: lora_embed_fn(head, lp, batch),
                               hlp)
            (dhlp,) = f_vjp(dx)
            return dhlp

        return LayerProgram(embed=jax.jit(lora_embed_fn),
                            block=jax.jit(lora_block_fn),
                            block_vjp=lora_block_vjp,
                            head_vjp=lora_head_vjp,
                            embed_vjp=lora_embed_vjp,
                            head_loss=jax.jit(lora_head_fn),
                            positions=positions, lora=True)

    # dy (the incoming activation cotangent) is consumed exactly once per
    # block — donate its buffer so the backward sweep reuses one
    # cotangent-sized allocation across all L blocks
    @functools.partial(jax.jit, donate_argnums=(4,))
    def block_vjp(bp, x, window, positions, dy, daux):
        _, f_vjp = jax.vjp(
            lambda p, xx: block_fn(p, xx, window, positions), bp, x)
        dp, dx = f_vjp((dy, daux))
        return dp, dx

    @jax.jit
    def head_vjp(head, x, batch, aux_sum):
        loss, f_vjp, metrics = jax.vjp(
            lambda h, xx, a: head_fn(h, xx, batch, a), head, x, aux_sum,
            has_aux=True)
        dhead, dx, daux = f_vjp(jnp.ones((), loss.dtype))
        return loss, metrics, dhead, dx, daux

    @jax.jit
    def embed_vjp(head, batch, dx):
        _, f_vjp = jax.vjp(lambda h: embed_fn(h, batch), head)
        (dhead,) = f_vjp(dx)
        return dhead

    return LayerProgram(embed=jax.jit(embed_fn), block=jax.jit(block_fn),
                        block_vjp=block_vjp, head_vjp=head_vjp,
                        embed_vjp=embed_vjp, head_loss=jax.jit(head_fn),
                        positions=positions)


# ----------------------------------------------------------------------------
# Decode (serve_step)
# ----------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    c: Dict[str, Any] = {}
    if cfg.family != "ssm":
        c["kv"] = T.cache_specs(cfg, batch, max_len, dtype)
    if cfg.family in ("ssm", "hybrid"):
        c["ssm"] = mamba2.mamba_state_specs(cfg, batch, jnp.float32)
    return c


# ----------------------------------------------------------------------------
# Paged KV cache primitives (serving tier)
# ----------------------------------------------------------------------------
# The serving engine (repro/serve) replaces the dense per-slot
# (slots, max_len, ...) cache with a shared pool of fixed-size pages plus a
# per-slot page table (repro/serve/paged.py holds the host-side accounting).
# These two primitives are the device half, called *inside* the jitted
# serving block: gather turns one row's table into a contiguous cache view
# for attention (cache_mode="append" in transformer.apply_attention), and
# scatter writes the fresh k/v of every row through the tables in one
# batched indexed update on the (donated) pool.

def paged_gather(pool, table):
    """Gather one row's pages into a contiguous cache strip.

    pool: (n_pages, page_size, ...); table: (W,) int32 page ids.
    Returns (W * page_size, ...) — position p of the row lives at strip
    offset p (page p // page_size, slot p % page_size).  Table entries that
    point at the sentinel page 0 yield garbage rows; the caller masks them
    by position.
    """
    g = pool[table]                                   # (W, page_size, ...)
    return g.reshape((g.shape[0] * g.shape[1],) + g.shape[2:])


def paged_scatter(pool, tables, index, vals):
    """Write every row's fresh k/v slab into its pages.

    pool: (n_pages, page_size, ...) (donated by the caller's jit);
    tables: (R, W) int32; index: (R,) write heads; vals: (R, S, ...).
    Row r position index[r] + t routes to page tables[r, pos // page_size]
    offset pos % page_size.  Rows the caller masked out (table row all
    sentinel) land in page 0, which no request owns.
    """
    psz = pool.shape[1]
    s = vals.shape[1]
    pos = index[:, None] + jnp.arange(s, dtype=jnp.int32)[None]    # (R, S)
    pid = jnp.take_along_axis(tables, pos // psz, axis=1)          # (R, S)
    return pool.at[pid, pos % psz].set(vals.astype(pool.dtype))


def decode_step(params, cache, tokens, index, cfg: ModelConfig,
                tcfg: TrainConfig):
    """tokens: (B, S); index: scalar int32 tokens already cached.

    S == 1 is one autoregressive decode step.  S > 1 is the chunked-prefill
    entry point: one jitted call pushes a slab of S prompt tokens through the
    cache (the attention mask already hides kv positions past the write head,
    and the SSM state path scans the slab token-by-token inside the jit), so
    filling a P-token prompt costs ceil(P/S) dispatches instead of P while
    matching step-wise decode numerics exactly.

    Returns (logits (B, vocab) at the *last* slab position, new_cache)."""
    cd = dtype_of(tcfg.compute_dtype)
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cd)
    if cfg.pos_variant == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["wpe"].astype(cd),
            jnp.minimum(index, cfg.max_seq_len - s), s, axis=0)[None]
    pos = index + jnp.arange(s, dtype=jnp.int32)
    if cfg.pos_variant == "mrope":
        positions = jnp.broadcast_to(pos[None, None], (b, 3, s))
    else:
        positions = jnp.broadcast_to(pos[None], (b, s))
    windows = T.layer_windows(cfg)
    full_attn = cfg.sliding_window <= 0  # see forward(): pin the zero window
    fam = cfg.family
    bspecs = block_specs(cfg)
    from repro.sharding import constrain_params

    def body(x, layer):
        layer = (constrain_params(layer[0], bspecs, tcfg.shard_preset),
                 ) + tuple(layer[1:])
        if fam in ("dense", "vlm", "moe"):
            lp, ck, cv, win = layer
            win = 0 if full_attn else win
            if fam == "moe":
                y, (ck, cv), _ = moe_mod.apply_moe_block(
                    lp, x, cfg, tcfg, positions=positions, window=win,
                    kv_cache=(ck, cv), cache_index=index)
            else:
                y, (ck, cv) = T.apply_block(
                    lp, x, cfg, tcfg, positions=positions, window=win,
                    kv_cache=(ck, cv), cache_index=index)
            return y, (ck, cv)
        if fam == "ssm":
            lp, conv, ssm = layer
            h, st = mamba2.apply_mamba(
                lp["mamba"], L.apply_norm(lp["ln1"], x, cfg.norm_variant),
                cfg, tcfg, state={"conv": conv, "ssm": ssm})
            return x + h, (st["conv"], st["ssm"])
        # hybrid
        lp, ck, cv, conv, ssm, win = layer
        win = 0 if full_attn else win
        y, (ck, cv), st = apply_hymba_block(
            lp, x, cfg, tcfg, positions=positions, window=win,
            kv_cache=(ck, cv), cache_index=index,
            ssm_state={"conv": conv, "ssm": ssm})
        return y, (ck, cv, st["conv"], st["ssm"])

    new_cache = dict(cache)
    if fam in ("dense", "vlm", "moe"):
        xs = (params["blocks"], cache["kv"]["k"], cache["kv"]["v"], windows)
        x, (nk, nv) = jax.lax.scan(body, x, xs)
        new_cache["kv"] = {"k": nk, "v": nv}
    elif fam == "ssm":
        xs = (params["blocks"], cache["ssm"]["conv"], cache["ssm"]["ssm"])
        x, (nconv, nssm) = jax.lax.scan(body, x, xs)
        new_cache["ssm"] = {"conv": nconv, "ssm": nssm}
    else:
        xs = (params["blocks"], cache["kv"]["k"], cache["kv"]["v"],
              cache["ssm"]["conv"], cache["ssm"]["ssm"], windows)
        x, (nk, nv, nconv, nssm) = jax.lax.scan(body, x, xs)
        new_cache["kv"] = {"k": nk, "v": nv}
        new_cache["ssm"] = {"conv": nconv, "ssm": nssm}

    x = L.apply_norm(params["ln_f"], x, cfg.norm_variant)
    logits = L.unembed(params["embed"], x.astype(jnp.float32),
                       cfg.tie_embeddings, cfg.logit_softcap,
                       cfg.vocab_size)
    return logits[:, -1], new_cache
