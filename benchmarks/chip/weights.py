"""Weights made from the run's seed, on the device.

The benchmark owns its weights: each tensor of a Qwen2-style checkpoint is
drawn from ``fold_in(key, crc32(name))`` in one jitted call, in the
checkpoint's own layout (``hf_weights``).  ``program_weights`` draws the very
same numbers and lays them out as the system under test stores them, inside
one jitted call too, so no second copy is ever live; ``reference.py`` reads
the checkpoint layout.  The two layouts differ only by renaming, the
concatenation of the up and gate projections and zero rows that pad the
vocabulary, so ``to_program`` also maps a gradient or an update of the
reference onto the program's leaves.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.flops import dims


def hf_shapes(c: dict) -> dict:
    """name -> (shape, init) of every tensor, layers stacked first."""
    m = dims(c)
    L, d, q, kv, ff = m["L"], m["d"], m["q"], m["kv"], m["ff"]
    s = {"embed_tokens": ((m["V"], d), "embed"),
         "input_layernorm": ((L, d), "norm"),
         "q_proj": ((L, d, q), "fanin"), "k_proj": ((L, d, kv), "fanin"),
         "v_proj": ((L, d, kv), "fanin"), "o_proj": ((L, q, d), "fanin"),
         "post_attention_layernorm": ((L, d), "norm"),
         "gate_proj": ((L, d, ff), "fanin"), "up_proj": ((L, d, ff), "fanin"),
         "down_proj": ((L, ff, d), "fanin"),
         "norm": ((d,), "norm")}
    if c.get("attention_bias", False):
        s.update({"q_bias": ((L, q), "bias"), "k_bias": ((L, kv), "bias"),
                  "v_bias": ((L, kv), "bias")})
    if not c["tie_word_embeddings"]:
        s["lm_head"] = ((d, m["V"]), "fanin")
    return s


def _draw(key, name, shape, init, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
    z = jax.random.normal(k, shape, jnp.float32)
    if init == "fanin":
        z = z / np.sqrt(shape[-2])
    elif init == "embed":
        z = z * 0.02
    elif init == "bias":
        z = z * 0.1
    elif init == "norm":
        z = 1.0 + 0.1 * z
    return z.astype(dtype)


def seed_key(seed: int):
    """A PRNG key from any whole number, however large."""
    s = np.random.SeedSequence(abs(int(seed))).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0])), int(s[1] >> 1))


def draw_hf(key, cfg_items, dtype):
    """The checkpoint-layout weights (traced; call inside a jit)."""
    c = dict(cfg_items)
    return {n: _draw(key, n, shp, init, dtype)
            for n, (shp, init) in hf_shapes(c).items()}


def frozen(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool, str))))


@partial(jax.jit, static_argnums=(1, 2))
def _hf_jit(key, cfg_items, dtype):
    return draw_hf(key, cfg_items, dtype)


def hf_weights(key, c: dict, dtype=jnp.float32):
    """The checkpoint-layout weights, made on the device in one call."""
    return _hf_jit(key, frozen(c), jnp.dtype(dtype).name)


def to_program(w: dict, c: dict, vocab_rows: int) -> dict:
    """Checkpoint layout -> the program's parameter tree (``models/lm.py``):
    x @ W matrices, up and gate concatenated as ``mlp.wi`` (up first), the
    token table padded with zero rows to ``vocab_rows``.  Linear, so it maps
    gradients and updates too."""
    emb = w["embed_tokens"]
    pad = vocab_rows - emb.shape[0]
    tok = jnp.pad(emb, ((0, pad), (0, 0))) if pad else emb
    attn = {"wq": w["q_proj"], "wk": w["k_proj"], "wv": w["v_proj"],
            "wo": w["o_proj"]}
    if "q_bias" in w:
        attn.update({"bq": w["q_bias"], "bk": w["k_bias"],
                     "bv": w["v_bias"]})
    embed = {"tok": tok}
    if "lm_head" in w:
        embed["unembed"] = jnp.pad(w["lm_head"], ((0, 0), (0, pad)))
    return {"embed": embed,
            "blocks": {"ln1": {"scale": w["input_layernorm"]},
                       "attn": attn,
                       "ln2": {"scale": w["post_attention_layernorm"]},
                       "mlp": {"wi": jnp.concatenate(
                                   [w["up_proj"], w["gate_proj"]], axis=-1),
                               "wo": w["down_proj"]}},
            "ln_f": {"scale": w["norm"]}}


@partial(jax.jit, static_argnums=(1, 2, 3))
def _program_jit(key, cfg_items, dtype, vocab_rows):
    c = dict(cfg_items)
    return to_program(draw_hf(key, cfg_items, dtype), c, vocab_rows)


def program_weights(key, c: dict, vocab_rows: int, dtype=jnp.float32):
    """The same numbers as ``hf_weights``, in the program's tree, made on
    the device in one jitted call."""
    return _program_jit(key, frozen(c), jnp.dtype(dtype).name,
                        int(vocab_rows))

