"""The plain reference: a Qwen2-style decoder in straightforward jax.numpy.

RMSNorm, rotary positions (half split), grouped-query attention with q/k/v
biases and a materialised causal softmax, a SwiGLU MLP and a tied or untied
head.  Every matrix product states its precision: ``HIGHEST`` for float32, so that the
chip does not round its operands to bfloat16; the control runs the same
code with ``dtype=bfloat16``.

It reads weights in the checkpoint layout that ``weights.hf_weights`` makes
and imports nothing of the program.  Training is computed one row at a
time, with each layer recomputed in the backward pass, so that it fits
beside the optimizer state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _prec(dtype):
    return HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def _mm(x, w, dtype):
    return jnp.matmul(x, w.astype(dtype), precision=_prec(dtype))


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """x: (S, H, D); rotation of the two halves of D, as Qwen2 does it."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _layer(c, dtype, x, lw, positions):
    """One decoder layer over one sequence x: (S, d)."""
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    s, d = x.shape
    hd = d // h if not c.get("head_dim") else c["head_dim"]
    eps = c["rms_norm_eps"]
    a = rms_norm(x, lw["input_layernorm"], eps)
    q, k, v = (_mm(a, lw[n], dtype) for n in ("q_proj", "k_proj", "v_proj"))
    if "q_bias" in lw:
        q = q + lw["q_bias"].astype(dtype)
        k = k + lw["k_bias"].astype(dtype)
        v = v + lw["v_bias"].astype(dtype)
    q = rope(q.reshape(s, h, hd), positions, c["rope_theta"])
    k = rope(k.reshape(s, kvh, hd), positions, c["rope_theta"])
    v = v.reshape(s, kvh, hd)
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=_prec(dtype))
    sc = sc.astype(jnp.float32) / np.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(dtype)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=_prec(dtype))
    x = x + _mm(o.reshape(s, h * hd), lw["o_proj"], dtype)
    b = rms_norm(x, lw["post_attention_layernorm"], eps)
    g = _mm(b, lw["gate_proj"], dtype)
    u = _mm(b, lw["up_proj"], dtype)
    return x + _mm(jax.nn.silu(g) * u, lw["down_proj"], dtype)


LAYER_KEYS = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
              "q_bias", "k_bias", "v_bias", "post_attention_layernorm",
              "gate_proj", "up_proj", "down_proj")


def hidden(w, c, tokens, dtype=jnp.float32, remat=False):
    """Final normed hidden states of one sequence: (S, d)."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = w["embed_tokens"].astype(dtype)[tokens]
    layers = {k: w[k] for k in LAYER_KEYS if k in w}
    body = partial(_layer, c, dtype)
    if remat:
        body = jax.checkpoint(body)

    def step(x, lw):
        return body(x, lw, positions), None

    x, _ = jax.lax.scan(step, x, layers)
    return rms_norm(x, w["norm"], c["rms_norm_eps"])


def head(w, x):
    """Logits in float32 for hidden states x: (n, d)."""
    dtype = x.dtype
    table = w["lm_head"] if "lm_head" in w else w["embed_tokens"].T
    return jnp.matmul(x, table.astype(dtype), precision=_prec(dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# training: loss, gradient and AdamW, a row at a time
# ---------------------------------------------------------------------------
def _row_nll(w, c, tokens, labels, dtype):
    x = hidden(w, c, tokens, dtype, remat=True)
    logits = head(w, x)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                               axis=-1)[:, 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask), jnp.sum(mask)


@partial(jax.jit, static_argnums=(2, 5), donate_argnums=(1,))
def _row_grad(w, gsum, c_items, tokens, labels, dtype):
    """Adds the gradient of the summed NLL of one row to ``gsum`` (donated,
    so the sum is kept in place)."""
    c = dict(c_items)
    (nll, n), g = jax.value_and_grad(
        lambda w: _row_nll(w, c, tokens, labels, dtype), has_aux=True)(w)
    return nll, n, jax.tree.map(jnp.add, gsum, g)


@jax.jit
def _zeros(w):
    return jax.tree.map(jnp.zeros_like, w)


def lr_at(step: int, hp: dict) -> float:
    """The learning rate of step ``step`` (0-based): linear warm-up, then
    cosine decay to a tenth, as the configuration states it."""
    warm, total, base = hp["warmup_steps"], hp["total_steps"], \
        hp["learning_rate"]
    lr = base * min(step / max(warm, 1), 1.0)
    if hp["schedule"] == "constant":
        return lr
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    if hp["schedule"] == "cosine":
        return lr * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * frac)))
    return lr * (1.0 - 0.9 * frac)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw(w, g, m, v, gsum_scale, lr, count, b1, b2, eps, wd, clip):
    g = jax.tree.map(lambda x: x * gsum_scale, g)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.where(clip > 0, jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9)),
                      1.0)
    g = jax.tree.map(lambda x: x * scale, g)
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    w = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                  + wd * p), w, m, v)
    return w, m, v, g


def train(w, c, batches, hp, first_grad, dtype=jnp.float32):
    """AdamW steps over ``batches`` (each {"tokens", "labels"} of shape
    (B, S), numpy) from the float32 weights ``w`` (consumed).

    ``first_grad`` is called once on the first step's clipped gradient, in
    the checkpoint layout, and what it returns is handed back.  Returns
    (losses, first_grad's result, the weights after the steps)."""
    items = _items(c)
    m = _zeros(w)
    v = _zeros(w)
    losses, first = [], None
    for step, b in enumerate(batches):
        tok, lab = np.asarray(b["tokens"]), np.asarray(b["labels"])
        gsum, nll, n = _zeros(w), 0.0, 0.0
        for t, lb in zip(tok, lab):
            l_, n_, gsum = _row_grad(w, gsum, items, jnp.asarray(t),
                                     jnp.asarray(lb), jnp.dtype(dtype).name)
            nll += float(l_)
            n += float(n_)
        losses.append(nll / max(n, 1.0))
        w, m, v, g = _adamw(
            w, gsum, m, v, jnp.float32(1.0 / max(n, 1.0)),
            jnp.float32(lr_at(step, hp)), jnp.float32(step + 1),
            jnp.float32(hp["beta1"]), jnp.float32(hp["beta2"]),
            jnp.float32(hp["eps"]), jnp.float32(hp["weight_decay"]),
            jnp.float32(hp["grad_clip"]))
        del gsum
        if step == 0:
            first = first_grad(g)
        del g
    return losses, first, w


def _items(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool, str))))
