"""Memory-efficient exact attention (paper §4.1.4, C4).

Three interchangeable implementations of *exact* softmax attention:

  naive      materializes the [B, H, Sq, Skv] score matrix — the paper's
             "unoptimized" baseline (①-off in the optimization-chain study).
  streaming  chunked online-softmax over KV blocks via ``lax.scan`` — the
             paper's row-streaming C++ operator re-blocked for vector units.
             Never materializes more than [B, chunk // 2, H, chunk] scores.
             Its own backward (``jax.custom_vjp``) saves only q, k, v, the
             positions, the output and each row's softmax max and
             denominator, and recomputes each (query block, kv chunk)
             block's probabilities from them, so its intermediates keep the
             forward's bound.  The default training path; also used for CPU
             tests and for the AOT dry-run lowering.
  flash      Pallas TPU kernel (kernels/flash_attention) — the TPU-native
             adaptation: 128-aligned query-block x key-block tiles staged
             through VMEM for the MXU, same online-softmax algorithm, and a
             recompute backward exactly as §4.1.4 prescribes.

All support GQA/MQA (grouped KV heads), causal masking, sliding windows,
padding masks via position sentinels, and decode (Sq=1 against a long cache).

Shapes: q (B, Sq, H, D); k, v (B, Skv, KVH, D); H % KVH == 0.
Positions: q_pos (B, Sq) int32 absolute positions; kv_pos (B, Skv).  A kv
position >= SENTINEL marks padding.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

SENTINEL = jnp.iinfo(jnp.int32).max // 2
NEG_INF = -1e30


def default_positions(batch: int, seq: int, offset=0):
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    return jnp.broadcast_to(pos, (batch, seq)).astype(jnp.int32)


def _mask(q_pos, kv_pos, causal: bool, window):
    """(B, Sq, Skv) bool — True = attend.

    ``window`` may be a python int or a traced scalar (hybrid models select
    full-vs-sliding per scanned layer); window <= 0 means no windowing.
    """
    valid = (kv_pos < SENTINEL)[:, None, :]
    m = valid
    if causal:
        m = m & (q_pos[:, :, None] >= kv_pos[:, None, :])
    if isinstance(window, int):
        if window > 0:
            m = m & (q_pos[:, :, None] - kv_pos[:, None, :] < window)
    else:
        wm = (q_pos[:, :, None] - kv_pos[:, None, :] < jnp.maximum(window, 1))
        m = m & (wm | (window <= 0))
    return m


def _group(q, kvh: int):
    b, sq, h, d = q.shape
    return q.reshape(b, sq, kvh, h // kvh, d)


def attention(q, k, v, *, q_pos=None, kv_pos=None, causal=True, window=0,
              impl="streaming", chunk=512, interpret=False):
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    if q_pos is None:
        q_pos = default_positions(b, sq, offset=skv - sq)
    if kv_pos is None:
        kv_pos = default_positions(b, skv)

    if impl == "naive":
        return _naive(q, k, v, q_pos, kv_pos, causal, window)
    if impl in ("streaming", "ref"):
        # "ref" aliases the streaming path: it is the numerics oracle the
        # flash Pallas kernel is validated against (tests + benches)
        return _streaming(q, k, v, q_pos, kv_pos, causal, window, chunk)
    if impl == "flash":
        if not isinstance(window, int):
            # scanned-layer drivers carry the per-layer sliding window as a
            # traced scalar, but the Pallas grid/skip structure specializes
            # on it — those layers ride the exact streaming oracle instead
            # (full-attention configs pass a static 0 and hit the kernel)
            return _streaming(q, k, v, q_pos, kv_pos, causal, window, chunk)
        from repro.kernels.flash_attention import ops as flash_ops
        # the Pallas kernel has no CPU lowering — interpret mode is the
        # correct (and only) execution path on the CPU backend, so gate it
        # on the backend instead of making every caller thread the flag
        interpret = interpret or jax.default_backend() == "cpu"
        return flash_ops.flash_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
            window=window, interpret=interpret)
    raise ValueError(f"unknown attention impl {impl!r}")


# ----------------------------------------------------------------------------
# naive — the paper's unoptimized baseline (materializes S x S)
# ----------------------------------------------------------------------------
def _naive(q, k, v, q_pos, kv_pos, causal, window):
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    scale = d ** -0.5
    qg = _group(q, kvh)                                   # (B,Sq,KVH,G,D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale     # (B,KVH,G,Sq,Skv)
    m = _mask(q_pos, kv_pos, causal, window)              # (B,Sq,Skv)
    scores = jnp.where(m[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


# ----------------------------------------------------------------------------
# streaming — chunked online softmax (paper C4).  Double-blocked: an outer
# map over query blocks bounds intermediates at O(q_chunk * kv_chunk) scores
# (the TPU re-blocking of the paper's row-at-a-time streaming).  Its own
# recompute backward keeps the same bound when differentiated.
# ----------------------------------------------------------------------------
class _Spec(NamedTuple):
    causal: bool
    chunk: int
    window: int | None      # None: the window is traced and passed along


def _streaming(q, k, v, q_pos, kv_pos, causal, window, chunk):
    if isinstance(window, int):
        return _streaming_vjp(_Spec(causal, chunk, window),
                              q, k, v, q_pos, kv_pos, None)
    return _streaming_vjp(_Spec(causal, chunk, None),
                          q, k, v, q_pos, kv_pos, window)


def _q_blocks(sq: int, chunk: int):
    """(rows per query block, number of blocks)."""
    qc = min(max(chunk // 2, 1), sq)
    return qc, -(-sq // qc)


def _pad_rows(x, n, value=0):
    """Pad axis 1 of ``x`` up to ``n`` rows."""
    if x.shape[1] == n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, n - x.shape[1])
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _streaming_vjp(spec, q, k, v, q_pos, kv_pos, window):
    return _streaming_fwd(spec, q, k, v, q_pos, kv_pos, window)[0]


def _streaming_fwd_rule(spec, q, k, v, q_pos, kv_pos, window):
    out, stats = _streaming_fwd(spec, q, k, v, q_pos, kv_pos, window)
    return out, (q, k, v, q_pos, kv_pos, window, out, stats)


def _streaming_bwd_rule(spec, res, dout):
    with jax.named_scope("streaming_bwd"):
        dq, dk, dv = _streaming_bwd(spec, *res, dout)
    return dq, dk, dv, None, None, None


_streaming_vjp.defvjp(_streaming_fwd_rule, _streaming_bwd_rule)


def _streaming_fwd(spec, q, k, v, q_pos, kv_pos, window):
    """The output and each row's softmax stats: the running max and the
    denominator, each (nq, B, q_chunk, KVH, G), padded rows included."""
    causal, chunk = spec.causal, spec.chunk
    window = spec.window if window is None else window
    b, sq, h, d = q.shape
    qc, nq = _q_blocks(sq, chunk)
    if nq == 1:
        out, mx, denom = _streaming_qblock(q, k, v, q_pos, kv_pos, causal,
                                           window, chunk)
        return out, (mx[None], denom[None])
    q = _pad_rows(q, nq * qc)
    q_pos = _pad_rows(q_pos, nq * qc)
    qs = q.reshape(b, nq, qc, h, d).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(b, nq, qc).transpose(1, 0, 2)

    def one(args):
        qb, pb = args
        return _streaming_qblock(qb, k, v, pb, kv_pos, causal, window, chunk)

    out, mx, denom = jax.lax.map(one, (qs, ps))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nq * qc, h, d)
    return out[:, :sq], (mx, denom)


def _streaming_qblock(q, k, v, q_pos, kv_pos, causal, window, chunk):
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = d ** -0.5
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    k = _pad_rows(k, n_chunks * chunk)
    v = _pad_rows(v, n_chunks * chunk)
    kv_pos = _pad_rows(kv_pos, n_chunks * chunk, SENTINEL)

    qg = _group(q, kvh).astype(jnp.float32) * scale       # (B,Sq,KVH,G,D)
    ks = k.reshape(b, n_chunks, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n_chunks, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    ps = kv_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, inputs):
        acc, mx, denom = carry
        kc, vc, pc = inputs                               # (B,C,KVH,D),(B,C)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kc.astype(jnp.float32))
        m = _mask(q_pos, pc, causal, window)              # (B,Sq,C)
        s = jnp.where(m[:, :, None, None], s, NEG_INF)
        new_mx = jnp.maximum(mx, jnp.max(s, axis=-1))
        corr = jnp.exp(mx - new_mx)
        p = jnp.exp(s - new_mx[..., None])
        denom = denom * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vc.astype(jnp.float32))
        return (acc, new_mx, denom), None

    acc0 = jnp.zeros((b, sq, kvh, g, d), jnp.float32)
    mx0 = jnp.full((b, sq, kvh, g), NEG_INF, jnp.float32)
    dn0 = jnp.zeros((b, sq, kvh, g), jnp.float32)
    (acc, mx, denom), _ = jax.lax.scan(body, (acc0, mx0, dn0), (ks, vs, ps))
    denom = jnp.maximum(denom, 1e-30)
    out = acc / denom[..., None]
    return out.reshape(b, sq, h, d).astype(q.dtype), mx, denom


def _streaming_bwd(spec, q, k, v, q_pos, kv_pos, window, out, stats, dout):
    """Flash-style recompute backward.  Per (query block, kv chunk) it
    rebuilds ``p = exp(s - mx) / denom`` from the saved row stats; mx and
    denom stay apart so that a wholly masked row (mx = NEG_INF, finite)
    gets the forward's p exactly.  Layout: (B, KVH) lead every contraction
    and the group G is folded into the query rows, so each is a batched
    matmul and dK/dV sum over the group inside it."""
    causal = spec.causal
    window = spec.window if window is None else window
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = d ** -0.5
    qc, nq = _q_blocks(sq, spec.chunk)
    chunk = min(spec.chunk, skv)
    nk = -(-skv // chunk)
    f32 = jnp.float32

    def rows(x):        # (B, Sq, H, D) -> (nq, B, KVH, qc*G, D)
        x = _pad_rows(x.astype(f32), nq * qc)
        x = x.reshape(b, nq, qc, kvh, g, d).transpose(1, 0, 3, 2, 4, 5)
        return x.reshape(nq, b, kvh, qc * g, d)

    def cols(x):        # (B, Skv, KVH, D) -> (nk, B, KVH, chunk, D)
        x = _pad_rows(x.astype(f32), nk * chunk)
        return x.reshape(b, nk, chunk, kvh, d).transpose(1, 0, 3, 2, 4)

    def row_stats(x):   # (nq, B, qc, KVH, G) -> (nq, B, KVH, qc*G)
        return x.transpose(0, 1, 3, 2, 4).reshape(nq, b, kvh, qc * g)

    qs, dos = rows(q) * scale, rows(dout)
    delta = jnp.sum(dos * rows(out), axis=-1)             # rowsum(dO * O)
    mx, denom = (row_stats(x) for x in stats)
    # each folded row's position: row r of a block is query r // G
    qps = jnp.repeat(_pad_rows(q_pos, nq * qc), g, axis=1)
    qps = qps.reshape(b, nq, qc * g).transpose(1, 0, 2)
    ks, vs = cols(k), cols(v)
    kps = _pad_rows(kv_pos, nk * chunk, SENTINEL).reshape(b, nk, chunk) \
        .transpose(1, 0, 2)

    def q_block(dkv, xs):
        qb, dob, db, mb, nb, qp = xs    # (B,KVH,R,D), (B,KVH,R), (B,R)
        # p is exactly 0 on a masked key of a row that sees any key; a row
        # that sees none (mx = NEG_INF) spreads p over all keys, but its
        # scores were constants, so dS is 0 there
        live = mb > NEG_INF

        def kv_chunk(dqb, ys):
            kc, vc, kp = ys                               # (B,KVH,C,D),(B,C)
            m = _mask(qp, kp, causal, window)[:, None]    # (B,1,R,C)
            s = jnp.einsum("bkrd,bkcd->bkrc", qb, kc)
            s = jnp.where(m, s, NEG_INF)
            p = jnp.exp(s - mb[..., None]) / nb[..., None]
            dp = jnp.einsum("bkrd,bkcd->bkrc", dob, vc)
            ds = jnp.where(live[..., None], p * (dp - db[..., None]), 0.0)
            dqb = dqb + jnp.einsum("bkrc,bkcd->bkrd", ds, kc)
            dkc = jnp.einsum("bkrc,bkrd->bkcd", ds, qb)
            dvc = jnp.einsum("bkrc,bkrd->bkcd", p, dob)
            return dqb, (dkc, dvc)

        dq0 = jnp.zeros((b, kvh, qc * g, d), f32)
        dqb, (dkb, dvb) = jax.lax.scan(kv_chunk, dq0, (ks, vs, kps))
        return (dkv[0] + dkb, dkv[1] + dvb), dqb

    dkv0 = jnp.zeros((nk, b, kvh, chunk, d), f32)
    (dk, dv), dq = jax.lax.scan(q_block, (dkv0, dkv0),
                                (qs, dos, delta, mx, denom, qps))
    dq = (dq * scale).reshape(nq, b, kvh, qc, g, d).transpose(1, 0, 3, 2, 4, 5)
    dq = dq.reshape(b, nq * qc, h, d)[:, :sq]

    def back(x, like):  # (nk, B, KVH, chunk, D) -> (B, Skv, KVH, D)
        x = x.transpose(1, 0, 3, 2, 4).reshape(b, nk * chunk, kvh, d)
        return x[:, :skv].astype(like.dtype)

    return dq.astype(q.dtype), back(dk, k), back(dv, v)
