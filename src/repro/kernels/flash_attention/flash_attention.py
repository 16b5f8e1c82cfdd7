"""Pallas TPU flash-attention kernels (paper C4, TPU-native adaptation).

MobileFineTuner §4.1.4 streams *one query row* at a time on a phone CPU and
recomputes row softmax statistics in the backward pass.  On TPU the same
exact-attention algorithm is re-blocked so the MXU sees 128-aligned
(block_q x block_k) tiles staged through VMEM:

  forward   online softmax over kv blocks; scratch carries (m, l, acc) across
            the sequential kv grid dimension; emits O and the LSE.
  backward  recomputes P = exp(S - LSE) blockwise (nothing quadratic is ever
            stored — exactly the paper's recompute strategy) and accumulates
            dQ, dK, dV.

Layouts: q (B, H, Sq, D); k, v (B, KVH, Skv, D); GQA maps q-head h to kv-head
h // (H // KVH) inside the BlockSpec index maps.  Per-row statistics (the
LSE, the backward's delta and the (m, l) scratch) are (.., Sq, 1) columns:
Mosaic tiles the last two block dims by (8, 128) or takes them whole, so a
trailing singleton lane axis is what lets a (block_q,) row slice compile.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask_block(iq, ik, *, block_q, block_k, causal, window, q_offset, kv_len):
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = k_pos < kv_len
    if causal:
        m = m & (q_pos >= k_pos)
    if window > 0:
        m = m & (q_pos - k_pos < window)
    return m


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, window, q_offset,
                kv_len, block_q, block_k, n_kv):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask_block(pl.program_id(2), ik, block_q=block_q, block_k=block_k,
                       causal=causal, window=window, q_offset=q_offset,
                       kv_len=kv_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (BQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _out():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(denom)).astype(lse_ref.dtype)


def flash_fwd(q, k, v, *, scale, causal, window, q_offset, kv_len,
              block_q=128, block_k=128, interpret=False):
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    nq = sq // block_q
    nk = skv // block_k
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, kv_len=kv_len, block_q=block_q, block_k=block_k,
        n_kv=nk)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ----------------------------------------------------------------------------
# Backward: recompute P blockwise from (q, k, LSE) — paper §4.1.4 strategy
# ----------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, window, q_offset, kv_len,
               block_q, block_k, n_kv):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)        # (BQ, 1)
    delta = delta_ref[0, 0].astype(jnp.float32)    # (BQ, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask_block(pl.program_id(2), ik, block_q=block_q, block_k=block_k,
                       causal=causal, window=window, q_offset=q_offset,
                       kv_len=kv_len)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta) * scale
    dq_scr[...] += jax.lax.dot(ds, k)

    @pl.when(ik == n_kv - 1)
    def _out():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                q_offset, kv_len, block_q, block_k, n_q):
    iq = pl.program_id(3)
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = delta_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask_block(iq, ik, block_q=block_q, block_k=block_k,
                       causal=causal, window=window, q_offset=q_offset,
                       kv_len=kv_len)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)                            # (BQ, BK)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta) * scale
    dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(iq == n_q - 1)
    def _out():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd(q, k, v, o, lse, do, *, scale, causal, window, q_offset,
              kv_len, block_q=128, block_k=128, interpret=False):
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    nq, nk = sq // block_q, skv // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, kv_len=kv_len,
                          block_q=block_q, block_k=block_k, n_kv=nk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv computed per q-head then group-summed (GQA)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, kv_len=kv_len,
                          block_q=block_q, block_k=block_k, n_q=nq),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ik, iq: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ik, iq: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((b, h, skv, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, skv, d), q.dtype)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(b, kvh, g, skv, d).sum(axis=2)
    dv = dv_h.reshape(b, kvh, g, skv, d).sum(axis=2)
    return dq, dk, dv
