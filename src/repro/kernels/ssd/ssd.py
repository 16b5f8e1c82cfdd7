"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk quadratic block.

The chunkwise SSD algorithm's hot spot is the per-chunk quadratic form
(scores = C B^T masked by the decay kernel L) — an attention-shaped matmul
that belongs on the MXU.  Grid = (B*NH, n_chunks); each program holds one
(Q, HD) x-tile, one (Q, DS) B/C tile in VMEM and emits:

  y_intra (Q, HD)   the within-chunk output contribution
  state   (HD, DS)  this chunk's local state contribution
  cs      (Q,)      cumulative log-decay (host combines chunks: the tiny
                    inter-chunk recurrence + cross-chunk y term stay in jnp)

The cumulative sum is a masked (Q, Q) reduction, not a serial scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, da_col_ref, da_row_ref, b_ref, c_ref,
                y_ref, state_ref, cs_ref, *, chunk):
    x = x_ref[0, 0].astype(jnp.float32)            # (Q, HD)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (Q, 1)
    da_col = da_col_ref[0, 0].astype(jnp.float32)  # (Q, 1)
    da_row = da_row_ref[0, 0].astype(jnp.float32)  # (1, Q)
    b = b_ref[0, 0].astype(jnp.float32)            # (Q, DS)
    c = c_ref[0, 0].astype(jnp.float32)            # (Q, DS)

    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = j <= i
    # inclusive cumsum of dA, once as a column and once as a row, so the
    # decay kernel is a broadcast difference with no in-kernel transpose
    cs_col = jnp.sum(jnp.where(causal, da_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(i <= j, da_col, 0.0), axis=0, keepdims=True)
    lmat = jnp.where(causal, jnp.exp(cs_col - cs_row), 0.0)  # decay j -> i

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))  # (Q, Q)
    y_ref[0, 0] = jax.lax.dot(scores * lmat, x * dt).astype(y_ref.dtype)

    total = jnp.sum(da_row, axis=1, keepdims=True)          # (1, 1)
    w = dt * jnp.exp(total - cs_col)                         # (Q, 1)
    state = jax.lax.dot_general(x * w, b,
                                (((0,), (0,)), ((), ())))  # (HD, DS)
    state_ref[0, 0] = state.astype(state_ref.dtype)
    cs_ref[0, 0] = cs_col.astype(cs_ref.dtype)


def ssd_intra(xh, dt, dA, B_, C_, *, chunk, interpret=False):
    """xh: (BH, n, Q, HD); dt, dA: (BH, n, Q); B_, C_: (G, n, Q, DS) where
    BH = B * NH and G = B (B/C shared across heads; index map bh -> bh // NH
    handled by the caller reshaping, here BH == G * NH).  Returns y_intra
    (BH, n, Q, HD), state (BH, n, HD, DS) and cs (BH, n, Q)."""
    bh, n, q, hd = xh.shape
    g = B_.shape[0]
    nh = bh // g
    ds = B_.shape[-1]

    # per-position vectors enter as (Q, 1) columns or a (1, Q) row: Mosaic
    # tiles the last two block dims, which a bare (Q,) slice cannot satisfy
    col = (1, 1, q, 1)
    kernel = functools.partial(_ssd_kernel, chunk=q)
    y, state, cs = pl.pallas_call(
        kernel,
        grid=(bh, n),
        in_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec(col, lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec(col, lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q, ds), lambda i, j: (i // nh, j, 0, 0)),
            pl.BlockSpec((1, 1, q, ds), lambda i, j: (i // nh, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec(col, lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, q, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, hd, ds), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dt[..., None], dA[..., None], dA[:, :, None, :], B_, C_)
    return y, state, cs[..., 0]
