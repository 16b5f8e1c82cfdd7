"""Per-kernel allclose sweeps (interpret mode) against the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_bwd, flash_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd_chunked_pallas
from repro.kernels.ssd.ref import ssd_ref
from repro.models.mamba2 import ssd_chunked


def _qkv(b, h, kvh, sq, skv, d, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, sq, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, kvh, skv, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, kvh, skv, d), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _lse_ref(q, k, *, causal, window, q_offset):
    """(B, H, Sq, 1) row log-sum-exp of the masked scaled scores."""
    b, h, sq, d = q.shape
    kf = jnp.repeat(k.astype(jnp.float32), h // k.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32), kf) * d ** -0.5
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(k.shape[2])[None, :]
    m = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        m = m & (q_pos >= k_pos)
    if window > 0:
        m = m & (q_pos - k_pos < window)
    return jax.nn.logsumexp(jnp.where(m, s, -1e30), axis=-1, keepdims=True)


FWD_CASES = [
    # b, h, kvh, sq, skv, d, causal, window, bq, bk
    (1, 1, 1, 8, 8, 4, True, 0, 4, 4),
    (2, 4, 2, 16, 16, 8, True, 0, 4, 8),
    (1, 4, 1, 16, 16, 8, True, 5, 8, 4),   # MQA + sliding window
    (2, 2, 2, 12, 20, 8, False, 0, 4, 4),  # cross-attention shape
    (1, 8, 4, 32, 32, 16, True, 0, 16, 16),
]


@pytest.mark.parametrize("case", FWD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_sweep(case, dtype):
    b, h, kvh, sq, skv, d, causal, window, bq, bk = case
    q, k, v = _qkv(b, h, kvh, sq, skv, d, dtype)
    q_off = skv - sq if causal else 0
    o, lse = flash_fwd(q, k, v, scale=d ** -0.5, causal=causal,
                       window=window, q_offset=q_off, kv_len=skv, block_q=bq,
                       block_k=bk, interpret=True)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=causal, window=window,
                        q_offset=q_off)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(o.astype(jnp.float32), ref, rtol=tol, atol=tol)
    # the LSE is a trailing-singleton column, the layout Mosaic accepts
    assert lse.shape == (b, h, sq, 1)
    np.testing.assert_allclose(
        lse, _lse_ref(q, k, causal=causal, window=window, q_offset=q_off),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("case", FWD_CASES[:3])
def test_flash_bwd_sweep(case):
    b, h, kvh, sq, skv, d, causal, window, bq, bk = case
    q, k, v = _qkv(b, h, kvh, sq, skv, d, jnp.float32)
    q_off = skv - sq if causal else 0
    o, lse = flash_fwd(q, k, v, scale=d ** -0.5, causal=causal, window=window,
                       q_offset=q_off, kv_len=skv, block_q=bq, block_k=bk,
                       interpret=True)
    do = jax.random.normal(jax.random.PRNGKey(3), o.shape, jnp.float32)
    dq, dk, dv = flash_bwd(q, k, v, o, lse, do, scale=d ** -0.5,
                           causal=causal, window=window, q_offset=q_off,
                           kv_len=skv, block_q=bq, block_k=bk, interpret=True)

    def f(q_, k_, v_):
        return (attention_ref(q_, k_, v_, causal=causal, window=window,
                              q_offset=q_off) * do).sum()

    gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(dq, gq, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(dk, gk, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(dv, gv, rtol=2e-4, atol=2e-5)


def test_flash_wrapper_padding_and_vjp():
    """Model-layout wrapper: non-multiple seq lengths get padded/cropped."""
    b, sq, h, d = 2, 10, 4, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sq, 2, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sq, 2, d))
    from repro.kernels.flash_attention.ops import flash_attention
    out = flash_attention(q, k, v, causal=True, block_q=4, block_k=4,
                          interpret=True)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(out.transpose(0, 2, 1, 3), ref, rtol=2e-5,
                               atol=2e-5)
    g = jax.grad(lambda x: (flash_attention(x, k, v, causal=True, block_q=4,
                                            block_k=4, interpret=True)
                            ** 2).sum())(q)
    assert bool(jnp.isfinite(g).all())


SSD_CASES = [
    # b, s, nh, hd, ds, chunk
    (1, 8, 1, 4, 4, 4),
    (2, 32, 3, 8, 16, 8),
    (1, 24, 2, 16, 8, 8),   # s not a power of two multiple
    (2, 16, 4, 8, 32, 16),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_pallas_vs_sequential_ref(case, with_init):
    b, s, nh, hd, ds, chunk = case
    xh = jax.random.normal(jax.random.PRNGKey(0), (b, s, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (b, s, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (nh,)) * 0.5)
    B_ = jax.random.normal(jax.random.PRNGKey(3), (b, s, ds))
    C_ = jax.random.normal(jax.random.PRNGKey(4), (b, s, ds))
    h0 = (jax.random.normal(jax.random.PRNGKey(5), (b, nh, hd, ds))
          if with_init else None)
    y_ref, h_ref = ssd_ref(xh, dt, A, B_, C_, initial_state=h0)
    y_pal, h_pal = ssd_chunked_pallas(xh, dt, A, B_, C_, chunk=chunk,
                                      initial_state=h0, interpret=True)
    np.testing.assert_allclose(y_pal, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_pal, h_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("case", SSD_CASES[:2])
def test_ssd_jnp_chunked_matches_ref(case):
    """The model's jnp chunked SSD (used in training) vs the sequential ref."""
    b, s, nh, hd, ds, chunk = case
    xh = jax.random.normal(jax.random.PRNGKey(0), (b, s, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (b, s, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (nh,)) * 0.5)
    B_ = jax.random.normal(jax.random.PRNGKey(3), (b, s, ds))
    C_ = jax.random.normal(jax.random.PRNGKey(4), (b, s, ds))
    y_ref, h_ref = ssd_ref(xh, dt, A, B_, C_)
    y_jnp, h_jnp = ssd_chunked(xh, dt, A, B_, C_, chunk)
    np.testing.assert_allclose(y_jnp, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_jnp, h_ref, rtol=2e-4, atol=2e-4)
