"""Memory-efficient attention (paper C4): streaming == naive exact softmax."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_stub

from repro.core.attention import SENTINEL, attention

hypothesis, st = hypothesis_or_stub()


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@hypothesis.settings(max_examples=25, deadline=None)
@hypothesis.given(
    b=st.integers(1, 3), sq=st.integers(1, 24), h=st.sampled_from([1, 2, 4]),
    kv_groups=st.sampled_from([1, 2]), d=st.sampled_from([4, 8]),
    chunk=st.sampled_from([3, 4, 8, 16]), causal=st.booleans(),
    window=st.sampled_from([0, 5]))
def test_streaming_matches_naive(b, sq, h, kv_groups, d, chunk, causal,
                                 window):
    if h % kv_groups:
        return
    kvh = h // kv_groups
    q = _rand(0, b, sq, h, d)
    k = _rand(1, b, sq, kvh, d)
    v = _rand(2, b, sq, kvh, d)
    out_n = attention(q, k, v, causal=causal, window=window, impl="naive")
    out_s = attention(q, k, v, causal=causal, window=window, impl="streaming",
                      chunk=chunk)
    np.testing.assert_allclose(out_n, out_s, rtol=2e-5, atol=2e-5)


def test_q_blocking_path():
    """sq large enough to trigger the outer q-chunk map."""
    q = _rand(0, 2, 40, 2, 8)
    k = _rand(1, 2, 40, 2, 8)
    v = _rand(2, 2, 40, 2, 8)
    out_n = attention(q, k, v, causal=True, impl="naive")
    out_s = attention(q, k, v, causal=True, impl="streaming", chunk=8)
    np.testing.assert_allclose(out_n, out_s, rtol=2e-5, atol=2e-5)


def test_decode_against_prefix():
    """Decode (sq=1 vs long cache with padding sentinel) == full attention row."""
    b, s, h, d = 2, 12, 2, 8
    q_full = _rand(0, b, s, h, d)
    k = _rand(1, b, s, h, d)
    v = _rand(2, b, s, h, d)
    full = attention(q_full, k, v, causal=True, impl="naive")
    # decode the last position against a padded cache
    smax = s + 5
    kp = jnp.pad(k, ((0, 0), (0, 5), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 5), (0, 0), (0, 0)))
    kv_pos = jnp.broadcast_to(jnp.arange(smax)[None], (b, smax))
    kv_pos = jnp.where(kv_pos < s, kv_pos, SENTINEL)
    q_pos = jnp.full((b, 1), s - 1, jnp.int32)
    row = attention(q_full[:, -1:], kp, vp, q_pos=q_pos, kv_pos=kv_pos,
                    causal=True, impl="streaming", chunk=4)
    np.testing.assert_allclose(full[:, -1:], row, rtol=2e-5, atol=2e-5)


def test_streaming_grad_finite():
    q = _rand(0, 1, 8, 2, 4)
    k = _rand(1, 1, 8, 2, 4)
    v = _rand(2, 1, 8, 2, 4)
    g = jax.grad(lambda q_: (attention(q_, k, v, impl="streaming",
                                       chunk=4) ** 2).sum())(q)
    assert bool(jnp.isfinite(g).all())
    gn = jax.grad(lambda q_: (attention(q_, k, v, impl="naive") ** 2).sum())(q)
    np.testing.assert_allclose(g, gn, rtol=2e-4, atol=2e-5)


def test_traced_window():
    """Hybrid layer scans pass the window as a traced scalar."""
    q = _rand(0, 1, 10, 2, 4)
    k = _rand(1, 1, 10, 2, 4)
    v = _rand(2, 1, 10, 2, 4)

    def f(w):
        return attention(q, k, v, causal=True, window=w, impl="streaming",
                         chunk=4)
    out_t = jax.jit(f)(jnp.int32(4))
    out_s = attention(q, k, v, causal=True, window=4, impl="naive")
    np.testing.assert_allclose(out_t, out_s, rtol=2e-5, atol=2e-5)
    out_t0 = jax.jit(f)(jnp.int32(0))
    out_s0 = attention(q, k, v, causal=True, window=0, impl="naive")
    np.testing.assert_allclose(out_t0, out_s0, rtol=2e-5, atol=2e-5)


def _positions(b, n, offset=0):
    return jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None] + offset,
                            (b, n))


# (name, h, kvh, sq, skv, chunk, causal, window, kv layout)
GRAD_CASES = [
    ("gqa1", 2, 2, 8, 8, 4, True, 0, "plain"),
    ("gqa2", 4, 2, 8, 8, 4, True, 0, "plain"),
    ("gqa7", 14, 2, 8, 8, 4, True, 0, "plain"),
    ("noncausal", 4, 2, 8, 8, 4, False, 0, "plain"),
    ("window", 4, 2, 12, 12, 4, True, 3, "plain"),
    ("traced_window", 4, 2, 12, 12, 4, True, "traced3", "plain"),
    ("traced_window0", 4, 2, 12, 12, 4, True, "traced0", "plain"),
    ("sentinel_pad", 4, 2, 8, 13, 4, True, 0, "sentinel"),
    ("q_blocked_ragged", 14, 2, 10, 10, 8, True, 0, "plain"),
    ("decode_offset", 4, 2, 3, 13, 4, True, 0, "offset"),
    ("masked_rows", 4, 2, 8, 8, 4, True, 0, "after_q"),
]


@pytest.mark.parametrize("case", GRAD_CASES, ids=[c[0] for c in GRAD_CASES])
def test_streaming_grad_matches_naive(case):
    """The streaming path's own backward against autodiff of ``naive``."""
    _, h, kvh, sq, skv, chunk, causal, window, layout = case
    b, d = 2, 4
    q, k, v = _rand(0, b, sq, h, d), _rand(1, b, skv, kvh, d), \
        _rand(2, b, skv, kvh, d)
    w = _rand(3, b, sq, h, d)
    q_pos, kv_pos = _positions(b, sq), _positions(b, skv)
    if layout == "sentinel":          # the cache's tail past row 9 is padding
        q_pos = _positions(b, sq, offset=2)
        kv_pos = jnp.where(kv_pos < 10, kv_pos, SENTINEL)
    elif layout == "offset":          # a few new rows against a longer cache
        q_pos = _positions(b, sq, offset=skv - sq)
    elif layout == "after_q":         # rows 0-2 see no key: wholly masked
        kv_pos = _positions(b, skv, offset=3)

    def loss(impl, q_, k_, v_, win):
        out = attention(q_, k_, v_, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                        window=win, impl=impl, chunk=chunk)
        return (out * w).sum()

    if isinstance(window, str):       # a scanned hybrid layer's window
        win = jnp.int32(int(window[len("traced"):]))
        grad_s = jax.jit(jax.grad(lambda *a: loss("streaming", *a, win),
                                  argnums=(0, 1, 2)))(q, k, v)
        window = int(win)
    else:
        grad_s = jax.grad(lambda *a: loss("streaming", *a, window),
                          argnums=(0, 1, 2))(q, k, v)
    grad_n = jax.grad(lambda *a: loss("naive", *a, window),
                      argnums=(0, 1, 2))(q, k, v)
    for gs, gn in zip(grad_s, grad_n):
        assert bool(jnp.isfinite(gs).all())
        np.testing.assert_allclose(gs, gn, rtol=2e-4, atol=2e-5)


def test_train_step_runs_the_streaming_backward():
    """The compiled train step differentiates streaming attention through
    its own backward: dots named ``attention/.../streaming_bwd``."""
    from repro import configs
    from repro.config import TrainConfig
    from repro.core.step import init_state, make_train_step
    cfg = configs.get_smoke("qwen25_05b")
    tcfg = TrainConfig(global_batch=2, seq_len=32, remat_policy="full",
                       attention_impl="streaming", attn_chunk=16,
                       compute_dtype="float32")
    state = jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), cfg, tcfg))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    text = jax.jit(make_train_step(cfg, tcfg)).lower(state, batch) \
        .compile().as_text()
    dots = re.findall(r' (?:dot|convolution)\(.*op_name="([^"]*)"', text)
    assert any(re.search(r"attention/(.*/)?streaming_bwd/", n) for n in dots)


def test_streaming_backward_never_holds_the_score_matrix():
    """C4 in the backward: the compiled gradient's temporaries stay below
    one float32 [B, H, Sq, Skv] score matrix."""
    b, s, h, kvh, d, chunk = 1, 1024, 4, 2, 16, 64
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, s, kvh, d), jnp.float32)
    grad = jax.grad(lambda *a: attention(*a, impl="streaming",
                                         chunk=chunk).sum(), argnums=(0, 1, 2))
    mem = jax.jit(grad).lower(q, kv, kv).compile().memory_analysis()
    assert mem.temp_size_in_bytes < b * h * s * s * 4
