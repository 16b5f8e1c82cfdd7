"""The plain reference agrees with the program at a small size on the CPU.

On the CPU float32 products are exact, so the two differ only by the order
of sums: losses agree to 1e-6 relative, gradients to 1e-4.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import SMOKE_CONFIG

from benchmarks.chip import reference, weights
from benchmarks.chip.harness import model_config, vocab_rows

C = SMOKE_CONFIG


def _program(cfg):
    from repro.config import TrainConfig
    return TrainConfig(compute_dtype="float32", param_dtype="float32",
                       attention_impl="streaming")


def _batch(seed=3, b=2, s=24):
    g = np.random.default_rng(seed)
    tok = g.integers(0, C["vocab_size"], (b, s + 1)).astype(np.int32)
    lab = tok[:, 1:].copy()
    lab[0, :3] = -1                  # ignored positions count nowhere
    return {"tokens": tok[:, :-1], "labels": lab}


def test_loss_and_grads_match_the_program():
    from repro.models import lm
    cfg = model_config(C)
    key = weights.seed_key(11)
    w = weights.hf_weights(key, C)
    p = weights.program_weights(key, C, vocab_rows(cfg))
    batch = _batch()
    tcfg = _program(cfg)

    def prog_loss(p):
        return lm.loss_fn(p, {k: jnp.asarray(v) for k, v in batch.items()},
                          cfg, tcfg)[0]

    def ref_loss(w):
        nll, n = jax.vmap(lambda t, l: reference._row_nll(
            w, C, t, l, jnp.float32))(jnp.asarray(batch["tokens"]),
                                      jnp.asarray(batch["labels"]))
        return jnp.sum(nll) / jnp.sum(n)

    lp, gp = jax.value_and_grad(prog_loss)(p)
    lr, gr = jax.value_and_grad(ref_loss)(w)
    assert abs(float(lp) - float(lr)) < 1e-6 * abs(float(lr))
    gr = weights.to_program(gr, C, vocab_rows(cfg))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)

