"""Compile-only TPU v5e checks of the Pallas kernels at real model widths.

Every other kernel test runs in interpret mode on the CPU, which accepts
block shapes the chip's compiler (Mosaic) refuses.  Here each kernel is
lowered and compiled for one chip of a *described* v5e:2x2 topology — no
chip attached, nothing runs — and the compiled HLO must contain the kernel
(``tpu_custom_call``), not a fallback.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssd.ops import ssd_chunked_pallas
from repro.models import mamba2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _heads(arch):
    cfg = configs.get(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim


# (arch whose head layout is compiled, dtype): qwen2.5-0.5B is GQA 14/2 x 64,
# gpt2-124m is MHA 12 x 64, qwen2-vl-7b is GQA 28/4 x 128
FLASH_CASES = [("qwen25_05b", jnp.float32), ("qwen25_05b", jnp.bfloat16),
               ("gpt2_124m", jnp.float32), ("qwen2_vl_7b", jnp.bfloat16)]


@pytest.mark.parametrize("arch,dtype", FLASH_CASES)
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_compiles_for_v5e(one_chip, arch, dtype, direction):
    h, kvh, d = _heads(arch)
    b, s = 2, 1024
    q = jax.ShapeDtypeStruct((b, s, h, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kvh, d), dtype, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd if direction == "fwd" else bwd, q, kv, kv)
    assert "tpu_custom_call" in text


def test_flash_compiles_for_v5e_at_decode_shape(one_chip):
    """One query row against a 1024-long cache: the wrapper pads Sq to 8."""
    h, kvh, d = _heads("qwen25_05b")
    q = jax.ShapeDtypeStruct((8, 1, h, d), jnp.float32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 1024, kvh, d), jnp.float32,
                              sharding=one_chip)
    text = _compiled_text(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)
    assert "tpu_custom_call" in text


def test_ssd_compiles_for_v5e_at_mamba2_widths(one_chip):
    cfg = configs.get("mamba2_130m")
    nh, hd, ds, chunk = (mamba2.n_ssm_heads(cfg), cfg.ssm_head_dim,
                         cfg.ssm_state, cfg.ssm_chunk)
    assert (nh, hd, ds, chunk) == (24, 64, 128, 256)
    b, s = 2, 4 * chunk

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = _compiled_text(
        lambda x, dt, a, bm, cm: ssd_chunked_pallas(x, dt, a, bm, cm,
                                                    chunk=chunk),
        sds(b, s, nh, hd), sds(b, s, nh), sds(nh), sds(b, s, ds),
        sds(b, s, ds))
    assert "tpu_custom_call" in text
