"""Sharding rules + multi-device SPMD correctness (8 fake CPU devices in a
subprocess, since the main test process is pinned to 1 device)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.param import is_spec
from repro.sharding import PRESETS, resolve_spec

MESH_AXES = ("data", "model")


def test_resolve_spec_basics():
    from jax.sharding import PartitionSpec as P
    rules = PRESETS["fsdp_tp"]
    assert resolve_spec(("embed", "mlp"), rules, MESH_AXES) == P("data", "model")
    assert resolve_spec(("layers", "embed", "heads"), rules, MESH_AXES) == \
        P(None, "data", "model")
    assert resolve_spec((None,), rules, MESH_AXES) == P()
    # pod axis dropped on single-pod mesh
    assert resolve_spec(("batch", None), rules, MESH_AXES) == P("data")
    # no mesh axis used twice
    assert resolve_spec(("mlp", "heads"), rules, MESH_AXES) == P("model")


def test_presets_differ():
    from jax.sharding import PartitionSpec as P
    assert resolve_spec(("embed",), PRESETS["dp"], MESH_AXES) == P()
    assert resolve_spec(("embed",), PRESETS["fsdp"], MESH_AXES) == P("data")
    assert resolve_spec(("mlp",), PRESETS["tp"], MESH_AXES) == P("model")
    assert resolve_spec(("batch",), PRESETS["fsdp_tp_long"], MESH_AXES) == P()


@pytest.mark.parametrize("arch", configs.ASSIGNED)
def test_all_params_divisible_on_production_mesh(arch):
    """Every weight dim a rule shards must divide by its mesh axes — this is
    the static guarantee behind the 40-cell dry-run."""
    cfg = configs.get(arch)
    sizes = {"data": 16, "model": 16, "pod": 2}
    rules = PRESETS["fsdp_tp"]
    import jax
    for path, s in jax.tree_util.tree_flatten_with_path(
            registry.param_specs(cfg), is_leaf=is_spec)[0]:
        pspec = resolve_spec(s.axes, rules, ("pod",) + MESH_AXES)
        for dim, entry in zip(s.shape, tuple(pspec) + (None,) * 8):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            denom = int(np.prod([sizes[a] for a in axes]))
            assert dim % denom == 0, (arch, path, s.shape, pspec)


def test_zero_bytes_accounting():
    """C1: FSDP frees (1 - 1/shards) of parameter memory per device."""
    import jax
    from jax.sharding import Mesh
    from repro.core.zero import bytes_per_device
    cfg = configs.get("qwen15_05b")
    specs = registry.param_specs(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), MESH_AXES)
    full = bytes_per_device(specs, mesh, "dp")

    class FakeMesh:
        axis_names = MESH_AXES
        devices = np.empty((16, 16))
    sharded = bytes_per_device(specs, FakeMesh(), "fsdp_tp")
    assert sharded < full / 100  # ~1/256 + replicated norms


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np

import sys
sys.path.insert(0, __SRC__)
from repro import configs
from repro.config import TrainConfig
from repro.core.step import init_state, make_train_step
from repro.models import registry
from repro.sharding import shardings_for_specs
from repro.core.zero import place_params

cfg = configs.get_smoke("qwen15_05b")
tcfg = TrainConfig(global_batch=4, seq_len=8, compute_dtype="float32",
                   microbatches=2, remat_policy="full",
                   shard_preset="fsdp_tp", total_steps=3, warmup_steps=0,
                   learning_rate=1e-3)
batch = registry.make_batch(jax.random.PRNGKey(1), cfg, 4, 8)

# single-device reference
state = init_state(jax.random.PRNGKey(0), cfg, tcfg)
step = jax.jit(make_train_step(cfg, tcfg))
s_ref = state
for _ in range(2):
    s_ref, m_ref = step(s_ref, batch)

# 8-device (2 data x 4 model) SPMD
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    state2 = init_state(jax.random.PRNGKey(0), cfg, tcfg)
    from repro.core.step import state_specs
    sspecs = state_specs(cfg, tcfg)
    sh = shardings_for_specs(sspecs, mesh, "fsdp_tp")
    state2 = jax.tree.map(jax.device_put, state2,
                          jax.tree.unflatten(jax.tree.structure(state2),
                                             jax.tree.leaves(sh)))
    batch2 = jax.device_put(batch, NamedSharding(mesh, P("data")))
    step2 = jax.jit(make_train_step(cfg, tcfg))
    s2 = state2
    for _ in range(2):
        s2, m2 = step2(s2, batch2)

# param distributed across devices?
w = s2["params"]["blocks"]["attn"]["wq"]
n_shards = len({d for d in w.sharding.device_set})
print(json.dumps({
    "loss_ref": float(m_ref["loss"]), "loss_spmd": float(m2["loss"]),
    "gnorm_ref": float(m_ref["grad_norm"]), "gnorm_spmd": float(m2["grad_norm"]),
    "n_shard_devices": n_shards,
}))
"""


@pytest.mark.slow
def test_spmd_matches_single_device(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _MULTIDEV_SCRIPT.replace("__SRC__", repr(os.path.abspath(src)))
    p = tmp_path / "spmd_check.py"
    p.write_text(script)
    # the child's 8 devices are virtual CPU devices: pin the platform, or on
    # a host with libtpu installed the child reaches for the TPU instead
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(p)], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_shard_devices"] == 8
    np.testing.assert_allclose(res["loss_spmd"], res["loss_ref"], rtol=1e-4)
    np.testing.assert_allclose(res["gnorm_spmd"], res["gnorm_ref"], rtol=1e-3)
