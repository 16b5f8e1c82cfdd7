"""ParamSpec: single source of truth for parameter shape/dtype/init/logical axes.

Every model module declares a pytree (nested dict) of ``ParamSpec``.  From that
one declaration we derive:

- abstract params for the AOT dry-run (``jax.ShapeDtypeStruct``, zero allocation)
- real initialization (``init_params``)
- NamedShardings (via ``repro.sharding`` rules)
- LoRA targeting and trainable masks
- checkpoint manifests

This is the JAX analogue of MobileFineTuner's shard "mapping table" (§4.1.1):
the physical location/state of every parameter segment is a pure function of
its logical axes + the active sharding rules.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    dtype: Any
    axes: Tuple[Optional[str], ...]   # logical axis name per dim (None = replicated dim)
    init: str = "normal"              # normal | zeros | ones | fanin | embed
    scale: float = 1.0


def spec(shape, axes, init="fanin", dtype=jnp.float32, scale=1.0) -> ParamSpec:
    assert len(shape) == len(axes), (shape, axes)
    return ParamSpec(tuple(int(s) for s in shape), dtype, tuple(axes), init, scale)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(fn: Callable[[ParamSpec], Any], specs):
    return jax.tree.map(fn, specs, is_leaf=is_spec)


def abstract_params(specs, dtype=None):
    """ShapeDtypeStruct pytree — used by jax.eval_shape-free dry-run lowering."""
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype), specs)


def _init_leaf(key, s: ParamSpec):
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    if s.init == "normal":
        return (jax.random.normal(key, s.shape) * s.scale).astype(s.dtype)
    if s.init == "embed":
        return (jax.random.normal(key, s.shape) * 0.02 * s.scale).astype(s.dtype)
    if s.init == "fanin":
        fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
        std = s.scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, s.shape) * std).astype(s.dtype)
    raise ValueError(f"unknown init {s.init}")


def init_params(rng, specs, dtype=None):
    """Materialize parameters.  Deterministic per-leaf fold of the path hash."""
    leaves, treedef = jax.tree.flatten_with_path(specs, is_leaf=is_spec)
    out = []
    for path, s in leaves:
        path_str = "/".join(str(p) for p in path)
        # crc32, not hash(): str hash is randomized per process, which would
        # make "same seed" give different params across runs
        key = jax.random.fold_in(rng, zlib.crc32(path_str.encode()) % (2 ** 31))
        x = _init_leaf(key, s)
        if dtype is not None:
            x = x.astype(dtype)
        out.append(x)
    return jax.tree.unflatten(treedef, out)


def logical_axes(specs):
    return tree_map_specs(lambda s: s.axes, specs)


def tree_param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))


def tree_bytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def flatten_names(tree, is_leaf=None):
    """[(dotted.name, leaf)] — used for checkpoint manifests and LoRA targeting."""
    leaves, _ = jax.tree.flatten_with_path(tree, is_leaf=is_leaf)
    out = []
    for path, leaf in leaves:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        out.append((".".join(parts), leaf))
    return out
