"""Logical-axis -> mesh-axis sharding rules (the paper's C1, TPU-native).

MobileFineTuner's ZeRO-inspired parameter sharding keeps only the *active*
parameter segment in RAM and offloads the rest to disk.  The TPU-native
realization is GSPMD FSDP: each weight is sharded over the ``data`` axis and
all-gathered just-in-time per layer.  The rule table below is the "mapping
table" of §4.1.1 — it fully determines where every parameter segment lives.

Presets (perf levers; selected by TrainConfig.shard_preset):
  dp       params replicated, batch over data              (paper's *unoptimized* baseline)
  fsdp     params sharded over data (ZeRO-3), no TP        (paper-faithful C1)
  tp       tensor parallel over model, params replicated over data
  fsdp_tp  FSDP over data x TP over model                  (beyond-paper default)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.param import ParamSpec, tree_map_specs

# Logical axis vocabulary used by every model module:
#   layers       scanned layer dim (never sharded)
#   vocab        embedding/unembedding vocab dim
#   embed        d_model dim (FSDP axis for most weights)
#   heads        q-head dim of attention projections
#   kv_heads     kv-head dim
#   qkv / out    fused projection output dims
#   mlp          ffn hidden dim
#   experts      MoE expert dim
#   ssm_inner    mamba inner dim
#   ssm_state    mamba state dim
#   batch / seq / act_embed / act_heads   activation axes

Rules = Dict[str, Optional[Tuple[str, ...]]]


def _rules(fsdp: bool, tp: bool) -> Rules:
    d = ("data",) if fsdp else None
    m = ("model",) if tp else None
    return {
        "layers": None,
        "conv_width": None,
        # weights: shard the contraction/embed dim over data (FSDP) and the
        # parallel dim over model (TP), MaxText-style.
        "vocab": m,
        "embed": d,
        "heads": m,
        "kv_heads": m,
        "mlp": m,
        "mlp_in": d,
        "experts": m,
        "expert_mlp": d,
        "ssm_inner": m,
        "ssm_state": None,
        "ssm_heads": m,
        "norm": None,
        "lora_rank": None,
        # activations
        "batch": ("pod", "data"),
        "seq": None,
        "act_embed": None,
        "act_heads": ("model",),
        "act_kv_heads": ("model",),
        "act_experts": ("model",),
        # decode caches: batch over (pod, data); sequence over model (kv-head
        # counts are not mesh-divisible across the arch pool, seq always is)
        "cache_heads": None,
        "cache_seq": m,
        "cache_batch": ("pod", "data"),
    }


def _long_rules() -> Rules:
    """long_500k (global_batch=1): nothing can shard on batch; the KV cache
    sequence shards over (data, model) instead."""
    r = dict(_rules(fsdp=True, tp=True))
    r["batch"] = None
    r["cache_batch"] = None
    r["cache_seq"] = ("data", "model")
    return r


def _fsdp_dp_rules() -> Rules:
    """Beyond-paper preset for small models: the ``model`` axis joins data
    parallelism (batch shards over pod x data x model); weights shard over
    ``data`` only (ZeRO-3), killing the TP activation all-reduces that
    dominate small-model cells.  Gradients all-reduce over model + pod and
    reduce-scatter over data."""
    r = dict(_rules(fsdp=True, tp=False))
    # batch over the in-pod axes; the pod axis does context parallelism
    # (sequence sharding — train_4k's 256 sequences cannot split 512 ways)
    r["batch"] = ("data", "model")
    r["seq"] = ("pod",)
    r["cache_batch"] = ("data", "model")
    r["cache_seq"] = None
    return r


PRESETS: Dict[str, Rules] = {
    "dp": _rules(fsdp=False, tp=False),
    "fsdp": _rules(fsdp=True, tp=False),
    "tp": _rules(fsdp=False, tp=True),
    "fsdp_tp": _rules(fsdp=True, tp=True),
    "fsdp_tp_long": _long_rules(),
    "fsdp_dp": _fsdp_dp_rules(),
}


def constrain_params(params, specs, preset: str):
    """Pin (sliced) layer parameters to their sharded layout inside a scan
    body, so GSPMD gathers ONE layer's weights just-in-time instead of
    hoisting the all-gather of the whole stacked tree out of the loop
    (which would materialize every layer gathered at once).  This is the
    TPU-native form of the paper's 'only the active segment is resident'
    rule (§4.1.1)."""
    from repro.param import is_spec

    def one(s, arr):
        # drop the leading 'layers' axis if the array was sliced out of the
        # stacked tree
        axes = s.axes[1:] if (s.axes and s.axes[0] == "layers"
                              and arr.ndim == len(s.axes) - 1) else s.axes
        return constrain(arr, axes, preset=preset)

    return jax.tree.map(one, specs, params, is_leaf=is_spec)


def resolve_spec(axes: Tuple[Optional[str], ...], rules: Rules,
                 mesh_axes: Tuple[str, ...]) -> P:
    """Map logical axes to a PartitionSpec, dropping mesh axes that do not
    exist in the current mesh (e.g. 'pod' on the single-pod mesh) and making
    sure no mesh axis is used twice (first logical axis wins)."""
    used = set()
    parts = []
    for ax in axes:
        if ax is None:
            parts.append(None)
            continue
        target = rules.get(ax, None)
        if target is None:
            parts.append(None)
            continue
        take = tuple(t for t in target if t in mesh_axes and t not in used)
        used.update(take)
        if not take:
            parts.append(None)
        elif len(take) == 1:
            parts.append(take[0])
        else:
            parts.append(take)
    # strip trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shardings_for_specs(specs, mesh: Mesh, preset: str):
    """NamedSharding pytree for a ParamSpec pytree."""
    rules = PRESETS[preset]
    mesh_axes = tuple(mesh.axis_names)

    def one(s: ParamSpec):
        return NamedSharding(mesh, resolve_spec(s.axes, rules, mesh_axes))

    return tree_map_specs(one, specs)


def sharding_for_axes(axes, mesh, preset: str) -> NamedSharding:
    rules = PRESETS[preset]
    return NamedSharding(mesh, resolve_spec(tuple(axes), rules,
                                            tuple(mesh.axis_names)))


def constrain(x, axes, preset: str = "fsdp_tp"):
    """with_sharding_constraint by logical activation axes.  The mesh is the
    one set around the trace with ``jax.set_mesh``; with none set there is
    nothing to constrain to and ``x`` passes through."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, sharding_for_axes(axes, mesh, preset))


def batch_sharding(mesh: Mesh, ndim: int, preset: str = "fsdp_tp"):
    """Sharding for a [batch, ...] input: batch over (pod,data)."""
    axes = ["batch"] + [None] * (ndim - 1)
    return sharding_for_axes(axes, mesh, preset)
