import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod AOT dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod)
  2. builds abstract inputs (ShapeDtypeStruct + NamedSharding — no allocation)
  3. lowers + compiles the appropriate step:
       train_4k     -> train_step (fwd+bwd+AdamW, grad accumulation, remat)
       prefill_32k  -> prefill_step (teacher-forced fwd, last-token logits)
       decode_*     -> serve_step (1 token against a donated KV/state cache)
  4. records memory_analysis, cost_analysis, and the collective-bytes tally
     parsed from the compiled HLO into benchmarks/results/dryrun/*.json
     together with the three roofline terms (TPU v5e constants).

Collective wire-bytes model (documented here, used by §Roofline):
  all-gather          result bytes              (~ full gathered tensor)
  reduce-scatter      result bytes x group      (full reduced tensor)
  all-reduce          2 x result bytes          (ring RS + AG)
  all-to-all          result bytes
  collective-permute  result bytes
"""
import argparse
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.config import SHAPES, ModelConfig, ShapeSpec, TrainConfig, dtype_of
from repro.core.step import make_train_step, state_specs
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.param import ParamSpec, tree_map_specs
from repro.sharding import PRESETS, resolve_spec

# ---------------------------------------------------------------------------
# TPU v5e hardware constants (per chip)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

# per-arch train micro-batching (memory lever; hillclimb overrides via CLI).
# granite/hymba raised after the mem-fix campaign (EXPERIMENTS.md §Dry-run).
TRAIN_MICRO = {
    "command-r-plus-104b": 16, "dbrx-132b": 16, "granite-34b": 16,
    "phi3.5-moe-42b": 8, "qwen2-vl-7b": 8, "minitron-8b": 8,
    "whisper-large-v3": 4, "hymba-1.5b": 8, "qwen1.5-0.5b": 2,
    "mamba2-130m": 2,
}


def cell_train_config(cfg: ModelConfig, shape: ShapeSpec,
                      overrides: Optional[Dict[str, Any]] = None
                      ) -> TrainConfig:
    o = dict(overrides or {})
    if shape.kind == "train":
        base = dict(global_batch=shape.global_batch, seq_len=shape.seq_len,
                    microbatches=TRAIN_MICRO.get(cfg.name, 4),
                    remat_policy="full", attention_impl="streaming",
                    attn_chunk=512, compute_dtype="bfloat16",
                    param_dtype="float32", shard_preset="fsdp_tp",
                    scan_layers=True)
    elif shape.kind == "prefill":
        base = dict(global_batch=shape.global_batch, seq_len=shape.seq_len,
                    remat_policy="none", attention_impl="streaming",
                    attn_chunk=512, compute_dtype="bfloat16",
                    param_dtype="bfloat16", shard_preset="fsdp_tp",
                    # bound MoE expert buffers at 1M-token prefill
                    moe_seq_chunks=8 if cfg.n_experts > 0 else 1)
    else:  # decode
        preset = "fsdp_tp_long" if shape.global_batch == 1 else "fsdp_tp"
        base = dict(global_batch=shape.global_batch, seq_len=shape.seq_len,
                    remat_policy="none", attention_impl="streaming",
                    attn_chunk=512, compute_dtype="bfloat16",
                    param_dtype="bfloat16", shard_preset=preset)
    base.update(o)
    return TrainConfig(**base)


# ---------------------------------------------------------------------------
# Abstract inputs (ShapeDtypeStruct + sharding, zero allocation)
# ---------------------------------------------------------------------------
def _sds(shape, dtype, mesh, pspec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, pspec))


def specs_to_abstract(specs, mesh, preset):
    rules = PRESETS[preset]
    mesh_axes = tuple(mesh.axis_names)

    def one(s: ParamSpec):
        return _sds(s.shape, s.dtype, mesh,
                    resolve_spec(s.axes, rules, mesh_axes))

    return tree_map_specs(one, specs)


def batch_abstract(cfg: ModelConfig, shape: ShapeSpec, mesh, preset: str):
    rules = PRESETS[preset]
    mesh_axes = tuple(mesh.axis_names)
    shapes = registry.batch_shapes(cfg, shape.global_batch, shape.seq_len,
                                   shape.kind)
    out = {}
    for k, (shp, dt) in shapes.items():
        axes = ["batch"] + [None] * (len(shp) - 1)
        out[k] = _sds(shp, dt, mesh, resolve_spec(tuple(axes), rules,
                                                  mesh_axes))
    return out


def input_specs(arch: str, shape_name: str, *, multi_pod: bool = False,
                overrides=None):
    """Harness entry point: ShapeDtypeStruct stand-ins for every model input
    of a cell, sharded for the production mesh."""
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    tcfg = cell_train_config(cfg, shape, overrides)
    return batch_abstract(cfg, shape, mesh, tcfg.shard_preset)


def decode_cache_len(seq_len: int) -> int:
    return seq_len + 512  # mesh-divisible headroom; masked past the index


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1}
_COLL_RE = re.compile(
    r"=\s.*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _line_result_bytes(line: str) -> int:
    """Sum byte sizes of the result shapes on an HLO line (handles tuples)."""
    # result type annotation appears right after '=': take shapes before op name
    m = re.search(r"=\s*(.*?)\s(all-gather|all-reduce|reduce-scatter|"
                  r"all-to-all|collective-permute)", line)
    if not m:
        return 0
    total = 0
    for dt, dims in _SHAPE_RE.findall(m.group(1)):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Tally collectives from the compiled HLO text.

    NOTE (recorded as a witness, not the roofline source): ops inside
    ``while`` bodies appear once in the text but execute trip-count times —
    exactly the same undercount as cost_analysis.  The analytic model in
    repro/analysis.py is the roofline source; this tally proves which
    collective kinds/groups the partitioner actually emitted.
    """
    per_kind: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    wire = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or (m.group(2) == "-done"):
            continue
        kind = m.group(1)
        rb = _line_result_bytes(line)
        gm = _GROUP_RE.search(line)
        if gm:
            group = len(gm.group(1).split(","))
        else:
            gi = _GROUP_IOTA_RE.search(line)
            group = int(gi.group(2)) if gi else 1
        if kind == "all-gather":
            w = rb
        elif kind == "reduce-scatter":
            w = rb * group
        elif kind == "all-reduce":
            w = 2 * rb
        else:
            w = rb
        per_kind[kind] = per_kind.get(kind, 0) + w
        counts[kind] = counts.get(kind, 0) + 1
        wire += w
    return {"wire_bytes": wire, "per_kind": per_kind, "counts": counts}


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------
def build_train(cfg, tcfg, shape, mesh):
    step = make_train_step(cfg, tcfg)
    st_specs = state_specs(cfg, tcfg)
    st_abs = specs_to_abstract(st_specs, mesh, tcfg.shard_preset)
    b_abs = batch_abstract(cfg, shape, mesh, tcfg.shard_preset)
    jitted = jax.jit(step, donate_argnums=(0,))
    return jitted, (st_abs, b_abs)


def build_prefill(cfg, tcfg, shape, mesh):
    fwd = registry.forward_fn(cfg)

    def prefill_step(params, batch):
        logits, _ = fwd(params, batch, cfg, tcfg)
        return logits[:, -1]

    pspecs = tree_map_specs(
        lambda s: ParamSpec(s.shape, dtype_of(tcfg.param_dtype), s.axes,
                            s.init, s.scale), registry.param_specs(cfg))
    p_abs = specs_to_abstract(pspecs, mesh, tcfg.shard_preset)
    b_abs = batch_abstract(cfg, shape, mesh, tcfg.shard_preset)
    return jax.jit(prefill_step), (p_abs, b_abs)


def build_decode(cfg, tcfg, shape, mesh):
    decode = registry.decode_fn(cfg)

    def serve_step(params, cache, tokens, index):
        return decode(params, cache, tokens, index, cfg, tcfg)

    pspecs = tree_map_specs(
        lambda s: ParamSpec(s.shape, dtype_of(tcfg.param_dtype), s.axes,
                            s.init, s.scale), registry.param_specs(cfg))
    p_abs = specs_to_abstract(pspecs, mesh, tcfg.shard_preset)
    cspecs = registry.cache_specs(cfg, shape.global_batch,
                                  decode_cache_len(shape.seq_len),
                                  jnp.bfloat16)
    c_abs = specs_to_abstract(cspecs, mesh, tcfg.shard_preset)
    b_abs = batch_abstract(cfg, shape, mesh, tcfg.shard_preset)
    idx = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    return (jax.jit(serve_step, donate_argnums=(1,)),
            (p_abs, c_abs, b_abs["tokens"], idx))


# ---------------------------------------------------------------------------
# Roofline terms — analytic model (repro/analysis.py) is the source; raw
# cost_analysis / HLO tallies are recorded as witnesses (while-body-once
# undercount documented there).
# ---------------------------------------------------------------------------
from repro.analysis import analytic_roofline  # noqa: E402


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             overrides=None, tag: str = "baseline",
             save: bool = True) -> Dict[str, Any]:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec = {"arch": cfg.name, "shape": shape_name, "status":
               "SKIP(full-attention)", "tag": tag,
               "mesh": "multi" if multi_pod else "single"}
        if save:
            _save(rec, arch, shape_name, multi_pod, tag)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    tcfg = cell_train_config(cfg, shape, overrides)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn, args = build_train(cfg, tcfg, shape, mesh)
        elif shape.kind == "prefill":
            fn, args = build_prefill(cfg, tcfg, shape, mesh)
        else:
            fn, args = build_decode(cfg, tcfg, shape, mesh)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = parse_collectives(compiled.as_text())

    rec = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev, "tag": tag, "status": "OK",
        "kind": shape.kind,
        "tcfg": {k: getattr(tcfg, k) for k in
                 ("microbatches", "remat_policy", "attention_impl",
                  "attn_chunk", "shard_preset", "compute_dtype",
                  "param_dtype", "grad_reduce_dtype", "moe_dispatch_dtype",
                  "moe_seq_chunks")},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "cost_raw": {k: cost.get(k) for k in
                     ("flops", "bytes accessed", "transcendentals")
                     if k in cost},
        "collectives_hlo": coll,
        "roofline": analytic_roofline(cfg, tcfg, shape, multi_pod),
    }
    if save:
        _save(rec, arch, shape_name, multi_pod, tag)
    return rec


def _save(rec, arch, shape_name, multi_pod, tag):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh_tag = "multi" if multi_pod else "single"
    path = os.path.join(RESULTS_DIR,
                        f"{arch}__{shape_name}__{mesh_tag}__{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every remaining baseline cell")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--preset", default=None)
    ap.add_argument("--grad-reduce-dtype", default=None)
    ap.add_argument("--moe-dispatch-dtype", default=None)
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--moe-seq-chunks", type=int, default=None)
    args = ap.parse_args()

    o = {}
    if args.microbatches is not None:
        o["microbatches"] = args.microbatches
    if args.attn_chunk is not None:
        o["attn_chunk"] = args.attn_chunk
    if args.remat is not None:
        o["remat_policy"] = args.remat
    if args.preset is not None:
        o["shard_preset"] = args.preset
    if args.grad_reduce_dtype is not None:
        o["grad_reduce_dtype"] = args.grad_reduce_dtype
    if args.moe_dispatch_dtype is not None:
        o["moe_dispatch_dtype"] = args.moe_dispatch_dtype
    if args.param_dtype is not None:
        o["param_dtype"] = args.param_dtype
    if args.moe_seq_chunks is not None:
        o["moe_seq_chunks"] = args.moe_seq_chunks

    cells = []
    archs = [args.arch] if args.arch else list(configs.ASSIGNED)
    shapes = [args.shape] if args.shape else list(SHAPES)
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    for arch, shape_name in cells:
        mesh_tag = "multi" if args.multi_pod else "single"
        path = os.path.join(
            RESULTS_DIR, f"{arch}__{shape_name}__{mesh_tag}__{args.tag}.json")
        if args.skip_done and os.path.exists(path):
            print(f"[skip] {arch} x {shape_name} ({mesh_tag})")
            continue
        print(f"[cell] {arch} x {shape_name} ({mesh_tag}) ...", flush=True)
        try:
            rec = run_cell(arch, shape_name, multi_pod=args.multi_pod,
                           overrides=o, tag=args.tag)
        except Exception as e:  # record the failure — these are bugs to fix
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                   "tag": args.tag, "status": f"FAIL: {type(e).__name__}",
                   "error": str(e)[:2000]}
            _save(rec, arch, shape_name, args.multi_pod, args.tag)
            print(f"  FAILED: {e}")
            continue
        if rec["status"] == "OK":
            r = rec["roofline"]
            tb = rec["memory"]["temp_bytes"]
            print(f"  OK lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"dominant={r['dominant']} "
                  f"frac={r['roofline_fraction']:.3f} "
                  f"temp={(tb or 0)/1e9:.2f}GB")
        else:
            print(f"  {rec['status']}")


if __name__ == "__main__":
    main()
