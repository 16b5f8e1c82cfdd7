#!/usr/bin/env python3
"""Bring-up check: the trainer and the serving engine on a TPU chip.

Drives the main paths once, through the entry points a user calls, at
qwen2.5-0.5B's published widths with weights made from a seed:

  train  ``repro.launch.train.train_loop`` (the CLI's in-memory jitted path)
         takes Full-FT steps, then LoRA r=8 steps, on the CLI's synthetic
         corpus; every loss is finite and the Full-FT loss falls
  flash  the same Full-FT step with ``attention_impl="flash"``: the compiled
         step holds the Pallas kernel, and its first-step loss matches the
         streaming path's within ``FLASH_LOSS_RTOL``
  serve  ``ServeEngine`` over the in-memory base answers mixed-length
         requests with two seeded adapters; each request's tokens equal
         those of the same request served alone, and the two adapters
         answer one prompt differently

    python chip_smoke.py             one chip: the three phases above
    python chip_smoke.py --chips 4   only the fsdp_tp train step on a 2x2
                                     ("data", "model") mesh, against the
                                     same step on one device

Each phase prints one line.  The last line of stdout is one JSON object
naming the device.  JAX is pinned to the TPU before it is imported, so with
no chip the script exits non-zero and prints no result.  The persistent
compilation cache is on (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

if __name__ == "__main__":
    # the chip or nothing: JAX must not fall back to the CPU
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

try:
    from repro import configs
    from repro.checkpoint.safetensors import save_adapter
    from repro.config import TrainConfig
    from repro.core.lora import lora_specs
    from repro.core.step import init_state, make_train_step, state_specs
    from repro.core.zero import place_params
    from repro.data.dataset import packed_batches
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import train_loop
    from repro.models import registry
    from repro.param import init_params, tree_map_specs
    from repro.runtime.trainer import build_data
    from repro.serve import AdapterCache, Request, ServeEngine
    from repro.sharding import batch_sharding
except ImportError as e:
    sys.exit(f"chip_smoke: cannot import the repo's code from "
             f"{ROOT / 'src'}: {e}")

ARCH = "qwen25_05b"
SEED = 0
BATCH, SEQ = 8, 1024          # fits one v5e chip with full remat in fp32
LR = 3e-4
FT_STEPS, LORA_STEPS, SPMD_STEPS = 6, 4, 2
LORA_RANK, LORA_ALPHA = 8, 32.0
LORA_TARGETS = ("wq", "wk", "wv", "wo")
# streaming and flash attention differ only in summation order and in the
# matmul passes of fp32 on the chip; the loss is a mean over BATCH * SEQ
# tokens, so 1e-3 relative is far above that noise and far below any real
# error in the kernel
FLASH_LOSS_RTOL = 1e-3
SPMD_RTOL = 1e-3
KERNEL_OP = "tpu_custom_call"  # how a Pallas kernel appears in compiled HLO
ADAPTER_DIR = ROOT / "runs" / "chip_smoke"


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Backend-compile wall time (a persistent-cache hit counts its read)
    and cache hits, summed from ``jax.monitoring`` events."""

    def __init__(self):
        self.secs, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs
            self.programs += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark):
        """'compile S s (N programs, H cache hits)' since ``mark``."""
        s, n, h = mark
        return (f"compile {self.secs - s:.1f} s ({self.programs - n} "
                f"programs, {self.hits - h} cache hits)")

    def mark(self):
        return self.secs, self.programs, self.hits


def peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 1e9:.2f} GB"


def train_config(steps: int, **kw) -> TrainConfig:
    """The CLI's in-memory TrainConfig (launch/train.py::main) at this
    script's geometry."""
    return TrainConfig(global_batch=BATCH, seq_len=SEQ, learning_rate=LR,
                       total_steps=steps, warmup_steps=1,
                       remat_policy="full", compute_dtype="float32", **kw)


def first_batches(ds, n: int):
    """The first ``n`` batches ``train_loop`` draws for ``SEED``."""
    it = packed_batches(ds, BATCH, seed=SEED, epochs=10_000)
    return [{k: jnp.asarray(v) for k, v in next(it).items()}
            for _ in range(n)]


def fmt(xs) -> str:
    return "[" + ", ".join(f"{x:.4f}" for x in xs) + "]"


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def phase_train(cfg, ds, meter, device):
    """Full-FT then LoRA through ``train_loop``; returns the Full-FT
    losses."""
    runs = {"Full-FT": train_config(FT_STEPS),
            f"LoRA r{LORA_RANK}": train_config(
                LORA_STEPS, lora_rank=LORA_RANK, lora_alpha=LORA_ALPHA,
                lora_targets=LORA_TARGETS)}
    out = {}
    for label, tcfg in runs.items():
        mark = meter.mark()
        state, obs = train_loop(cfg, tcfg, out_dir=None, seed=SEED,
                                resume=False, dataset=ds, print_fn=None)
        del state
        losses = [r["loss"] for r in obs.rows]
        steady = np.median([r["step_time_s"] for r in obs.rows[1:]])
        print(f"train  {label}: {len(losses)} steps of {BATCH}x{SEQ} | "
              f"losses {fmt(losses)} | {meter.since(mark)} | later steps "
              f"{steady:.3f} s median | peak HBM {peak_gb(device)}",
              flush=True)
        check(all(math.isfinite(x) for x in losses),
              f"{label}: a loss is not finite: {losses}")
        out[label] = losses
    ft = out["Full-FT"]
    check(ft[-1] < ft[0], f"Full-FT loss did not fall: {ft}")
    return ft


def phase_flash(cfg, ds, stream_loss0: float, meter, device):
    tcfg = train_config(FT_STEPS, attention_impl="flash")
    mark = meter.mark()
    state = init_state(jax.random.PRNGKey(SEED), cfg, tcfg)
    batch = first_batches(ds, 1)[0]
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    compiled = step.lower(state, batch).compile()
    check(KERNEL_OP in compiled.as_text(),
          f"the flash train step holds no {KERNEL_OP}: the kernel did not "
          "reach the compiled program")
    state, metrics = compiled(state, batch)
    loss = float(metrics["loss"])
    del state
    rel = abs(loss - stream_loss0) / abs(stream_loss0)
    print(f"flash  Full-FT step with the Pallas kernel ({KERNEL_OP} in the "
          f"compiled step) | loss {loss:.6f} vs streaming "
          f"{stream_loss0:.6f}: rel diff {rel:.2e} (limit "
          f"{FLASH_LOSS_RTOL:.0e}) | {meter.since(mark)} | peak HBM "
          f"{peak_gb(device)}", flush=True)
    check(math.isfinite(loss) and rel <= FLASH_LOSS_RTOL,
          f"flash loss {loss} differs from streaming {stream_loss0} by "
          f"{rel:.2e} relative")


def phase_serve(cfg, meter, device):
    tcfg = TrainConfig(compute_dtype="float32", attention_impl="streaming")
    mark = meter.mark()
    specs = registry.param_specs(cfg)
    ADAPTER_DIR.mkdir(parents=True, exist_ok=True)
    paths = []
    # b starts at zero, a no-op adapter: draw it fan-in random like a, so
    # each adapter changes the model its own way
    aspecs = tree_map_specs(lambda s: s._replace(init="fanin"),
                            lora_specs(specs, LORA_TARGETS, LORA_RANK))
    for i in range(2):
        lt = init_params(jax.random.PRNGKey(100 + i), aspecs)
        paths.append(save_adapter(
            str(ADAPTER_DIR / f"adapter{i}.safetensors"), lt,
            rank=LORA_RANK, alpha=LORA_ALPHA, targets=LORA_TARGETS))
    eng = ServeEngine(
        cfg, tcfg, init_params(jax.random.PRNGKey(SEED), specs), slots=4,
        max_len=128, chunk=32,
        adapters=AdapterCache(cfg, rank=LORA_RANK, alpha=LORA_ALPHA,
                              targets=LORA_TARGETS, capacity=2))
    rng = np.random.default_rng(SEED)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, size=n).tolist()

    shared = prompt(40)
    reqs = [Request(0, prompt(17), 12, None),
            Request(1, shared, 8, paths[0]),
            Request(2, shared, 8, paths[1]),
            Request(3, prompt(64), 16, paths[1]),
            Request(4, prompt(96), 10, paths[0])]
    for r in reqs:
        eng.submit(r)
    batched = eng.run()
    st = eng.stats()
    # the same engine, drained: each request again with nobody else in it
    solo = {}
    for r in reqs:
        eng.submit(r)
        solo[r.rid] = eng.run()[r.rid]
    eng.close()
    same = [np.array_equal(batched[r.rid], solo[r.rid]) for r in reqs]
    toks = np.concatenate([batched[r.rid] for r in reqs])
    personal = not np.array_equal(batched[1], batched[2])
    print(f"serve  {len(reqs)} requests (prompts 17-96 tokens; base + 2 "
          f"adapters) over 4 slots, peak {st['peak_active']} in flight: "
          f"{toks.size} tokens, {np.unique(toks).size} distinct | batched "
          f"== solo for {sum(same)}/{len(reqs)} | one prompt, adapter 0 vs "
          f"1: {batched[1].tolist()} vs {batched[2].tolist()} | "
          f"{meter.since(mark)} | peak HBM {peak_gb(device)}", flush=True)
    for r, ok in zip(reqs, same):
        check(ok, f"request {r.rid}: batched tokens {batched[r.rid]} != "
                  f"solo {solo[r.rid]}")
    check(personal, "two adapters gave the same tokens for one prompt")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def spmd_steps(cfg, tcfg, batches, mesh=None):
    """SPMD_STEPS train steps; with ``mesh`` the state is laid out by the
    fsdp_tp rule table and the step traced under that mesh.  Returns
    (losses, grad norms, the wq leaf after the steps)."""
    state = init_state(jax.random.PRNGKey(SEED), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    if mesh is not None:
        state = place_params(state, state_specs(cfg, tcfg), mesh,
                             tcfg.shard_preset)
        bsh = batch_sharding(mesh, 2, tcfg.shard_preset)
        batches = [jax.device_put(b, bsh) for b in batches]
    losses, gnorms = [], []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))
    return losses, gnorms, state["params"]["blocks"]["attn"]["wq"]


def phase_fsdp(cfg, ds, devices, meter):
    tcfg = train_config(SPMD_STEPS, shard_preset="fsdp_tp")
    batches = first_batches(ds, SPMD_STEPS)
    mark = meter.mark()
    ref_l, ref_g, _ = spmd_steps(cfg, tcfg, batches)
    print(f"fsdp   one device: losses {fmt(ref_l)} | grad norms "
          f"{fmt(ref_g)} | {meter.since(mark)}", flush=True)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices)
    mark = meter.mark()
    with jax.set_mesh(mesh):
        sh_l, sh_g, wq = spmd_steps(cfg, tcfg, batches, mesh)
    n_dev = len(wq.sharding.device_set)
    shard = wq.addressable_shards[0].data.shape
    rel = max(abs(a - b) / abs(b) for a, b in
              zip(sh_l + sh_g, ref_l + ref_g))
    print(f"fsdp   2x2 (data, model) mesh, fsdp_tp: wq {tuple(wq.shape)} "
          f"as {wq.sharding.spec} over {n_dev} devices, shard {shard} | "
          f"losses {fmt(sh_l)} | grad norms {fmt(sh_g)} | max rel diff "
          f"{rel:.2e} (limit {SPMD_RTOL:.0e}) | {meter.since(mark)} | peak "
          f"HBM on device 0 {peak_gb(devices[0])}", flush=True)
    check(n_dev == 4 and shard != tuple(wq.shape),
          f"wq is not split over the 4 devices: {wq.sharding}")
    check(rel <= SPMD_RTOL, f"sharded step differs from one device by "
                            f"{rel:.2e} relative")


# ---------------------------------------------------------------------------
def tpu_devices(n: int):
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: JAX found no TPU: {e}")
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX runs on {devices[0].platform}, not tpu")
    if len(devices) < n:
        sys.exit(f"chip_smoke: needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train, flash and serve phases; 4: only the "
                         "2x2 fsdp_tp step against one device")
    args = ap.parse_args()
    devices = tpu_devices(args.chips)
    cache = enable_compile_cache()
    meter = CompileMeter()
    cfg = configs.get(ARCH)
    ds = build_data(cfg, train_config(1), seed=SEED)
    print(f"setup  {cfg.name} ({cfg.param_count() / 1e6:.0f}M params) on "
          f"{len(devices)} x {devices[0].device_kind} | compile cache "
          f"{cache}", flush=True)
    if args.chips == 4:
        phase_fsdp(cfg, ds, devices, meter)
    else:
        ft = phase_train(cfg, ds, meter, devices[0])
        phase_flash(cfg, ds, ft[0], meter, devices[0])
        phase_serve(cfg, meter, devices[0])
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
