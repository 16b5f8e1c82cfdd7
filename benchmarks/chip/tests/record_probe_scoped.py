#!/usr/bin/env python3
"""Record ``data/probe_scoped.xplane.pb`` on a TPU: three traced steps of a
toy train step driven as the benchmark's training drive drives the real
one.

The step is a scanned, rematerialised two-layer model whose parts sit in
the program's four scopes (``attention`` with a nested scan of its own,
``mlp``, ``lm_head``, ``optimizer``); the trainer runtime
(``TrainerRuntime.steps`` / ``end_step``) opens its ``train.`` spans, and
the loop opens the drive's ``bench.`` spans around them, so the two kinds
interleave as they do in a cell's trace.

    python3 benchmarks/chip/tests/record_probe_scoped.py --out PATH
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
for p in (str(CHECKOUT), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

B, S, D, F, V, LAYERS, CHUNKS = 8, 128, 512, 1024, 1024, 2, 2


def init(key):
    k = jax.random.split(key, 5)
    n = jax.random.normal
    return {"params": {
        "emb": 0.02 * n(k[0], (V, D)),
        "blocks": {"q": 0.02 * n(k[1], (LAYERS, D, D)),
                   "k": 0.02 * n(k[2], (LAYERS, CHUNKS, D, D)),
                   "up": 0.02 * n(k[3], (LAYERS, D, F)),
                   "down": 0.02 * n(k[4], (LAYERS, F, D))}}}


def train_step(state, batch):
    def loss_of(p):
        with jax.named_scope("lm_head"):
            x = p["emb"][batch["tokens"]]

        def layer(x, w):
            with jax.named_scope("attention"):
                h, _ = jax.lax.scan(lambda h, k: (jnp.tanh(h @ k), None),
                                    x @ w["q"], w["k"])
                x = x + h
            with jax.named_scope("mlp"):
                x = x + jax.nn.silu(x @ w["up"]) @ w["down"]
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["blocks"])
        with jax.named_scope("lm_head"):
            logits = x @ p["emb"].T
            gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                                       axis=-1)[..., 0]
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    loss, grads = jax.value_and_grad(loss_of)(state["params"])
    with jax.named_scope("optimizer"):
        params = jax.tree.map(lambda p, g: p - 1e-2 * g, state["params"],
                              grads)
    return {"params": params}, {"loss": loss}


def without_plane(xspace: bytes, name: str) -> bytes:
    """The serialised XSpace less its plane ``name`` (``/host:metadata``
    holds the compiled programs' HLO protos, for a graph viewer: most of the
    file, and nothing the reduction reads)."""
    from benchmarks.chip.xplane_meta import _fields, _varint
    out, i = bytearray(), 0
    while i < len(xspace):
        start = i
        key, i = _varint(xspace, i)
        size, i = _varint(xspace, i)        # every XSpace field is a message
        body, i = xspace[i:i + size], i + size
        if key >> 3 == 1 and dict(_fields(body)).get(2) == name.encode():
            continue
        out += xspace[start:i]
    return bytes(out)


def main():
    from repro import configs
    from repro.config import TrainConfig
    from repro.runtime.trainer import TrainerRuntime
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cfg = configs.get_smoke("qwen25_05b")       # only its data pipeline
    tcfg = TrainConfig(global_batch=B, seq_len=S, total_steps=5)
    rt = TrainerRuntime(cfg, tcfg, out_dir=None, print_fn=None)
    step_fn = jax.jit(train_step, donate_argnums=(0,))
    state = init(jax.random.PRNGKey(0))
    tmp = tempfile.mkdtemp(prefix="probe-")
    feed, window = rt.steps(0), None
    for k in range(tcfg.total_steps):
        if k == 2:                              # two steps compile and warm
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # no Python-call events
            jax.profiler.start_trace(tmp, profiler_options=opts)
            window = jax.profiler.TraceAnnotation("bench.window")
            window.__enter__()
        with jax.profiler.TraceAnnotation("bench.feed"):
            step, batch = next(feed)
        with jax.profiler.TraceAnnotation("bench.step"):
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
        with jax.profiler.TraceAnnotation("bench.end_step"):
            rt.end_step(step, metrics)
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()
    feed.close()
    path = next(Path(tmp).rglob("*.xplane.pb"))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_bytes(without_plane(path.read_bytes(),
                                             "/host:metadata"))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{args.out}: {Path(args.out).stat().st_size} bytes")


if __name__ == "__main__":
    main()
