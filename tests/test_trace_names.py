"""The names a profiler trace attributes time by: the train step's named
scopes in the compiled program's op metadata, and the trainer runtime's
host spans in a trace of ``train_loop``."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.config import TrainConfig
from repro.core.step import init_state, make_train_step
from repro.launch.train import train_loop

LAYERS = ("attention", "mlp", "lm_head")
SCOPES = LAYERS + ("optimizer",)
# one HLO instruction: "%name = <shape> <opcode>(...), ..., op_name="...""
INSTR = re.compile(r'^\s*(?:ROOT )?%?\S+ = .*? ([a-z][\w-]*)\(.*'
                   r'op_name="([^"]*)"', re.M)


def scopes_in(op_name):
    """The scope names on an op_name path, bare or under transforms such
    as ``jvp(lm_head)``."""
    return [m.group(1) for part in op_name.split("/")
            for m in [re.fullmatch(r"(?:[\w.]+\()*(%s)\)*" % "|".join(SCOPES),
                                   part)] if m]


@pytest.fixture(scope="module")
def compiled_ops():
    cfg = configs.get_smoke("qwen25_05b")
    tcfg = TrainConfig(global_batch=2, seq_len=32, remat_policy="full",
                       attention_impl="streaming", attn_chunk=16,
                       scan_layers=True, compute_dtype="float32")
    state = jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), cfg, tcfg))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    text = jax.jit(make_train_step(cfg, tcfg)).lower(state, batch) \
        .compile().as_text()
    return INSTR.findall(text)


def test_every_matmul_carries_one_layer_scope(compiled_ops):
    dots = [name for op, name in compiled_ops if op in ("dot", "convolution")]
    assert dots
    for name in dots:
        assert len(set(scopes_in(name)) & set(LAYERS)) == 1, name
    # forward, backward and the rematerialised forward all keep the name
    for layer in LAYERS:
        mine = [n for n in dots if layer in scopes_in(n)]
        assert any(n.split("/")[1].startswith("jvp(") for n in mine), layer
        assert any(n.split("/")[1].startswith("transpose(") for n in mine), \
            layer
    rematted = [n for n in dots if "rematted_computation" in n]
    assert {s for n in rematted for s in scopes_in(n)} >= {"attention",
                                                            "mlp"}


def test_the_update_carries_the_optimizer_scope(compiled_ops):
    opt = [name for _, name in compiled_ops if "optimizer" in scopes_in(name)]
    assert opt
    assert not any(set(scopes_in(n)) & set(LAYERS) for n in opt)
    # AdamW's sqrt of the second moment and the clip's global norm are the
    # step's only square roots (the norms take rsqrt)
    roots = [name for op, name in compiled_ops if op == "sqrt"]
    assert roots and all(scopes_in(n) == ["optimizer"] for n in roots)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events if e.name.startswith("train.")]
    return sorted(out)


def _smoke(**kw):
    cfg = configs.get_smoke("qwen25_05b")
    tcfg = TrainConfig(**{"global_batch": 2, "seq_len": 32, "total_steps": 3,
                          "warmup_steps": 1, "compute_dtype": "float32",
                          **kw})
    return cfg, tcfg


def test_train_loop_spans_each_phase_of_each_step(tmp_path):
    cfg, tcfg = _smoke(checkpoint_every=1)
    with jax.profiler.trace(str(tmp_path / "trace")):
        train_loop(cfg, tcfg, out_dir=str(tmp_path / "run"), print_fn=None)
    spans = _host_spans(tmp_path / "trace")
    steps = [n for _, _, n in spans if n != "train.checkpoint"]
    assert steps == ["train.feed", "train.step", "train.end_step",
                     "train.end_step.pull"] * 3
    assert "train.checkpoint" in [n for _, _, n in spans]
    # each train.step closes where end_step begins, before the next feed
    step = [(s, e) for s, e, n in spans if n == "train.step"]
    ends = [s for s, _, n in spans if n == "train.end_step"]
    feeds = [s for s, _, n in spans if n == "train.feed"]
    for (s, e), end, nxt in zip(step, ends, feeds[1:] + [float("inf")]):
        assert s < e <= end < nxt
    pulls = [(s, e) for s, e, n in spans if n == "train.end_step.pull"]
    outer = [(s, e) for s, e, n in spans if n == "train.end_step"]
    assert all(a <= s and e <= b for (s, e), (a, b) in zip(pulls, outer))


def test_profile_steps_traces_only_those_steps(tmp_path):
    cfg, tcfg = _smoke(total_steps=5,
                       profile_dir=str(tmp_path / "trace"),
                       profile_steps=(1, 2))
    train_loop(cfg, tcfg, out_dir=None, print_fn=None)
    names = [n for _, _, n in _host_spans(tmp_path / "trace")]
    assert names.count("train.step") == 2
    assert names.count("train.feed") == 2
