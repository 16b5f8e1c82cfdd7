"""CPU test set-up for the benchmark: JAX on the CPU, the checkout and the
program importable, and small cells built from the real ones."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
CHECKOUT = Path(__file__).resolve().parents[3]
for p in (str(CHECKOUT), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

SMOKE_CONFIG = {
    "name": "qwen2-smoke", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 500,
    "max_position_embeddings": 4096, "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
    "attention_bias": True, "torch_dtype": "bfloat16"}


def smoke_cell(name: str, **traffic):
    """The cell ``<config>.<mix>`` with the smoke config, the mix cut small
    and the cell's committed limits, if it has any; ``traffic`` overrides
    keys of the cut mix."""
    from benchmarks.chip.harness import Cell
    config, mix = name.split(".", 1)
    bench = {"workloads": [{"name": name, "config": config, "traffic": mix,
                            "chips": 1}],
             "end_to_end": [{"name": "train_tokens_per_s",
                             "unit": "tokens/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    cell = Cell(name, bench=bench)
    cell.config = dict(SMOKE_CONFIG)
    tr = dict(cell.traffic)
    tr.update(global_batch=4, seq_len=32, rows=64,
              documents={"median": 20, "sigma": 1.0, "min": 4, "max": 64})
    tr.update(traffic)
    cell.traffic = tr
    return cell


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices()
