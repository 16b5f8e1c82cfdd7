"""Operation and byte counts against counts made by hand, and the peaks."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import flops
from benchmarks.chip.peaks import peak

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

# Qwen1.5-0.5B's and Qwen2.5-1.5B's published sizes (huggingface.co/Qwen)
QWEN15_05B = {"hidden_size": 1024, "intermediate_size": 2816,
              "num_hidden_layers": 24, "num_attention_heads": 16,
              "num_key_value_heads": 16, "vocab_size": 151936,
              "attention_bias": True, "tie_word_embeddings": True}
QWEN25_15B = {"hidden_size": 1536, "intermediate_size": 8960,
              "num_hidden_layers": 28, "num_attention_heads": 12,
              "num_key_value_heads": 2, "vocab_size": 151936,
              "attention_bias": True, "tie_word_embeddings": True}


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("c, layer, head", [
    # q + k + v + o + gate/up/down, by hand
    (cfg("qwen25-05b"), 896 * 896 + 2 * 896 * 128 + 896 * 896
     + 3 * 896 * 4864, 896 * 151936),
    (QWEN15_05B, 4 * 1024 * 1024 + 3 * 1024 * 2816, 1024 * 151936),
    (QWEN25_15B, 1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536
     + 3 * 1536 * 8960, 1536 * 151936),
])
def test_parameter_counts(c, layer, head):
    assert flops.layer_matmul_params(c) == layer
    assert flops.head_params(c) == head


def test_train_flops_per_token_by_hand():
    # 3 x (24 layers x 2 x 14,909,440 + 2 x 136,134,656 head
    #      + 24 x 4 x 14 heads x 64 x 1025 / 2 causal pairs per token)
    want = 3 * (24 * 2 * 14_909_440 + 2 * 136_134_656
                + 24 * 4 * 14 * 64 * 1025 / 2)
    assert flops.train_flops_per_token(cfg("qwen25-05b"), 1024) == \
        pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(3.096e9, rel=1e-3)


def test_attention_pairs_by_hand():
    # a chunk of 3 at positions 10..12 attends to 11 + 12 + 13 positions
    assert flops.attention_pairs(10, 3) == 36
    # one token at position 99 attends to 100 positions
    c = cfg("qwen25-05b")
    assert flops.forward_flops(c, 99, 1) == 24 * (
        2 * 14_909_440 + 4 * 14 * 64 * 100)


def test_unknown_device_kind_raises():
    assert peak("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peak("TPU v9 imaginary")
