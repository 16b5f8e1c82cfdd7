"""The least operations and bytes a piece of work needs, from its shapes.

Operations count a multiply and an add as two.  Nothing the program does
beyond the least counts: no recomputation under remat, no masked attention
positions, no padding of the vocabulary.  So a share of a peak built on
these numbers cannot pass 100% unless the time leaves out part of the
work.
"""
from __future__ import annotations


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    kvh = c["num_key_value_heads"]
    return {"L": c["num_hidden_layers"], "d": d, "h": h, "kvh": kvh,
            "hd": hd, "q": h * hd, "kv": kvh * hd,
            "ff": c["intermediate_size"], "V": c["vocab_size"]}


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer's matrix products."""
    m = dims(c)
    d = m["d"]
    return d * m["q"] + 2 * d * m["kv"] + m["q"] * d + 3 * d * m["ff"]


def head_params(c: dict) -> int:
    m = dims(c)
    return m["d"] * m["V"]


def attention_pairs(start: int, n: int) -> int:
    """Causal (query, key) pairs of ``n`` tokens at positions start..: each
    attends to itself and all before it."""
    return n * start + n * (n + 1) // 2


def forward_flops(c: dict, start: int, n: int) -> int:
    """Operations of ``n`` tokens at positions ``start``.. through every
    layer, without the head."""
    m = dims(c)
    return m["L"] * (2 * n * layer_matmul_params(c)
                     + 4 * m["h"] * m["hd"] * attention_pairs(start, n))


def head_flops(c: dict, n: int) -> int:
    return 2 * n * head_params(c)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward (three times the forward) per token of rows of
    ``seq_len`` tokens, the head at every position, causal attention at its
    least."""
    return 3 * (forward_flops(c, 0, seq_len) + head_flops(c, seq_len)) \
        / seq_len

