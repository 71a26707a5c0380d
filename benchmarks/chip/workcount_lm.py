"""Operations and bytes of a hybrid LM's served prefill, counted from the
configuration's shapes (`configs/<c>.json`, the published keys),
independent of any implementation.

A multiply-add is two operations. Counted: every product of a layer's
projections, the SSD map of each Mamba-2 mixer as the state-space-dual
algorithm computes it in chunks of `mamba_chunk_size` (the intra-chunk
(C.B) x and inter-chunk state products), the depthwise convolution,
causal attention's scores and weighted values (the lower triangle), the
SwiGLU MLP, and the heads at the last position only. Norms, activations,
decays and softmaxes are left out: a few percent of a layer.
"""
from __future__ import annotations

import math


def _mamba(cfg: dict, seq: int) -> int:
    d, di = cfg["hidden_size"], cfg["mamba_expand"] * cfg["hidden_size"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    heads, hd, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    chunk = min(cfg["mamba_chunk_size"], seq)
    proj = 2 * seq * d * (2 * di + 2 * gn + heads) + 2 * seq * di * d
    conv = 2 * seq * cfg["mamba_d_conv"] * (di + 2 * gn)
    # per chunk: C.B over the chunk's pairs, their weights times x, each
    # position's state contribution, and the states read back by C
    per_chunk = (2 * chunk * chunk * gn + 2 * chunk * chunk * heads * hd
                 + 2 * 2 * chunk * heads * hd * n)
    return proj + conv + math.ceil(seq / chunk) * per_chunk


def _attention(cfg: dict, seq: int) -> int:
    d, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    proj = 2 * seq * d * (heads + 2 * kv) * hd + 2 * seq * heads * hd * d
    pairs = seq * (seq + 1) // 2
    return proj + 2 * 2 * pairs * heads * hd


def _mlp(cfg: dict, seq: int) -> int:
    return 3 * 2 * seq * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def layer_flops(cfg: dict, i: int, seq: int) -> int:
    """Layer i (0-based) of one sequence of `seq` tokens: mixer and MLP."""
    mixer = _attention if cfg["layer_types"][i] == "attention" else _mamba
    return mixer(cfg, seq) + _mlp(cfg, seq)


def head_flops(cfg: dict) -> int:
    """One last-position head: hidden size x vocabulary."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def edge_flops(cfg: dict, seq: int) -> int:
    """Per sequence: the layers up to the exit, and the exit head."""
    cut = cfg["exit"]["after_layer"] + 1
    return sum(layer_flops(cfg, i, seq) for i in range(cut)) + head_flops(cfg)


def cloud_flops(cfg: dict, seq: int) -> int:
    """Per refused sequence: the layers after the exit, and the final head."""
    cut = cfg["exit"]["after_layer"] + 1
    return sum(layer_flops(cfg, i, seq) for i in range(cut, cfg["num_hidden_layers"])) \
        + head_flops(cfg)


def payload_elements(cfg: dict, seq: int) -> int:
    """Elements of the hidden state the edge ships for one sequence."""
    return seq * cfg["hidden_size"]


def value_bytes(cfg: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[cfg["dtype"]]


def codec_bytes(rows: int, elements: int, bits: int, tile: int, value_bytes: int) -> int:
    """HBM bytes of one encode plus one decode of `rows` payloads of
    `value_bytes`-byte values: encode reads the payload and writes the
    packed words and one float32 scale per `tile` values; decode reads
    those back and writes the payload. Padding is not counted."""
    wire = elements * bits // 8 + 4 * math.ceil(elements / tile)
    return 2 * rows * (value_bytes * elements + wire)
