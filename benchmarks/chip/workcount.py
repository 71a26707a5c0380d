"""Operations and bytes counted from shapes, independent of any implementation.

A multiply-add is two operations. Only the products of convolutions and
dense layers are counted (biases, activations and pooling are a few
percent of a convolution's adds and are left out), so a model's count is
what its layers require, whichever kernels compute them.
"""
from __future__ import annotations

import math


def _conv_flops(h: int, w: int, spec: dict) -> int:
    """A stride-1 SAME convolution over an h x w map."""
    return 2 * h * w * spec["cout"] * spec["k"] * spec["k"] * spec["cin"]


def _after_pool(h: int, cfg: dict) -> int:
    return -(-h // cfg["pool"]["stride"])  # SAME padding


def _walk(cfg: dict):
    """Yield (name, flops, map size after the layer) along the trunk."""
    h, w = cfg["input"][0], cfg["input"][1]
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            flops = _conv_flops(h, w, layer)
            if layer["pool"]:
                h, w = _after_pool(h, cfg), _after_pool(w, cfg)
        else:
            flops = 2 * layer["din"] * layer["dout"]
        yield layer["name"], flops, (h, w)


def branch_flops(cfg: dict, branch: int) -> int:
    """The side branch `branch` (1-based): its convolution and dense head."""
    spec = cfg["branches"][branch - 1]
    for name, _, (h, w) in _walk(cfg):
        if name == spec["after"]:
            return _conv_flops(h, w, spec["conv"]) + 2 * spec["fc"]["din"] * spec["fc"]["dout"]
    raise KeyError(spec["after"])


def edge_flops(cfg: dict, branch: int) -> int:
    """Per image: the trunk up to the branch's layer, plus the branch."""
    after = cfg["branches"][branch - 1]["after"]
    total = 0
    for name, flops, _ in _walk(cfg):
        total += flops
        if name == after:
            break
    return total + branch_flops(cfg, branch)


def cloud_flops(cfg: dict, branch: int) -> int:
    """Per image: every trunk layer after the branch's layer."""
    after = cfg["branches"][branch - 1]["after"]
    total, past = 0, False
    for name, flops, _ in _walk(cfg):
        if past:
            total += flops
        past = past or name == after
    return total


def payload_elements(cfg: dict, branch: int) -> int:
    """Elements of the activation the edge ships for one image."""
    after = cfg["branches"][branch - 1]["after"]
    cout = {l["name"]: l.get("cout") for l in cfg["layers"]}
    for name, _, (h, w) in _walk(cfg):
        if name == after:
            return h * w * cout[name]
    raise KeyError(after)


def codec_bytes(rows: int, elements: int, bits: int, tile: int) -> int:
    """HBM bytes of one encode plus one decode of `rows` payloads.

    Encode reads the float32 payload and writes the packed words and one
    float32 scale per `tile` features; decode reads those back and writes
    the float32 payload. Padding to the kernels' blocks is not counted.
    """
    groups = math.ceil(elements / tile)
    raw = 4 * elements
    wire = elements * bits // 8 + 4 * groups
    return 2 * rows * (raw + wire)


def gate_bytes(rows: int, classes: int) -> int:
    """The exit gate: float32 logits in, three float32 values per row out
    (confidence, prediction, entropy); the padded tile is not counted."""
    return rows * (4 * classes + 3 * 4)
