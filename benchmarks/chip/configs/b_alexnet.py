"""Plain reference of the served B-AlexNet offload path, from `b_alexnet.json`.

Written from the configuration alone; it imports nothing of the program.

* `make_params`: the run's weights, one jitted call on the device from a
  key, in the layout the program's convnet takes (HWIO convolutions,
  (din, dout) dense layers, `branch1` / `branch2` side heads).
* `forward`: the unpartitioned model in `jax.numpy`, NHWC, every
  convolution and product at the precision asked for: "highest" is float32,
  "high" is the three-pass bfloat16 product (a = hi + lo in bfloat16;
  hi*hi + hi*lo + lo*hi, each exact in float32), written out so that it
  means the same on every backend.
* `fit_temperature`: temperature scaling, the NLL over T in
  [t_min, t_max] minimised in float64 by golden section over 1/T (the NLL
  is convex in 1/T); `tied_interval`: the temperatures whose NLL ties the
  optimum to the resolution of a float32 fit.
* `encode` / `decode`: the uplink codec's wire format, per (row,
  128-feature group) absmax scale and round-to-nearest-even signed ints,
  packed little-endian into uint32 words.
* `serve`: what the served path must answer for a batch: the gate's
  decision and confidence on the branch, the branch's answer for samples
  that exit and the cloud's answer, through the codec, for those offloaded.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = json.loads((Path(__file__).with_suffix(".json")).read_text())


# ------------------------------------------------------------------ weights
def _shapes(cfg):
    out = {}
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            out[layer["name"]] = ((layer["k"], layer["k"], layer["cin"], layer["cout"]),
                                  layer["cout"])
        else:
            out[layer["name"]] = ((layer["din"], layer["dout"]), layer["dout"])
    for br in cfg["branches"]:
        c, f = br["conv"], br["fc"]
        out[br["name"] + ".conv"] = ((c["k"], c["k"], c["cin"], c["cout"]), c["cout"])
        out[br["name"] + ".fc"] = ((f["din"], f["dout"]), f["dout"])
    return out


def make_params(k, cfg=CONFIG):
    """Seeded random weights on the device, in one jitted call."""
    shapes = _shapes(cfg)

    @jax.jit
    def build(k):
        keys = jax.random.split(k, 2 * len(shapes))
        flat = {}
        for i, (name, (wshape, nb)) in enumerate(sorted(shapes.items())):
            fan_in = int(np.prod(wshape[:-1]))
            w = jax.random.normal(keys[2 * i], wshape, jnp.float32) * np.sqrt(2.0 / fan_in)
            b = jax.random.normal(keys[2 * i + 1], (nb,), jnp.float32) * 0.05
            flat[name] = {"w": w, "b": b}
        return flat

    flat = build(k)
    params = {}
    for name, leaf in flat.items():
        if "." in name:
            top, sub = name.split(".")
            params.setdefault(top, {})[sub] = leaf
        else:
            params[name] = leaf
    return params


# ------------------------------------------------------------------ forward
def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _product(op, x, w, precision):
    full = functools.partial(op, precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return full(x, w)
    if precision == "high":
        xh, xl = _split(x)
        wh, wl = _split(w)
        return full(xh, wh) + (full(xh, wl) + full(xl, wh))
    raise ValueError(f"unknown precision {precision!r}")


def _conv_op(x, w, precision):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


def _dense_op(x, w, precision):
    return jnp.matmul(x, w, precision=precision)


def _conv(p, x, pool, precision, cfg):
    y = jax.nn.relu(_product(_conv_op, x, p["w"], precision) + p["b"])
    if pool:
        pw, ps = cfg["pool"]["window"], cfg["pool"]["stride"]
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, pw, pw, 1),
                                  (1, ps, ps, 1), cfg["pool"]["padding"])
    return y


def _dense(p, x, relu, precision):
    y = _product(_dense_op, x, p["w"], precision) + p["b"]
    return jax.nn.relu(y) if relu else y


def _branch(p, x, spec, precision, cfg):
    y = _conv(p["conv"], x, spec["conv"]["pool"], precision, cfg)
    return _dense(p["fc"], y.reshape(y.shape[0], -1), False, precision)


@functools.partial(jax.jit, static_argnames=("branch", "precision"))
def edge(params, images, branch: int, precision: str):
    """(branch logits, the activation the branch reads): the edge partition."""
    cfg = CONFIG
    x = images
    after = cfg["branches"][branch - 1]["after"]
    for layer in cfg["layers"]:
        x = _conv(params[layer["name"]], x, layer["pool"], precision, cfg)
        if layer["name"] == after:
            break
    spec = cfg["branches"][branch - 1]
    return _branch(params[spec["name"]], x, spec, precision, cfg), x


@functools.partial(jax.jit, static_argnames=("branch", "precision"))
def cloud(params, hidden, branch: int, precision: str):
    """Final logits from the activation after branch `branch`'s layer."""
    cfg = CONFIG
    after = cfg["branches"][branch - 1]["after"]
    names = [layer["name"] for layer in cfg["layers"]]
    x = hidden
    for layer in cfg["layers"][names.index(after) + 1:]:
        if layer["kind"] == "conv":
            x = _conv(params[layer["name"]], x, layer["pool"], precision, cfg)
        else:
            x = _dense(params[layer["name"]], x.reshape(x.shape[0], -1),
                       layer["relu"], precision)
    return x


def edge_blocks(params, images, branch, precision, rows: int = 256):
    """`edge` over a host array in blocks of `rows`: host (logits, hidden)."""
    zs, hs = [], []
    for i in range(0, len(images), rows):
        z, h = edge(params, jnp.asarray(images[i:i + rows]), branch, precision)
        zs.append(np.asarray(z))
        hs.append(np.asarray(h))
    return np.concatenate(zs), np.concatenate(hs)


# -------------------------------------------------------------- calibration
def softmax64(z, temperature=1.0):
    u = np.asarray(z, np.float64) / float(temperature)
    u = u - u.max(-1, keepdims=True)
    e = np.exp(u)
    return e / e.sum(-1, keepdims=True)


def nll(z, y, temperature) -> float:
    u = np.asarray(z, np.float64) / float(temperature)
    m = u.max(-1)
    lse = m + np.log(np.exp(u - m[:, None]).sum(-1))
    return float(np.mean(lse - u[np.arange(len(y)), y]))


def fit_temperature(z, y, t_min, t_max, iters: int = 200) -> float:
    """argmin over T in [t_min, t_max] of the mean NLL of softmax(z / T)."""
    lo, hi = 1.0 / t_max, 1.0 / t_min  # in beta = 1 / T
    phi = (np.sqrt(5.0) - 1.0) / 2.0
    f = lambda beta: nll(z, y, 1.0 / beta)
    a, b = lo + (1 - phi) * (hi - lo), lo + phi * (hi - lo)
    fa, fb = f(a), f(b)
    for _ in range(iters):
        if fa <= fb:
            hi, b, fb = b, a, fa
            a = lo + (1 - phi) * (hi - lo)
            fa = f(a)
        else:
            lo, a, fa = a, b, fb
            b = lo + phi * (hi - lo)
            fb = f(b)
    best = min((f(1.0 / t_max), 1.0 / t_max), (f(1.0 / t_min), 1.0 / t_min),
               (min(fa, fb), (a + b) / 2))
    return 1.0 / best[1]


# -------------------------------------------------------------------- codec
def _rows_cols(shape):
    return int(shape[0]), int(np.prod(shape[1:]))


def encode(x, bits: int, tile: int):
    """(words uint32 (rows, padded * bits / 32), scales float32 (rows, groups))."""
    x = np.asarray(x, np.float32)
    rows, cols = _rows_cols(x.shape)
    z = x.reshape(rows, cols)
    z = np.pad(z, ((0, 0), (0, (-cols) % tile)))
    z = np.where(np.isfinite(z), z, np.float32(0.0))
    qmax = np.float32((1 << (bits - 1)) - 1)
    zt = z.reshape(rows, -1, tile)
    scales = (np.abs(zt).max(axis=2) * (np.float32(1.0) / qmax)).astype(np.float32)
    safe = np.where(scales > 0, scales, np.float32(1.0))
    q = np.clip(np.round(zt / safe[:, :, None]), -qmax, qmax).astype(np.int32)
    q = q.reshape(rows, -1)
    per = 32 // bits
    words = np.zeros((rows, q.shape[1] // per), np.uint32)
    for k in range(per):
        words |= (q[:, k::per].astype(np.uint32) & np.uint32((1 << bits) - 1)) \
            << np.uint32(bits * k)
    return words, scales


def decode(words, scales, shape, bits: int, tile: int):
    per = 32 // bits
    half, full = 1 << (bits - 1), 1 << bits
    words = np.asarray(words, np.uint32)
    rows = words.shape[0]
    v = np.empty((rows, words.shape[1] * per), np.int32)
    for k in range(per):
        u = ((words >> np.uint32(bits * k)) & np.uint32(full - 1)).astype(np.int32)
        v[:, k::per] = np.where(u >= half, u - full, u)
    z = v.reshape(rows, -1, tile).astype(np.float32) * np.asarray(scales, np.float32)[:, :, None]
    cols = _rows_cols(shape)[1]
    return z.reshape(rows, -1)[:, :cols].reshape(shape)


# ------------------------------------------------------------------ serving
def tied_interval(z, y, t, t_min, t_max, tol, iters: int = 100):
    """[T_lo, T_hi]: every temperature whose NLL lies within `tol` of the
    optimum's at `t` (bisection on each side; the NLL is unimodal in T)."""
    target = nll(z, y, t) + tol

    def edge(inside, outside):
        if nll(z, y, outside) <= target:
            return outside
        for _ in range(iters):
            mid = 0.5 * (inside + outside)
            inside, outside = (mid, outside) if nll(z, y, mid) <= target else (inside, mid)
        return inside

    return edge(t, t_min), edge(t, t_max)


def calibrate(params, val_x, val_y, branch, precision, share, cfg=CONFIG):
    """The temperature fit on the validation split and the threshold that
    offloads `share` of it: {"t", "t_lo", "t_hi", "p_tar", "p_lo", "p_hi"}.

    A float32 fit settles anywhere its NLL ties the optimum to float32
    resolution (`calibration.nll_tie`), so every temperature in that tie is
    as right as `t`; the confidences and the threshold then lie between
    their values at `t_hi` and at `t_lo` (both fall as T rises).
    """
    z, _ = edge_blocks(params, val_x, branch, precision)
    cal = cfg["calibration"]
    t = fit_temperature(z, val_y, cal["t_min"], cal["t_max"])
    t_lo, t_hi = tied_interval(z, val_y, t, cal["t_min"], cal["t_max"], cal["nll_tie"])
    q = lambda temp: float(np.quantile(softmax64(z, temp).max(-1), share))
    return {"t": t, "t_lo": t_lo, "t_hi": t_hi,
            "p_tar": q(t), "p_lo": q(t_hi), "p_hi": q(t_lo)}


def serve(params, images, cal, precision, cfg=CONFIG):
    """What the served path answers for one batch under the calibration
    `cal` (from `calibrate`), and the logits behind it."""
    served = cfg["served"]
    branch, bits, tile = served["branch"], served["codec_bits"], served["codec_tile"]
    z, h = edge(params, jnp.asarray(images), branch, precision)
    z, h = np.asarray(z), np.asarray(h)
    conf_edge = softmax64(z, cal["t"]).max(-1)
    words, scales = encode(h, bits, tile)
    h_wire = decode(words, scales, h.shape, bits, tile)
    final = np.asarray(cloud(params, jnp.asarray(h_wire), branch, precision))
    conf_cloud = softmax64(final).max(-1)
    exit_mask = conf_edge >= cal["p_tar"]
    return {
        "on_device": exit_mask,
        "prediction": np.where(exit_mask, z.argmax(-1), final.argmax(-1)),
        "confidence": np.where(exit_mask, conf_edge, conf_cloud),
        "edge_logits": z, "edge_confidence": conf_edge,
        "edge_conf_lo": softmax64(z, cal["t_hi"]).max(-1),
        "edge_conf_hi": softmax64(z, cal["t_lo"]).max(-1),
        "final_logits": final, "cloud_confidence": conf_cloud,
    }
