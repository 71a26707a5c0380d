"""Plain reference of granite-4.0-h-micro at prefill, from `granite_4_0_h_micro.json`.

Written from the configuration alone (the published `config.json` keys and
the modelling code they name, `granitemoehybrid`); it imports nothing of
the program. Every function takes the configuration as a dict of those
keys, so the CPU tests run it on a small configuration of the same shape.

* `make_params`: the run's weights from a key, in the layout the program's
  stack takes: one entry per run of layers of one kind (Mamba or attention)
  that does not cross the exit, stacked on a leading layer axis where the
  run is longer than one layer. Matrices in bfloat16, the configuration's
  dtype; norm scales and the Mamba A, D and dt bias in float32.
* `edge` / `cloud`: the forward pass in `jax.numpy`, float32, layer by
  layer (one jitted call per layer and block of sequences, no scan over
  layers, no cache), every product at `HIGHEST`. The Mamba-2 mixer is the SSD
  recurrence one time step at a time, h_t = exp(-dt_t A) h_{t-1} +
  (dt_t x_t) B_t^T and y_t = h_t C_t + D x_t, a sequential `lax.scan` over
  the sequence; attention is full causal softmax with no position encoding.
* `precision="f8"` is the control: both operands of every product, weights
  and activations, rounded (saturating) to float8 e4m3 first, one step
  below the configuration's bfloat16.
* temperature fit and tied interval: as in the B-AlexNet reference, with
  the float64 NLL of a 100,352-class vocabulary summed over threads by rows
  and each search ended once its bracket stops moving in float64; the
  codec's wire format: the B-AlexNet reference's functions, which this
  module loads.

Granite's scalings, where the published modelling code applies them:
embeddings times `embedding_multiplier`; attention scores times
`attention_multiplier` (no 1/sqrt(head_dim)); each mixer and MLP output
times `residual_multiplier` before its residual add; logits divided by
`logits_scaling`. The gated RMSNorm of the Mamba mixer normalises
y * silu(z) over the group (here the whole d_inner: one group).

Departures from the published model, both the system's: the exit after
`exit.after_layer` and its head, an RMSNorm and an unembed of its own
(d x vocab), whose logits are divided by `logits_scaling` as the final
head's are.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from collections import namedtuple
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
CONFIG = json.loads((Path(__file__).with_suffix(".json")).read_text())
HIGHEST = jax.lax.Precision.HIGHEST


def _alexnet_reference():
    """The B-AlexNet reference, for its calibration and codec functions."""
    spec = importlib.util.spec_from_file_location("granite_ref_common", HERE / "b_alexnet.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_COMMON = _alexnet_reference()
softmax64 = _COMMON.softmax64
encode = _COMMON.encode
decode = _COMMON.decode

#: the sizes a layer's computation needs, hashable for `jax.jit`
Dims = namedtuple("Dims", "d vocab heads kv hd mheads mhd state groups conv di ff eps "
                          "emb attn res logit")


def dims(cfg) -> Dims:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    assert di == cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return Dims(d=d, vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=d // cfg["num_attention_heads"],
                mheads=cfg["mamba_n_heads"], mhd=cfg["mamba_d_head"],
                state=cfg["mamba_d_state"], groups=cfg["mamba_n_groups"],
                conv=cfg["mamba_d_conv"], di=di, ff=cfg["shared_intermediate_size"],
                eps=float(cfg["rms_norm_eps"]), emb=float(cfg["embedding_multiplier"]),
                attn=float(cfg["attention_multiplier"]),
                res=float(cfg["residual_multiplier"]), logit=float(cfg["logits_scaling"]))


def segments(cfg):
    """[(kind, first layer, layers)]: runs of one mixer kind, each ending at
    the exit or where the kind changes, as the program stacks them."""
    kinds = cfg["layer_types"]
    exit_after = cfg["exit"]["after_layer"]
    out, start = [], 0
    for i, kind in enumerate(kinds):
        if i + 1 == len(kinds) or kinds[i + 1] != kind or i == exit_after:
            out.append((kind, start, i - start + 1))
            start = i + 1
    return out


# ------------------------------------------------------------------ weights
def _normal(k, shape, std, dtype=jnp.bfloat16):
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _scale(k, n):
    return 1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)


def _layer(k, kind, m: Dims):
    ks = jax.random.split(k, 17)
    p = {"mixer_norm": {"scale": _scale(ks[0], m.d)}, "ffn_norm": {"scale": _scale(ks[1], m.d)},
         "mlp": {"w_gate": _normal(ks[2], (m.d, m.ff), m.d ** -0.5),
                 "w_up": _normal(ks[3], (m.d, m.ff), m.d ** -0.5),
                 "w_down": _normal(ks[4], (m.ff, m.d), m.ff ** -0.5)}}
    if kind == "attention":
        p["attn"] = {"wq": _normal(ks[5], (m.d, m.heads, m.hd), m.d ** -0.5),
                     "wk": _normal(ks[6], (m.d, m.kv, m.hd), m.d ** -0.5),
                     "wv": _normal(ks[7], (m.d, m.kv, m.hd), m.d ** -0.5),
                     "wo": _normal(ks[8], (m.heads, m.hd, m.d), (m.heads * m.hd) ** -0.5)}
    else:
        conv_ch = m.di + 2 * m.groups * m.state
        in_dim = 2 * m.di + 2 * m.groups * m.state + m.mheads
        # Mamba-2's initialisation: A in [1, 16], dt in [1e-3, 1e-1] log-uniform
        a = jax.random.uniform(ks[9], (m.mheads,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(ks[10], (m.mheads,), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        p["mamba"] = {"in_proj": _normal(ks[11], (m.d, in_dim), m.d ** -0.5),
                      "conv_w": _normal(ks[12], (m.conv, conv_ch), m.conv ** -0.5),
                      "conv_b": _normal(ks[13], (conv_ch,), 0.1),
                      "A_log": jnp.log(a), "D": _scale(ks[14], m.mheads),
                      "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                      "norm_scale": _scale(ks[15], m.di),
                      "out_proj": _normal(ks[16], (m.di, m.d), m.di ** -0.5)}
    return p


def make_params(key, cfg=CONFIG):
    """Seeded random weights on the device, one jitted call per run of
    layers. Matrices normal with variance 1 / fan-in; the embedding normal
    with standard deviation 0.5 / sqrt(d), at which the last prompt token
    is the full model's answer for about a third of random prompts (not
    for all: a larger embedding, times 12, outweighs every branch); the
    exit head's unembed starts as the transposed embedding, as an exit
    head is commonly initialised from the tied head, so the exit agrees
    with the full model on some prompts and its calibrated confidence
    spreads."""
    m = dims(cfg)
    k_embed, k_final, k_exit, k_layers = jax.random.split(key, 4)

    @functools.partial(jax.jit, static_argnames=("kind", "n"))
    def run(k, kind, n):
        if n == 1:
            return _layer(k, kind, m)
        return jax.vmap(lambda kk: _layer(kk, kind, m))(jax.random.split(k, n))

    segs = [run(jax.random.fold_in(k_layers, start), kind=kind, n=n)
            for kind, start, n in segments(cfg)]

    @jax.jit
    def heads(ke, kf, kx):
        embed = _normal(ke, (m.vocab, m.d), 0.5 * m.d ** -0.5)
        return ({"w": embed}, {"scale": _scale(kf, m.d)},
                [{"norm": {"scale": _scale(kx, m.d)}, "head": {"w": embed.T}}])

    embed, final_norm, exits = heads(k_embed, k_final, k_exit)
    return {"embed": embed, "segments": segs, "final_norm": final_norm, "exits": exits}


def layer_params(params, cfg, i):
    """(kind, weights) of layer i, sliced out of its run."""
    for j, (kind, start, n) in enumerate(segments(cfg)):
        if start <= i < start + n:
            seg = params["segments"][j]
            return kind, (seg if n == 1 else jax.tree.map(lambda a: a[i - start], seg))
    raise IndexError(i)


# ------------------------------------------------------------------ forward
def _f8(x):
    """Round to float8 e4m3, saturating at its largest finite value."""
    big = float(jnp.finfo(jnp.float8_e4m3fn).max)
    return jnp.clip(x, -big, big).astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _product(precision):
    """einsum over float32 operands, both rounded first for the control."""
    cast = _f8 if precision == "f8" else (lambda a: a)
    if precision not in ("f32", "f8"):
        raise ValueError(f"unknown precision {precision!r}")

    def mm(spec, a, b):
        a, b = cast(a.astype(jnp.float32)), cast(b.astype(jnp.float32))
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return mm


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def ssd(x, dt, A, B, C, D, mm):
    """The SSD recurrence, one time step at a time. x (b,t,h,p), dt (b,t,h),
    A (h,) > 0, B, C (b,t,g,n), D (h,) -> y (b,t,h,p); head i reads group
    i // (h / g)."""
    b, _, h, p = x.shape
    rep = h // B.shape[2]

    def step(state, inputs):
        xt, dtt, bt, ct = inputs  # (b,h,p), (b,h), (b,g,n), (b,g,n)
        bt, ct = jnp.repeat(bt, rep, axis=1), jnp.repeat(ct, rep, axis=1)
        state = (state * jnp.exp(-dtt * A)[..., None, None]
                 + mm("bhp,bhn->bhpn", dtt[..., None] * xt, bt))
        return state, mm("bhpn,bhn->bhp", state, ct)

    time_major = lambda a: jnp.moveaxis(a, 1, 0)
    state = jnp.zeros((b, h, p, B.shape[3]), jnp.float32)
    _, y = jax.lax.scan(step, state, (time_major(x), time_major(dt), time_major(B),
                                      time_major(C)))
    return jnp.moveaxis(y, 0, 1) + x * D[:, None]


def _mamba(p, x, m: Dims, mm):
    bsz, t, _ = x.shape
    gn = m.groups * m.state
    proj = mm("btd,de->bte", x, p["in_proj"])
    z, xbc, dt = jnp.split(proj, [m.di, 2 * m.di + 2 * gn], axis=-1)
    w = p["conv_w"].astype(jnp.float32)
    pad = jnp.concatenate([jnp.zeros((bsz, m.conv - 1, xbc.shape[-1])), xbc], axis=1)
    conv = sum(pad[:, i:i + t] * w[i] for i in range(m.conv)) + p["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    xs, B, C = jnp.split(xbc, [m.di, m.di + gn], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(xs.reshape(bsz, t, m.mheads, m.mhd), dt, jnp.exp(p["A_log"]),
            B.reshape(bsz, t, m.groups, m.state), C.reshape(bsz, t, m.groups, m.state),
            p["D"], mm).reshape(bsz, t, m.di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], m.eps)
    return mm("bte,ed->btd", y, p["out_proj"])


def _attention(p, x, m: Dims, mm):
    t = x.shape[1]
    q = mm("btd,dhk->bthk", x, p["wq"])
    k = mm("btd,dhk->bthk", x, p["wk"])
    v = mm("btd,dhk->bthk", x, p["wv"])
    rep = m.heads // m.kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)  # query head i reads kv i // rep
    s = mm("bthk,bshk->bhts", q, k) * m.attn
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bhts,bshk->bthk", probs, v)
    return mm("bthk,hkd->btd", o, p["wo"])


def _mlp(p, x, mm):
    up = mm("btd,df->btf", x, p["w_up"])
    gate = mm("btd,df->btf", x, p["w_gate"])
    return mm("btf,fd->btd", jax.nn.silu(gate) * up, p["w_down"])


@functools.partial(jax.jit, static_argnames=("kind", "m", "precision"))
def block(p, x, kind: str, m: Dims, precision: str):
    """One layer: mixer and MLP, each scaled by the residual multiplier."""
    mm = _product(precision)
    h = rms_norm(x, p["mixer_norm"]["scale"], m.eps)
    h = _attention(p["attn"], h, m, mm) if kind == "attention" else _mamba(p["mamba"], h, m, mm)
    x = x + m.res * h
    h = _mlp(p["mlp"], rms_norm(x, p["ffn_norm"]["scale"], m.eps), mm)
    return x + m.res * h


@functools.partial(jax.jit, static_argnames=("m",))
def embed(w, tokens, m: Dims):
    return jnp.take(w, tokens, axis=0).astype(jnp.float32) * m.emb


@functools.partial(jax.jit, static_argnames=("m", "precision", "spec"))
def head(norm, w, x_last, m: Dims, precision: str, spec: str = "bd,dv->bv"):
    """Last-position logits: RMSNorm, an unembed w ((d, vocab) or, tied,
    the (vocab, d) embedding with `spec` "bd,vd->bv"), / logits_scaling."""
    return _product(precision)(spec, rms_norm(x_last, norm["scale"], m.eps), w) / m.logit


def _layers(params, cfg, xs, first, last, precision):
    """Layers [first, last) over each block of sequences in `xs`, layer by
    layer: each layer's weights are sliced out of its run once."""
    m = dims(cfg)
    for i in range(first, last):
        kind, p = layer_params(params, cfg, i)
        xs = [block(p, x, kind, m, precision) for x in xs]
    return xs


def edge_blocks(params, tokens, precision="f32", cfg=CONFIG, rows=None):
    """(exit logits (b, vocab) float32, the hidden state after the exit as a
    list of `rows`-sequence blocks): the layers up to the exit and the exit
    head, all sequences at once where `rows` is None."""
    m = dims(cfg)
    tokens = np.asarray(tokens)
    rows = rows or len(tokens)
    xs = [embed(params["embed"]["w"], jnp.asarray(tokens[i:i + rows]), m)
          for i in range(0, len(tokens), rows)]
    xs = _layers(params, cfg, xs, 0, cfg["exit"]["after_layer"] + 1, precision)
    ex = params["exits"][0]
    z = [head(ex["norm"], ex["head"]["w"], x[:, -1], m, precision) for x in xs]
    return jnp.concatenate(z), xs


def edge(params, tokens, precision: str = "f32", cfg=CONFIG, rows=None):
    """(exit logits (b, vocab), hidden (b, t, d)) float32: the layers up to
    the exit and the exit head."""
    z, xs = edge_blocks(params, tokens, precision, cfg, rows)
    return z, jnp.concatenate(xs)


def cloud(params, hidden, precision: str = "f32", cfg=CONFIG, rows=None):
    """Final logits (b, vocab) float32 from the hidden state after the exit:
    the remaining layers and the tied head, in blocks of `rows` sequences
    (the last block padded with zero rows, so that every block has one
    shape)."""
    m = dims(cfg)
    n = len(hidden)
    rows = rows or n
    hidden = jnp.asarray(hidden).astype(jnp.float32)
    pad = -n % rows
    if pad:
        hidden = jnp.concatenate([hidden, jnp.zeros((pad,) + hidden.shape[1:], hidden.dtype)])
    xs = [hidden[i:i + rows] for i in range(0, n + pad, rows)]
    xs = _layers(params, cfg, xs, cfg["exit"]["after_layer"] + 1, cfg["num_hidden_layers"],
                 precision)
    return jnp.concatenate([head(params["final_norm"], params["embed"]["w"], x[:, -1], m,
                                 precision, "bd,vd->bv") for x in xs])[:n]


# -------------------------------------------------------------- calibration
class Nll:
    """The mean float64 NLL of softmax(z / T) against labels y, and the
    largest probability of each row, for many T. Each row's offsets from
    its largest logit are kept once (z - max <= 0); the sums over the
    vocabulary run on `pool`'s threads, a share of the rows each."""

    def __init__(self, z, y, pool: ThreadPoolExecutor, threads: int):
        z = np.asarray(z, np.float64)
        self.d = z - z.max(-1, keepdims=True)
        self.d_y = self.d[np.arange(len(y)), np.asarray(y)]
        n, k = len(z), threads
        self.parts = [slice(i * n // k, (i + 1) * n // k) for i in range(k)]
        self.pool = pool

    def _sums(self, t):
        """sum_j exp((z_j - max) / T) of each row."""
        beta = 1.0 / float(t)
        return np.concatenate(list(self.pool.map(
            lambda s: np.exp(self.d[s] * beta).sum(-1), self.parts)))

    def __call__(self, t) -> float:
        return float(np.mean(np.log(self._sums(t)) - self.d_y / float(t)))

    def confidence(self, t):
        return 1.0 / self._sums(t)


def fit_temperature(nll, t_min, t_max, iters: int = 200) -> float:
    """argmin over T in [t_min, t_max] of `nll(T)`: the B-AlexNet
    reference's golden-section search in beta = 1 / T, ended once the
    bracket is as narrow as float64 resolves."""
    lo, hi = 1.0 / t_max, 1.0 / t_min
    phi = (np.sqrt(5.0) - 1.0) / 2.0
    f = lambda beta: nll(1.0 / beta)
    a, b = lo + (1 - phi) * (hi - lo), lo + phi * (hi - lo)
    fa, fb = f(a), f(b)
    for _ in range(iters):
        if hi - lo <= 4 * np.finfo(np.float64).eps * hi:
            break
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


def tied_interval(nll, t, t_min, t_max, tol, iters: int = 100):
    """[T_lo, T_hi]: every temperature whose `nll` lies within `tol` of the
    optimum's at `t` (the B-AlexNet reference's bisection on each side,
    ended where the midpoint rounds to an end)."""
    target = nll(t) + tol

    def edge(inside, outside):
        if nll(outside) <= target:
            return outside
        for _ in range(iters):
            mid = 0.5 * (inside + outside)
            if mid in (inside, outside):
                break
            inside, outside = (mid, outside) if nll(mid) <= target else (inside, mid)
        return inside

    return edge(t, t_min), edge(t, t_max)


def calibrate(params, val_tokens, labels, precision, share, rows, cfg=CONFIG, chunk=64):
    """The temperature fit of the reference's exit logits on the validation
    prompts against `labels`, and the threshold that offloads `share` of
    them: {"t", "t_lo", "t_hi", "p_tar", "p_lo", "p_hi"} (as the B-AlexNet
    reference's `calibrate`: every temperature whose NLL ties the optimum
    is as right as `t`). The exit runs on `chunk` prompts at a time, so
    that no more of their hidden states are held at once."""
    z = np.concatenate([np.asarray(edge_blocks(params, val_tokens[i:i + chunk], precision,
                                               cfg, rows)[0])
                        for i in range(0, len(val_tokens), chunk)])
    cal = cfg["calibration"]
    threads = min(os.cpu_count() or 1, 16)
    with ThreadPoolExecutor(threads) as pool:
        nll = Nll(z, labels, pool, threads)
        t = fit_temperature(nll, cal["t_min"], cal["t_max"])
        t_lo, t_hi = tied_interval(nll, t, cal["t_min"], cal["t_max"], cal["nll_tie"])
        q = lambda temp: float(np.quantile(nll.confidence(temp), share))
        return {"t": t, "t_lo": t_lo, "t_hi": t_hi, "p_tar": q(t), "p_lo": q(t_hi),
                "p_hi": q(t_lo)}
