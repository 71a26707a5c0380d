"""Basic layers: norms, MLPs, embeddings, rotary embeddings.

Pure-functional style: ``init_*`` builds a params pytree (nested dicts of
jnp arrays); ``apply`` functions consume it. Compute follows the usual mixed
precision discipline: params and matmuls in cfg.dtype (bf16), normalization
and softmax statistics in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


def cdtype(cfg):
    return DTYPES[cfg.dtype]


# ----------------------------------------------------------------- norms
def init_norm(key, cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm_type == "nonparametric_ln":  # OLMo: no scale/bias
        return {}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(p, cfg, x):
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_type in ("layernorm", "nonparametric_ln"):
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        if p:
            y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps)
        y = y * p["scale"]
    return y.astype(x.dtype)


def rms_norm_headwise(x, scale, eps):
    """qk-norm: RMS over the head_dim of (..., head_dim)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ----------------------------------------------------------------- MLP
def init_mlp(key, cfg, d_ff=None):
    d, dt = cfg.d_model, cdtype(cfg)
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d ** -0.5
    s_out = d_ff ** -0.5
    p = {
        "w_up": (jax.random.normal(k2, (d, d_ff)) * s_in).astype(dt),
        "w_down": (jax.random.normal(k3, (d_ff, d)) * s_out).astype(dt),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = (jax.random.normal(k1, (d, d_ff)) * s_in).astype(dt)
    return p


def apply_mlp(p, cfg, x):
    up = x @ p["w_up"]
    if cfg.mlp_type == "swiglu":
        up = jax.nn.silu(x @ p["w_gate"]) * up
    else:
        up = jax.nn.gelu(up)
    return up @ p["w_down"]


# ----------------------------------------------------------------- embeddings
def init_embed(key, cfg):
    dt = cdtype(cfg)
    w = jax.random.normal(key, (cfg.vocab_size, cfg.d_model)) * 0.02
    return {"w": w.astype(dt)}


def apply_embed(p, tokens):
    return jnp.take(p["w"], tokens, axis=0)


def init_unembed(key, cfg):
    dt = cdtype(cfg)
    w = jax.random.normal(key, (cfg.d_model, cfg.vocab_size)) * cfg.d_model ** -0.5
    return {"w": w.astype(dt)}


def apply_unembed(p, x):
    return x @ p["w"]


# ----------------------------------------------------------------- rotary
def rope_freqs(cfg, positions):
    """positions: int32 (...,). Returns cos/sin of shape (..., head_dim//2)."""
    hd = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., seq, heads, head_dim); cos/sin: (..., seq, head_dim//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(
        x.dtype
    )
