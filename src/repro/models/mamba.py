"""Mamba2 (state-space duality / SSD) block.

Implements the chunked SSD algorithm of Dao & Gu (arXiv:2405.21060):
  * in_proj -> [z, x, B, C, dt]; causal depthwise conv over (x, B, C);
  * intra-chunk "attention-like" quadratic term + inter-chunk linear
    recurrence over per-chunk states (the duality);
  * gated RMSNorm and out_proj.

Decode keeps O(1) state per layer: a (conv_k-1)-step conv buffer and the
(heads, head_dim, state) SSD state -- this is why long_500k decode is
natively cheap for SSM and hybrid architectures.

Sharding: heads/channels shard on the 'model' mesh axis; the scan over
chunks is sequential in the sequence dimension (time), which shards on
nothing -- batch shards on data axes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import cdtype


def _dims(cfg):
    di = cfg.d_inner
    h = cfg.ssm_heads
    g, n, ck = cfg.ssm_n_groups, cfg.ssm_state, cfg.ssm_conv
    conv_ch = di + 2 * g * n
    return di, h, g, n, ck, conv_ch


def init_mamba(key, cfg):
    d = cfg.d_model
    di, h, g, n, ck, conv_ch = _dims(cfg)
    dt = cdtype(cfg)
    ks = jax.random.split(key, 4)
    if cfg.mamba_split_proj:
        # perf-pass: keep dt separate so in_proj's width (2*di + 2*g*n) is
        # divisible by the 16-way model axis -- the fused width includes the
        # head count (e.g. +24) which breaks divisibility and forces the
        # whole projection to replicate (collective-bound prefill).
        p = {
            "in_proj": (
                jax.random.normal(ks[0], (d, 2 * di + 2 * g * n)) * d ** -0.5
            ).astype(dt),
            "dt_proj": (jax.random.normal(ks[2], (d, h)) * d ** -0.5).astype(dt),
        }
    else:
        in_dim = 2 * di + 2 * g * n + h
        p = {
            "in_proj": (jax.random.normal(ks[0], (d, in_dim)) * d ** -0.5).astype(dt),
        }
    p.update({
        "conv_w": (jax.random.normal(ks[1], (ck, conv_ch)) * ck ** -0.5).astype(dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01, jnp.float32))),
        "norm_scale": jnp.ones((di,), jnp.float32),
        "out_proj": (jax.random.normal(ks[3], (di, d)) * di ** -0.5).astype(dt),
    })
    return p


def _project_in(p, cfg, x):
    """x @ in_proj -> (z, xbc, dt_raw), handling the split-proj variant."""
    di, h, g, n, _, _ = _dims(cfg)
    if cfg.mamba_split_proj:
        zxbc = x @ p["in_proj"]
        dt_raw = x @ p["dt_proj"]
        z, xbc = jnp.split(zxbc, [di], axis=-1)
        return z, xbc, dt_raw
    proj = x @ p["in_proj"]
    z, xbc_dt = jnp.split(proj, [di], axis=-1)
    xbc, dt_raw = jnp.split(xbc_dt, [di + 2 * g * n], axis=-1)
    return z, xbc, dt_raw


def _gated_out(p, cfg, y, z):
    # gated RMSNorm: norm(y * silu(z)) * scale
    yz = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.float32)
    ms = jnp.mean(jnp.square(yz), axis=-1, keepdims=True)
    yn = yz * jax.lax.rsqrt(ms + cfg.norm_eps) * p["norm_scale"]
    return yn.astype(cdtype(cfg)) @ p["out_proj"]


def ssd_chunked(x, dt, A, B, C, D, chunk):
    """SSD scan. x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n) D:(h,).

    Returns y:(b,s,h,p) fp32 and the final state (b,h,p,n).
    """
    b, s, h, ph = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g  # heads per B/C group
    nc = s // chunk
    xf = x.astype(jnp.float32)
    Bf, Cf = B.astype(jnp.float32), C.astype(jnp.float32)

    xc = xf.reshape(b, nc, chunk, h, ph)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = Bf.reshape(b, nc, chunk, g, n)
    Cc = Cf.reshape(b, nc, chunk, g, n)

    dA = dtc * A  # (b,nc,l,h), positive decay rates (A = exp(A_log) > 0)
    dA_cs = jnp.cumsum(dA, axis=2)  # inclusive cumsum

    # ---- intra-chunk (quadratic) term -------------------------------------
    # CB[i,j] per group, decay exp(-(cs_i - cs_j)) for i>=j, weight dt_j
    cb = jnp.einsum("bclgn,bcmgn->bcglm", Cc, Bc)  # (b,nc,g,l,l)
    cb = jnp.repeat(cb, hg, axis=2)  # (b,nc,h,l,l)
    seg = dA_cs[..., :, None, :] - dA_cs[..., None, :, :]  # (b,nc,l,l,h) = cs_i-cs_j
    seg = jnp.moveaxis(seg, -1, 2)  # (b,nc,h,l,l)
    li = jnp.arange(chunk)
    causal = li[:, None] >= li[None, :]
    decay = jnp.where(causal, jnp.exp(-seg), 0.0)
    att = cb * decay * jnp.moveaxis(dtc, -1, 2)[..., None, :]  # * dt_j
    y_diag = jnp.einsum("bchlm,bcmhp->bclhp", att, xc)

    # ---- per-chunk input states --------------------------------------------
    # S_c = sum_j exp(-(cs_last - cs_j)) * dt_j * B_j (x) x_j
    decay_states = jnp.exp(-(dA_cs[:, :, -1:, :] - dA_cs))  # (b,nc,l,h)
    w = decay_states * dtc
    Bh = jnp.repeat(Bc, hg, axis=3)  # (b,nc,l,h,n)
    S_in = jnp.einsum("bclh,bclhn,bclhp->bchpn", w, Bh, xc)

    # ---- inter-chunk recurrence over chunk states ---------------------------
    chunk_decay = jnp.exp(-dA_cs[:, :, -1, :])  # (b,nc,h)

    def step(S, inp):
        dec, Sc = inp  # dec:(b,h)  Sc:(b,h,p,n)
        S = S * dec[:, :, None, None] + Sc
        return S, S

    S0 = jnp.zeros((b, h, ph, n), jnp.float32)
    dec_t = jnp.moveaxis(chunk_decay, 1, 0)  # (nc,b,h)
    Sin_t = jnp.moveaxis(S_in, 1, 0)  # (nc,b,h,p,n)
    S_final, S_all = jax.lax.scan(step, S0, (dec_t, Sin_t))
    # states entering each chunk (exclusive)
    S_prev = jnp.concatenate([S0[None], S_all[:-1]], axis=0)
    S_prev = jnp.moveaxis(S_prev, 0, 1)  # (b,nc,h,p,n)

    # ---- inter-chunk output: C_i . S_prev with decay exp(-cs_i) -------------
    Ch = jnp.repeat(Cc, hg, axis=3)  # (b,nc,l,h,n)
    y_off = jnp.einsum("bclhn,bchpn->bclhp", Ch, S_prev) * jnp.exp(-dA_cs)[..., None]

    y = (y_diag + y_off).reshape(b, s, h, ph)
    y = y + xf * D[None, None, :, None]
    return y, S_final


def mamba_prefill(p, cfg, x, q_chunk_unused=None):
    """x: (b, s, d) -> (out (b,s,d), cache{conv, ssd})."""
    b, s, d = x.shape
    di, h, g, n, ck, conv_ch = _dims(cfg)
    z, xbc, dt_raw = _project_in(p, cfg, x)

    # causal depthwise conv, kernel ck
    pad = jnp.zeros((b, ck - 1, conv_ch), xbc.dtype)
    xbc_pad = jnp.concatenate([pad, xbc], axis=1)
    conv = sum(
        xbc_pad[:, i : i + s, :] * p["conv_w"][i][None, None, :] for i in range(ck)
    )
    xbc_c = jax.nn.silu((conv + p["conv_b"]).astype(jnp.float32)).astype(xbc.dtype)

    xs, B, C = jnp.split(xbc_c, [di, di + g * n], axis=-1)
    xs = xs.reshape(b, s, h, cfg.ssm_head_dim)
    B = B.reshape(b, s, g, n)
    C = C.reshape(b, s, g, n)
    A = jnp.exp(p["A_log"])  # (h,) positive
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (b,s,h)

    chunk = min(cfg.ssm_chunk, s)
    if s % chunk != 0:
        chunk = s  # fall back to single chunk for odd smoke shapes
    y, S = ssd_chunked(xs, dtv, A, B, C, p["D"], chunk)
    y = y.reshape(b, s, di)
    out = _gated_out(p, cfg, y.astype(cdtype(cfg)), z)
    cache = {"conv": xbc_pad[:, -(ck - 1) :, :] if ck > 1 else None, "ssd": S}
    return out, cache


def init_mamba_cache(cfg, batch):
    di, h, g, n, ck, conv_ch = _dims(cfg)
    dt = cdtype(cfg)
    return {
        "conv": jnp.zeros((batch, ck - 1, conv_ch), dt),
        "ssd": jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
    }


def mamba_decode(p, cfg, x, cache, pos=None):
    """One-token step. x: (b, 1, d) -> (out (b,1,d), new cache)."""
    b = x.shape[0]
    di, h, g, n, ck, conv_ch = _dims(cfg)
    z, xbc, dt_raw = _project_in(p, cfg, x[:, 0, :])

    conv_buf = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)  # (b,ck,ch)
    conv = jnp.einsum("bkc,kc->bc", conv_buf, p["conv_w"]) + p["conv_b"]
    xbc_c = jax.nn.silu(conv.astype(jnp.float32)).astype(xbc.dtype)

    xs, B, C = jnp.split(xbc_c, [di, di + g * n], axis=-1)
    xs = xs.reshape(b, h, cfg.ssm_head_dim).astype(jnp.float32)
    B = B.reshape(b, g, n).astype(jnp.float32)
    C = C.reshape(b, g, n).astype(jnp.float32)
    hg = h // g
    Bh = jnp.repeat(B, hg, axis=1)  # (b,h,n)
    Ch = jnp.repeat(C, hg, axis=1)
    A = jnp.exp(p["A_log"])
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (b,h)

    decay = jnp.exp(-dtv * A)  # (b,h)
    S = cache["ssd"] * decay[:, :, None, None] + jnp.einsum(
        "bh,bhn,bhp->bhpn", dtv, Bh, xs
    )
    y = jnp.einsum("bhn,bhpn->bhp", Ch, S) + xs * p["D"][None, :, None]
    y = y.reshape(b, 1, di)
    out = _gated_out(p, cfg, y.astype(cdtype(cfg)), z[:, None, :])
    return out, {"conv": conv_buf[:, 1:, :], "ssd": S}
