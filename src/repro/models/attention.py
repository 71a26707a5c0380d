"""GQA attention: chunked causal prefill + KV-cache decode.

Features driven by ModelConfig: grouped-query attention (num_kv_heads <
num_heads), qk-norm (Qwen3), QKV bias (Qwen2), sliding-window masking
(used for long-context decode on dense archs), RoPE or no-PE (Whisper uses
learned absolute embeddings applied outside).

Prefill uses a lax.scan over query chunks with an O(chunk x seq) working set
(flash-attention-style restructuring, implemented at the XLA level; the
per-chunk body is rematerialized in the backward pass). Decode uses a
ring-buffer cache when a sliding window is configured so the cache size is
min(seq_len, window) -- the steady-state memory of windowed attention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, cdtype, rms_norm_headwise, rope_freqs

NEG_INF = -1e30


def init_attention(key, cfg, cross=False):
    d, hd, qh, kvh = cfg.d_model, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = cdtype(cfg)
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    so = (qh * hd) ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, qh, hd)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, kvh, hd)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, kvh, hd)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (qh, hd, d)) * so).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qh, hd), dt)
        p["bk"] = jnp.zeros((kvh, hd), dt)
        p["bv"] = jnp.zeros((kvh, hd), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _project_qkv(p, cfg, x, positions, rope=True):
    """x: (b, s, d) -> q (b,s,qh,hd), k/v (b,s,kvh,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm_headwise(k, p["k_norm"], cfg.norm_eps)
    if rope and cfg.use_rope:
        cos, sin = rope_freqs(cfg, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _gqa_scores(cfg, q, k):
    """q: (b,sq,qh,hd) k: (b,sk,kvh,hd) -> (b,kvh,g,sq,sk) fp32, times the
    score scale: `cfg.attention_multiplier`, else 1/sqrt(head_dim)."""
    b, sq, qh, hd = q.shape
    kvh = k.shape[2]
    g = qh // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    s = jnp.einsum("bqhgk,bshk->bhgqs", qg, k).astype(jnp.float32)
    scale = cfg.attention_multiplier
    return s * (hd ** -0.5 if scale is None else scale)


def _gqa_out(probs, v):
    """probs: (b,kvh,g,sq,sk) fp32; v: (b,sk,kvh,hd) -> (b,sq,qh,hd)."""
    b, kvh, g, sq, sk = probs.shape
    hd = v.shape[-1]
    o = jnp.einsum("bhgqs,bshk->bqhgk", probs.astype(v.dtype), v)
    return o.reshape(b, sq, kvh * g, hd)


def attention_prefill(p, cfg, x, positions, q_chunk=1024, memory=None):
    """Causal (optionally sliding-window) self-attention over a full sequence.

    x: (b, s, d); positions: (b, s) int32. Returns (out (b,s,d), cache).
    ``memory``: if given (cross-attention), attend to it instead (no mask).
    """
    b, s, d = x.shape
    if memory is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
        scores = _gqa_scores(cfg, q, k)
        probs = jax.nn.softmax(scores, axis=-1)
        o = _gqa_out(probs, v)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), {"k": k, "v": v}

    q, k, v = _project_qkv(p, cfg, x, positions)

    q_chunk = min(q_chunk, s)
    n_chunks = s // q_chunk if s % q_chunk == 0 else 0
    if n_chunks <= 1:
        out = _attend_block(cfg, q, k, v, positions, positions)
    else:
        qc = q.reshape(b, n_chunks, q_chunk, *q.shape[2:]).transpose(1, 0, 2, 3, 4)
        pc = positions.reshape(b, n_chunks, q_chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def body(carry, qp):
            qi, pi = qp
            return carry, _attend_block(cfg, qi, k, v, pi, positions)

        _, outs = jax.lax.scan(body, None, (qc, pc))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, q.shape[2], q.shape[3])
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return proj, {"k": k, "v": v}


def _attend_block(cfg, q, k, v, q_pos, k_pos):
    """q: (b,sq,qh,hd); k/v: (b,sk,kvh,hd); positions (b,sq)/(b,sk)."""
    scores = _gqa_scores(cfg, q, k)  # (b,kvh,g,sq,sk)
    mask = q_pos[:, :, None] >= k_pos[:, None, :]  # causal (b,sq,sk)
    if cfg.sliding_window:
        mask &= (q_pos[:, :, None] - k_pos[:, None, :]) < cfg.sliding_window
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v)


def attention_decode_stacked(p, cfg, x, cache, pos, layer_idx):
    """Decode against a STACKED multi-layer cache (perf-pass decode path).

    cache: {"k"/"v": (n_layers, b, L, kvh, hd)}. The new token's K/V are
    written with ONE dynamic-update-slice directly into the stacked buffer
    (64 KB-scale write) instead of rebuilding the layer cache and writing
    it back through the scan carry (134 MB-scale write per layer at 32k) --
    the memory-term optimization of EXPERIMENTS.md #Perf.
    """
    positions = jnp.broadcast_to(pos, (x.shape[0], 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)
    L = cache["k"].shape[2]
    slot = (pos % L).astype(jnp.int32) if cfg.sliding_window else pos.astype(jnp.int32)
    li = jnp.int32(layer_idx)
    zero = jnp.int32(0)
    ck = jax.lax.dynamic_update_slice(cache["k"], k[None], (li, zero, slot, zero, zero))
    cv = jax.lax.dynamic_update_slice(cache["v"], v[None], (li, zero, slot, zero, zero))
    layer_k = jax.lax.dynamic_slice_in_dim(ck, layer_idx, 1, axis=0)[0]
    layer_v = jax.lax.dynamic_slice_in_dim(cv, layer_idx, 1, axis=0)[0]

    scores = _gqa_scores(cfg, q, layer_k)
    idx = jnp.arange(L)
    if cfg.sliding_window:
        age = (slot - idx) % L
        valid = age < jnp.minimum(pos + 1, L)
    else:
        valid = idx <= pos
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _gqa_out(probs, layer_v)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"k": ck, "v": cv}


# --------------------------------------------------------------------- decode
def init_kv_cache(cfg, batch, seq_len):
    """Decode cache. Sliding window => ring buffer of window size."""
    L = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    dt = cdtype(cfg)
    return {
        "k": jnp.zeros((batch, L, cfg.num_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, L, cfg.num_kv_heads, cfg.head_dim), dt),
    }


def attention_decode(p, cfg, x, cache, pos, memory_cache=None):
    """One-token decode. x: (b, 1, d); pos: scalar int32 (same for batch).

    Returns (out (b,1,d), new_cache).
    ``memory_cache``: projected cross-attn K/V (Whisper decoder) -> attends to
    it with no mask and does not update any cache.
    """
    if memory_cache is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        scores = _gqa_scores(cfg, q, memory_cache["k"])
        probs = jax.nn.softmax(scores, axis=-1)
        o = _gqa_out(probs, memory_cache["v"])
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), cache

    positions = jnp.broadcast_to(pos, (x.shape[0], 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)

    L = cache["k"].shape[1]
    slot = (pos % L).astype(jnp.int32) if cfg.sliding_window else pos.astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))

    scores = _gqa_scores(cfg, q, ck)  # (b,kvh,g,1,L)
    idx = jnp.arange(L)
    if cfg.sliding_window:
        # ring buffer: entry i holds absolute position p with p % L == i, the
        # latest such p <= pos. Valid iff that p is within the window.
        age = (slot - idx) % L
        valid = age < jnp.minimum(pos + 1, L)
    else:
        valid = idx <= pos
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _gqa_out(probs, cv)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"k": ck, "v": cv}
