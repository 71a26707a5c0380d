"""Decoder-only transformer stack with early-exit side branches.

Covers the dense / GQA / MoE / SSM / hybrid / VLM families of the assigned
architectures through ModelConfig.layer_plan(). The stack is organised into
*segments*: maximal runs of layers with identical (mixer, ffn) kind that do
not cross an early-exit boundary. Homogeneous segments are scanned
(jax.lax.scan over stacked params) so an 80-layer dense model compiles as one
scanned block -- essential for dry-run compile times -- while hybrid models
(Jamba) fall out as per-layer segments naturally.

Early exits (the paper's technique): after segment boundaries listed in
cfg.exit_layers, an exit head (norm + unembed) produces side-branch logits.
The stack returns them all; gating/calibration live in repro.core.

Granite's scalings apply where its modelling code puts them, each a no-op
at its default: token embeddings times `embedding_multiplier`, each
mixer and MLP branch times `residual_multiplier` before its residual add,
final and exit logits divided by `logits_scaling` (the score scale and the
norms' eps live in attention.py, layers.py and mamba.py). Each block's
mixer runs under `jax.named_scope("mamba")` or `("attention")` and its
dense MLP under `("mlp")`, so device traces name the operations of each
(docs/observability.md).
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models.layers import (
    apply_embed,
    apply_mlp,
    apply_norm,
    apply_unembed,
    cdtype,
    init_embed,
    init_mlp,
    init_norm,
    init_unembed,
)


# ---------------------------------------------------------------- segmentation
def segment_plan(cfg: ModelConfig):
    """[(kind=(mixer,ffn), n_layers, exit_after: bool)] covering all layers."""
    plan = cfg.layer_plan()
    exits = set(cfg.exit_layers)
    segs = []
    start = 0
    for i in range(cfg.num_layers):
        boundary = (
            i + 1 == cfg.num_layers
            or plan[i + 1] != plan[i]
            or i in exits
        )
        if boundary:
            segs.append((plan[i], i - start + 1, i in exits))
            start = i + 1
    return segs


# ------------------------------------------------------------------- one block
def init_block(key, cfg, kind):
    mixer, ffn = kind
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"mixer_norm": init_norm(ks[0], cfg)}
    if mixer == "attn":
        p["attn"] = attn.init_attention(ks[1], cfg)
    else:
        p["mamba"] = mb.init_mamba(ks[1], cfg)
    if ffn != "none":
        p["ffn_norm"] = init_norm(ks[2], cfg)
        if ffn == "dense":
            p["mlp"] = init_mlp(ks[3], cfg)
        else:
            from repro.models.moe import init_moe

            p["moe"] = init_moe(ks[3], cfg)
    return p


def _branch(cfg, h):
    """A block branch as it joins the residual stream."""
    m = cfg.residual_multiplier
    return h if m == 1.0 else h * m


def _apply_ffn(p, cfg, ffn, x):
    """The block's feed-forward branch and its residual add: (x, aux)."""
    if ffn == "none":
        return x, {}
    h = apply_norm(p["ffn_norm"], cfg, x)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            h, aux = apply_mlp(p["mlp"], cfg, h), {}
    else:
        from repro.models.moe import apply_moe

        h, aux = apply_moe(p["moe"], cfg, h)
    return x + _branch(cfg, h), aux


def apply_block_seq(p, cfg, kind, x, positions):
    """Full-sequence (train/prefill) block. Returns (x, cache, aux)."""
    mixer, ffn = kind
    h = apply_norm(p["mixer_norm"], cfg, x)
    if mixer == "attn":
        with jax.named_scope("attention"):
            h, cache = attn.attention_prefill(p["attn"], cfg, h, positions)
    else:
        with jax.named_scope("mamba"):
            h, cache = mb.mamba_prefill(p["mamba"], cfg, h)
    x, aux = _apply_ffn(p, cfg, ffn, x + _branch(cfg, h))
    x = sharding.constrain(x, "dp", None, None)
    return x, cache, aux


def apply_block_decode(p, cfg, kind, x, cache, pos):
    mixer, ffn = kind
    h = apply_norm(p["mixer_norm"], cfg, x)
    if mixer == "attn":
        with jax.named_scope("attention"):
            h, cache = attn.attention_decode(p["attn"], cfg, h, cache, pos)
    else:
        with jax.named_scope("mamba"):
            h, cache = mb.mamba_decode(p["mamba"], cfg, h, cache, pos)
    x, _ = _apply_ffn(p, cfg, ffn, x + _branch(cfg, h))
    x = sharding.constrain(x, "dp", None, None)
    return x, cache


def _apply_block_decode_stacked(p, cfg, kind, x, cache, pos, layer_idx):
    """Unrolled-decode block against a stacked (n_layers, ...) cache."""
    mixer, ffn = kind
    h = apply_norm(p["mixer_norm"], cfg, x)
    if mixer == "attn":
        with jax.named_scope("attention"):
            h, cache = attn.attention_decode_stacked(p["attn"], cfg, h, cache, pos,
                                                     layer_idx)
    else:
        # mamba state IS the full per-layer payload: slice, update, write back
        layer_c = jax.tree.map(lambda a: a[layer_idx], cache)
        with jax.named_scope("mamba"):
            h, layer_c = mb.mamba_decode(p["mamba"], cfg, h, layer_c, pos)
        cache = jax.tree.map(
            lambda a, l: jax.lax.dynamic_update_slice_in_dim(
                a, l[None].astype(a.dtype), layer_idx, axis=0
            ),
            cache,
            layer_c,
        )
    x, _ = _apply_ffn(p, cfg, ffn, x + _branch(cfg, h))
    x = sharding.constrain(x, "dp", None, None)
    return x, cache


def init_block_cache(cfg, kind, batch, seq_len):
    mixer, _ = kind
    if mixer == "attn":
        return attn.init_kv_cache(cfg, batch, seq_len)
    return mb.init_mamba_cache(cfg, batch)


# ------------------------------------------------------------------- the model
def init_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    segs = segment_plan(cfg)
    params: Dict[str, Any] = {"embed": init_embed(ks[0], cfg)}
    if cfg.max_position_embeddings:
        params["pos_embed"] = (
            jax.random.normal(ks[1], (cfg.max_position_embeddings, cfg.d_model)) * 0.02
        ).astype(cdtype(cfg))
    seg_params = []
    seg_keys = jax.random.split(ks[2], len(segs))
    for (kind, n, _), sk in zip(segs, seg_keys):
        if n == 1:
            seg_params.append(init_block(sk, cfg, kind))
        else:
            seg_params.append(
                jax.vmap(lambda k: init_block(k, cfg, kind))(jax.random.split(sk, n))
            )
    params["segments"] = seg_params
    params["final_norm"] = init_norm(ks[3], cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_unembed(ks[4], cfg)
    exit_keys = jax.random.split(ks[5], max(len(cfg.exit_layers), 1))
    params["exits"] = [
        {"norm": init_norm(ek, cfg), "head": init_unembed(ek, cfg)}
        for ek in exit_keys[: len(cfg.exit_layers)]
    ]
    return params


def _embed(params, cfg, tokens):
    """Token embeddings, times the embedding multiplier."""
    x = apply_embed(params["embed"], tokens)
    m = cfg.embedding_multiplier
    return x if m == 1.0 else x * m


def _scaled_logits(cfg, logits):
    """Logits divided by the logits scaling, sharded over the vocabulary."""
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return sharding.constrain(logits, "dp", None, "tp")


def _lm_logits(params, cfg, x):
    h = apply_norm(params["final_norm"], cfg, x)
    if cfg.tie_embeddings:
        logits = h @ params["embed"]["w"].T
    else:
        logits = apply_unembed(params["lm_head"], h)
    return _scaled_logits(cfg, logits)


def exit_logits_fn(params, cfg, i, x):
    """Exit head `i`: its own norm and unembed, scaled as the final head."""
    ep = params["exits"][i]
    h = apply_norm(ep["norm"], cfg, x)
    return _scaled_logits(cfg, apply_unembed(ep["head"], h))


def _run_segments_seq(params, cfg, x, positions, remat: bool):
    """Returns (x, exit_hiddens, aux_sum, caches)."""
    segs = segment_plan(cfg)
    exit_hiddens: List[Any] = []
    caches: List[Any] = []
    aux_sum = jnp.zeros((), jnp.float32)
    for sp, (kind, n, exit_after) in zip(params["segments"], segs):
        if n == 1:
            body = apply_block_seq
            if remat:
                body = jax.checkpoint(body, static_argnums=(1, 2))
            x, cache, aux = body(sp, cfg, kind, x, positions)
            if "moe_aux_loss" in aux:
                aux_sum = aux_sum + aux["moe_aux_loss"]
            caches.append(cache)
        else:

            def scan_body(carry, layer_p, _kind=kind):
                xx, acc = carry
                xx, cache, aux = apply_block_seq(layer_p, cfg, _kind, xx, positions)
                acc = acc + aux.get("moe_aux_loss", jnp.zeros((), jnp.float32))
                return (xx, acc), cache

            if remat:
                scan_body = jax.checkpoint(scan_body)
            (x, aux_sum), cache = jax.lax.scan(scan_body, (x, aux_sum), sp)
            caches.append(cache)
        if exit_after:
            exit_hiddens.append(x)
    return x, exit_hiddens, aux_sum, caches


def forward_train(params, cfg: ModelConfig, batch, remat: bool = True):
    """batch: {tokens (b, s) int32, ...}. Returns logits dict for the loss."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.max_position_embeddings:
        x = x + params["pos_embed"][:s][None]
    x = sharding.constrain(x, "dp", None, None)
    x, exit_hiddens, aux_sum, _ = _run_segments_seq(params, cfg, x, positions, remat)
    logits = _lm_logits(params, cfg, x)
    ex_logits = [
        exit_logits_fn(params, cfg, i, h) for i, h in enumerate(exit_hiddens)
    ]
    return {"logits": logits, "exit_logits": ex_logits, "moe_aux_loss": aux_sum}


def forward_prefill(params, cfg: ModelConfig, batch):
    """Prefill: full sequence, returns last-position logits + caches + exits."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.max_position_embeddings:
        x = x + params["pos_embed"][:s][None]
    x = sharding.constrain(x, "dp", None, None)
    x, exit_hiddens, _, caches = _run_segments_seq(params, cfg, x, positions, False)
    logits = _lm_logits(params, cfg, x[:, -1:, :])
    ex_logits = [
        exit_logits_fn(params, cfg, i, h[:, -1:, :])
        for i, h in enumerate(exit_hiddens)
    ]
    return {"logits": logits, "exit_logits": ex_logits, "caches": caches}


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    segs = segment_plan(cfg)
    caches = []
    for kind, n, _ in segs:
        c = init_block_cache(cfg, kind, batch, seq_len)
        if n > 1:
            c = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), c)
        caches.append(c)
    return caches


def decode_step(params, cfg: ModelConfig, token, caches, pos):
    """token: (b, 1) int32; pos: scalar int32. Returns (out, new_caches).

    out: {"logits": (b,1,V), "exit_logits": [(b,1,V)...]}
    """
    segs = segment_plan(cfg)
    x = _embed(params, cfg, token)
    if cfg.max_position_embeddings:
        x = x + params["pos_embed"][pos][None, None, :]
    x = sharding.constrain(x, "dp", None, None)
    new_caches = []
    exit_hiddens = []
    for sp, cache, (kind, n, exit_after) in zip(params["segments"], caches, segs):
        if n == 1:
            x, cache = apply_block_decode(sp, cfg, kind, x, cache, pos)
        elif cfg.decode_unroll:
            # perf-pass decode: unrolled layers + in-place stacked-cache
            # updates (no scan carry write-back; see EXPERIMENTS.md #Perf)
            for i in range(n):
                layer_p = jax.tree.map(lambda a: a[i], sp)
                x, cache = _apply_block_decode_stacked(
                    layer_p, cfg, kind, x, cache, pos, i
                )
        else:

            def scan_body(xx, inp, _kind=kind):
                layer_p, layer_c = inp
                xx, layer_c = apply_block_decode(layer_p, cfg, _kind, xx, layer_c, pos)
                return xx, layer_c

            x, cache = jax.lax.scan(scan_body, x, (sp, cache))
        new_caches.append(cache)
        if exit_after:
            exit_hiddens.append(x)
    logits = _lm_logits(params, cfg, x)
    ex_logits = [
        exit_logits_fn(params, cfg, i, h) for i, h in enumerate(exit_hiddens)
    ]
    return {"logits": logits, "exit_logits": ex_logits}, new_caches


# ----------------------------------------------------- partitioned execution
def edge_forward(params, cfg: ModelConfig, batch, exit_index: int = 0):
    """The *edge partition*: blocks up to exit `exit_index` + that exit head.

    Returns {"exit_logits": (b,1,V) last position, "hidden": (b,s,d), "caches"}.
    The hidden is the partition payload the offloading engine ships to the
    cloud partition when the gate refuses the sample.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.max_position_embeddings:
        x = x + params["pos_embed"][:s][None]
    segs = segment_plan(cfg)
    caches = []
    n_exits_seen = 0
    for sp, (kind, n, exit_after) in zip(params["segments"], segs):
        if n == 1:
            x, cache, _ = apply_block_seq(sp, cfg, kind, x, positions)
        else:

            def scan_body(xx, layer_p, _kind=kind):
                xx, cache, _ = apply_block_seq(layer_p, cfg, _kind, xx, positions)
                return xx, cache

            x, cache = jax.lax.scan(scan_body, x, sp)
        caches.append(cache)
        if exit_after:
            if n_exits_seen == exit_index:
                logits = exit_logits_fn(params, cfg, n_exits_seen, x[:, -1:, :])
                return {"exit_logits": logits, "hidden": x, "caches": caches}
            n_exits_seen += 1
    raise ValueError(f"exit_index {exit_index} not found in {cfg.name}")


def cloud_forward(params, cfg: ModelConfig, hidden, exit_index: int = 0):
    """The *cloud partition*: remaining blocks after exit `exit_index`."""
    b, s, _ = hidden.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    segs = segment_plan(cfg)
    x = hidden
    n_exits_seen = 0
    started = False
    for sp, (kind, n, exit_after) in zip(params["segments"], segs):
        if started:
            if n == 1:
                x, _, _ = apply_block_seq(sp, cfg, kind, x, positions)
            else:

                def scan_body(xx, layer_p, _kind=kind):
                    xx, cache, _ = apply_block_seq(layer_p, cfg, _kind, xx, positions)
                    return xx, cache

                x, _ = jax.lax.scan(scan_body, x, sp)
        if exit_after and not started:
            if n_exits_seen == exit_index:
                started = True
            n_exits_seen += 1
    return {"logits": _lm_logits(params, cfg, x[:, -1:, :])}
