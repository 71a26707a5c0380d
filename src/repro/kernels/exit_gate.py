"""Fused early-exit gate kernel (Pallas TPU).

Computes, per row of a (rows, vocab) logits matrix and a scalar temperature:
    confidence = max softmax(z / T)
    entropy    = H(softmax(z / T))        (nats)
    argmax     = argmax z
WITHOUT materializing the softmax: an online-softmax sweep over vocab tiles
keeps only (running max m, rescaled denom S, rescaled sum W = sum u*e^u,
best value/index) per row in VMEM scratch.

Why this is the paper's hot spot on TPU: the gate runs after every early
exit for every token; at Qwen-scale vocab (151,936) a naive
softmax().max() + entropy materializes and re-reads a (tokens, vocab) fp32
tensor from HBM three times. The fused kernel streams each logits tile
HBM->VMEM once -- it is purely memory-bound, so this is a ~3x traffic cut.

Tiling: rows block R=8 (fp32 sublane), vocab block C=512 lanes; the vocab
grid dimension is 'arbitrary' (sequential) so scratch carries across tiles.

Math: with u_i = z_i/T - m (m = running max of z/T):
    S = sum e^{u_i};  W = sum u_i e^{u_i}
    confidence = e^{u_max}/S = 1/S  (since m is the global max)
    entropy    = log S - W/S
Rescaling when the max improves from m to m': S *= e^{m-m'},
W' = e^{m-m'} (W + (m-m') S).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(temp_ref, z_ref, conf_ref, ent_ref, idx_ref, m_s, s_s, w_s, bv_s, bi_s):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    C = z_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG)
        s_s[:] = jnp.zeros_like(s_s)
        w_s[:] = jnp.zeros_like(w_s)
        bv_s[:] = jnp.full_like(bv_s, NEG)
        bi_s[:] = jnp.zeros_like(bi_s)

    t = temp_ref[0, 0]
    z = z_ref[:].astype(jnp.float32) / t  # (R, C)

    # --- running max / rescale; per-row state is (R, 1) ---
    m_old = m_s[:]
    tile_max = jnp.max(z, axis=1, keepdims=True)
    m_new = jnp.maximum(m_old, tile_max)
    scale = jnp.exp(m_old - m_new)
    s_old = s_s[:] * scale
    w_old = (w_s[:] + (m_old - m_new) * s_s[:]) * scale

    u = z - m_new
    e = jnp.exp(u)
    s_s[:] = s_old + jnp.sum(e, axis=1, keepdims=True)
    w_s[:] = w_old + jnp.sum(u * e, axis=1, keepdims=True)
    m_s[:] = m_new

    # --- streaming argmax (T > 0 preserves argmax): first column that
    # attains the tile max, as jnp.argmax breaks ties ---
    cols = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    tile_arg = jnp.min(jnp.where(z == tile_max, cols, C), axis=1, keepdims=True)
    better = tile_max > bv_s[:]
    bv_s[:] = jnp.where(better, tile_max, bv_s[:])
    bi_s[:] = jnp.where(better, tile_arg + j * C, bi_s[:])

    @pl.when(j == nj - 1)
    def _finish():
        S = s_s[:]
        conf_ref[:] = 1.0 / S
        ent_ref[:] = jnp.log(S) - w_s[:] / S
        idx_ref[:] = bi_s[:]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def exit_gate_kernel(
    logits, temperature, *, interpret: bool, block_rows: int = 8,
    block_cols: int = 512,
):
    """logits: (rows, vocab); temperature: scalar. Returns (conf, ent, idx),
    each (rows, 1): per-row values live on the sublane axis, the 2-D layout
    the TPU tiling accepts for a row block.

    rows must be a multiple of block_rows and vocab of block_cols (ops.py
    pads). interpret=True executes on CPU for validation; on TPU pass False.
    """
    rows, vocab = logits.shape
    assert rows % block_rows == 0 and vocab % block_cols == 0
    grid = (rows // block_rows, vocab // block_cols)
    temp = jnp.asarray(temperature, jnp.float32).reshape(1, 1)

    out_shapes = (
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),  # confidence
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),  # entropy
        jax.ShapeDtypeStruct((rows, 1), jnp.int32),  # argmax
    )
    row_spec = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        ],
        out_specs=(row_spec, row_spec, row_spec),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),  # running max
            pltpu.VMEM((block_rows, 1), jnp.float32),  # S
            pltpu.VMEM((block_rows, 1), jnp.float32),  # W
            pltpu.VMEM((block_rows, 1), jnp.float32),  # best value
            pltpu.VMEM((block_rows, 1), jnp.int32),  # best index
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(temp, logits)
