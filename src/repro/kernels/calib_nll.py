"""Fused calibration-NLL kernel (Pallas TPU) -- kernel #2.

Temperature Scaling fits T by minimizing
    NLL(T) = mean_r [ logsumexp(z_r / T) - z_{r,y_r} / T ].
Each Newton iteration needs NLL plus its first/second derivatives in T:
    dNLL/dT   = (z_y - E_p[z]) / T^2
    d2NLL/dT2 = -2 (z_y - E_p[z]) / T^3 + Var_p[z] / T^4
with p = softmax(z/T). All three reduce to FOUR streaming row statistics
    m  = max(z/T),  S = sum e^{z/T - m},
    W1 = sum z e^{z/T - m},  W2 = sum z^2 e^{z/T - m},
plus the label logit z_y -- so one pass over the (rows, vocab) logits in
VMEM tiles yields the whole Newton step. The jnp path reads the logits
~3x per iteration (logsumexp, E[z], E[z^2]); at Qwen-scale vocab and a
3k-sample validation split this kernel makes calibration one HBM sweep
per iteration.

Grid: (row blocks, vocab blocks); vocab dim is 'arbitrary' (sequential)
with rescale-on-new-max in VMEM scratch, like exit_gate. The label logit
is picked up by masking the tile whose column range contains y_r.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(temp_ref, labels_ref, z_ref, e1_ref, e2_ref, zy_ref, nll_ref,
            m_s, s_s, w1_s, w2_s, zy_s):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    C = z_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG)
        s_s[:] = jnp.zeros_like(s_s)
        w1_s[:] = jnp.zeros_like(w1_s)
        w2_s[:] = jnp.zeros_like(w2_s)
        zy_s[:] = jnp.zeros_like(zy_s)

    t = temp_ref[0, 0]
    zraw = z_ref[:].astype(jnp.float32)  # (R, C)
    u = zraw / t

    # --- label logit: the tile that contains column y_r contributes it;
    # labels and per-row state are (R, 1) ---
    col0 = j * C
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, zraw.shape, 1)
    hit = cols == labels_ref[:]
    zy_s[:] = zy_s[:] + jnp.sum(jnp.where(hit, zraw, 0.0), axis=1, keepdims=True)

    # --- streaming max rescale ---
    m_old = m_s[:]
    m_new = jnp.maximum(m_old, jnp.max(u, axis=1, keepdims=True))
    scale = jnp.exp(m_old - m_new)
    e = jnp.exp(u - m_new)
    s_s[:] = s_s[:] * scale + jnp.sum(e, axis=1, keepdims=True)
    w1_s[:] = w1_s[:] * scale + jnp.sum(zraw * e, axis=1, keepdims=True)
    w2_s[:] = w2_s[:] * scale + jnp.sum(zraw * zraw * e, axis=1, keepdims=True)
    m_s[:] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        S = s_s[:]
        e1_ref[:] = w1_s[:] / S  # E_p[z]
        e2_ref[:] = w2_s[:] / S  # E_p[z^2]
        zy_ref[:] = zy_s[:]
        nll_ref[:] = jnp.log(S) + m_s[:] - zy_s[:] / t


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def calib_nll_kernel(logits, labels, temperature, *, interpret: bool,
                     block_rows: int = 8, block_cols: int = 512):
    """logits (rows, vocab), labels (rows, 1) int32, temperature scalar.

    Returns (e1, e2, zy, nll), each (rows, 1); rows/vocab must be tile
    multiples (ops.py pads: rows with label 0 / large negative logits,
    masked out afterwards).
    """
    rows, vocab = logits.shape
    assert rows % block_rows == 0 and vocab % block_cols == 0
    grid = (rows // block_rows, vocab // block_cols)
    temp = jnp.asarray(temperature, jnp.float32).reshape(1, 1)
    row_spec = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    out_shapes = tuple(
        jax.ShapeDtypeStruct((rows, 1), jnp.float32) for _ in range(4)
    )
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            row_spec,
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        ],
        out_specs=(row_spec, row_spec, row_spec, row_spec),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((block_rows, 1), jnp.float32) for _ in range(5)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(temp, labels, logits)
