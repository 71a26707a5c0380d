"""Jitted public wrappers around the Pallas kernels.

Handles arbitrary leading dims, row/vocab padding to tile multiples, and
the CPU-vs-TPU interpret switch. `exit_gate` is what repro.core.exits calls
with use_kernel=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.exit_gate import NEG, exit_gate_kernel


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode: off the TPU only.

    The one place this is decided. On a TPU every kernel is compiled by
    Mosaic, and a kernel the compiler refuses fails the call -- nothing
    falls back to the interpreter or to a jnp path there.
    """
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols"))
def exit_gate(logits, temperature=1.0, block_rows: int = 8, block_cols: int = 512):
    """(confidence, prediction, entropy) of softmax(logits/T).

    logits: (..., vocab). Matches repro.core.exits.gate_statistics' return
    order (confidence, prediction, entropy).
    """
    shape = logits.shape
    vocab = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    z = logits.reshape(rows, vocab)

    pr = (-rows) % block_rows
    pc = (-vocab) % block_cols
    if pr or pc:
        z = jnp.pad(z, ((0, pr), (0, pc)), constant_values=NEG)

    conf, ent, idx = exit_gate_kernel(
        z,
        temperature,
        block_rows=block_rows,
        block_cols=block_cols,
        interpret=interpret_mode(),
    )
    conf = conf[:rows, 0].reshape(shape[:-1])
    ent = ent[:rows, 0].reshape(shape[:-1])
    idx = idx[:rows, 0].reshape(shape[:-1])
    return conf, idx, ent


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols"))
def calib_stats(logits, labels, temperature, block_rows: int = 8, block_cols: int = 512):
    """One-pass Newton statistics for Temperature Scaling over (N, vocab)
    validation logits: returns (nll_mean, dNLL/dT, d2NLL/dT2).

        dNLL/dT   = mean (z_y - E_p[z]) / T^2
        d2NLL/dT2 = mean [ -2 (z_y - E_p[z]) / T^3 + Var_p[z] / T^4 ]
    """
    from repro.kernels.calib_nll import calib_nll_kernel

    rows, vocab = logits.shape
    pr = (-rows) % block_rows
    pc = (-vocab) % block_cols
    z = logits
    y = labels.astype(jnp.int32)
    if pr or pc:
        # pad constant: large enough to underflow exp() at any T >= 0.05,
        # small enough that z^2 stays finite in fp32 (1e30^2 would be inf
        # and poison the E[z^2] accumulator with inf*0 = nan)
        z = jnp.pad(z, ((0, pr), (0, pc)), constant_values=-3e4)
        y = jnp.pad(y, (0, pr))
    e1, e2, zy, nll = calib_nll_kernel(
        z, y[:, None], temperature, block_rows=block_rows,
        block_cols=block_cols, interpret=interpret_mode(),
    )
    e1, e2, zy, nll = (a[:rows, 0] for a in (e1, e2, zy, nll))
    t = jnp.asarray(temperature, jnp.float32)
    var = e2 - e1 * e1
    d1 = jnp.mean((zy - e1) / (t * t))
    d2 = jnp.mean(-2.0 * (zy - e1) / t**3 + var / t**4)
    return jnp.mean(nll), d1, d2


def fit_temperature_kernel(logits, labels, t0=1.0, iters: int = 25,
                           t_min: float = 0.05, t_max: float = 20.0):
    """Newton's method on T using the fused one-pass kernel statistics."""

    def step(t, _):
        nll, d1, d2 = calib_stats(logits, labels, t)
        delta = jnp.where(jnp.abs(d2) > 1e-12, d1 / d2, jnp.sign(d1) * 0.1)
        delta = jnp.clip(delta, -0.5 * t, 0.5 * t)
        return jnp.clip(t - delta, t_min, t_max), nll

    t, nlls = jax.lax.scan(step, jnp.float32(t0), None, length=iters)
    return t, nlls[-1]
