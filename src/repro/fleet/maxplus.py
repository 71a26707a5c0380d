"""Max-plus semiring solvers for deterministic-service FIFO queues.

The FIFO recurrence

    done_i = max(t_i, done_{i-1}) + s_i

is an affine map over the max-plus semiring: with f_i(x) = max(x + A_i, b_i),
A_i = s_i and b_i = t_i + s_i, we have done_i = (f_i . f_{i-1} . ... . f_1)(free).
Composition is associative,

    (f2 . f1) = (A1 + A2, max(b1 + A2, b2)),

so the whole chain resolves with `lax.associative_scan` in O(log n) depth:

    done_i = max(b_scan_i, free + A_scan_i)

The identity element (A, b) = (0, -inf) lets masked-out rows pass through
unchanged, which is what the compiled fleet pipeline uses to run one padded
scan per device lane. The formula is valid for UNSORTED arrival times t
(done_i = max_{j<=i} (t_j + sum_{k=j..i} s_k) holds regardless of ordering).

`fifo_oracle` / `kserver_oracle` are the deliberately naive per-request
Python references; `tests/test_fleet_properties.py` pins the scan solvers
against them (exactly, on dyadic-rational inputs where float addition is
associative).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.gatepath import _next_pow2

__all__ = [
    "fifo_oracle",
    "kserver_oracle",
    "maxplus_fifo",
    "fifo_done_maxplus",
    "kserver_done_maxplus",
]


def fifo_oracle(t, service, free_s: float = 0.0) -> np.ndarray:
    """Per-request Python FIFO: the ground-truth oracle for the scan solver."""
    t = np.asarray(t, dtype=np.float64)
    service = np.asarray(service, dtype=np.float64)
    done = np.empty(t.shape[0], dtype=np.float64)
    prev = float(free_s)
    for i in range(t.shape[0]):
        prev = max(float(t[i]), prev) + float(service[i])
        done[i] = prev
    return done


def kserver_oracle(t, service, k: int) -> np.ndarray:
    """Naive K-server FIFO: each job goes to the earliest-free server.

    With constant service times this matches the residue-class decomposition
    (job i waits for job i-K) used by the fleet cloud tier.
    """
    t = np.asarray(t, dtype=np.float64)
    service = np.asarray(service, dtype=np.float64)
    free = [0.0] * int(k)
    done = np.empty(t.shape[0], dtype=np.float64)
    for i in range(t.shape[0]):
        r = min(range(len(free)), key=lambda j: free[j])
        d = max(float(t[i]), free[r]) + float(service[i])
        free[r] = d
        done[i] = d
    return done


def _combine(x, y):
    """Max-plus affine composition, elementwise over stacked (A, b) pairs."""
    import jax.numpy as jnp

    a1, b1 = x
    a2, b2 = y
    return a1 + a2, jnp.maximum(b1 + a2, b2)


def maxplus_fifo(t, service, mask, free):
    """Masked FIFO completion times via `lax.associative_scan` (jnp -> jnp).

    Works on any leading axis layout `associative_scan` accepts (scan is over
    axis 0). Rows with ``mask == False`` are the semiring identity; their
    output positions are undefined and must be re-masked by the caller.
    """
    import jax.numpy as jnp
    from jax import lax

    a = jnp.where(mask, service, 0.0)
    b = jnp.where(mask, t + service, -jnp.inf)
    a_s, b_s = lax.associative_scan(_combine, (a, b))
    return jnp.maximum(b_s, free + a_s)


_JIT_CACHE: dict = {}


def _scan_fn():
    if "fifo" not in _JIT_CACHE:
        import jax

        _JIT_CACHE["fifo"] = jax.jit(maxplus_fifo)
    return _JIT_CACHE["fifo"]


def fifo_done_maxplus(t, service, free_s: float = 0.0) -> np.ndarray:
    """Host-callable max-plus FIFO solver (float64, jitted scan).

    Pads to the next power of two so a sweep over chain lengths 1..N costs at
    most log2(N)+1 compilations, mirroring the gate-path padding contract.
    """
    import jax

    t = np.asarray(t, dtype=np.float64)
    service = np.asarray(service, dtype=np.float64)
    n = t.shape[0]
    if n == 0:
        return np.empty(0, dtype=np.float64)
    m = _next_pow2(n)
    tp = np.zeros(m, dtype=np.float64)
    sp = np.zeros(m, dtype=np.float64)
    mask = np.zeros(m, dtype=bool)
    tp[:n] = t
    sp[:n] = service
    mask[:n] = True
    with jax.enable_x64(True):
        out = _scan_fn()(tp, sp, mask, np.float64(free_s))
    return np.asarray(out)[:n]


def kserver_done_maxplus(t, service, k: int) -> np.ndarray:
    """K-server completion times via residue-class max-plus chains.

    Jobs must already be in FIFO order; chain r serves jobs r, r+K, r+2K, ...
    exactly as the fleet cloud tier decomposes its shared servers.
    """
    t = np.asarray(t, dtype=np.float64)
    service = np.asarray(service, dtype=np.float64)
    done = np.empty(t.shape[0], dtype=np.float64)
    for r in range(min(int(k), t.shape[0])):
        idx = np.arange(r, t.shape[0], int(k))
        done[idx] = fifo_done_maxplus(t[idx], service[idx])
    return done
