"""Pallas bottleneck codec for the offload payload (encode on the edge,
decode in the cloud).

The paper prices every offload as the raw float32 intermediate activation
crossing the 18.8 Mbps uplink; this kernel makes the payload a control
knob. Per (row, 128-feature tile) of the flattened activation it computes
an absmax scale and quantizes to signed int8 (level 1) or int4 (level 2),
packing values little-endian into uint32 words with the float32 scales
emitted in the same pass -- one HBM read of the activation produces the
whole wire image, which is what makes the encode affordable on the edge
hot path (the activation is re-read zero extra times).

Wire format (shared bit-exactly with the numpy oracle in `ref.py`):

    words  (rows, padded_features * bits / 32) uint32, little-endian
           packed two's-complement `bits`-bit values
    scales (rows, padded_features / 128)       float32, absmax / qmax

Compressed size is analytic -- `compressed_nbytes(n, level)` = n*bits/8
payload + 4 bytes per 128-wide scale group -- so the control plane can
price a (branch, level) candidate without touching a tensor; level 2
(int4) lands at ~7.5x under the float32 payload, level 1 (int8) at ~3.9x.

Edge cases: non-finite inputs are zeroed before the absmax (one inf
would otherwise flush its whole tile to zeros with an inf scale), and an
all-zero tile stores scale 0 but divides by 1, so encode never divides
by zero. Level 0 is the identity and never reaches these kernels.

Layout: Mosaic lowers no lane split, so the wrapper hands the kernel
a "plane" view of the payload. Features index as (group g, word w, slot
k) with g the 128-wide scale group, w the uint32 word inside it and k
the value's slot in that word; the jitted wrapper transposes
(rows, G, W, per) -> (rows, per, W, G). In that view the scale is a max
over the leading and sublane axes, packing is an elementwise OR of the
`per` planes, and every block keeps G on the lanes: words (8, W, G),
scales (8, 1, G), W = 32 (int8) or 16 (int4). G is one block when it is
at most 128 and 128-lane blocks otherwise; the grid is fully parallel.
The transposes back to the wire format run in the same jitted wrapper.
`ops.interpret_mode()` decides interpret mode, as for the gate kernel.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import interpret_mode
from repro.kernels.ref import CODEC_BITS, CODEC_TILE, _codec_layout

#: the codec's public level axis: 0 = identity float32, 1 = int8, 2 = int4
LEVELS = (0, 1, 2)


def compressed_nbytes(n_elements: int, level: int) -> int:
    """Wire bytes for an n-element float32 payload at `level` (analytic,
    unpadded): packed values + one float32 scale per 128-element group.
    The single source of truth every pricing surface derives from."""
    n = int(n_elements)
    if int(level) == 0:
        return 4 * n
    bits = CODEC_BITS[int(level)]
    groups = -(-n // CODEC_TILE)
    return (n * bits + 7) // 8 + 4 * groups


def scaled_payload_nbytes(raw_nbytes: int, level: int) -> int:
    """Wire bytes for a payload whose RAW float32 size is `raw_nbytes` --
    the (branch, level) table entry. Level 0 returns `raw_nbytes`
    unchanged (the bit-exact identity the parity suites pin)."""
    if int(level) == 0:
        return int(raw_nbytes)
    return compressed_nbytes(int(raw_nbytes) // 4, level)


# ---------------------------------------------------------------- kernels
def _split12(a):
    """a = hi + lo exactly, hi keeping the top 12 significant bits."""
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFFF000),
        jnp.float32,
    )
    return hi, a - hi


def _residual(z, c, s):
    """z - c * s, exact for c within a few ulps of z / s: Dekker's
    two-product on 12-bit halves (no fused multiply-add needed), and
    z - c * s is then Sterbenz-exact."""
    p = c * s
    ch, cl = _split12(c)
    sh, sl = _split12(s)
    e = ((ch * sh - p) + ch * sl + cl * sh) + cl * sl
    return (z - p) - e


def _nearest_quotient(z, s, y):
    """The correctly rounded (to nearest, ties to even) float32 z / s,
    given an approximate quotient y within a few ulps of it.

    The TPU's float32 divide is not correctly rounded (on a TPU v5e it
    differs from the correctly rounded quotient on about a third of
    random operands), and a quotient off in its last bits lands on the
    other side of a rounding boundary now and then -- which changes a
    quantized code. Multiplies, adds and bit operations are
    exact, so one Newton step followed by a choice among y and its two
    neighbours by exact residual gives numpy's quotient bit for bit,
    while no product underflows.
    """
    y = y + _residual(z, y, s) / s
    bits = jax.lax.bitcast_convert_type(y, jnp.int32)
    best, r_best = y, jnp.abs(_residual(z, y, s))
    odd_best = bits & 1  # int32, not bool: Mosaic selects no i1 vectors
    for nb in (bits - 1, bits + 1):
        c = jax.lax.bitcast_convert_type(nb, jnp.float32)
        r = jnp.abs(_residual(z, c, s))  # NaN (never chosen) below 0
        odd = nb & 1
        take = (r < r_best) | ((r == r_best) & (odd < odd_best))
        best = jnp.where(take, c, best)
        r_best = jnp.where(take, r, r_best)
        odd_best = jnp.where(take, odd, odd_best)
    return best


def _encode_kernel(x_ref, words_ref, scale_ref, *, bits: int):
    per = 32 // bits
    qmax = jnp.float32((1 << (bits - 1)) - 1)
    mask = jnp.uint32((1 << bits) - 1)
    z = x_ref[...].astype(jnp.float32)  # (R, per, W, G)
    z = jnp.where(jnp.isfinite(z), z, jnp.float32(0.0))
    amax = jnp.max(jnp.max(jnp.abs(z), axis=1), axis=1, keepdims=True)
    # reciprocal-multiply, matching ref.encode_codec_ref bit-for-bit
    scale = amax * jnp.float32(_np.float32(1.0) / _np.float32((1 << (bits - 1)) - 1))
    safe = jnp.where(scale > 0, scale, jnp.float32(1.0))  # (R, 1, G)
    w = jnp.zeros(words_ref.shape, jnp.uint32)
    for k in range(per):  # static unroll: 4 (int8) or 8 (int4) ors
        zk = z[:, k]
        q = _nearest_quotient(zk, safe, zk / safe)
        q = jnp.clip(jnp.round(q), -qmax, qmax).astype(jnp.int32)
        w = w | ((q.astype(jnp.uint32) & mask) << jnp.uint32(bits * k))
    words_ref[...] = w
    scale_ref[...] = scale


def _decode_kernel(words_ref, scale_ref, out_ref, *, bits: int):
    per = 32 // bits
    half, full = 1 << (bits - 1), 1 << bits
    mask = jnp.uint32(full - 1)
    w = words_ref[...]  # (R, W, G) uint32
    scale = scale_ref[...]  # (R, 1, G)
    for k in range(per):
        u = ((w >> jnp.uint32(bits * k)) & mask).astype(jnp.int32)
        v = jnp.where(u >= half, u - full, u).astype(jnp.float32)
        out_ref[:, k] = v * scale


def _group_block(n_groups: int) -> int:
    """Lane block over scale groups: all of them up to 128, else 128."""
    return n_groups if n_groups <= 128 else 128


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "block_rows"))
def encode_pallas(planes, bits: int, *, interpret: bool, block_rows: int = 8):
    """planes: (rows, per, W, G) float32 plane view (module docstring),
    rows % block_rows == 0, G one lane block or a multiple of 128.
    Returns words (rows, W, G) uint32 and scales (rows, 1, G) float32."""
    rows, per, nw, ng = planes.shape
    bg = _group_block(ng)
    assert per == 32 // bits and nw * per == CODEC_TILE
    assert rows % block_rows == 0 and ng % bg == 0
    return pl.pallas_call(
        functools.partial(_encode_kernel, bits=bits),
        grid=(rows // block_rows, ng // bg),
        in_specs=[
            pl.BlockSpec((block_rows, per, nw, bg), lambda i, j: (i, 0, 0, j)),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, nw, bg), lambda i, j: (i, 0, j)),
            pl.BlockSpec((block_rows, 1, bg), lambda i, j: (i, 0, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows, nw, ng), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 1, ng), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(planes)


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "block_rows"))
def decode_pallas(words, scales, bits: int, *, interpret: bool,
                  block_rows: int = 8):
    """Inverse of `encode_pallas`: words (rows, W, G), scales (rows, 1, G)
    -> float32 planes (rows, per, W, G)."""
    per = 32 // bits
    rows, nw, ng = words.shape
    bg = _group_block(ng)
    assert nw * per == CODEC_TILE
    assert rows % block_rows == 0 and ng % bg == 0
    return pl.pallas_call(
        functools.partial(_decode_kernel, bits=bits),
        grid=(rows // block_rows, ng // bg),
        in_specs=[
            pl.BlockSpec((block_rows, nw, bg), lambda i, j: (i, 0, j)),
            pl.BlockSpec((block_rows, 1, bg), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, per, nw, bg),
                               lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, per, nw, ng), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(words, scales)


def _padded_dims(rows: int, n_groups: int, block_rows: int = 8):
    """(rows, groups) padded to the kernels' block multiples."""
    bg = _group_block(n_groups)
    return -(-rows // block_rows) * block_rows, -(-n_groups // bg) * bg


@functools.partial(jax.jit, static_argnames=("bits",))
def _encode_wire(z, bits: int):
    """(rows, cols) float -> wire (words, scales) of `ref.encode_codec_ref`,
    which encodes the payload's float32 values."""
    per = 32 // bits
    nw = CODEC_TILE // per
    rows, cols = z.shape
    ng = -(-cols // CODEC_TILE)
    rp, gp = _padded_dims(rows, ng)
    z = jnp.pad(z.astype(jnp.float32), ((0, rp - rows), (0, gp * CODEC_TILE - cols)))
    planes = z.reshape(rp, gp, nw, per).transpose(0, 3, 2, 1)
    words, scales = encode_pallas(planes, bits, interpret=interpret_mode())
    words = words.transpose(0, 2, 1)[:rows, :ng].reshape(rows, ng * nw)
    return words, scales[:rows, 0, :ng]


@functools.partial(jax.jit, static_argnames=("bits", "dtype"))
def _decode_wire(words, scales, bits: int, dtype=jnp.float32):
    """Wire (words, scales) -> (rows, groups * 128) float32 values, rounded
    to `dtype`."""
    per = 32 // bits
    nw = CODEC_TILE // per
    rows, ng = scales.shape
    rp, gp = _padded_dims(rows, ng)
    w = jnp.pad(words.reshape(rows, ng, nw), ((0, rp - rows), (0, gp - ng), (0, 0)))
    sc = jnp.pad(scales, ((0, rp - rows), (0, gp - ng)))[:, None, :]
    planes = decode_pallas(w.transpose(0, 2, 1), sc, bits,
                           interpret=interpret_mode())
    out = planes.transpose(0, 3, 2, 1).reshape(rp, gp * CODEC_TILE)
    return out[:rows, : ng * CODEC_TILE].astype(dtype)


# ----------------------------------------------------------- public wrappers
@dataclass(frozen=True)
class EncodedPayload:
    """One encoded offload payload: the wire image + enough metadata to
    decode. `nbytes` is the analytic unpadded wire size (what the uplink
    is charged), not the padded device buffer size."""

    words: Any  # (rows, ceil(features/128)*128 * bits / 32) uint32
    scales: Any  # (rows, ceil(features/128)) float32
    shape: Tuple[int, ...]
    level: int
    dtype: Any = jnp.float32  # the payload's dtype, which decode returns

    @property
    def nbytes(self) -> int:
        rows, cols = _codec_layout(self.shape)
        return rows * compressed_nbytes(cols, self.level)


def encode(x, level: int) -> EncodedPayload:
    """Encode an arbitrary-shape float payload through the Pallas kernel
    (interpret mode off-TPU) into the 128-aligned wire format of
    `ref.encode_codec_ref`, bit-exactly."""
    level = int(level)
    if level == 0:
        raise ValueError("level 0 is the identity; nothing to encode")
    x = jnp.asarray(x)
    rows, cols = _codec_layout(x.shape)
    words, scales = _encode_wire(x.reshape(rows, cols), CODEC_BITS[level])
    return EncodedPayload(
        words=words, scales=scales,
        shape=tuple(int(d) for d in x.shape), level=level, dtype=x.dtype,
    )


def decode(enc: EncodedPayload):
    """Decode an `EncodedPayload` back to its original shape and dtype."""
    rows, cols = _codec_layout(enc.shape)
    out = _decode_wire(jnp.asarray(enc.words), jnp.asarray(enc.scales),
                       CODEC_BITS[int(enc.level)], jnp.dtype(enc.dtype))
    return out[:, :cols].reshape(enc.shape)


def roundtrip(x, level: int):
    """decode(encode(x)) through the kernels; level 0 is the identity."""
    if int(level) == 0:
        return jnp.asarray(x)
    return decode(encode(x, level))
