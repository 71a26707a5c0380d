"""The one traffic generator: images for the served cells, made on the device.

A traffic file (`traffic/<name>.json`) holds only parameters; this module
turns them and a seed into arrays. The image distribution is a copy of the
program's `cifar_like` generator (`repro.data.synthetic`), written in
`jax.numpy` so that a whole split is one jitted call on the device:

* ten class templates, smooth random 32x32x3 fields (a separable box blur
  of Gaussian noise), normalised to unit RMS;
* an easy sample is its class template plus noise; a hard one is a convex
  mix of two templates with weight alpha in [0.5, 0.85], labelled from the
  mixture;
* `noise` scales the per-pixel Gaussian noise, `easy_frac` is the share of
  easy samples.

Every stream is keyed from the run's seed (`streams`), so the same seed
gives the same images and labels, and the validation, warm-up and window
streams never overlap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the named streams drawn from one run seed, in a fixed order
STREAMS = ("weights", "templates", "val", "warm", "window", "check")


def streams(seed: int) -> dict:
    """Independent 32-bit seeds for each named stream of a run.

    `seed` may be any non-negative integer (64-bit and larger included):
    numpy's SeedSequence hashes it, so nearby seeds give unrelated streams.
    """
    words = np.random.SeedSequence(int(seed)).generate_state(len(STREAMS))
    return {name: int(w) & 0x7FFFFFFF for name, w in zip(STREAMS, words)}


def key(stream_seed: int):
    return jax.random.PRNGKey(stream_seed)


def _smooth_field(k, shape, smooth: int = 4):
    f = jax.random.normal(k, shape, jnp.float32)
    for axis in (0, 1):
        for _ in range(smooth):
            f = 0.5 * f + 0.25 * (jnp.roll(f, 1, axis) + jnp.roll(f, -1, axis))
    return f


@functools.partial(jax.jit, static_argnames=("classes", "shape"))
def templates(k, classes: int, shape=(32, 32, 3)):
    """(classes, *shape) unit-RMS smooth class templates."""
    t = jax.vmap(lambda kk: _smooth_field(kk, shape))(jax.random.split(k, classes))
    return t / jnp.sqrt(jnp.mean(t ** 2, axis=(1, 2, 3), keepdims=True))


@functools.partial(jax.jit, static_argnames=("n",))
def images(k, tmpl, n: int, easy_frac: float, noise: float):
    """(x (n, 32, 32, 3) float32, y (n,) int32) drawn like `cifar_like`."""
    classes = tmpl.shape[0]
    ka, ke, kb, kal, kn, kt = jax.random.split(k, 6)
    ya = jax.random.randint(ka, (n,), 0, classes)
    easy = jax.random.uniform(ke, (n,)) < easy_frac
    yb = (ya + jax.random.randint(kb, (n,), 1, classes)) % classes
    alpha = jnp.where(easy, 1.0, jax.random.uniform(kal, (n,), minval=0.5, maxval=0.85))
    a = alpha[:, None, None, None]
    x = a * tmpl[ya] + (1.0 - a) * tmpl[yb]
    x = x + noise * jax.random.normal(kn, x.shape, jnp.float32)
    y = jnp.where(jax.random.uniform(kt, (n,)) < alpha, ya, yb)
    return x.astype(jnp.float32), y.astype(jnp.int32)


def image_split(stream_seed: int, tmpl, n: int, spec: dict):
    """Host arrays of one split: images and labels from the traffic spec.

    The images come back in row-major order, each image contiguous, as a
    client holds them: a TPU hands the array back in its own layout, with
    the batch dimension innermost, and gathering a batch from that takes
    some ten times longer than serving it."""
    x, y = images(key(stream_seed), tmpl, n, float(spec["easy_frac"]),
                  float(spec["noise"]))
    return np.ascontiguousarray(x), np.asarray(y)
