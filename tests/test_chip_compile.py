"""The served path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) runs the kernel bodies on the
CPU and cannot see what the chip's compiler refuses: block shapes that
break the (8, 128) tiling rule, rank-1 per-row blocks, lane reshapes
Mosaic does not lower. These tests compile each kernel with
`interpret=False` for a described -- not attached -- `v5e:2x2` topology
and check that the compiled program holds the Mosaic kernel
(`tpu_custom_call`). Nothing runs; the shapes are the ones the chip sees:

* exit gate: a 256-row B-AlexNet-sized block, and 64 rows at the
  qwen3-8b vocab (151,936 padded to the 512-column tile);
* calib_nll: a 3,072-row validation pass;
* codec encode/decode at int8 and int4 for the B-AlexNet branch payloads
  (a 256-sample batch of 16,384 features at branch 1, 6,144 at branch 2).

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the one that runs
this file loads the TPU compiler.
"""
import functools

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import compress
from repro.kernels.calib_nll import calib_nll_kernel
from repro.kernels.exit_gate import exit_gate_kernel
from repro.kernels.ref import CODEC_BITS, CODEC_TILE

QWEN3_VOCAB_PADDED = -(-151_936 // 512) * 512
BALEXNET_PAYLOADS = {1: 16 * 16 * 64, 2: 8 * 8 * 96}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("rows,vocab,dtype", [
    (256, 512, jnp.float32),
    (64, QWEN3_VOCAB_PADDED, jnp.float32),
    (64, QWEN3_VOCAB_PADDED, jnp.bfloat16),
])
def test_exit_gate_compiles(one_chip, rows, vocab, dtype):
    z = jax.ShapeDtypeStruct((rows, vocab), dtype, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda z, t: exit_gate_kernel(z, t, interpret=False), z, t
    )
    assert "tpu_custom_call" in text


def test_calib_nll_compiles(one_chip):
    rows, vocab = 3072, 512
    z = jax.ShapeDtypeStruct((rows, vocab), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((rows, 1), jnp.int32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda z, y, t: calib_nll_kernel(z, y, t, interpret=False), z, y, t
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("branch", sorted(BALEXNET_PAYLOADS))
@pytest.mark.parametrize("level", sorted(CODEC_BITS))
@pytest.mark.parametrize("stage", ["encode", "decode"])
def test_codec_compiles(one_chip, stage, level, branch):
    bits = CODEC_BITS[level]
    per = 32 // bits
    nw = CODEC_TILE // per
    rows, ng = 256, BALEXNET_PAYLOADS[branch] // CODEC_TILE

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    if stage == "encode":
        fn = functools.partial(compress.encode_pallas, bits=bits,
                               interpret=False)
        args = (shape((rows, per, nw, ng), jnp.float32),)
    else:
        fn = functools.partial(compress.decode_pallas, bits=bits,
                               interpret=False)
        args = (shape((rows, nw, ng), jnp.uint32),
                shape((rows, 1, ng), jnp.float32))
    assert "tpu_custom_call" in _compiled_text(fn, *args)
