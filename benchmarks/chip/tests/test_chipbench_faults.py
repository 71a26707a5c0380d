"""A whole run of a served cell on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath,
at the place an answer is produced, it comes out not correct."""
import json

import numpy as np
import pytest

import harness
from conftest import CHECKOUT, small_root
from repro.kernels import compress as _compress

_ENCODE, _DECODE = _compress.encode, _compress.decode

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
WORKLOAD = "alexnet-b1-int8-offload80"


def _cloud_answer_altered(engine):
    import jax.numpy as jnp

    cloud = engine.cloud_fn

    def broken(h):
        out = cloud(h)
        return {"logits": jnp.roll(out["logits"], 1, axis=-1)}

    engine.cloud_fn = broken


def _edge_answer_altered(engine):
    import dataclasses

    gate = engine.plan.gate

    def broken(*a, **k):
        g = gate(*a, **k)
        return dataclasses.replace(g, prediction=(g.prediction + 1) % 10)

    engine.plan.gate = broken


def _codec_word_altered(engine):
    import dataclasses

    from repro.kernels import compress

    encode = compress.encode

    def broken(x, level):
        e = encode(x, level)
        return dataclasses.replace(e, words=e.words ^ np.uint32(1 << 3))

    compress.encode = broken


def _codec_called_uncaptured(engine):
    """The codec still runs, but not through the module attributes the
    benchmark watches, as after a refactor that calls it another way."""
    from repro.kernels import compress

    compress.encode, compress.decode = _ENCODE, _DECODE


def _gate_threshold_altered(engine):
    engine.plan = engine.plan.with_p_tar(engine.plan.p_tar - 0.01)


FAULTS = {"none": None, "cloud_answer": _cloud_answer_altered,
          "edge_answer": _edge_answer_altered, "codec_word": _codec_word_altered,
          "gate_threshold": _gate_threshold_altered,
          "codec_uncaptured": _codec_called_uncaptured}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_run_is_correct_only_when_sound(fault, tmp_path, restore_jax_config):
    from repro.kernels import compress

    encode, decode = compress.encode, compress.decode
    hooks = {"engine": FAULTS[fault]} if FAULTS[fault] else {}
    try:
        res = harness.run_cell(BENCH, WORKLOAD, seed=2**33 + 7, seconds=0.5, trace=False,
                               root=small_root(tmp_path, WORKLOAD),
                               require_accelerator=False, hooks=hooks, cache=False)
    finally:
        compress.encode, compress.decode = encode, decode
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"served_images_per_s", "served_batch_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is (fault == "none"), res["checks"]
