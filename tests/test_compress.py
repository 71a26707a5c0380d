"""Pallas bottleneck codec (ISSUE 10 tentpole): kernel-vs-oracle parity,
absmax edge cases, analytic wire pricing, and the level-0 identity
contract across the serving / fleet / compiled stacks.

The codec's wire format is pinned by the numpy oracle in
`repro.kernels.ref`; the Pallas encode/decode pair must reproduce it
BIT-exactly (words, scales, and decoded floats), because the control
plane's fit-time accuracy-delta tables are computed through the oracle
while the hot path ships payloads through the kernel. Level 0 is the
identity, and a level-0 deployment must be indistinguishable -- float
for float -- from the pre-codec stacks.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.calibration import TemperatureScaling
from repro.core.policy import OffloadPlan
from repro.kernels import compress
from repro.kernels.ref import (
    CODEC_BITS,
    CODEC_TILE,
    decode_codec_ref,
    encode_codec_ref,
    roundtrip_codec_ref,
)


@pytest.fixture(autouse=True, scope="module")
def _release_codec_executables():
    """The interpret-mode encode/decode kernels compile one executable
    per (shape, level) this module sweeps; drop them at teardown so the
    suite-wide XLA executable footprint stays at its pre-codec level
    (the CPU backend has segfaulted compiling later LM smoke archs with
    the extra residents held alive)."""
    yield
    import jax

    jax.clear_caches()


def _rand(shape, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * scale).astype(np.float32)


# ------------------------------------------------------ kernel vs oracle
@pytest.mark.parametrize("level", [1, 2])
@pytest.mark.parametrize("shape", [
    (4, 256, 13, 13),   # branch-1 style conv payload
    (8, 1536),          # aligned 2D
    (3, 700),           # ragged rows and cols (pad both axes)
    (130,),             # 1D payload -> single row
])
def test_encode_matches_oracle_bitexact(level, shape):
    x = _rand(shape, seed=level * 101 + len(shape))
    enc = compress.encode(x, level)
    words, scales = encode_codec_ref(x, level)
    np.testing.assert_array_equal(np.asarray(enc.words), words)
    np.testing.assert_array_equal(np.asarray(enc.scales), scales)
    out = np.asarray(compress.decode(enc))
    ref = decode_codec_ref(words, scales, x.shape, level)
    assert out.dtype == np.float32 and out.shape == x.shape
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("ulps", [-2, -1, 0, 1, 2])
def test_nearest_quotient_corrects_an_inexact_divide(ulps):
    """The encode kernel's quotient is numpy's correctly rounded float32
    divide even when the hardware divide it starts from is a few ulps
    off (the TPU's is): simulated here by nudging the exact quotient."""
    rng = np.random.default_rng(3)
    s = (np.abs(rng.standard_normal(4096)) * 0.05 + 1e-3).astype(np.float32)
    # operands whose quotients sit near the half-integers a code rounds at
    k = rng.integers(-127, 127, 4096).astype(np.float32) + np.float32(0.5)
    z = np.concatenate([(k * s).astype(np.float32),
                        _rand(4096, seed=4), np.zeros(8, np.float32)])
    s = np.concatenate([s, s, s[:8]])
    exact = z / s
    y = np.where(exact != 0, exact.view(np.int32) + np.int32(ulps), 0)
    got = np.asarray(compress._nearest_quotient(
        jnp.asarray(z), jnp.asarray(s), jnp.asarray(y.view(np.float32))))
    np.testing.assert_array_equal(got.view(np.int32), exact.view(np.int32))


@pytest.mark.parametrize("level", [1, 2])
def test_roundtrip_error_bounded_by_quantization_step(level):
    x = _rand((16, 640), seed=7)
    out = np.asarray(compress.roundtrip(x, level))
    qmax = (1 << (CODEC_BITS[level] - 1)) - 1
    step = np.abs(x.reshape(16, -1, CODEC_TILE)).max(axis=2) / qmax
    err = np.abs(out - x).reshape(16, -1, CODEC_TILE)
    assert (err <= step[:, :, None] * 0.5 + 1e-7).all()


def test_all_zero_tile_stores_zero_scale_and_decodes_zero():
    x = np.zeros((8, 512), np.float32)
    x[:, 256:] = _rand((8, 256), seed=3)  # half the tiles are live
    for level in (1, 2):
        enc = compress.encode(x, level)
        scales = np.asarray(enc.scales)
        assert (scales[:, :2] == 0.0).all() and (scales[:, 2:] > 0).all()
        out = np.asarray(compress.decode(enc))
        assert np.isfinite(out).all()
        assert (out[:, :256] == 0.0).all()


def test_nonfinite_inputs_are_zeroed_not_flushed():
    """One inf must not give its tile an inf scale (flushing every other
    value to zero on decode); nan must not poison the absmax."""
    x = _rand((8, 512), seed=11)
    x[0, 5] = np.inf
    x[3, 200] = -np.inf
    x[7, 300] = np.nan
    for level in (1, 2):
        enc = compress.encode(x, level)
        assert np.isfinite(np.asarray(enc.scales)).all()
        out = np.asarray(compress.decode(enc))
        assert np.isfinite(out).all()
        clean = np.where(np.isfinite(x), x, np.float32(0.0))
        np.testing.assert_array_equal(out, roundtrip_codec_ref(clean, level))


def test_level0_roundtrip_is_identity_no_cast():
    x = _rand((4, 320), seed=5)
    ref = roundtrip_codec_ref(x, 0)
    assert ref is x  # the input object itself: no cast, no copy
    np.testing.assert_array_equal(np.asarray(compress.roundtrip(x, 0)), x)
    with pytest.raises(ValueError):
        compress.encode(x, 0)


# ------------------------------------------------------- analytic pricing
def test_analytic_nbytes_matches_wire_image():
    for shape in [(4, 256, 13, 13), (3, 700), (130,)]:
        x = _rand(shape, seed=1)
        for level in (1, 2):
            enc = compress.encode(x, level)
            bits = CODEC_BITS[level]
            packed = np.asarray(enc.words).shape[0] * np.asarray(
                enc.words).shape[1] * 4
            scale_bytes = np.asarray(enc.scales).size * 4
            # padded buffers equal the analytic padded size; the analytic
            # UNPADDED size never exceeds them
            assert enc.nbytes <= packed + scale_bytes
            rows = np.asarray(enc.scales).shape[0]
            cols = int(np.prod(shape[1:])) if len(shape) > 1 else shape[0]
            groups = -(-cols // CODEC_TILE)
            want = rows * ((cols * bits + 7) // 8 + 4 * groups)
            assert enc.nbytes == want


def test_branch_payload_byte_table():
    """The paper's two branch payloads at each level -- level 2 clears the
    4x floor the congested-uplink CI assertion relies on."""
    assert [compress.scaled_payload_nbytes(65536, l) for l in (0, 1, 2)] \
        == [65536, 16896, 8704]
    assert [compress.scaled_payload_nbytes(24576, l) for l in (0, 1, 2)] \
        == [24576, 6336, 3264]
    assert 65536 / 8704 > 4.0 and 24576 / 3264 > 4.0


# ---------------------------------------------- control-plane integration
def _plan(p_tar=0.8):
    return OffloadPlan(
        p_tar=p_tar,
        calibrators=[TemperatureScaling.from_temperature(1.0),
                     TemperatureScaling.from_temperature(1.0)],
    )


@pytest.fixture(scope="module")
def cascade():
    from repro.serving.scenarios import synthetic_cascade_logits

    return synthetic_cascade_logits(512)


def test_rescore_level0_only_reproduces_legacy_table(cascade):
    from repro.core.control import rescore_plan
    from repro.offload import latency as L

    exits, final, y = cascade
    plan = _plan()
    profile = L.paper_2020()
    args = ([exits[1], exits[2]],
            [L.edge_time(profile, b) for b in (1, 2)],
            [L.cloud_time(profile, b) for b in (1, 2)],
            [L.payload_bytes_for(b) for b in (1, 2)])
    kw = dict(final_logits=final, labels=y, uplink_bps=2e6,
              p_tar_grid=(0.5, 0.8), min_accuracy=0.5,
              arrival_rate_hz=50.0)
    legacy_plan, legacy = rescore_plan(plan, *args, **kw)
    lvl0_plan, lvl0 = rescore_plan(plan, *args,
                                   compression_levels=(0,), **kw)
    assert len(legacy) == len(lvl0)
    for a, b in zip(legacy, lvl0):
        assert b["compression_level"] == 0
        for k in a:
            assert a[k] == b[k] or (a[k] != a[k] and b[k] != b[k]), k
    assert lvl0_plan.compression_level == 0
    assert lvl0_plan.exit_index == legacy_plan.exit_index
    assert lvl0_plan.p_tar == legacy_plan.p_tar


def test_rescore_compression_axis_prices_bytes_and_accuracy(cascade):
    from repro.core.control import rescore_plan
    from repro.offload import latency as L

    exits, final, y = cascade
    plan = _plan()
    profile = L.paper_2020()
    _, table = rescore_plan(
        plan, [exits[1], exits[2]],
        [L.edge_time(profile, b) for b in (1, 2)],
        [L.cloud_time(profile, b) for b in (1, 2)],
        [L.payload_bytes_for(b) for b in (1, 2)],
        final_logits=final, labels=y,
        uplink_bps=1.5e6, arrival_rate_hz=40.0,
        p_tar_grid=(0.8,), compression_levels=(0, 1, 2),
    )
    assert len(table) == 2 * 1 * 3  # branch x p_tar x level
    by = {(r["exit_index"], r["compression_level"]): r for r in table}
    for i, raw in ((0, 65536), (1, 24576)):
        for lvl in (0, 1, 2):
            r = by[(i, lvl)]
            pb = compress.scaled_payload_nbytes(raw, lvl)
            assert r["uplink_nbytes"] == pytest.approx(
                pb * r["offload_prob"])
            if lvl > 0:
                # smaller payload: strictly better latency and utilization
                assert r["expected_latency_s"] < by[(i, 0)][
                    "expected_latency_s"]
                assert r["uplink_utilization"] < by[(i, 0)][
                    "uplink_utilization"]


def test_plan_compression_level_survives_serialization():
    plan = _plan().with_compression(2)
    assert plan.compression_level == 2
    back = OffloadPlan.from_dict(plan.to_dict())
    assert back.compression_level == 2
    # pre-codec plan dicts load at level 0
    d = plan.to_dict()
    d.pop("compression_level")
    assert OffloadPlan.from_dict(d).compression_level == 0


def test_serving_level0_controller_bitexact_with_legacy(cascade):
    """A bytes-aware controller restricted to level 0 must reproduce the
    bytes-blind controller's run float-for-float (the PR 8/9 parity
    rule, at serving scale)."""
    from repro.serving.controller import ControllerConfig
    from repro.serving.scenarios import run_congested_markov

    exits, final, y = cascade
    base = dict(interval_s=0.5, window_s=1.0, min_accuracy=0.9)
    a = run_congested_markov(_plan(), exits, final, y, n_requests=300,
                             with_controller=True,
                             controller_config=ControllerConfig(**base))
    b = run_congested_markov(_plan(), exits, final, y, n_requests=300,
                             with_controller=True,
                             controller_config=ControllerConfig(
                                 **base, compression_levels=(0,)))
    assert a.summary() == b.summary()


def test_serving_compressed_plan_ships_scaled_bytes(cascade):
    from repro.serving.scenarios import run_congested_markov

    exits, final, y = cascade
    a = run_congested_markov(_plan(), exits, final, y, n_requests=300)
    b = run_congested_markov(_plan().with_compression(2), exits, final, y,
                             n_requests=300)
    sa, sb = a.summary(), b.summary()
    assert sb["requests"] == sa["requests"] == 300
    # int4 payloads cross the congested link ~7.5x faster
    assert sb["p99_ms"] < sa["p99_ms"]
    assert sb["energy_j_total"] < sa["energy_j_total"]


def test_fleet_compiled_parity_at_level2(cascade):
    """Host and compiled fleet backends agree per-request on a COMPRESSED
    static deployment (scaled wire bytes, per-level cloud predictions,
    energy column)."""
    from repro.fleet.scenarios import reference_fleet, run_fleet
    from repro.serving.scenarios import (
        fit_drift_plans,
        synthetic_distorted_cascade,
    )

    val, test = synthetic_distorted_cascade(
        directions={"gaussian_blur": "under"})
    _, global_plan, _ = fit_drift_plans(val)
    plan = global_plan.with_compression(2)
    scn = reference_fleet(n_cells=4, requests_per_cell=120, seed=0,
                          val=val, test=test, cloud_servers=2)
    a = run_fleet(plan, scn)
    b = run_fleet(plan, scn, backend="compiled")
    sa, sb = a.fleet_summary(), b.fleet_summary()
    assert set(sa) == set(sb)
    for k in sa:
        np.testing.assert_allclose(sb[k], sa[k], rtol=1e-9, atol=1e-12)
    # and the compressed run genuinely differs from the raw one
    raw = run_fleet(global_plan, scn).fleet_summary()
    assert raw["energy_j_total"] > sa["energy_j_total"]


def test_engine_infer_compresses_actual_payload():
    """OffloadEngine runs the REAL kernel codec on the shipped activation
    when the plan carries a level: stats charge the encoded wire bytes
    and the cloud partition sees the dequantized floats."""
    seen = {}

    def edge(batch):
        n = batch["x"].shape[0]
        logits = jnp.stack([jnp.zeros(n), jnp.linspace(-2, 2, n)], axis=1)
        return {"exit_logits": logits, "payload": jnp.asarray(batch["x"])}

    def cloud(payload):
        seen["payload"] = np.asarray(payload)
        return {"logits": jnp.zeros((payload.shape[0], 2))}

    from repro.offload.engine import OffloadEngine

    x = _rand((32, 256), seed=9)
    plan = OffloadPlan(
        p_tar=0.9, calibrators=[TemperatureScaling.from_temperature(1.0)],
    ).with_compression(1)
    eng = OffloadEngine(edge, cloud, plan)
    res = eng.infer({"x": x})
    m = eng.stats.offloaded
    assert m > 0
    # charged bytes = analytic encoded size of the offloaded subset
    assert eng.stats.payload_bytes == compress.compressed_nbytes(256, 1) * m
    # the cloud saw the dequantized payload (the oracle roundtrip of the
    # refused rows), not the raw floats
    refused = x[~np.asarray(res["on_device"])]
    np.testing.assert_array_equal(seen["payload"],
                                  roundtrip_codec_ref(refused, 1))
    assert not np.array_equal(seen["payload"], refused)


def test_rescore_branch_pin_isolates_codec_axis(cascade):
    """branches=(k,) restricts the table to one split, so with
    p_tar_grid=None the codec level is the ONLY candidate axis -- the
    controlled comparison the BENCH compression sweep asserts on."""
    import pytest

    from repro.core.control import rescore_plan
    from repro.offload import latency as L

    exits, final, y = cascade
    plan = _plan()
    profile = L.paper_2020()
    args = ([exits[1], exits[2]],
            [L.edge_time(profile, b) for b in (1, 2)],
            [L.cloud_time(profile, b) for b in (1, 2)],
            [L.payload_bytes_for(b) for b in (1, 2)])
    kw = dict(final_logits=final, labels=y, uplink_bps=1.5e6,
              arrival_rate_hz=50.0)
    _, table = rescore_plan(plan, *args, branches=(1,),
                            compression_levels=(0, 1, 2), **kw)
    assert len(table) == 3  # one branch x plan's p_tar x three levels
    assert {r["exit_index"] for r in table} == {0}
    assert {r["compression_level"] for r in table} == {0, 1, 2}
    # pinning changes WHICH rows exist, not how a row is priced
    _, free = rescore_plan(plan, *args,
                           compression_levels=(0, 1, 2), **kw)
    by_lvl = {r["compression_level"]: r for r in free if r["exit_index"] == 0}
    for r in table:
        assert r == by_lvl[r["compression_level"]]
    with pytest.raises(ValueError):
        rescore_plan(plan, *args, branches=(3,), **kw)
