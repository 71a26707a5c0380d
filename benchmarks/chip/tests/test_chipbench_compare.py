"""The comparison that decides `correct` passes on the reference's own
answers and fails on each kind of perturbed answer."""
import json

import numpy as np
import pytest

import harness
from conftest import CHECKOUT

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
CELL = harness.find_cell(BENCH, BENCH["workloads"][0]["name"])
DRV = CELL.driver()
REF = CELL.reference()
LIMITS = CELL.traffic["limits"]


def _batch(seed, rows=12, classes=10):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(rows, classes)) * 3
    f = rng.normal(size=(rows, classes)) * 3
    ce = REF.softmax64(z, 2.0).max(-1)
    p_tar = float(np.median(ce))
    cal = {"t": 2.0, "t_lo": 2.0, "t_hi": 2.0, "p_tar": p_tar, "p_lo": p_tar, "p_hi": p_tar}
    on = ce >= p_tar
    ref = {"on_device": on, "edge_logits": z, "edge_confidence": ce,
           "edge_conf_lo": ce, "edge_conf_hi": ce,
           "final_logits": f, "cloud_confidence": REF.softmax64(f).max(-1)}
    ans = {"on_device": on.copy(),
           "prediction": np.where(on, z.argmax(-1), f.argmax(-1)),
           "confidence": np.where(on, ce, ref["cloud_confidence"]).astype(np.float32)}
    return cal, ans, ref


def _codec(seed):
    x = np.random.default_rng(seed).normal(size=(3, 4, 4, 64)).astype(np.float32)
    words, scales = REF.encode(x, 8, 128)
    return dict(codec_in=x, words=words.copy(), scales=scales.copy(), shape=x.shape,
                decoded=REF.decode(words, scales, x.shape, 8, 128), ref=REF,
                bits=8, tile=128)


def _checks(cal, ans, ref, codec=None):
    return DRV.compare(cal["p_tar"], [ans], cal, [ref], [codec], LIMITS)


def _ok(checks):
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_reference_answers_pass():
    assert _ok(_checks(*_batch(0), _codec(0)))


@pytest.mark.parametrize("fault", ["edge_answer", "cloud_answer", "edge_confidence",
                                   "cloud_confidence", "gate", "p_tar",
                                   "codec_word", "codec_scale", "codec_decode",
                                   "codec_uncaptured"])
def test_perturbed_answers_fail(fault):
    cal, ans, ref = _batch(1)
    p_tar = cal["p_tar"]
    codec = _codec(1)
    on = ans["on_device"]
    i_on, i_off = np.flatnonzero(on)[0], np.flatnonzero(~on)[0]
    if fault == "edge_answer":
        ans["prediction"][i_on] = (ans["prediction"][i_on] + 1) % 10
    elif fault == "cloud_answer":
        ans["prediction"][i_off] = (ans["prediction"][i_off] + 1) % 10
    elif fault == "edge_confidence":
        ans["confidence"][i_on] += 1e-3
    elif fault == "cloud_confidence":
        ans["confidence"][i_off] -= 1e-2
    elif fault == "gate":
        far = np.argmax(np.abs(ref["edge_confidence"] - p_tar))
        ans["on_device"][far] = not ans["on_device"][far]
    elif fault == "p_tar":
        p_tar += 1e-3
    elif fault == "codec_word":
        codec["words"][1, 7] ^= np.uint32(1 << 9)
    elif fault == "codec_scale":
        codec["scales"][2, 0] = np.nextafter(codec["scales"][2, 0], np.float32(1))
    elif fault == "codec_decode":
        codec["decoded"] = codec["decoded"].copy()
        codec["decoded"][0, 1, 2, 3] += 1e-3
    else:  # offloaded rows, but the codec was not seen
        codec = None
    checks = DRV.compare(p_tar, [ans], cal, [ref], [codec], LIMITS)
    assert not _ok(checks)


def test_codec_oracle_round_trip_is_within_half_a_step():
    x = np.random.default_rng(2).normal(size=(5, 16, 16, 64)).astype(np.float32)
    words, scales = REF.encode(x, 8, 128)
    back = REF.decode(words, scales, x.shape, 8, 128)
    step = np.repeat(scales, 128, axis=1).reshape(x.shape)
    assert np.all(np.abs(back - x) <= 0.5 * step * (1 + 1e-6))


def test_confidence_within_the_tied_temperatures_passes():
    cal, ans, ref = _batch(4)
    on = ans["on_device"]
    ref["edge_conf_lo"] = ref["edge_confidence"] - 1e-3
    ref["edge_conf_hi"] = ref["edge_confidence"] + 1e-3
    ans["confidence"] = np.where(on, ans["confidence"] + 5e-4, ans["confidence"])
    cal = dict(cal, p_lo=cal["p_tar"] - 1e-3, p_hi=cal["p_tar"] + 1e-3)
    assert _ok(DRV.compare(cal["p_tar"] + 5e-4, [ans], cal, [ref], [_codec(4)], LIMITS))


def test_tied_interval_brackets_the_optimum():
    rng = np.random.default_rng(5)
    z = rng.normal(size=(3000, 10)) * 4
    y = np.array([rng.choice(10, p=p) for p in REF.softmax64(z, 3.0)])
    t = REF.fit_temperature(z, y, 0.05, 20.0)
    lo, hi = REF.tied_interval(z, y, t, 0.05, 20.0, 2e-6)
    assert lo < t < hi and hi / lo < 1.05
    assert REF.nll(z, y, lo) - REF.nll(z, y, t) == pytest.approx(2e-6, rel=1e-3)


def test_temperature_fit_finds_the_generating_temperature():
    rng = np.random.default_rng(3)
    z = rng.normal(size=(4000, 10)) * 4
    y = np.array([rng.choice(10, p=p) for p in REF.softmax64(z, 2.5)])
    assert REF.fit_temperature(z, y, 0.05, 20.0) == pytest.approx(2.5, rel=0.1)
    assert REF.fit_temperature(rng.normal(size=(500, 10)), y[:500], 0.05, 20.0) <= 20.0
