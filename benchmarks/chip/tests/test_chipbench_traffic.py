"""The served cells' batches are fresh seeded draws from the pool: the
same seed gives the same batches, every image is served once per pass,
and a batch's refused count falls where its images do."""
import json

import numpy as np

import harness
from conftest import CHECKOUT

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
DRV = harness.find_cell(BENCH, BENCH["workloads"][0]["name"]).driver()


def test_draws_are_seeded_passes_over_the_pool():
    a = DRV.draw_batches(2**33 + 1, 1024, 256, 10)
    assert a.shape == (10, 256)
    assert np.array_equal(a, DRV.draw_batches(2**33 + 1, 1024, 256, 10))
    assert not np.array_equal(a, DRV.draw_batches(2**33 + 2, 1024, 256, 10))
    for p in range(2):  # 4 batches to a pass over 1,024 images
        assert np.array_equal(np.sort(a[4 * p:4 * p + 4].ravel()), np.arange(1024))


def test_refused_counts_spread_like_a_binomial():
    refused = np.random.default_rng(0).random(32768) < 0.8
    counts = refused[DRV.draw_batches(5, 32768, 256, 4096)].sum(axis=1)
    assert abs(counts.mean() - 0.8 * 256) < 1.0
    assert 5.5 < counts.std() < 7.3  # sqrt(256 * 0.8 * 0.2) = 6.4
    assert len(np.unique(counts)) > 32


def test_images_are_row_major():
    import imagegen

    tmpl = imagegen.templates(imagegen.key(1), 10, (32, 32, 3))
    x, y = imagegen.image_split(7, tmpl, 64, {"easy_frac": 0.6, "noise": 1.2})
    assert x.shape == (64, 32, 32, 3) and x.flags["C_CONTIGUOUS"] and y.shape == (64,)


def test_warmed_counts_are_the_central_binomial_range_and_the_windows():
    tr = {"batch": 256, "offload_share": 0.8, "warm_sigmas": 5}
    central = DRV.warm_counts(tr, np.array([], dtype=int))
    assert central[0] == 173 and central[-1] == 236  # 204.8 -+ 5 x 6.4
    assert np.array_equal(central, np.arange(173, 237))
    window = np.array([171, 200, 200, 240])
    warmed = DRV.warm_counts(tr, window)
    assert set(window) <= set(warmed) and set(central) <= set(warmed)
    assert len(warmed) == len(central) + 2
    low = DRV.warm_counts({"batch": 256, "offload_share": 0.1, "warm_sigmas": 5}, window[:0])
    assert low[0] == 2 and low[-1] == 49
