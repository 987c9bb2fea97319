"""Inputs made from the seed: Johnstone's spiked sample covariance."""

import numpy as np

from harness import data


def test_spiked_wishart_is_symmetric_seeded_and_spiked():
    n, samples, multiples = 1024, 2048, [2.0, 2.2, 2.4, 2.6]
    a = data.spiked_wishart_pool(2**33 + 5, 2, n, samples, multiples)
    assert a.shape == (2, n, n) and a.dtype == np.float32
    np.testing.assert_array_equal(a[0], a[0].T)
    np.testing.assert_array_equal(
        a, data.spiked_wishart_pool(2**33 + 5, 2, n, samples, multiples))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(
        a[0], data.spiked_wishart_pool(7, 1, n, samples, multiples)[0])
    gamma = n / samples
    edge = (1 + np.sqrt(gamma)) ** 2  # Marchenko-Pastur bulk edge
    h = data.spike_strengths(n, samples, multiples)
    assert np.allclose(h / np.sqrt(gamma), multiples)
    # Where a spike 1 + h lands in the sample covariance (BBP): above the
    # bulk edge for every h over the threshold sqrt(gamma).
    placed = np.sort((1 + h) * (1 + gamma / h))
    lam = np.linalg.eigvalsh(a[0].astype(np.float64))
    np.testing.assert_allclose(lam[-4:], placed, rtol=0.08)
    assert lam[-5] < edge + 0.1 < placed[0]
    assert abs(np.trace(a[0]) / n - (1 + h.sum() / n)) < 0.01
