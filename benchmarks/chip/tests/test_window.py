"""Window arithmetic: a rate over the whole window, and the seeded sample
of answers kept for the check."""

import numpy as np
import pytest

from harness import window


def test_rate_counts_the_whole_window_including_idle_time():
    # 100 answers, all in the first second of a 4 s window: 25/s, not 100/s.
    assert window.rate(100, 10.0, 14.0) == pytest.approx(25.0)
    with pytest.raises(ValueError):
        window.rate(1, 2.0, 2.0)


def test_reservoir_is_uniform_and_seeded():
    picks = []
    for seed in range(2):
        r = window.Reservoir(5, np.random.default_rng(seed))
        for i in range(1000):
            r.offer(i)
        picks.append(sorted(r.items))
        assert len(r.items) == 5 and r.seen == 1000
    r = window.Reservoir(5, np.random.default_rng(0))
    for i in range(1000):
        r.offer(i)
    assert sorted(r.items) == picks[0] != picks[1]
