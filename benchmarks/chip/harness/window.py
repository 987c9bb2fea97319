"""Window arithmetic: a rate over the whole window, and the seeded sample
of answers kept for the check.

Every time here is ``time.perf_counter()`` seconds.  A rate counts the
work completed inside the window over the window's full length.
"""

from __future__ import annotations

import numpy as np


def rate(completed: int, t_start: float, t_end: float) -> float:
    """Completed work per second over ``[t_start, t_end]``."""
    span = t_end - t_start
    if span <= 0:
        raise ValueError(f"empty window [{t_start}, {t_end}]")
    return completed / span


class Reservoir:
    """Uniform sample of ``size`` items from a stream of unknown length,
    drawn from a seeded generator (Algorithm R): the same seed and the same
    stream give the same sample."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.seen = 0
        self.items: list = []

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1
