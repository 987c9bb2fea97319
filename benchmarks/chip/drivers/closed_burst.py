"""Closed-loop bursts: submit ``burst`` matrices, ``flush``, wait for every
answer, then send the next burst, cycling through a pool of distinct
matrices in a seed-drawn order.  One client; the next burst waits for the
last.  Reports ``solves_per_s``: answers that came back verified and not
degraded, over the whole window; and its reciprocal, the mean
``time_to_solution_s`` per such answer (for one large matrix).

Mix keys: ``burst``, ``pool``, ``warm_bursts``, ``sample`` (answers drawn
for the check), ``trace`` (``lead_s``, ``length_s``).
"""

from __future__ import annotations

import time

from harness import client, window
from harness.reference import Sample


class Driver:
    def __init__(self, cell, seed: int):
        from repro.engine import EeiServer

        cfg, mix = cell.config, cell.traffic
        self.k, self.largest = cfg["k"], cfg["largest"]
        self.burst = mix["burst"]
        self.rng = client.data.host_rng(seed)
        self.pool = client.make_pool(cfg, seed, mix["pool"])
        self.pool_peak_bytes = client.device_peak_bytes()
        self.order = self.rng.permutation(len(self.pool))
        self.cursor = 0
        self.sample = window.Reservoir(mix["sample"], client.data.host_rng(
            seed, 1))
        self.server = EeiServer(**client.server_settings(cell))
        for _ in range(mix["warm_bursts"]):
            self._burst()

    def _burst(self):
        idx = [int(self.order[(self.cursor + j) % len(self.order)])
               for j in range(self.burst)]
        self.cursor += self.burst
        with client.span("bench.submit"):
            futs = [self.server.submit(self.pool[j], self.k, self.largest)
                    for j in idx]
        with client.span("bench.flush"):
            self.server.flush()
        out = []
        with client.span("bench.wait"):
            for j, fut in zip(idx, futs):
                try:
                    out.append((j, fut.result(timeout=600)))
                except Exception:  # an answer that never came
                    out.append((j, None))
        return out

    def window(self, seconds: float, stretch) -> client.WindowRecord:
        stats0 = self.server.stats()
        attempted = failed = solved = 0
        t0 = time.perf_counter()
        stretch.start()
        t_end = t0
        while t_end < t0 + seconds:
            for j, res in self._burst():
                attempted += 1
                if res is None or res.degraded:
                    failed += 1
                else:
                    solved += 1
                self.sample.offer((j, res))
            t_end = time.perf_counter()
        stretch.join()
        return client.WindowRecord(
            t_start=t0, t_end=t_end, attempted=attempted, failed=failed,
            end_to_end={"solves_per_s": window.rate(solved, t0, t_end),
                        "time_to_solution_s": ((t_end - t0) / solved
                                               if solved else float("inf"))},
            counters=client.counter_delta(stats0, self.server.stats()))

    def release(self) -> list:
        client.release(self.server)
        return [Sample(a=self.pool[j], k=self.k, largest=self.largest,
                       lam=None if res is None else res.eigenvalues,
                       vecs=None if res is None else res.vectors, key=j)
                for j, res in self.sample.items]
