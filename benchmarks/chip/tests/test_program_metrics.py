"""Per-layer readers of the program's own counters: host milliseconds per
stack, device wait per stack and Lanczos steps per request."""

import importlib
import time

import numpy as np
import pytest

from conftest import HARNESS_DIR, ROOT
from harness import client, spec

SPANS = ("assemble", "copy_in", "launch", "fetch", "retire", "fallback")

#: A window of 4 stacks and 4 requests on a program that has the counters.
COUNTERS = {
    "stacks_dispatched": 4, "requests_completed": 4,
    **{f"{s}_ns": 1_000_000 * (i + 1) for i, s in enumerate(SPANS)},
    "device_wait_ns": 12_000_000_000, "lanczos_steps": 640,
}

EXPECTED = {
    "server_host_ms": (1 + 2 + 3 + 4 + 5 + 6) / 4,
    "device_wait_ms": 3000.0,
    "lanczos_steps": 160.0,
}

#: What each reader needs, besides its denominator.
NEEDS = {
    "server_host_ms": [f"{s}_ns" for s in SPANS],
    "device_wait_ms": ["device_wait_ns"],
    "lanczos_steps": ["lanczos_steps"],
}
DENOMINATOR = {"server_host_ms": "stacks_dispatched",
               "device_wait_ms": "stacks_dispatched",
               "lanczos_steps": "requests_completed"}


def _reader(base: str):
    return importlib.import_module(f"metrics.{base}")


def _ctx(counters: dict):
    record = client.WindowRecord(t_start=0.0, t_end=1.0, attempted=4,
                                 failed=0, end_to_end={}, counters=counters)
    return type("Ctx", (), {"record": record, "trace": None})


@pytest.mark.parametrize("base", sorted(EXPECTED))
def test_reader_value(base):
    assert _reader(base).read(_ctx(COUNTERS)) == pytest.approx(EXPECTED[base])


def _without(key):
    return {k: v for k, v in COUNTERS.items() if k != key}


CASES = [(base, f"no {key}", _without(key))
         for base, keys in NEEDS.items() for key in keys[:1] + keys[-1:]]
CASES += [(base, f"{den} 0", dict(COUNTERS, **{den: 0}))
          for base, den in DENOMINATOR.items()]
CASES += [(base, f"no {den}", _without(den))
          for base, den in DENOMINATOR.items()]
# A plan without a Krylov reduce (or a server with verify off) counts no
# step: that is no reading, not the best one.
CASES += [("lanczos_steps", "lanczos_steps 0",
           dict(COUNTERS, lanczos_steps=0))]


@pytest.mark.parametrize("base, counters",
                         [(b, c) for b, _, c in CASES],
                         ids=[f"{b}-{why}" for b, why, _ in CASES])
def test_reader_reads_none_without_its_counters(base, counters):
    assert _reader(base).read(_ctx(counters)) is None


def test_benchmark_lists_the_readers_as_program_counters():
    bench = spec.Benchmark.load(ROOT / "BENCHMARK.json", HARNESS_DIR)
    names = {m["name"]: m for m in bench.cell("pca8192.solve").per_layer}
    for base in EXPECTED:
        entry = names[f"{base}.solve"]
        assert entry["source"] == "program_counter"
        assert entry["moves"] == "time_to_solution_s"
        assert hasattr(bench.metric_reader(entry["name"]), "read")


def test_readers_read_a_served_krylov_solve():
    """The counters of a real server, as a window reads them: deltas of
    two ``stats()`` around it."""
    from repro.engine import EeiServer, SolverPlan

    rng = np.random.default_rng(2**31 + 5)
    x = rng.standard_normal((64, 64))
    a = ((x + x.T) / 2).astype(np.float32)
    server = EeiServer(SolverPlan(method="eei_krylov", backend="jnp",
                                  spectrum="windowed", krylov_m=48))
    for _ in range(2):
        server.submit(a, 4)
    server.flush()  # warm: compiles the stack of two outside the window
    before = server.stats()
    t0 = time.perf_counter()
    futs = [server.submit(a, 4) for _ in range(2)]
    server.flush()
    assert all(not f.result(timeout=120).degraded for f in futs)
    counters = client.counter_delta(before, server.stats())
    assert counters["program_compiles"] == 0
    ctx = _ctx(counters)
    host = _reader("server_host_ms").read(ctx)
    wait = _reader("device_wait_ms").read(ctx)
    steps = _reader("lanczos_steps").read(ctx)
    assert 0 < host and 0 < wait
    assert host + wait <= (time.perf_counter() - t0) * 1e3
    assert 0 < steps <= 48
