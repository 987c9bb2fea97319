"""Tests of the chip benchmark's harness.  They run on the CPU, at sizes a
test run can hold:

    python -m pytest benchmarks/chip/tests
"""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HARNESS_DIR = Path(__file__).resolve().parents[1]
ROOT = HARNESS_DIR.parents[1]
for p in (str(HARNESS_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def _limits(config: str) -> dict:
    """The limits of a real configuration, which the tiny copies keep."""
    path = HARNESS_DIR / "configs" / f"{config}.json"
    return json.loads(path.read_text())["limits"]


#: A CPU-sized copy of the configuration: same kind of matrices, same
#: driver, same limits.
TINY_CONFIGS = {
    "spiked_tiny": {"n": 64, "k": 4, "largest": True, "dtype": "float32",
                    "matrices": {"kind": "spiked_wishart", "samples": 128,
                                 "spikes": [2.0, 2.2, 2.4, 2.6]},
                    "krylov_m": 64, "bisect_iters": 32,
                    "server": {"max_batch": 4, "max_inflight": 2},
                    "limits": _limits("pca8192")},
}
TRACE = {"lead_s": 0.1, "length_s": 0.3}
TINY_TRAFFIC = {
    "solo": {"driver": "closed_burst", "burst": 1, "pool": 2,
             "warm_bursts": 1, "sample": 4, "trace": TRACE},
    "burst": {"driver": "closed_burst", "burst": 6, "pool": 8,
              "warm_bursts": 1, "sample": 4, "trace": TRACE},
}
TINY_CELLS = [("spiked_tiny", "solo"), ("spiked_tiny", "burst")]


def tiny_doc(harness_rel: str) -> dict:
    """A BENCHMARK.json for the tiny cells, harness under ``harness_rel``."""
    rates = {"solo": "time_to_solution_s", "burst": "solves_per_s"}
    e2e = [{"name": name, "unit": "s" if name.endswith("_s") else "1/s",
            "better": "lower" if name.endswith("_s") else "higher",
            "bound": 0.05, "source": "host_clock",
            "workloads": [f"{c}.{t}" for c, t in TINY_CELLS if rates[t] == name]}
           for name in sorted(set(rates.values()))]
    e2e.append({"name": "setup_s", "unit": "s", "better": "lower",
                "bound": 0.25, "source": "host_clock"})
    layer = [{"name": f"device_idle_frac.{t}", "unit": "frac",
              "better": "lower", "source": "device_trace", "layer": "device",
              "moves": rates[t],
              "workloads": [f"{c}.{tt}" for c, tt in TINY_CELLS if tt == t]}
             for t in TINY_TRAFFIC]
    return {
        "command": ["python3", f"{harness_rel}/run.py"],
        "paths": [harness_rel],
        "run_seconds": 1,
        "configs": [{"name": c, "source": "https://example.org/tiny",
                     "file": f"{harness_rel}/configs/{c}.json",
                     "reduced": ["n"], "why": "CPU-sized"}
                    for c in TINY_CONFIGS],
        "workloads": [{"name": f"{c}.{t}", "config": c, "traffic": t,
                       "chips": 1, "why": "CPU-sized"}
                      for c, t in TINY_CELLS],
        "end_to_end": e2e,
        "per_layer": layer,
    }


@pytest.fixture
def tiny_bench(tmp_path):
    """A repository root in ``tmp_path`` whose harness directory holds
    copies of the real drivers and readers and the tiny cells' files."""
    from harness import spec

    harness = tmp_path / "bench"
    for sub in ("drivers", "metrics"):
        shutil.copytree(HARNESS_DIR / sub, harness / sub)
    (harness / "configs").mkdir()
    (harness / "traffic").mkdir()
    for name, cfg in TINY_CONFIGS.items():
        (harness / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, mix in TINY_TRAFFIC.items():
        (harness / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    doc = tiny_doc("bench")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return spec.Benchmark.load(tmp_path / "BENCHMARK.json", harness)
