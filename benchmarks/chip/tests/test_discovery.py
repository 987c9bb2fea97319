"""The harness finds a cell's configuration, mix, driver and metric
readers by name, including ones added later as new files and entries."""

import json

import pytest

from harness import spec


def _snapshot(root):
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


def test_tiny_cells_resolve_by_name(tiny_bench):
    cell = tiny_bench.cell("spiked_tiny.burst")
    assert cell.config["n"] == 64
    assert cell.traffic["burst"] == 6
    assert tiny_bench.driver(cell).__name__.endswith("closed_burst")
    assert [m["name"] for m in cell.per_layer] == ["device_idle_frac.burst"]
    with pytest.raises(spec.SpecError):
        tiny_bench.cell("spiked_tiny.nope")


def test_added_config_mix_and_metric_are_found_without_edits(tiny_bench):
    root, harness = tiny_bench.root, tiny_bench.harness_dir
    before = _snapshot(root)
    # New files only ...
    (harness / "configs" / "spiked_wide.json").write_text(json.dumps(
        dict(json.loads((harness / "configs" / "spiked_tiny.json").read_text()),
             n=128)))
    (harness / "traffic" / "pairs.json").write_text(json.dumps(
        {"driver": "closed_burst", "burst": 2, "pool": 4, "warm_bursts": 0,
         "sample": 2, "trace": {"lead_s": 0.0, "length_s": 0.1}}))
    (harness / "metrics" / "burst_size.py").write_text(
        "def read(ctx):\n    return float(ctx.traffic['burst'])\n")
    # ... and new entries in BENCHMARK.json.
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "spiked_wide", "source": "https://x.org",
                           "file": "bench/configs/spiked_wide.json",
                           "reduced": [], "why": "added later"})
    doc["workloads"].append({"name": "spiked_wide.pairs", "config": "spiked_wide",
                             "traffic": "pairs", "chips": 1, "why": "added"})
    next(m for m in doc["end_to_end"] if m["name"] == "solves_per_s")[
        "workloads"].append("spiked_wide.pairs")
    doc["per_layer"].append({
        "name": "burst_size.pairs", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "client", "moves": "solves_per_s",
        "workloads": ["spiked_wide.pairs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    changed = [p for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []
    bench = spec.Benchmark.load(root / "BENCHMARK.json", harness)
    cell = bench.cell("spiked_wide.pairs")
    assert cell.config["n"] == 128 and cell.traffic["burst"] == 2
    assert [m["name"] for m in cell.per_layer] == ["burst_size.pairs"]
    reader = bench.metric_reader("burst_size.pairs")
    assert reader.read(type("Ctx", (), {"traffic": cell.traffic})) == 2.0
    assert hasattr(bench.driver(cell), "Driver")
