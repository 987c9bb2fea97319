"""Names, units, keys and bounds of ``BENCHMARK.json``."""

import copy
import json

import pytest

from conftest import HARNESS_DIR, ROOT
from harness import spec


def real_doc():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_repository_benchmark_is_valid_and_every_cell_resolves():
    bench = spec.Benchmark.load(ROOT / "BENCHMARK.json", HARNESS_DIR)
    for w in bench.doc["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert hasattr(bench.driver(cell), "Driver")
        for m in cell.per_layer:
            assert hasattr(bench.metric_reader(m["name"]), "read")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)


def _metric(doc, name):
    return next(m for m in doc["end_to_end"] + doc["per_layer"]
                if m["name"] == name)


BREAKS = {
    "space in a name": lambda d: d["workloads"][0].update(name="a b"),
    "slash in a name": lambda d: _metric(d, "setup_s").update(name="a/b"),
    "non-ascii unit": lambda d: _metric(d, "setup_s").update(unit="µs"),
    "unit with spaces": lambda d: _metric(d, "setup_s").update(
        unit="solves per s"),
    "better neither": lambda d: _metric(d, "setup_s").update(better="up"),
    "bound over 0.25": lambda d: _metric(d, "setup_s").update(bound=0.3),
    "bound under 1%": lambda d: _metric(d, "setup_s").update(bound=0.005),
    "no setup_s": lambda d: d["end_to_end"].remove(_metric(d, "setup_s")),
    "extra key on a metric": lambda d: d["per_layer"][0].update(why="x"),
    "unknown moves": lambda d: d["per_layer"][0].update(moves="nope"),
    "unknown cell listed": lambda d: d["per_layer"][0].update(
        workloads=["nope.cell"]),
    "duplicate metric": lambda d: d["per_layer"].append(
        copy.deepcopy(d["per_layer"][0])),
    "program source for end-to-end": lambda d: _metric(
        d, "setup_s").update(source="program_counter"),
    "chips 2": lambda d: d["workloads"][0].update(chips=2),
    "run_seconds 52": lambda d: d.update(run_seconds=52),
    "absolute command": lambda d: d.update(command=["/usr/bin/python3"]),
    "config outside paths": lambda d: d["configs"][0].update(
        file="configs/x.json"),
    "tab in a why": lambda d: d["workloads"][0].update(why="a\tb"),
    "extra top-level key": lambda d: d.update(notes="x"),
}


@pytest.mark.parametrize("break_it", list(BREAKS.values()), ids=list(BREAKS))
def test_contract_breaks_are_refused(break_it):
    doc = real_doc()
    break_it(doc)
    with pytest.raises(spec.SpecError):
        spec.validate(doc)
