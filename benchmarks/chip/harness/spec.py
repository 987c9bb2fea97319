"""``BENCHMARK.json``: validation, and lookup of each cell's files by name.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric lives in a file of its own, found by its name:

    configs/<config>.json        named by the configuration entry's ``file``
    traffic/<traffic>.json       the mix; its ``driver`` key names the driver
    drivers/<driver>.py          defines ``Driver``
    metrics/<base>.py            defines ``read(ctx)``; ``<base>`` is the
                                 metric name up to its first dot, so
                                 ``device_idle_frac.solves`` reads with
                                 ``metrics/device_idle_frac.py``

A later cell, mix or metric is added as new files plus entries in
``BENCHMARK.json``; no existing file changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE_RE = re.compile(r"^[^\t\r\n]{1,200}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}


class SpecError(ValueError):
    """``BENCHMARK.json`` breaks the benchmark's contract."""


def _check_keys(entry: dict, required: set, optional: set, what: str):
    keys = set(entry)
    if not required <= keys or not keys <= required | optional:
        raise SpecError(f"{what} has keys {sorted(keys)}; expected "
                        f"{sorted(required)} (+ optional {sorted(optional)})")


def _check_name(value, what: str):
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise SpecError(f"{what} {value!r} is not a valid name")


def _check_line(value, what: str):
    if not isinstance(value, str) or not LINE_RE.match(value):
        raise SpecError(f"{what} must be one line of 1 to 200 characters")


def _check_unique(entries: list, what: str):
    names = [e["name"] for e in entries]
    if len(names) != len(set(names)):
        raise SpecError(f"duplicate {what} names")


def validate(doc: dict) -> None:
    """Raise :class:`SpecError` where ``doc`` breaks the contract's names,
    units, keys and bounds."""
    if set(doc) != TOP_KEYS:
        raise SpecError(f"top-level keys {sorted(doc)} != {sorted(TOP_KEYS)}")
    cmd = doc["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        raise SpecError("command must be a list of 1 to 32 strings")
    for word in cmd:
        _check_line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise SpecError(f"command word {word!r} leaves the repository")
    paths = doc["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        raise SpecError("paths must list 1 to 16 directories")
    for p in paths:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            raise SpecError(f"path {p!r} is not a relative repository path")
    rs = doc["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        raise SpecError("run_seconds must be a whole number from 1 to 51")

    configs = doc["configs"]
    if not 1 <= len(configs) <= 24:
        raise SpecError("configs must hold 1 to 24 entries")
    for c in configs:
        _check_keys(c, CONFIG_KEYS, set(), f"config {c.get('name')!r}")
        _check_name(c["name"], "config name")
        _check_line(c["source"], "config source")
        _check_line(c["why"], "config why")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            raise SpecError(f"config file {c['file']!r} is not under paths")
        if not (isinstance(c["reduced"], list) and len(c["reduced"]) <= 16):
            raise SpecError("reduced must list at most 16 keys")
        for key in c["reduced"]:
            _check_name(key, "reduced key")
    _check_unique(configs, "config")
    if len({c["file"] for c in configs}) != len(configs):
        raise SpecError("two configurations share a file")
    config_names = {c["name"] for c in configs}

    cells = doc["workloads"]
    if not 1 <= len(cells) <= 24:
        raise SpecError("workloads must hold 1 to 24 cells")
    for w in cells:
        _check_keys(w, WORKLOAD_KEYS, set(), f"workload {w.get('name')!r}")
        _check_name(w["name"], "workload name")
        _check_name(w["traffic"], "traffic name")
        _check_line(w["why"], "workload why")
        if w["config"] not in config_names:
            raise SpecError(f"workload {w['name']!r} names an unknown config")
        if w["chips"] not in (1, 4):
            raise SpecError("chips must be 1 or 4")
    _check_unique(cells, "workload")
    if len({(w["config"], w["traffic"]) for w in cells}) != len(cells):
        raise SpecError("a (config, traffic) pair appears twice")
    if {w["config"] for w in cells} != config_names:
        raise SpecError("every configuration must be used by some cell")
    cell_names = {w["name"] for w in cells}

    e2e, layer = doc["end_to_end"], doc["per_layer"]
    if not 1 <= len(e2e) <= 16:
        raise SpecError("end_to_end must hold 1 to 16 metrics")
    if not 1 <= len(layer) <= 128:
        raise SpecError("per_layer must hold 1 to 128 metrics")
    for m in e2e + layer:
        is_e2e = m in e2e
        _check_keys(m, E2E_KEYS if is_e2e else LAYER_KEYS, {"workloads"},
                    f"metric {m.get('name')!r}")
        _check_name(m["name"], "metric name")
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            raise SpecError(f"metric {m['name']!r} unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"metric {m['name']!r}: better must be "
                            "'lower' or 'higher'")
        if m["source"] not in (E2E_SOURCES if is_e2e else SOURCES):
            raise SpecError(f"metric {m['name']!r}: bad source {m['source']!r}")
        for cell in m.get("workloads", []):
            if cell not in cell_names:
                raise SpecError(f"metric {m['name']!r} lists unknown cell "
                                f"{cell!r}")
        if is_e2e:
            b = m["bound"]
            if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
                raise SpecError(f"bound of {m['name']!r} must lie in "
                                "[0.01, 0.25]")
        else:
            _check_line(m["layer"], f"layer of {m['name']!r}")
    _check_unique(e2e + layer, "metric")
    if "setup_s" not in {m["name"] for m in e2e}:
        raise SpecError("end_to_end must define setup_s")
    e2e_names = {m["name"] for m in e2e}
    for m in layer:
        if m["moves"] not in e2e_names:
            raise SpecError(f"{m['name']!r} moves unknown {m['moves']!r}")
    for w in cells:
        reported = [m for m in e2e if _reports(m, w["name"])]
        if len(reported) < 2:
            raise SpecError(f"cell {w['name']!r} reports no end-to-end "
                            "metric besides setup_s")
        if not any(_reports(m, w["name"]) for m in layer):
            raise SpecError(f"cell {w['name']!r} reports no per-layer metric")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload with its configuration, mix and metrics resolved."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1


class Benchmark:
    """A validated ``BENCHMARK.json`` and the harness directory whose
    ``configs/``, ``traffic/``, ``drivers/`` and ``metrics/`` it names."""

    def __init__(self, doc: dict, root: Path, harness_dir: Path):
        validate(doc)
        self.doc = doc
        self.root = Path(root)
        self.harness_dir = Path(harness_dir)

    @classmethod
    def load(cls, path: Path, harness_dir: Path) -> "Benchmark":
        path = Path(path)
        with path.open() as fh:
            return cls(json.load(fh), path.parent, harness_dir)

    def cell(self, name: str) -> Cell:
        by_name = {w["name"]: w for w in self.doc["workloads"]}
        if name not in by_name:
            raise SpecError(f"no workload {name!r}; have {sorted(by_name)}")
        w = by_name[name]
        entry = next(c for c in self.doc["configs"] if c["name"] == w["config"])
        config = _read_json(self.root / entry["file"])
        traffic = _read_json(self.traffic_path(w["traffic"]))
        e2e = tuple(m for m in self.doc["end_to_end"] if _reports(m, name))
        per_layer = tuple(m for m in self.doc["per_layer"]
                          if _reports(m, name)
                          and m["moves"] in {e["name"] for e in e2e})
        return Cell(name=name, chips=w["chips"], config_name=w["config"],
                    traffic_name=w["traffic"], config=config, traffic=traffic,
                    end_to_end=e2e, per_layer=per_layer)

    def traffic_path(self, traffic: str) -> Path:
        return self.harness_dir / "traffic" / f"{traffic}.json"

    def driver(self, cell: Cell) -> ModuleType:
        return load_module(self.harness_dir / "drivers"
                           / f"{cell.traffic['driver']}.py")

    def metric_reader(self, metric_name: str) -> ModuleType:
        base = metric_name.split(".", 1)[0]
        return load_module(self.harness_dir / "metrics" / f"{base}.py")


def _read_json(path: Path) -> dict:
    with Path(path).open() as fh:
        return json.load(fh)


def load_module(path: Path) -> ModuleType:
    """Import one driver or metric reader from its file."""
    path = Path(path)
    if not path.is_file():
        raise SpecError(f"no file {path}")
    name = f"chipbench_{path.parent.name}_{path.stem}"
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module
