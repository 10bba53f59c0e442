"""Find a cell's files by name from `BENCHMARK.json`.

Everything that belongs to one configuration, traffic mix, job kind or
metric sits in a file of its own, found by the name `BENCHMARK.json`
gives it:

    bench/configs/<config>.json        sizes, source, `reduced`, `assumed`
    bench/traffic/<traffic>.json       job kind and its parameters
    bench/cells/<workload>.json        the limit of each number `correct` compares
    bench/jobs/<kind>.py               builds the system, runs one job, checks it
    bench/end_to_end/<metric>.py       reduces the window's job records
    bench/layer_metrics/<metric>.py    reads one per-layer metric; a metric
                                       `base.cell` falls back to `base.py`

So a new cell of an existing job kind is a traffic file, a cell file and
an entry in `BENCHMARK.json`: no file that is already there changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class BenchError(ValueError):
    """`BENCHMARK.json` or a file it names cannot be read as a benchmark."""


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise BenchError(f"{what}: bad name {value!r}")
    return value


def _keys(entry: dict, required: set, what: str, optional=()) -> None:
    if not isinstance(entry, dict):
        raise BenchError(f"{what}: not an object")
    extra = set(entry) - required - set(optional)
    missing = required - set(entry)
    if extra or missing:
        raise BenchError(f"{what}: extra keys {sorted(extra)}, missing "
                         f"{sorted(missing)}")


def _unique(names: list, what: str) -> set:
    seen = set()
    for name in names:
        if name in seen:
            raise BenchError(f"{what} {name} given twice")
        seen.add(name)
    return seen


def _metric(m: dict, keys: set, cells: set, what: str) -> None:
    _keys(m, keys, what, optional=("workloads",))
    _name(m["name"], what)
    if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
        raise BenchError(f"{what}: bad unit {m['unit']!r}")
    for w in m.get("workloads", ()):
        if w not in cells:
            raise BenchError(f"{what}: unknown workload {w!r}")


def validate(bench: dict) -> dict:
    """Check what the harness needs to find every file by name: the keys,
    the names and units, and that every reference resolves.  Returns
    ``bench`` or raises BenchError."""
    _keys(bench, TOP_KEYS, "BENCHMARK.json")
    for c in bench["configs"]:
        _keys(c, CONFIG_KEYS, f"config {c.get('name')!r}")
        _name(c["name"], "config")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced")
    configs = _unique([c["name"] for c in bench["configs"]], "config")
    for w in bench["workloads"]:
        _keys(w, WORKLOAD_KEYS, f"workload {w.get('name')!r}")
        name = _name(w["name"], "workload")
        _name(w["traffic"], f"workload {name} traffic")
        if w["config"] not in configs:
            raise BenchError(f"workload {name}: unknown config {w['config']!r}")
    cells = _unique([w["name"] for w in bench["workloads"]], "workload")
    for m in bench["end_to_end"]:
        _metric(m, E2E_KEYS, cells, f"metric {m.get('name')!r}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        raise BenchError("end_to_end must hold setup_s")
    for m in bench["per_layer"]:
        _metric(m, LAYER_KEYS, cells, f"metric {m.get('name')!r}")
        if m["moves"] not in e2e:
            raise BenchError(f"metric {m['name']}: moves unknown {m['moves']!r}")
    _unique([m["name"] for m in bench["end_to_end"] + bench["per_layer"]],
            "metric")
    return bench


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return validate(json.load(f))


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one harness file by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    mod_name = "bench_file_" + re.sub(r"\W", "_", str(path.relative_to(
        path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, workload: str, group: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics a cell reports."""
    return [m for m in bench[group]
            if workload in m.get("workloads", (workload,))]


def find_metric_reader(kind_dir: Path, name: str):
    """`<name>.py`, else the reader of the name's base before its first dot."""
    path = kind_dir / f"{name}.py"
    if not path.is_file():
        path = kind_dir / f"{name.split('.')[0]}.py"
    return load_module(path)


class Cell:
    """One workload of `BENCHMARK.json` with every file it names loaded."""

    def __init__(self, name: str, root: Path = ROOT,
                 bench_dir: Path = BENCH_DIR):
        self.bench = load_benchmark(root)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        self.config = _read_json(bench_dir / "configs"
                                 / f"{self.entry['config']}.json")
        self.traffic = _read_json(bench_dir / "traffic"
                                  / f"{self.entry['traffic']}.json")
        self.limits = _read_json(bench_dir / "cells" / f"{name}.json")["limits"]
        kind = _name(self.traffic.get("kind"), f"traffic {self.entry['traffic']} kind")
        self.job_module = load_module(bench_dir / "jobs" / f"{kind}.py")
        self.end_to_end = metrics_for(self.bench, name, "end_to_end")
        self.per_layer = metrics_for(self.bench, name, "per_layer")
        self.e2e_readers = {
            m["name"]: find_metric_reader(bench_dir / "end_to_end", m["name"])
            for m in self.end_to_end if m["name"] != "setup_s"}
        self.layer_readers = {
            m["name"]: find_metric_reader(bench_dir / "layer_metrics", m["name"])
            for m in self.per_layer}
