"""`BENCHMARK.json`'s format, and cells found by name from files alone."""
import copy
import json
import shutil

import pytest

from bench import registry


@pytest.fixture
def bench():
    return registry.load_benchmark()


def test_the_committed_benchmark_is_valid(bench):
    names = [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = registry.Cell(name)
        assert cell.end_to_end[0]["name"] == "setup_s"
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert set(cell.layer_readers) == {m["name"] for m in cell.per_layer}


@pytest.mark.parametrize("name", ["has space", "a,b", "a/b", "", "-lead",
                                  "x" * 65, "µs"])
def test_bad_names_are_refused(bench, name):
    bad = copy.deepcopy(bench)
    bad["workloads"][0]["name"] = name
    with pytest.raises(registry.BenchError):
        registry.validate(bad)


@pytest.mark.parametrize("unit", ["tokens per second", "", "µs",
                                  "x" * 17, "ms,s"])
def test_bad_units_are_refused(bench, unit):
    bad = copy.deepcopy(bench)
    bad["end_to_end"][1]["unit"] = unit
    with pytest.raises(registry.BenchError):
        registry.validate(bad)


@pytest.mark.parametrize("change", [
    lambda b: b["end_to_end"][1].update(why="extra key"),
    lambda b: b["end_to_end"].pop(0),
    lambda b: b["per_layer"][0].update(moves="nothing"),
    lambda b: b["per_layer"][0].update(workloads=["no-such-cell"]),
    lambda b: b["workloads"][0].update(config="missing"),
    lambda b: b["workloads"].append(dict(b["workloads"][0])),
    lambda b: b["per_layer"].append(dict(b["per_layer"][0])),
    lambda b: b["workloads"][0].update(traffic="a b"),
])
def test_unresolvable_entries_are_refused(bench, change):
    bad = copy.deepcopy(bench)
    change(bad)
    with pytest.raises(registry.BenchError):
        registry.validate(bad)


def test_a_cell_added_as_new_files_is_found(tmp_path, bench):
    """A new cell of an existing job kind: a traffic file, a cell file
    and an entry in BENCHMARK.json; no existing file changes."""
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    new = copy.deepcopy(bench)
    new["workloads"].append({"name": "paper-steady-128",
                             "config": "paper-ap-dram2",
                             "traffic": "steady-apfloorplan-128", "chips": 1,
                             "why": "a coarser map"})
    for m in new["end_to_end"][1:] + new["per_layer"]:
        if "paper-steady-256" in m.get("workloads", ()):
            m["workloads"].append("paper-steady-128")
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    traffic = json.loads((root / "bench/traffic/steady-apfloorplan-256.json")
                         .read_text())
    traffic.update(die_cells=128, margin_cells=32)
    (root / "bench/traffic/steady-apfloorplan-128.json").write_text(
        json.dumps(traffic))
    shutil.copy(root / "bench/cells/paper-steady-256.json",
                root / "bench/cells/paper-steady-128.json")
    cell = registry.Cell("paper-steady-128", root=root,
                         bench_dir=root / "bench")
    assert cell.traffic["die_cells"] == 128
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "solve_p95_ms"]
    assert "vcycles_per_solve.solve" in cell.layer_readers
    assert hasattr(cell.job_module, "Job")


def test_a_metric_reader_falls_back_to_its_base_name():
    mod = registry.find_metric_reader(
        registry.BENCH_DIR / "layer_metrics", "device_idle_pct.anything")
    assert mod.read({"trace": None}) is None


def test_an_unknown_workload_is_refused():
    with pytest.raises(registry.BenchError):
        registry.Cell("no-such-cell")
