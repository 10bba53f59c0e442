"""The trace reduction on a small recorded trace."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data" / "small_trace.json"


@pytest.fixture(scope="module")
def small():
    with open(DATA) as f:
        raw = json.load(f)
    trace = {"device_ops": [tuple(o) for o in raw["device_ops"]],
             "host_spans": [tuple(s) for s in raw["host_spans"]]}
    return trace, raw["expect"]


@pytest.mark.parametrize("key", ["busy_s", "window_s", "idle_pct",
                                 "ops_from_s", "ops_to_s"])
def test_busy_is_the_union_of_overlapping_ops_in_the_window(small, key):
    trace, expect = small
    assert trace_reduce.reduce(trace)[key] == pytest.approx(expect[key])


@pytest.mark.parametrize("key", ["modules", "idle_gaps"])
def test_time_per_module_and_gap_labels(small, key):
    trace, expect = small
    got = trace_reduce.reduce(trace)[key]
    assert [k for k, _ in got] == [k for k, _ in expect[key]]
    assert [v for _, v in got] == pytest.approx([v for _, v in expect[key]])


def test_ops_are_named_by_module_and_ranked():
    trace = {"device_ops": [("/device:TPU:0", 0, 10, "m", "a"),
                            ("/device:TPU:0", 10, 40, "m", "b")],
             "host_spans": []}
    out = trace_reduce.reduce(trace)
    assert out["ops"] == [["m/b", pytest.approx(3e-8)],
                          ["m/a", pytest.approx(1e-8)]]
    assert out["idle_pct"] == pytest.approx(0.0)


def test_busy_is_averaged_over_devices():
    trace = {"device_ops": [("/device:TPU:0", 0, 100, "m", "a"),
                            ("/device:TPU:1", 0, 50, "m", "a")],
             "host_spans": [(0, 100, "window")]}
    out = trace_reduce.reduce(trace)
    assert out["n_devices"] == 2
    assert out["busy_s"] == pytest.approx(75e-9)


def test_a_trace_with_no_device_op_reads_nothing():
    out = trace_reduce.reduce({"device_ops": [], "host_spans": []})
    assert out["busy_s"] == 0.0 and out["idle_pct"] is None


def test_ops_take_the_module_that_encloses_them():
    ops = [("/device:TPU:0", 5, 7, "fusion")]
    mods = [("/device:TPU:0", 0, 4, "jit_a"), ("/device:TPU:0", 4, 9, "jit_b")]
    assert trace_reduce._attach_modules(ops, mods) == [
        ("/device:TPU:0", 5, 7, "jit_b", "fusion")]


def test_an_op_that_encloses_others_is_left_out_of_the_op_ranking():
    trace = {"device_ops": [("/device:TPU:0", 0, 100, "m", "%while.1"),
                            ("/device:TPU:0", 10, 30, "m", "%fusion.2"),
                            ("/device:TPU:0", 40, 90, "m", "%fusion.3")],
             "host_spans": []}
    out = trace_reduce.reduce(trace)
    assert [k for k, _ in out["ops"]] == ["m/%fusion.3", "m/%fusion.2"]
    assert out["modules"] == [["m", pytest.approx(1e-7)]]
