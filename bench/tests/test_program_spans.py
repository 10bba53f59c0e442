"""Idle time, self time and counts of the program's spans, on hand-built traces."""
import json
from pathlib import Path

import pytest

from bench import program_spans, registry, trace_reduce

DATA = Path(__file__).parent / "data" / "small_trace.json"
TPU = "/device:TPU:0"

#: window 0-1100 ns; the device is busy 0-100, 400-500 and 900-1000;
#: `submit` runs 50-950 and `fetch` 1000-1050
HAND = {
    "device_ops": [(TPU, 0, 100, "m", "a"), (TPU, 400, 500, "m", "b"),
                   (TPU, 900, 1000, "m", "c")],
    "host_spans": [(0, 1100, "window"), (50, 950, "submit"),
                   (1000, 1050, "fetch")],
    "program_spans": [
        (60, 880, "thermal/steady", "python"),
        (100, 300, "thermal/steady/fields", "python"),
        (300, 450, "thermal/steady/solve", "python"),
        (350, 420, "sync/iters", "python"),
        (500, 700, "thermal/steady/residual", "python"),
        (600, 700, "sync/residual", "python"),
        (1200, 1300, "thermal/steady", "python"),   # after the window
    ],
}
S = program_spans.SEP
STEADY = "thermal/steady"
FIELDS = S.join([STEADY, "thermal/steady/fields"])
SOLVE = S.join([STEADY, "thermal/steady/solve"])
ITERS = S.join([SOLVE, "sync/iters"])
RESID = S.join([STEADY, "thermal/steady/residual"])
SYNC_RESID = S.join([RESID, "sync/residual"])


@pytest.fixture(scope="module")
def hand():
    return program_spans.reduce(HAND)


@pytest.mark.parametrize("label,ns", [
    (FIELDS, 200), (SOLVE, 50), (ITERS, 50), (RESID, 100),
    (SYNC_RESID, 100), (STEADY, 180), ("submit", 20), ("fetch", 50),
    ("other", 50)])
def test_idle_goes_to_the_innermost_span_open_over_it(hand, label, ns):
    assert hand["idle_by_span"][label] == pytest.approx(ns * 1e-9)


def test_idle_by_span_adds_up_to_the_idle_time(hand):
    t = trace_reduce.reduce(HAND)
    assert sum(hand["idle_by_span"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"])


@pytest.mark.parametrize("path,ns", [
    (STEADY, 820 - 200 - 150 - 200), (FIELDS, 200), (SOLVE, 150 - 70),
    (ITERS, 70), (RESID, 100), (SYNC_RESID, 100)])
def test_self_time_leaves_out_the_children(hand, path, ns):
    assert hand["span_self_s"][path] == pytest.approx(ns * 1e-9)


def test_spans_are_counted_by_path_inside_the_window(hand):
    assert hand["span_counts"] == {STEADY: 1, FIELDS: 1, SOLVE: 1, ITERS: 1,
                                   RESID: 1, SYNC_RESID: 1}


def test_spans_nest_per_thread():
    nested = program_spans.nest([(0, 100, "engine/run", "python"),
                                 (10, 20, "sync/matched", "worker"),
                                 (30, 40, "engine/pack", "python")])
    assert {p for _, _, p, _, _ in nested} == {
        ("engine/run",), ("sync/matched",), ("engine/run", "engine/pack")}


def test_deepest_span_over_threads_takes_the_idle_time():
    trace = {"device_ops": [(TPU, 0, 10, "m", "a"), (TPU, 90, 100, "m", "b")],
             "host_spans": [(0, 100, "window")],
             "program_spans": [(0, 100, "engine/run", "python"),
                               (20, 60, "engine/charge", "worker"),
                               (30, 50, "sync/matched", "worker")]}
    idle = program_spans.reduce(trace)["idle_by_span"]
    assert idle == pytest.approx({
        "engine/run": 40e-9, "engine/charge": 20e-9,
        S.join(["engine/charge", "sync/matched"]): 20e-9})


@pytest.fixture(scope="module")
def small():
    with open(DATA) as f:
        raw = json.load(f)
    return {"device_ops": [tuple(o) for o in raw["device_ops"]],
            "host_spans": [tuple(s) for s in raw["host_spans"]]}


def test_a_trace_without_program_spans_keeps_the_phase_labels(small):
    """On the recorded trace (no program span), every idle stretch keeps
    its phase label, and trace_reduce reads the same with or without the
    added key."""
    got = program_spans.reduce(small)
    t = trace_reduce.reduce(small)
    assert set(got["idle_by_span"]) <= set(trace_reduce.PHASES) | {"other"}
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        sum(v for _, v in t["idle_gaps"]))
    assert got["span_self_s"] == {} and got["span_counts"] == {}
    assert trace_reduce.reduce(dict(small, program_spans=[])) == t


def _reader(name):
    return registry.find_metric_reader(registry.BENCH_DIR / "layer_metrics",
                                       name)


AP = {
    "device_ops": [(TPU, 0, 10, "m", "a"), (TPU, 990, 1000, "m", "b")],
    "host_spans": [(0, 1000, "window"), (0, 1000, "submit")],
    "program_spans": [
        (10, 400, "engine/load", "python"), (20, 390, "engine/pack", "python"),
        (400, 500, "engine/run", "python"),
        (500, 700, "engine/charge", "python"),
        (500, 600, "sync/matched", "python"),
        (700, 900, "engine/read", "python"), (700, 750, "sync/read", "python"),
        (750, 900, "engine/unpack", "python"),
    ],
}
SWEEP = {
    "device_ops": [(TPU, 0, 1000, "m", "a")],
    "host_spans": [(0, 1000, "window")],
    "program_spans": [
        (0, 600, "sweep/group", "python"),
        (0, 300, "sweep/assemble", "python"),
        (300, 500, "feedback/replay", "python"),
        (500, 600, "feedback/reports", "python"),
        (520, 580, "sync/reports", "python"),
    ],
}


@pytest.mark.parametrize("name,trace,records,want", [
    ("steady_host_ms.solve", HAND, [{}, {}], 680e-9 * 1e3 / 2),
    ("host_syncs_per_solve.solve", HAND, [{}, {}], 1.0),
    ("ap_host_us_per_cycle.ap", AP, [{"cycles": 1000}], 890e-9 * 1e6 / 1000),
    ("host_syncs_per_job.ap", AP, [{"cycles": 1000}], 2.0),
    ("assemble_ms_per_case.sweep", SWEEP, [{"cases": 4}], 300e-9 * 1e3 / 4),
])
def test_program_span_readers(name, trace, records, want, small):
    summary = dict(trace_reduce.reduce(trace), **program_spans.reduce(trace))
    reader = _reader(name)
    assert reader.read({"trace": summary, "records": records}) == \
        pytest.approx(want)
    # the recorded trace has no program span: nothing to read, never 0
    bare = dict(trace_reduce.reduce(small), **program_spans.reduce(small))
    assert reader.read({"trace": bare, "records": records}) is None
    # a summary without the added keys (the harness as it stands)
    assert reader.read({"trace": trace_reduce.reduce(trace),
                        "records": records}) is None
    assert reader.read({"trace": None, "records": records}) is None
