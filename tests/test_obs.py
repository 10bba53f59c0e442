"""The observability layer: registry math, spans, disabled-mode no-op,
Chrome trace-event export."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.obs.registry import Histogram, Registry, percentile
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test sees a fresh, disabled obs state and restores none of
    its own residue on the module singletons."""
    prev = obs.is_enabled()
    obs.disable()
    obs.reset()
    yield
    obs.reset()
    (obs.enable if prev else obs.disable)()


# ------------------------------------------------------------- disabled

def test_disabled_mode_is_strict_noop():
    obs.count("x")
    obs.gauge("g", 3.0)
    obs.observe("h", 1.0)
    obs.observe_many("h", [2.0, 3.0])
    with obs.span("s", k=1):
        pass
    snap = obs.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert obs.trace_events()["traceEvents"] == []
    assert obs.value("x") == 0


def test_disabled_span_is_shared_null_singleton():
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()     # no profiler recording
    a, b = obs.span("a"), obs.span("b", attr=1)
    assert a is b                   # no per-call allocation when off


def test_scoped_restores_prior_state():
    assert not obs.is_enabled()
    with obs.scoped():
        assert obs.is_enabled()
        with obs.scoped(on=False):
            assert not obs.is_enabled()
        assert obs.is_enabled()
    assert not obs.is_enabled()


# -------------------------------------------------------------- metrics

def test_counter_gauge_roundtrip():
    with obs.scoped():
        obs.count("c")
        obs.count("c", 4)
        obs.gauge("g", 2.0)
        obs.gauge("g", 7.5)         # last write wins
    snap = obs.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 7.5
    assert obs.value("c") == 5      # readable even while disabled


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    vals = rng.exponential(size=501)
    h = Histogram()
    h.extend(vals)
    s = h.summary()
    assert s["count"] == 501
    np.testing.assert_allclose(s["p50"], np.percentile(vals, 50))
    np.testing.assert_allclose(s["p95"], np.percentile(vals, 95))
    np.testing.assert_allclose(s["p99"], np.percentile(vals, 99))
    np.testing.assert_allclose(s["mean"], vals.mean())
    assert s["min"] == vals.min() and s["max"] == vals.max()


def test_percentile_edge_cases():
    assert np.isnan(percentile([], 50))
    assert percentile([4.0], 99) == 4.0
    assert percentile([1.0, 2.0], 50) == 1.5


def test_empty_histogram_summary():
    assert Histogram().summary() == {"count": 0}


def test_registry_snapshot_is_json_serializable_and_sorted():
    r = Registry()
    r.counter("b").inc()
    r.counter("a").inc(2)
    r.histogram("h").observe(1.0)
    snap = json.loads(json.dumps(r.snapshot()))
    assert list(snap["counters"]) == ["a", "b"]
    assert snap["histograms"]["h"]["count"] == 1


# ---------------------------------------------------------------- spans

def test_nested_span_parent_child_ordering():
    tr = Tracer()
    with tr.span("outer", case="x"):
        with tr.span("inner"):
            pass
    by_name = {e["name"]: e for e in tr.trace_object()["traceEvents"]}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["args"]["depth"] == 0 and inner["args"]["depth"] == 1
    # child lies within the parent's [ts, ts+dur] window (same tid row)
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"]["case"] == "x"


def test_span_durations_feed_histograms():
    with obs.scoped():
        with obs.span("work"):
            pass
        with obs.span("work"):
            pass
    assert obs.snapshot()["histograms"]["span/work"]["count"] == 2


def test_span_depth_restored_after_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError
    with tr.span("after"):
        pass
    by_name = {e["name"]: e for e in tr.trace_object()["traceEvents"]}
    assert by_name["after"]["args"]["depth"] == 0


def test_chrome_trace_event_json_validity(tmp_path):
    """The exported file is valid Chrome trace-event JSON: the object
    form with a traceEvents list of complete ('X') events carrying the
    required keys with the right types (ts/dur in microseconds)."""
    with obs.scoped():
        with obs.span("phase", n=3, label="a b"):
            with obs.span("leaf"):
                pass
    path = tmp_path / "trace.json"
    obs.write_trace(str(path))
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert ev["ph"] == "X" and ev["cat"] == "obs"
        assert isinstance(ev["name"], str)
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["args"], dict)
    # non-JSON-native span args were coerced to strings at record time
    phase = next(e for e in events if e["name"] == "phase")
    assert phase["args"]["n"] == 3 and phase["args"]["label"] == "a b"


def test_reset_restarts_trace_clock():
    with obs.scoped():
        with obs.span("one"):
            pass
        obs.reset()
        with obs.span("two"):
            pass
        events = obs.trace_events()["traceEvents"]
    assert [e["name"] for e in events] == ["two"]


# ------------------------------------------------- jit trace-time counts

def test_count_inside_jit_fires_per_trace_not_per_call():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        obs.count("test/retrace/f")
        return x + 1

    with obs.scoped():
        f(jnp.zeros(3))
        f(jnp.ones(3))              # same shape: cached, no retrace
        assert obs.value("test/retrace/f") == 1
        f(jnp.zeros(5))             # new shape: one more trace
        assert obs.value("test/retrace/f") == 2


# ------------------------------------------------ the profiler's trace

@pytest.fixture(scope="module")
def program_spans():
    """The benchmark's reader of program spans in a profiler trace."""
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import program_spans
    return program_spans


def _tiny_steady():
    from repro.core import thermal
    grid = thermal.Grid(die_w=2.3e-3, ny=8, nx=8, margin=2)
    power = np.full((4, 8, 8), 0.01, np.float32)
    return lambda: thermal.steady_state_stats(power, grid, solver="mg")


def _profiled_spans(fn, trace_dir, program_spans):
    """Program spans of one call of ``fn`` (warmed first) under the
    profiler, nested: [(start, end, path, thread, parent), ...]."""
    import jax
    fn()
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    trace = program_spans.from_xplane(str(trace_dir))
    return program_spans.nest(trace["program_spans"])


def test_spans_reach_the_profiler_trace_with_obs_off(tmp_path,
                                                      program_spans):
    spans = _profiled_spans(_tiny_steady(), tmp_path, program_spans)
    paths = [p for _, _, p, _, _ in spans]
    steady = "thermal/steady"
    assert sorted(paths) == sorted([
        (steady,),
        (steady, "thermal/steady/fields"),
        (steady, "thermal/steady/rhs"),
        (steady, "thermal/steady/solve"),
        (steady, "thermal/steady/check"),
        (steady, "thermal/steady/check", "sync/health")])
    assert obs.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_obs_adds_no_sync_to_a_steady_solve(tmp_path, program_spans):
    solve = _tiny_steady()
    syncs = {}
    for on in (False, True):
        with obs.scoped(on):
            spans = _profiled_spans(solve, tmp_path / str(on),
                                    program_spans)
        syncs[on] = sum(1 for _, _, p, _, _ in spans
                        if p[-1].startswith("sync/"))
    assert syncs[False] == syncs[True] == 1     # one fetch an attempt
    # the registry saw the same syncs, in both of its calls
    hist = obs.snapshot()["histograms"]
    assert sum(h["count"] for k, h in hist.items()
               if k.startswith("span/sync/")) == 2 * syncs[True]


def _lower_transient_fields():
    import jax.numpy as jnp
    from repro.core import thermal
    grid = thermal.Grid(die_w=2.3e-3, ny=8, nx=8, margin=4)
    T0 = jnp.zeros((grid.n_layers, grid.dom_ny, grid.dom_nx), jnp.float32)
    return thermal.transient_implicit_fields.lower(
        T0, T0, grid.fields(), grid.capacity_field(), 1e-3, 3,
        solver="mg").as_text()


def _lower_closed_loop():
    import jax.numpy as jnp
    from repro.core import cosim
    from repro.core import models as M
    from repro.stack import feedback
    from repro.stack.spec import PAPER_STACK, dram_on_logic
    spec = dram_on_logic(1)
    dp = cosim.comparable_design_point("dmm")
    trace = cosim.simd_phase_trace(M.WORKLOADS["dmm"], dp, 4)
    dyn, l0, r0, lm, F, cap3 = feedback.assemble_case(
        dp, "dmm", "simd", spec, PAPER_STACK, 8, trace, 2)
    batch = [jnp.asarray(x)[None] for x in (dyn, l0, r0, lm)]
    Fb = {k: v[None] for k, v in F.items()}
    return feedback.closed_loop_batch.lower(
        *batch, Fb, cap3[None], 1e-3, fb=feedback.FeedbackParams(),
        die_n=8, n_die=spec.n_die_layers, margin=2, n_cg=5).as_text()


@pytest.mark.parametrize("lower", [_lower_transient_fields,
                                   _lower_closed_loop],
                         ids=["transient_implicit_fields",
                              "closed_loop_batch"])
@pytest.mark.parametrize("sink", ["registry", "profiler"])
def test_obs_changes_no_compiled_program(lower, sink, tmp_path):
    import jax
    jax.clear_caches()                  # trace afresh, not from the cache
    off = lower()
    jax.clear_caches()
    if sink == "registry":
        with obs.scoped():
            on = lower()
    else:
        jax.profiler.start_trace(str(tmp_path))
        try:
            on = lower()
        finally:
            jax.profiler.stop_trace()
    assert on == off
