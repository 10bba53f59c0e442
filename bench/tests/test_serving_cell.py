"""The serving cell is found by name; its program passes, its controls fail.

At a size a CPU test holds: a 16^2 die grid (24^2 x 7 field) and a
600 s horizon of the cell's traffic.  The same code decides `correct` on
the chip at the cell's own size.
"""
import time
import types

import jax
import numpy as np
import pytest

from bench import common, control, program_spans, registry, run
from bench import trace_reduce

NAME = "deepseek-v2-lite-serve-bursty"
SEED = 2 ** 31 + 16
SPANS = ("serving/cost", "serving/queue", "serving/plan", "serving/frames",
         "serving/replay", "sync/replay")


def small() -> registry.Cell:
    cell = registry.Cell(NAME)
    cell.traffic.update(grid_n=16, horizon_s=600.0)
    return cell


@pytest.fixture(scope="module")
def window():
    """One job's window and the cell it ran in."""
    cell = small()
    job = cell.job_module.Job(cell.config, cell.traffic, jax.devices())
    records, outputs, _, _ = run._run_window(job, SEED, 1e-3)
    return cell, records, outputs


def _check(window, control=False):
    cell, records, outputs = window
    return cell.job_module.check(cell.config, cell.traffic, SEED, records,
                                 outputs, common.rng(SEED, 0x5A17),
                                 control=control)


def test_the_registry_finds_every_file_of_the_cell():
    cell = registry.Cell(NAME)
    assert cell.chips == 1
    assert cell.config["name"] == "deepseek-v2-lite-ap-dram2"
    assert cell.config["reduced"] == []
    assert cell.traffic["kind"] == "serving"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "cases_per_s"]
    assert set(cell.layer_readers) == {
        "device_idle_pct.serve", "replay_device_ms_per_case.serve",
        "coarse_intervals_per_case.serve"}
    assert set(cell.limits) == {"cost_rel", "latency_rel", "latency_rel_fed",
                                "temp_gap_C", "duty_gap", "verdict_flips",
                                "plan_invalid"}
    # the device idle share is the shared reader's, as in every cell
    assert cell.layer_readers["device_idle_pct.serve"].__file__ \
        == str(registry.BENCH_DIR / "layer_metrics" / "device_idle_pct.py")
    reader = registry.find_metric_reader(
        registry.BENCH_DIR / "layer_metrics", "serving_host_ms_per_case.serve")
    assert reader.read({"trace": None, "records": []}) is None


def test_the_configuration_holds_the_published_model():
    cfg = registry.Cell(NAME).config
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["vocab_size"]) \
        == (27, 2048, 16, 102400)
    assert (cfg["kv_lora_rank"], cfg["q_lora_rank"], cfg["qk_rope_head_dim"],
            cfg["qk_nope_head_dim"], cfg["v_head_dim"]) \
        == (512, None, 64, 128, 128)
    assert (cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"], cfg["moe_intermediate_size"],
            cfg["first_k_dense_replace"], cfg["intermediate_size"]) \
        == (64, 6, 2, 1408, 1, 10944)


def test_the_program_is_within_every_limit(window):
    cell, records, _ = window
    assert records and not records[0]["failed"]
    assert records[0]["cases"] == 2
    got = _check(window)
    assert set(got) == set(cell.limits)
    for key, value in got.items():
        assert value <= cell.limits[key], (key, value)


@pytest.mark.parametrize("which,number", [("bfloat16", "temp_gap_C"),
                                          ("active_params", "cost_rel")])
def test_each_control_fails_its_number(window, which, number):
    cell = window[0]
    got = _check(window, control=which)
    assert got[number] > cell.limits[number], got


def test_the_control_reading_fails():
    cell = small()
    (line,) = control.readings(cell, [SEED], 1e-3, jax.devices())
    assert line["failed"] == 0 and line["program"]
    assert any(v > cell.limits[k] for k, v in line["control"].items())


@pytest.mark.parametrize("load", [0.05, 0.7, 1.3])
def test_the_reference_queue_finishes_requests_as_the_program_does(load):
    """Request by request against the program's fluid queue, with the
    queue emptying between arrivals, throttled intervals and a backlog
    left at the horizon."""
    from repro.serving import fluid_queue
    from bench.reference import serving as ref
    g = np.random.default_rng(int(load * 100))
    work, cap, dt, T = 10.0, 1.0, 1.0, 2000
    arr = g.poisson(load * cap / work, T)
    throttle = np.where(g.random(T) < 0.3, g.uniform(0.25, 1.0, T), 1.0)
    floor = dict(request_flops=work, prefill_flops=0.5,
                 decode_flops_per_token=1e-3)
    got = fluid_queue(arr, types.SimpleNamespace(
        **floor, request=types.SimpleNamespace(output_tokens=8)),
        cap, throttle, dt, 4)
    want = ref.fluid_queue(arr, types.SimpleNamespace(**floor, output=8),
                           cap, throttle, dt, 4)
    assert arr.sum() > 5
    np.testing.assert_array_equal(got.busy, want["busy"])
    np.testing.assert_array_equal(got.batch, want["batch"])
    np.testing.assert_allclose(got.latency_s, want["latency_s"], rtol=1e-12)


def _overlong_first_block(cosim, max_merge):
    real = cosim.CoarsePlan.pad_to

    def broken(self, n):
        reps = list(real(self, n).reps)
        while reps[0] <= max_merge and len(reps) > 1:
            reps[0] += reps.pop(1)
        return cosim.CoarsePlan(np.asarray(reps, np.int64))
    return broken


def test_a_plan_with_a_block_over_max_merge_is_not_correct(monkeypatch):
    from repro.core import cosim
    cell = small()
    monkeypatch.setattr(cosim.CoarsePlan, "pad_to", _overlong_first_block(
        cosim, cell.traffic["max_merge"]))
    res = run.run_cell(cell, SEED, 1e-3, False, jax.devices(),
                       time.perf_counter())
    assert res["attempted"] > 0
    assert res["checks"]["plan_invalid"]["value"] > 0
    assert res["correct"] is False


def test_a_sound_run_is_correct_and_reports_its_metrics():
    cell = small()
    res = run.run_cell(cell, SEED, 1e-3, False, jax.devices(),
                       time.perf_counter())
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "cases_per_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in res["metrics"].values())


def test_a_traced_job_shows_the_serving_spans(tmp_path, monkeypatch):
    # `program_spans` collects the spans of the cells before this one;
    # add the serving path's prefix to see all of its spans
    monkeypatch.setattr(program_spans, "PREFIXES",
                        program_spans.PREFIXES + ("serving/",))
    cell = small()
    cell.traffic["n_cg"] = 20       # fewer device ops for the profiler
    job = cell.job_module.Job(cell.config, cell.traffic, jax.devices())
    jax.profiler.start_trace(str(tmp_path))
    try:
        records, _, _, _ = run._run_window(job, SEED, 1e-3)
    finally:
        jax.profiler.stop_trace()
    trace = program_spans.from_xplane(str(tmp_path))
    counts = program_spans.reduce(trace)["span_counts"]
    names = {path.rsplit(program_spans.SEP, 1)[-1] for path in counts}
    assert set(SPANS) <= names, sorted(names)
    ctx = {"records": records, "trace": trace_reduce.reduce(trace)}
    n = registry.find_metric_reader(
        registry.BENCH_DIR / "layer_metrics",
        "coarse_intervals_per_case.serve").read(ctx)
    assert n == np.mean(records[0]["n_coarse"]) and n > 0


def test_the_readers_scale_a_device_trace_that_ends_early():
    """The profiler drops this cell's ops 4 s into a 16 s window."""
    S = program_spans.SEP
    ctx = {"records": [{"cases": 2}] * 3, "trace": {
        "n_devices": 1, "busy_s": 3.0, "ops_to_s": 4.0, "window_s": 16.0,
        "idle_pct": 81.25,
        "modules": [["jit_closed_loop_replay", 2.5], ["jit_add", 0.5]],
        "idle_by_span": {S.join(["serving/machine", "serving/round",
                                 "serving/queue"]): 0.6,
                         S.join(["serving/machine", "serving/round",
                                 "sync/replay"]): 11.0,
                         "submit": 0.2}}}

    def read(name):
        return registry.find_metric_reader(
            registry.BENCH_DIR / "layer_metrics", name).read(ctx)
    assert read("device_idle_pct.serve") == 81.25
    assert read("replay_device_ms_per_case.serve") == pytest.approx(
        2.5 / 4.0 * 16.0 * 1e3 / 6)
    assert read("serving_host_ms_per_case.serve") == pytest.approx(100.0)
