"""Job kind `sweep`: one closed-loop scenario sweep per job.

Each job is the program's `run_sweep` over the traffic's workloads, one
dataset size, the configuration's DRAM dies, closed-loop feedback and
the ramp DTM policy: every (workload, machine) case replayed with
leakage, refresh and throttling in one batch.  The job ends when every
case's report is on the host.  Only the heat sink's convection
resistance changes from job to job, drawn from the seed within
``r_convec_spread`` of the configuration's: every job asks for the same
work on the same shapes.

Traffic keys: ``workloads``, ``size``, ``machines``, ``grid_n``,
``n_intervals``, ``t_end_s``, ``steps_per_interval``, ``n_picard``,
``solver``, ``r_convec_spread``, ``sample`` (jobs whose cases are all
compared with the reference after the window), ``verdict_margin_C`` (a
verdict counts as wrong only where the reference's hottest judged cell
lies further than this from the limit).
"""
from __future__ import annotations

import math
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import common
from bench.reference import closed_loop, paper


def r_convec(config: dict, traffic: dict, seed: int, index: int) -> float:
    g = common.rng(seed, index)
    return config["package"]["r_convec_K_W"] * (
        1.0 + traffic["r_convec_spread"] * g.uniform(-1.0, 1.0))


class Job:
    """The program's sweep of the traffic's scenario grid."""

    def __init__(self, config: dict, traffic: dict, devices):
        from repro.stack.spec import StackParams
        from repro.sweep import SweepSpec, run_sweep
        self.config, self.traffic = config, traffic
        self.run_sweep, self.StackParams = run_sweep, StackParams
        self.spec = SweepSpec(
            workloads=tuple(traffic["workloads"]), sizes=(traffic["size"],),
            n_dram=(config["dram_dies"],), fb_modes=("closed",),
            policies=("ramp",), machines=tuple(traffic["machines"]),
            grid_n=traffic["grid_n"], n_intervals=traffic["n_intervals"],
            t_end=traffic["t_end_s"],
            steps_per_interval=traffic["steps_per_interval"],
            n_picard=traffic["n_picard"], solver=traffic["solver"])
        self.run(seed=-1, index=0)          # every shape the window uses

    def run(self, seed: int, index: int):
        with TraceAnnotation("gen_inputs"):
            params = self.StackParams(
                r_convec=r_convec(self.config, self.traffic, seed, index))
        t0 = time.perf_counter()
        with TraceAnnotation("submit"):
            res = self.run_sweep(self.spec, use_cache=False, params=params)
        with TraceAnnotation("fetch"):
            cases = [{"workload": r.point.workload, "machine": r.machine,
                      "peak_C": np.asarray(r.report.peak_C, np.float64),
                      "min_C": np.asarray(r.report.min_C, np.float64),
                      "duty": np.asarray(r.report.throttle, np.float64),
                      "verdict_ok": bool(r.verdict_ok)}
                     for r in res.records]
        latency = time.perf_counter() - t0
        finite = all(np.isfinite(c[k]).all() for c in cases
                     for k in ("peak_C", "min_C", "duty"))
        rec = {"latency_s": latency, "cases": len(cases),
               "failed": bool(res.n_failed) or not finite}
        return rec, cases

    def close(self) -> None:
        self.spec = None


def case_inputs(config: dict, traffic: dict, workload: str, machine: str):
    """The reference's own assembly of one case: (die width in m,
    frames [T, Ld, n, n], leak0, refresh0) from the paper's models."""
    if machine != "simd":
        raise ValueError("the reference models the SIMD's analytic trace "
                         f"only, not the {machine!r} machine")
    n, T = traffic["grid_n"], traffic["n_intervals"]
    dp = paper.comparable_design_point(config, workload, traffic["size"])
    w_mm = math.sqrt(dp["simd_area_mm2"])
    kinds = [l["kind"] for l in config["layers"][:-1]]
    n_dram = kinds.count("dram")
    leak_cell = config["models"]["gamma_W_mm2"] * dp["simd_area_mm2"] / n ** 2
    dyn_logic = paper.simd_power_map(config, n, dp, w_mm) - leak_cell
    act = paper.simd_phase_trace(config, dp, T)
    act_W = paper.dram_activate_W(config, paper.traffic_bytes_per_s(
        config, workload, dp["ap_n_pus"]), n_dram)
    act_map = paper.dram_activate_map(config, n) * act_W
    frames = np.zeros((T, len(kinds), n, n))
    leak0 = np.zeros((len(kinds), n, n))
    refresh0 = np.zeros((len(kinds), n, n))
    for l, kind in enumerate(kinds):
        if kind == "logic":
            frames[:, l] = act[:, None, None] * dyn_logic
            leak0[l] = leak_cell
        else:
            frames[:, l] = act[:, None, None] * act_map
            leak0[l] = paper.dram_leak_W(config, w_mm) / n ** 2
            refresh0[l] = paper.dram_refresh_map(config, n)
    return w_mm * 1e-3, frames, leak0, refresh0


def reference_case(config: dict, traffic: dict, workload: str,
                   machine: str, r_conv: float, dtype: str = "float64"):
    w, frames, leak0, refresh0 = case_inputs(config, traffic, workload,
                                             machine)
    n = traffic["grid_n"]
    return closed_loop.replay(
        config, w, r_conv, n, n // 4, frames, leak0, refresh0,
        traffic["t_end_s"] / traffic["n_intervals"],
        traffic["steps_per_interval"], traffic["n_picard"], dtype=dtype)


def check(config, traffic, seed, records, outputs, g,
          control: bool = False) -> dict:
    """Widest gaps of every case of the sampled jobs from the reference.

    ``control=True`` puts the control in the program's place: the
    reference computed in bfloat16, one step below the stated float32.
    """
    picked = common.sample(records, outputs, traffic["sample"], g)
    if not picked:
        return {}
    worst = {"temp_gap_C": 0.0, "duty_gap": 0.0, "verdict_flips": 0.0}
    for i in picked:
        r_conv = r_convec(config, traffic, seed, i)
        for case in outputs[i]:
            key = (case["workload"], case["machine"])
            want = reference_case(config, traffic, *key, r_conv)
            got = case
            if control:
                got = reference_case(config, traffic, *key, r_conv,
                                     dtype="bfloat16")
            for name, value in closed_loop.gaps(
                    got, want, config, traffic["verdict_margin_C"]).items():
                worst[name] = (worst[name] + value if name == "verdict_flips"
                               else max(worst[name], value))
    return worst
