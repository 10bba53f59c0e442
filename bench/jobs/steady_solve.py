"""Job kind `steady_solve`: one steady thermal map per job.

Each job draws a power map from the seed and the job index, hands it to
`thermal.steady_state_stats` on the configuration's stack, and ends when
the temperatures are on the host.  Its latency runs from the submit to
that point; drawing the map comes before it.

The map is the paper's AP at the configuration's PU count: the Fig 8
floorplan with the eq-17 power on every logic layer, and the DRAM
floorplan with the activate power of one workload's memory traffic on
every DRAM die.  Only values change from job to job: the activity of
each AP bank's dynamic power, per logic layer, and the DRAM traffic.

Traffic keys: ``die_cells`` (per side), ``margin_cells``, ``solver``,
``bank_activity`` (range), ``dram_workload``, ``dram_activity`` (range),
``sample`` (answers compared with the reference after the window).
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import common
from bench.reference import paper
from bench.reference import thermal as reference


def die_w_m(config: dict) -> float:
    return paper.ap_die_w_mm(config) * 1e-3


def power_map(config: dict, traffic: dict, seed: int, index: int
              ) -> np.ndarray:
    """Watts per die cell, [n_die_layers, n, n] float32."""
    g = common.rng(seed, index)
    n = traffic["die_cells"]
    n_pus = config["models"]["n_data"]
    w_mm = paper.ap_die_w_mm(config)
    layer_W = paper.ap_power_W(config, n_pus)
    banks = config["ap_floorplan"]["banks"]
    kinds = [l["kind"] for l in config["layers"][:-1]]
    n_dram = kinds.count("dram")
    act_W = paper.dram_activate_W(config, paper.traffic_bytes_per_s(
        config, traffic["dram_workload"], n_pus), n_dram)
    P = np.zeros((len(kinds), n, n))
    for l, kind in enumerate(kinds):
        if kind == "logic":
            act = g.uniform(*traffic["bank_activity"], size=(banks, banks))
            P[l] = paper.ap_power_map(config, n, layer_W, w_mm, act)
        elif kind == "dram":
            P[l] = (act_W * g.uniform(*traffic["dram_activity"])
                    * paper.dram_activate_map(config, n)
                    + paper.dram_refresh_map(config, n)
                    + paper.dram_leak_W(config, w_mm) / n ** 2)
    return P.astype(np.float32)


class Job:
    """The program's steady solver on one grid, built and warmed once."""

    def __init__(self, config: dict, traffic: dict, devices):
        from repro.core import thermal
        from repro.stack.spec import dram_on_logic
        self.config, self.traffic = config, traffic
        self.thermal = thermal
        n = traffic["die_cells"]
        self.grid = thermal.Grid(die_w=die_w_m(config), ny=n, nx=n,
                                 margin=traffic["margin_cells"],
                                 spec=dram_on_logic(config["dram_dies"]))
        self.run(seed=-1, index=0)          # every shape the window uses

    def run(self, seed: int, index: int):
        with TraceAnnotation("gen_inputs"):
            power = power_map(self.config, self.traffic, seed, index)
        t0 = time.perf_counter()
        with TraceAnnotation("submit"):
            T, stats = self.thermal.steady_state_stats(
                power, self.grid, solver=self.traffic["solver"])
        with TraceAnnotation("fetch"):
            T = np.asarray(T)
        latency = time.perf_counter() - t0
        rec = {"latency_s": latency, "iterations": int(stats["iterations"]),
               "attempts": int(stats["attempts"]),
               "failed": not bool(np.isfinite(T).all())}
        return rec, T

    def close(self) -> None:
        self.grid = None


def check(config, traffic, seed, records, outputs, g,
          control: bool = False) -> dict:
    """Widest gap in C between a sampled answer and the reference.

    ``control=True`` puts the control in the program's place: the
    reference computed in bfloat16, one step below the stated float32.
    """
    n, m = traffic["die_cells"], traffic["margin_cells"]
    picked = common.sample(records, outputs, traffic["sample"], g)
    if not picked:
        return {}
    worst = 0.0
    for i in picked:
        power = power_map(config, traffic, seed, i)
        want, _ = reference.steady_rise(power, config, n, m, die_w_m(config))
        if control:
            got, _ = reference.steady_rise(power, config, n, m,
                                           die_w_m(config), dtype="bfloat16")
        else:
            got = np.asarray(outputs[i], np.float64) - config["ambient_C"]
        worst = max(worst, float(np.abs(got - want).max()))
    return {"max_abs_C": worst}
