"""Job kind `serving`: one serving co-simulation per job.

Each job is the program's `run_serving_cosim` of the configuration's
model under the traffic's request trace, on each machine of the
traffic's same-performance AP/SIMD pair: the fluid queue, the coarse
plan, the stack's power frames and the closed-loop replay with leakage,
refresh and the ramp DTM, for every throttle↔queue round.  The job ends
when every machine's report is on the host.  The trace is fixed by the
traffic's ``trace_seed``; only the heat sink's convection resistance
changes from job to job, drawn from the seed within ``r_convec_spread``
of the configuration's, so every job does fresh thermal work on the
same shapes.

Traffic keys: ``shape``, ``burst_ratio``, ``p_enter``, ``p_exit``,
``horizon_s``, ``interval_s``, ``trace_seed``, ``load``,
``prompt_tokens``, ``output_tokens``, ``max_batch``, ``grid_n``,
``coarsen_tol``, ``max_merge``, ``pad_quantum``, ``n_rounds``,
``n_picard``, ``n_cg`` (PCG iterations of each implicit step),
``machines``, ``r_convec_spread``, ``sample`` (jobs whose
machines and rounds are all compared with the reference after the
window), ``verdict_margin_C`` and ``plan_slack`` (float rounding a
plan's blocks may show over ``coarsen_tol`` in the reference's signals).
"""
from __future__ import annotations

import math
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import common
from bench.reference import closed_loop
from bench.reference import serving as ref

#: the control's two parts: the reference's replay in bfloat16, one step
#: below the stated float32, and the cost rule this cell replaced
CONTROLS = ("bfloat16", "active_params")


def r_convec(config: dict, traffic: dict, seed: int, index: int) -> float:
    g = common.rng(seed, index)
    return config["package"]["r_convec_K_W"] * (
        1.0 + traffic["r_convec_spread"] * g.uniform(-1.0, 1.0))


def program_cost_counters(cost, max_batch: int) -> dict:
    """The program's cost counters under the reference's names."""
    out = {f"weight_bytes_B{b}": cost.weight_bytes_per_step(b)
           for b in range(1, max_batch + 1)}
    out.update(kv_bytes_tok=cost.kv_bytes_tok,
               attn_flops_per_token=cost.attn_flops_per_token,
               attn_prefill_flops=cost.attn_prefill_flops,
               request_flops=cost.request_flops)
    return out


class Job:
    """The program's serving co-simulation of the traffic's scenario."""

    def __init__(self, config: dict, traffic: dict, devices):
        from repro.serving import (RequestShape, ServingScenario,
                                   TrafficSpec, run_serving_cosim)
        from repro.stack.feedback import FeedbackParams
        from repro.stack.spec import StackParams
        self.config, self.traffic = config, traffic
        self.run_cosim, self.StackParams = run_serving_cosim, StackParams
        request = RequestShape(traffic["prompt_tokens"],
                               traffic["output_tokens"])
        self.scenario = ServingScenario(
            config=config["model"]["program_config"],
            traffic=TrafficSpec(
                shape=traffic["shape"], horizon_s=traffic["horizon_s"],
                interval_s=traffic["interval_s"],
                seed=traffic["trace_seed"],
                burst_ratio=traffic["burst_ratio"],
                p_enter=traffic["p_enter"], p_exit=traffic["p_exit"]),
            request=request, load=traffic["load"],
            max_batch=traffic["max_batch"], n_dram=config["dram_dies"],
            grid_n=traffic["grid_n"], coarsen_tol=traffic["coarsen_tol"],
            max_merge=traffic["max_merge"],
            pad_quantum=traffic["pad_quantum"],
            n_rounds=traffic["n_rounds"], n_cg=traffic["n_cg"])
        self.fb = FeedbackParams(n_picard=traffic["n_picard"])
        self.run(seed=-1, index=0)          # every shape the window uses

    def run(self, seed: int, index: int):
        with TraceAnnotation("gen_inputs"):
            params = self.StackParams(
                r_convec=r_convec(self.config, self.traffic, seed, index))
        t0 = time.perf_counter()
        with TraceAnnotation("submit"):
            reps = self.run_cosim(self.scenario,
                                  machines=tuple(self.traffic["machines"]),
                                  fb=self.fb, params=params)
        with TraceAnnotation("fetch"):
            out = {"machines": {}}
            for machine, r in reps.items():
                dram = list(r.stack.spec.dram_layers)
                limit = self.config["feedback"]["dram_limit_C"]
                out["machines"][machine] = {
                    "cost": program_cost_counters(
                        r.cost, self.traffic["max_batch"]),
                    "reps": np.rint(r.durations_s / self.traffic[
                        "interval_s"]).astype(np.int64),
                    "rounds": [{
                        "p50_s": float(np.median(rd.latency_s)),
                        "p99_s": float(np.percentile(rd.latency_s, 99)),
                        "peak_C": np.asarray(rd.peak_C, np.float64),
                        "min_C": np.asarray(rd.min_C, np.float64),
                        "duty": np.asarray(rd.throttle, np.float64),
                        "verdict_ok": not float(
                            np.asarray(rd.peak_C)[:, dram].max()) > limit,
                    } for rd in r.rounds]}
        latency = time.perf_counter() - t0
        finite = all(np.isfinite(rd[k]).all()
                     for m in out["machines"].values() for rd in m["rounds"]
                     for k in ("peak_C", "min_C", "duty"))
        rec = {"latency_s": latency, "cases": len(reps),
               "n_coarse": [len(m["reps"]) for m in out["machines"].values()],
               "failed": not finite}
        return rec, out

    def close(self) -> None:
        self.scenario = None


def _rel(got: float, want: float) -> float:
    if got == want:
        return 0.0
    return abs(got - want) / abs(want) if want else math.inf


class ReferenceQueue:
    """The reference's arrivals, design point and capacity for one cost
    rule; `round` gives the queue and DRAM traffic under a throttle."""

    def __init__(self, config: dict, traffic: dict, cost: ref.Cost):
        self.config, self.traffic, self.cost = config, traffic, cost
        ai = cost.decode_ai(traffic["max_batch"])
        self.dp = ref.design_point(config, ai, config["models"]["n_data"])
        self.cap = ref.ap_flops_per_s(config, self.dp["ap_n_pus"])
        self.arr = ref.arrivals(traffic,
                                traffic["load"] * self.cap / cost.request_flops)

    def round(self, throttle: np.ndarray):
        """(queue, DRAM traffic per interval) under ``throttle``."""
        q = ref.fluid_queue(self.arr, self.cost, self.cap, throttle,
                            self.traffic["interval_s"],
                            self.traffic["max_batch"])
        return q, ref.traffic_bytes_per_s(self.config, self.cost, q,
                                          self.dp["ap_n_pus"])


def reference_machine(config: dict, traffic: dict, got: dict, machine: str,
                      r_conv: float, cost: ref.Cost, dtype: str = "float64",
                      lus: dict | None = None) -> dict:
    """The reference's closed loop of one machine on the program's plan
    once the plan is checked: the first round's queue runs unthrottled,
    each later round's under the duty of the reference's own replay of
    the round before.  Returns {"plan_invalid", "rounds": [replay with
    "p50_s" and "p99_s"]}.  ``lus`` is the replay's LU cache, shared by
    the rounds (and by callers that replay the same machine again)."""
    lus = {} if lus is None else lus
    n = traffic["grid_n"]
    reps = got["reps"]
    rq = ReferenceQueue(config, traffic, cost)
    f_base = np.ones(rq.arr.shape[0])
    out = {"plan_invalid": 0.0, "rounds": []}
    for k in range(traffic["n_rounds"]):
        q, tb = rq.round(f_base)
        if k == 0:
            signals = np.stack([q["busy"], tb / max(tb.max(), 1e-30)], 1)
            out["plan_invalid"] = ref.plan_invalid(
                reps, signals, traffic["coarsen_tol"], traffic["max_merge"],
                traffic["plan_slack"])
            if out["plan_invalid"]:
                return out
        w, frames, leak0, refresh0 = ref.stack_inputs(
            config, machine, rq.dp, n, ref.merge(reps, q["busy"]),
            ref.merge(reps, tb))
        want = ref.replay(config, w, r_conv, n, n // 4, frames, leak0,
                          refresh0, reps, traffic["interval_s"],
                          traffic["n_picard"], dtype=dtype, lus=lus)
        want.update(_percentiles(q["latency_s"]))
        out["rounds"].append(want)
        f_base = np.repeat(want["duty"], reps)
    return out


def _percentiles(lat: np.ndarray) -> dict:
    return {"p50_s": float(np.median(lat)),
            "p99_s": float(np.percentile(lat, 99))}


def check(config, traffic, seed, records, outputs, g,
          control=False) -> dict:
    """Widest gaps of every machine and round of the sampled jobs from
    the reference.

    ``latency_rel`` takes the first round's p50 and p99, where both
    queues run unthrottled; ``latency_rel_fed`` every later round's,
    where each side's queue runs under its own replay's duty, so the
    duty's gap carries into it.  ``control`` puts a control in the
    program's place: "bfloat16" the reference's closed loop with its
    replay in bfloat16 (temperatures, duties and the later rounds'
    latencies), "active_params" today's-rule cost (batch-1 active
    parameters at every batch, no attention FLOPs) for the cost
    counters and the queues, True both.
    """
    picked = common.sample(records, outputs, traffic["sample"], g)
    if not picked:
        return {}
    controls = CONTROLS if control is True else (control,) if control \
        else ()
    prompt, output = traffic["prompt_tokens"], traffic["output_tokens"]
    max_batch = traffic["max_batch"]
    want_cost = ref.Cost(config, prompt, output)
    want_counters = want_cost.counters(max_batch)
    old_cost = ref.Cost(config, prompt, output, rule="active")
    old_queue = ReferenceQueue(config, traffic, old_cost) \
        if "active_params" in controls else None
    worst = {"cost_rel": 0.0, "latency_rel": 0.0, "latency_rel_fed": 0.0,
             "temp_gap_C": 0.0, "duty_gap": 0.0, "verdict_flips": 0.0,
             "plan_invalid": 0.0}
    for i in picked:
        r_conv = r_convec(config, traffic, seed, i)
        for machine, got in outputs[i]["machines"].items():
            got_cost = old_cost.counters(max_batch) \
                if "active_params" in controls else got["cost"]
            worst["cost_rel"] = max(worst["cost_rel"], max(
                _rel(got_cost[k], v) for k, v in want_counters.items()))
            lus = {}
            want = reference_machine(config, traffic, got, machine, r_conv,
                                     want_cost, lus=lus)
            worst["plan_invalid"] += want["plan_invalid"]
            if want["plan_invalid"]:
                continue
            rounds = got["rounds"]
            if "bfloat16" in controls:
                rounds = reference_machine(config, traffic, got, machine,
                                           r_conv, want_cost,
                                           dtype="bfloat16",
                                           lus=lus)["rounds"]
            if old_queue is not None:
                f_base, fed = np.ones(old_queue.arr.shape[0]), []
                for rd, w_rd in zip(rounds, want["rounds"]):
                    q, _ = old_queue.round(f_base)
                    fed.append({**rd, **_percentiles(q["latency_s"])})
                    f_base = np.repeat(w_rd["duty"], got["reps"])
                rounds = fed
            if len(rounds) != len(want["rounds"]):
                worst["latency_rel"] = math.inf
            for k, (g_rd, w_rd) in enumerate(zip(rounds, want["rounds"])):
                name = "latency_rel" if k == 0 else "latency_rel_fed"
                worst[name] = max(worst[name],
                                  _rel(g_rd["p50_s"], w_rd["p50_s"]),
                                  _rel(g_rd["p99_s"], w_rd["p99_s"]))
                for key, value in closed_loop.gaps(
                        g_rd, w_rd, config,
                        traffic["verdict_margin_C"]).items():
                    worst[key] = (worst[key] + value
                                  if key == "verdict_flips"
                                  else max(worst[key], value))
    return worst
