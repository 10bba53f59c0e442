"""Job kind `ap_program`: every word-parallel AP program of the traffic, once per job.

Set-up builds one `APEngine` with the configuration's word count on the
program's default backend and allocates its fields once.  A job runs
every program of the traffic once, in an order drawn from the seed: for
each it loads fresh seeded operands into every word, runs the program
through the program's `isa`/`arith` routines and reads the result field
back.  So every job, and every seed, asks for the same work, and a rate
over whole jobs does not swing with which program ends the window.

Traffic keys: ``programs`` (names in `bench.reference.ap.PROGRAMS`) and
``sample`` (jobs whose answers are compared with the reference after
the window).
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import common
from bench.reference import ap as reference


def order_of(traffic: dict, seed: int, index: int) -> list[str]:
    progs = traffic["programs"]
    return [progs[k] for k in common.rng(seed, index).permutation(len(progs))]


def operands(program: str, n_words: int, seed: int, index: int,
             step: int) -> dict:
    wa, wb, wr = reference.PROGRAMS[program]
    g = common.rng(seed, index, step)
    ops = {"a": g.integers(0, 1 << wa, n_words, dtype=np.uint64),
           "b": g.integers(0, 1 << wb, n_words, dtype=np.uint64)}
    if program == "mac8":
        ops["acc"] = g.integers(0, 1 << wr, n_words, dtype=np.uint64)
    return ops


class Job:
    """One engine and its fields; each job reuses them."""

    def __init__(self, config: dict, traffic: dict, devices):
        from repro.core import arith, isa
        from repro.core.engine import APEngine
        self.traffic = traffic
        self.n = config["ap"]["n_words"]
        self.eng = APEngine(n_words=self.n, n_bits=config["ap"]["n_bits"])
        alloc = self.eng.alloc
        a, b = alloc.alloc(16, "a"), alloc.alloc(16, "b")
        acc, prod = alloc.alloc(16, "acc"), alloc.alloc(32, "prod")
        carry = alloc.alloc(1, "carry")
        a8, b8 = a.slice(0, 8), b.slice(0, 8)
        eng = self.eng
        self.programs = {
            "add16": ({"a": a, "b": b}, b,
                      lambda: isa.run_add(eng, a, b, carry)),
            "mac8": ({"a": a8, "b": b8, "acc": acc}, acc,
                     lambda: arith.run_mac(eng, a8, b8, acc, carry)),
            "mul16": ({"a": a, "b": b}, prod,
                      lambda: arith.run_mul(eng, a, b, prod, carry)),
        }
        self.run(seed=-1, index=0)          # every shape the window uses

    def _run(self, name: str, ops: dict):
        fields, result, body = self.programs[name]
        self.eng.reset_counters()
        with TraceAnnotation("submit"):
            for key, field in fields.items():
                self.eng.load(field, ops[key])
            body()
        with TraceAnnotation("fetch"):
            out = self.eng.read(result)
        return out, self.eng.counters()

    def run(self, seed: int, index: int):
        latency, cycles, answers = 0.0, 0, []
        for step, name in enumerate(order_of(self.traffic, seed, index)):
            with TraceAnnotation("gen_inputs"):
                ops = operands(name, self.n, seed, index, step)
            t0 = time.perf_counter()
            out, ctr = self._run(name, ops)
            latency += time.perf_counter() - t0
            cycles += (ctr["compare_cycles"] + ctr["write_cycles"]
                       + ctr["bwrite_cycles"])
            answers.append((name, out.astype(np.uint32), ctr))
        return {"latency_s": latency, "cycles": cycles}, answers

    def close(self) -> None:
        self.eng = None
        self.programs = None


def check(config, traffic, seed, records, outputs, g,
          control: bool = False) -> dict:
    """Words and counters against the reference over a sample of jobs.

    ``bad_words``: result words that differ; ``counter_gap``: the summed
    absolute gap of the integer counters; ``energy_rel``: the widest
    relative gap of the energy.  ``control=True`` puts the controls in
    the program's place: for words and counters the reference on a
    datapath of half the stated widths, for the energy the reference
    accumulating it in float32, one step below the stated float64.
    """
    n = config["ap"]["n_words"]
    energy = config["ap"]["energy_per_bit"]
    picked = common.sample(records, outputs, traffic["sample"], g)
    if not picked:
        return {}
    bad, gap, rel = 0, 0, 0.0
    for i in picked:
        for step, (name, out, ctr) in enumerate(outputs[i]):
            got, got_ctr = out, ctr
            ops = operands(name, n, seed, i, step)
            want, want_ctr = reference.run(name, ops, n, energy)
            if control:
                got, got_ctr = reference.run(name, ops, n, energy,
                                             narrow=True)
                got_ctr["energy"] = reference.run(
                    name, ops, n, energy, energy_dtype=np.float32)[1]["energy"]
            bad += int(np.count_nonzero(np.asarray(got, np.uint64) != want))
            gap += sum(abs(int(got_ctr[k]) - int(want_ctr[k]))
                       for k in reference.INT_COUNTERS)
            rel = max(rel, abs(got_ctr["energy"] - want_ctr["energy"])
                      / abs(want_ctr["energy"]))
    return {"bad_words": float(bad), "counter_gap": float(gap),
            "energy_rel": rel}
