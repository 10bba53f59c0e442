"""Plain reference for the closed-loop thermal replay of one stack.

Imports nothing of the program.  A case is one machine's die under the
configuration's stack, driven by a power trace over ``n_intervals``
equal intervals:

- each interval the DTM controller reads the hottest logic cell at the
  interval's start and sets the duty f: 1 below the trip, falling
  linearly over the ramp to the floor; the dynamic power is f times the
  interval's frame;
- leakage exp(beta (T - ambient)) and DRAM refresh (1x, 2x from 85 C,
  4x from 95 C) depend on the temperature at the interval's end, found
  by Picard iteration: each iterate re-integrates the interval from its
  start with the previous iterate's leakage and refresh, at most
  ``n_picard`` times, stopping early once an iterate moves no cell by
  more than `PICARD_SETTLED_K` (further iterates then change nothing the
  comparison can see);
- the interval is ``steps`` backward-Euler steps (C/dt + G) dx = P - G x,
  each solved exactly by a sparse LU factorisation in float64.

Reports per interval the hottest and coolest footprint cell of every die
layer, the duty, and the case's verdict (no DRAM cell above 85 C; no die
cell where the stack has no DRAM).  The
control (``dtype="bfloat16"``) rounds the state, the power, every right
side and every increment to bfloat16.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np

from bench.reference.thermal import Operator

PICARD_SETTLED_K = 1e-6


def _rounder(dtype: str):
    if dtype == "float64":
        return lambda v: v
    low = np.dtype(getattr(ml_dtypes, dtype))
    return lambda v: np.asarray(v, low).astype(np.float64)


def replay(config: dict, die_w_m: float, r_convec: float, n: int,
           margin: int, frames: np.ndarray, leak0: np.ndarray,
           refresh0: np.ndarray, interval_s: float, steps: int,
           n_picard: int, dtype: str = "float64") -> dict:
    """One case.  ``frames`` [T, Ld, n, n] dynamic W per die cell before
    the duty; ``leak0`` / ``refresh0`` [Ld, n, n] leakage at ambient and
    1x refresh.  Returns float64 arrays ``peak_C`` / ``min_C`` [T, Ld],
    ``duty`` [T] and ``verdict_ok``."""
    from scipy.sparse import diags
    from scipy.sparse.linalg import splu

    q = _rounder(dtype)
    fb, dr = config["feedback"], config["dram"]
    amb = config["ambient_C"]
    kinds = [l["kind"] for l in config["layers"][:-1]]
    logic = [i for i, k in enumerate(kinds) if k == "logic"]
    dram = [i for i, k in enumerate(kinds) if k == "dram"]
    op = Operator(config, n, margin, die_w_m, r_convec)
    G = op.matrix()
    dt = interval_s / steps
    # minimum degree on the symmetric pattern: half the fill of COLAMD
    lu = splu((diags(op.capacities() / dt) + G).tocsc(),
              permc_spec="MMD_AT_PLUS_A", options={"SymmetricMode": True})
    Ld, nD = op.Ld, op.Ld * n * n
    b1, b2 = dr["refresh_bins_C"]
    m1, m2 = dr["refresh_multipliers"]
    leak0 = np.asarray(leak0, np.float64)
    refresh0 = np.asarray(refresh0, np.float64)

    x = np.zeros(op.size)
    peaks, mins, duty = [], [], []
    for frame in np.asarray(frames, np.float64):
        rise = x[:nD].reshape(Ld, n, n)
        hot = max(max(float(rise[l].max()), 0.0) for l in logic) + amb
        f = min(max(1.0 - (hot - fb["dtm_trip_C"]) / fb["dtm_ramp_C"],
                    fb["dtm_floor"]), 1.0)
        base = f * frame
        xk = x
        for _ in range(n_picard):
            T = xk[:nD].reshape(Ld, n, n) + amb
            mult = np.where(T >= b2, m2, np.where(T >= b1, m1, 1.0))
            P = np.zeros(op.size)
            P[:nD] = (base + leak0 * np.exp(fb["leak_beta_per_K"] * (T - amb))
                      + refresh0 * mult).ravel()
            P = q(P)
            d = x
            for _ in range(steps):
                d = q(d + q(lu.solve(q(P - G @ d))))
            done = float(np.abs(d - xk).max()) <= PICARD_SETTLED_K
            xk = d
            if done:
                break
        x = xk
        rise = x[:nD].reshape(Ld, n, n)
        peaks.append(rise.max(axis=(1, 2)) + amb)
        mins.append(rise.min(axis=(1, 2)) + amb)
        duty.append(f)
    peaks = np.asarray(peaks)
    judged = float(peaks[:, dram or list(range(Ld))].max())
    return {"peak_C": peaks, "min_C": np.asarray(mins),
            "duty": np.asarray(duty),
            "verdict_ok": not judged > fb["dram_limit_C"],
            "judged_peak_C": judged}


def gaps(got: dict, want: dict, config: dict, margin_C: float) -> dict:
    """Widest temperature and duty gaps, and 1 where the verdicts differ
    although the reference's hottest judged cell lies more than
    ``margin_C`` from the limit (closer, a temperature within its limit
    may fall on either side)."""
    t = max(float(np.abs(got["peak_C"] - want["peak_C"]).max()),
            float(np.abs(got["min_C"] - want["min_C"]).max()))
    clear = abs(want["judged_peak_C"]
                - config["feedback"]["dram_limit_C"]) > margin_C
    flip = got["verdict_ok"] != want["verdict_ok"] and clear
    return {"temp_gap_C": t if math.isfinite(t) else math.inf,
            "duty_gap": float(np.abs(got["duty"] - want["duty"]).max()),
            "verdict_flips": float(flip)}
