"""Plain reference of the paper's area, power and floorplan models.

Imports nothing of the program; every constant comes from the
configuration (`models`, `ap_floorplan`, `simd_floorplan`, `dram`).
Section 3 of arXiv:1307.3853: the SIMD's speedup (eq 3), area (eq 4) and
power (eq 14) over its PU count; the AP's speedup (eq 8), area (eq 9) and
power (eq 17); a same-performance pair sizes the SIMD to the AP's
speedup.  Section 4: the AP floorplan of Fig 8 and the SIMD floorplan of
Fig 11 as power maps; the DRAM dies as a bank array split by an IO spine.
All in float64.
"""
from __future__ import annotations

import math

import numpy as np


def _area_mm2(cfg: dict, a_norm: float) -> float:
    return a_norm * cfg["models"]["a_sram_um2"] * 1e-6


def _watts(cfg: dict, p_norm: float) -> float:
    return p_norm * cfg["models"]["p_sram_uW"] * 1e-6


def workloads(cfg: dict) -> dict:
    """{name: (i_s, s_apu)}: synchronisation intensity and AP per-PU
    speedup, scaled off the DMM anchor by arithmetic intensity."""
    md = cfg["models"]
    s_star = md["dmm_anchor_speedup"]
    i_s_dmm = 1.0 / s_star - 1.0 / md["dmm_anchor_n_simd"]
    s_apu_dmm = s_star / float(md["n_data"])
    ai = md["arith_intensity"]
    # fft pays serial inter-PU communication, bs runs 1.5x the fp32-mul
    # bound, spmv is DMM-like with a tag-masked reduction
    s_apu = {"dmm": s_apu_dmm, "fft": s_apu_dmm / 2.0,
             "bs": 1.5 / md["ap_cycles_fp32_mul"], "spmv": s_apu_dmm / 2.0,
             **md["s_apu_suite"]}
    return {w: (i_s_dmm * ai["dmm"] / ai[w], s_apu[w]) for w in ai}


def simd_pu_area(cfg: dict) -> float:
    md = cfg["models"]
    m, k = md["m_bits"], md["k_words"]
    return md["a_pu_bit"] * m * m + md["a_rf_bit"] * k * m


def simd_cache_area(cfg: dict) -> float:
    md = cfg["models"]
    return float(md["n_data"]) * md["m_bits"] * md["cache_overhead"]


def simd_phase_powers(cfg: dict, i_s: float, n: float):
    """Eq 14 split into (execute W, synchronise W, execute time share)."""
    md = cfg["models"]
    m, k = md["m_bits"], md["k_words"]
    f_run = (1.0 / n) / (1.0 / n + i_s)
    p_exec = _watts(cfg, n * (md["p_pu_bit"] * m * m + md["p_rf_bit"] * k * m)
                    * f_run)
    p_sync = _watts(cfg, i_s * md["p_sync_bit"] * m / (1.0 / n + i_s))
    return p_exec, p_sync, f_run


def ap_area_mm2(cfg: dict, n_pus: float) -> float:
    md = cfg["models"]
    return _area_mm2(cfg, n_pus * md["a_ap_bit"] * md["k_words"]
                     * md["m_bits"])


def ap_power_W(cfg: dict, n_pus: float) -> float:
    """Eq 17: dynamic (a pass writes 2 bits and compares 3) plus leakage."""
    md = cfg["models"]
    per_pu = (2.0 * (1 / 8 + 7 / 8 * md["p_miswrite"])
              + 3.0 * (1 / 8 * md["p_match"] + 7 / 8 * md["p_mismatch"])) / 2
    return (_watts(cfg, n_pus * per_pu)
            + md["gamma_W_mm2"] * ap_area_mm2(cfg, n_pus))


def design_point(cfg: dict, workload: str, n_ap: int) -> dict | None:
    """The same-performance pair for an AP of ``n_ap`` PUs, or None where
    the SIMD's synchronisation ceiling 1/I_s lies below the AP's speedup."""
    md = cfg["models"]
    i_s, s_apu = workloads(cfg)[workload]
    s = s_apu * n_ap
    if s * i_s >= 1.0:
        return None
    n_simd = 1.0 / (1.0 / s - i_s)
    area = n_simd * simd_pu_area(cfg) + simd_cache_area(cfg)
    p_dyn = _watts(cfg, (md["p_pu_bit"] * md["m_bits"] ** 2
                         + md["p_rf_bit"] * md["k_words"] * md["m_bits"]
                         + i_s * md["p_sync_bit"] * md["m_bits"])
                   / (1.0 / n_simd + i_s))
    return {"workload": workload, "i_s": i_s, "ap_n_pus": n_ap,
            "ap_area_mm2": ap_area_mm2(cfg, n_ap),
            "ap_power_W": ap_power_W(cfg, n_ap),
            "simd_n_pus": int(round(n_simd)),
            "simd_area_mm2": _area_mm2(cfg, area),
            "simd_power_W": p_dyn + md["gamma_W_mm2"] * _area_mm2(cfg, area)}


def comparable_design_point(cfg: dict, workload: str, n_start: int) -> dict:
    """The largest AP, halved from ``n_start``, that has a pair."""
    n = n_start
    while n >= 1024:
        dp = design_point(cfg, workload, n)
        if dp is not None:
            return dp
        n //= 2
    raise ValueError(f"no comparable design point for {workload!r}")


def traffic_bytes_per_s(cfg: dict, workload: str, n_pus: int) -> float:
    """DRAM traffic: the AP's MAC rate over the arithmetic intensity."""
    md = cfg["models"]
    flops = 2.0 * n_pus * md["ap_clock_hz"] / (md["ap_cycles_fp32_mul"]
                                               + md["ap_cycles_fp32_add"])
    return flops / md["arith_intensity"][workload] * md["bytes_per_word"]


def ap_power_map(cfg: dict, n: int, layer_W: float, die_w_mm: float,
                 bank_activity: np.ndarray | None = None) -> np.ndarray:
    """[n, n] W per cell of one AP logic layer (Fig 8): leakage uniform,
    dynamic power by region (array, KEY/MASK strip on top, TAG strip on
    the right of every block).  ``bank_activity`` [banks, banks] scales
    each bank's dynamic power (1 everywhere is the paper's map)."""
    md, fp = cfg["models"], cfg["ap_floorplan"]
    n_cells = fp["words_per_block"] * fp["bits_per_word"]
    ff = fp["reg_activity"] * md["p_rf_bit"] / md["a_rf_bit"]
    per_pu = (2.0 * (1 / 8 + 7 / 8 * md["p_miswrite"])
              + 3.0 * (1 / 8 * md["p_match"] + 7 / 8 * md["p_mismatch"])) / 2
    dens = {"array": per_pu * fp["words_per_block"]
            / (n_cells * md["a_ap_bit"]), "regs": ff, "tag": ff}
    areas = {"array": n_cells * md["a_ap_bit"],
             "regs": 2 * fp["bits_per_word"] * md["a_rf_bit"],
             "tag": fp["words_per_block"] * md["a_rf_bit"]}
    bpe = fp["banks"] * fp["blocks"]
    nb = bpe * bpe
    leak_W = md["gamma_W_mm2"] * die_w_mm ** 2
    total = sum(dens[r] * areas[r] for r in dens) * nb
    region_W = {r: (layer_W - leak_W) * dens[r] * areas[r] * nb / total
                for r in dens}
    cpb = n // bpe
    if cpb < 3:                 # too coarse for the strips: uniform
        dyn = np.full((n, n), (layer_W - leak_W) / n ** 2)
    else:
        if cpb * bpe != n:
            raise ValueError(f"n must be a multiple of {bpe}")
        strip = max(1, int(round(fp["strip_share"] * cpb)))
        arr_cells = cpb * cpb - strip * cpb - strip * (cpb - strip)
        block = np.zeros((cpb, cpb))
        block[strip:, :cpb - strip] = region_W["array"] / nb / arr_cells
        block[:strip, :] = region_W["regs"] / nb / (strip * cpb)
        block[strip:, cpb - strip:] = (region_W["tag"] / nb
                                       / (strip * (cpb - strip)))
        dyn = np.tile(block, (bpe, bpe))
    if bank_activity is not None:
        per_bank = n // fp["banks"]
        dyn = dyn * np.kron(bank_activity, np.ones((per_bank, per_bank)))
    return dyn + leak_W / n ** 2


def simd_power_map(cfg: dict, n: int, dp: dict, die_w_mm: float
                   ) -> np.ndarray:
    """[n, n] W per cell of one SIMD logic layer (Fig 11): execution
    power in the PU columns, half the synchronisation power in the L1s
    and half in the central L2, leakage everywhere."""
    md, fp = cfg["models"], cfg["simd_floorplan"]
    n_pu = dp["simd_n_pus"]
    p_exec, p_sync, _ = simd_phase_powers(cfg, dp["i_s"], n_pu)
    p_leak = md["gamma_W_mm2"] * dp["simd_area_mm2"]
    um2 = md["a_sram_um2"] * 1e-6
    a_pu = n_pu * simd_pu_area(cfg) * um2
    a_l1 = fp["l1_frac_of_cache"] * simd_cache_area(cfg) * um2
    col_w = max(1, int(round((a_pu + a_l1) / die_w_mm ** 2 / 2.0 * n)))
    core_h = n // (fp["n_cores"] // 2)
    pu_w = max(1, int(round(col_w * a_pu / (a_pu + a_l1))))
    kind = np.zeros((n, n))                 # 0: L2, 1: PUs, 2: L1
    for side in (0, 1):
        x0 = 0 if side == 0 else n - col_w
        for c in range(fp["n_cores"] // 2):
            rows = slice(c * core_h, (c + 1) * core_h)
            if side == 0:
                pu, l1 = (x0, x0 + pu_w), (x0 + pu_w, x0 + col_w)
            else:
                pu, l1 = (x0 + col_w - pu_w, x0 + col_w), (x0, x0 + col_w - pu_w)
            kind[rows, pu[0]:pu[1]] = 1
            kind[rows, l1[0]:l1[1]] = 2
    n_pu_cells, n_l1 = (kind == 1).sum(), (kind == 2).sum()
    n_l2 = n * n - n_pu_cells - n_l1
    if n_pu_cells == 0 or n_l2 == 0:
        return np.full((n, n), (p_exec + p_sync + p_leak) / n ** 2)
    out = np.zeros((n, n))
    out[kind == 1] = p_exec / n_pu_cells
    l1_W = 0.5 * p_sync if n_l1 else 0.0
    out[kind == 2] = l1_W / max(n_l1, 1)
    out[kind == 0] = (p_sync - l1_W) / n_l2
    return out + p_leak / n ** 2


def simd_phase_trace(cfg: dict, dp: dict, n_intervals: int,
                     period: int = 8) -> np.ndarray:
    """Mean-1 activity of the SIMD: execute and synchronise levels
    alternating at the execute time share, ``period`` intervals a cycle."""
    p_exec, p_sync, f_run = simd_phase_powers(cfg, dp["i_s"],
                                              dp["simd_n_pus"])
    hi = p_exec / max(f_run, 1e-9)
    lo = p_sync / max(1.0 - f_run, 1e-9)
    act = np.array([hi if (i % period) / period < f_run else lo
                    for i in range(n_intervals)])
    return act / act.mean()


def dram_bank_mask(cfg: dict, n: int) -> np.ndarray:
    mask = np.ones((n, n))
    if n >= 4:
        h = max(1, int(round(cfg["dram"]["io_frac"] * n)))
        y0 = (n - h) // 2
        mask[y0:y0 + h, :] = 0.0
    return mask


def dram_activate_map(cfg: dict, n: int) -> np.ndarray:
    """Share of the activate/IO power per cell (sums to 1)."""
    bank = dram_bank_mask(cfg, n)
    if bank.sum() in (0, bank.size):
        return np.full((n, n), 1.0 / bank.size)
    spine = 1.0 - bank
    share = cfg["dram"]["io_power_share"]
    return share * spine / spine.sum() + (1.0 - share) * bank / bank.sum()


def dram_refresh_map(cfg: dict, n: int) -> np.ndarray:
    """1x refresh W per cell: the banks only."""
    bank = dram_bank_mask(cfg, n)
    d = cfg["dram"]
    return bank / bank.sum() * d["refresh_W_per_Gbit"] * d["capacity_Gbit"]


def dram_activate_W(cfg: dict, traffic_bytes_per_s: float, n_dies: int
                    ) -> float:
    """Activate/IO W of one DRAM die: the traffic striped over the dies."""
    return (traffic_bytes_per_s * 8.0 * cfg["dram"]["e_act_pJ_per_bit"]
            * 1e-12 / max(n_dies, 1))


def dram_leak_W(cfg: dict, die_w_mm: float) -> float:
    return cfg["dram"]["gamma_W_mm2"] * die_w_mm ** 2


def ap_die_w_mm(cfg: dict) -> float:
    """The AP die edge: the square root of its eq-9 area."""
    return math.sqrt(ap_area_mm2(cfg, cfg["models"]["n_data"]))
