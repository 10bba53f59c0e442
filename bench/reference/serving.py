"""Plain reference for the serving co-simulation of one model on a stack.

Imports nothing of the program.  Every number comes from the
configuration: the model's published `config.json` keys at its top level,
the deployment (bytes per weight and per latent element), the paper's
machine models and the stack.  Three parts, each a straightforward
float64 computation:

- **cost**: parameters counted from the published widths (embedding,
  head, per-layer attention, norms, the dense FFN of the first layers,
  and in every MoE layer the router, the shared experts and the routed
  experts).  A decode step at batch B streams everything but the routed
  experts once and E(1 - (1 - k/E)^B) routed experts a MoE layer
  (uniform routing).  Latent attention costs 2H(kv_lora + qk_rope) +
  2H kv_lora FLOPs per layer and context position in decode (absorbed
  form) and 2H(qk_nope + qk_rope) + 2H v_dim per layer and causal
  (query, key) pair in prefill (expanded form); the latent cache holds
  kv_lora + qk_rope elements a layer and token.
- **queue**: the bursty two-state arrival process, drawn from its seed,
  and a fluid FIFO queue with continuous batching: interval t serves
  min(backlog, f_t C dt) of work, the batch is the requests in system
  capped at the scenario's.  A request's latency is its finish on a
  FIFO server of rate f_t C, request by request (past the horizon at
  the last rate), less its arrival time, floored by its serialised
  decode at the batch in effect.
- **replay**: per coarse interval of the program's plan, once the plan
  is checked against this reference's own signals, one backward-Euler
  step (C/dt + G) dx = P - G x of the interval's length, solved by a
  sparse LU factorisation in float64, with Picard iteration on leakage
  and refresh and the ramp DTM read at the interval's start, as in
  `bench.reference.closed_loop`.  The control (``dtype="bfloat16"``)
  rounds the state, the power, every right side and every increment.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import paper
from bench.reference.closed_loop import PICARD_SETTLED_K, _rounder
from bench.reference.thermal import Operator


# ------------------------------------------------------------------ cost

def param_counts(cfg: dict) -> dict:
    """Parameters of the model by part, from the published widths."""
    d, H, V = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    L, first = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    kv, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    q = d * H * qk if not ql else d * ql + ql + ql * H * qk
    attn = (q + d * (kv + cfg["qk_rope_head_dim"]) + kv
            + kv * H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * d)
    E, de = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    n_moe = L - first
    routed_layer = 3 * d * de * E
    shared_layer = 3 * d * de * cfg["n_shared_experts"]
    dense_ffn = 3 * d * cfg["intermediate_size"]
    head = 0 if cfg["tie_word_embeddings"] else d * V
    total = (V * d + head + d + L * (attn + 2 * d)
             + first * dense_ffn
             + n_moe * (d * E + shared_layer + routed_layer))
    return {"total": total, "routed_layer": routed_layer, "n_moe": n_moe,
            "shared_dense": first * dense_ffn + n_moe * shared_layer}


class Cost:
    """Serving cost of the configuration's model for one request shape.

    ``rule="active"`` is the control: the batch-1 active parameters at
    every batch and no attention FLOPs over the context.
    """

    def __init__(self, config: dict, prompt: int, output: int,
                 rule: str = "mechanisms"):
        dep = config["deployment"]
        self.cfg, self.rule = config, rule
        self.prompt, self.output = prompt, output
        pc = param_counts(config)
        self.E = config["n_routed_experts"]
        self.k = config["num_experts_per_tok"]
        self.routed = float(pc["routed_layer"] * pc["n_moe"])
        self.non_routed = float(pc["total"]) - self.routed
        self.w_bytes = dep["bytes_per_weight"]
        H, L = config["num_attention_heads"], config["num_hidden_layers"]
        kv, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
        self.kv_bytes_tok = float(L * (kv + rope) * dep["bytes_per_latent"])
        on = rule == "mechanisms"
        self.attn_ctx = float(L * (2 * H * (kv + rope) + 2 * H * kv)) \
            if on else 0.0
        self.attn_pair = float(L * (2 * H * (config["qk_nope_head_dim"] + rope)
                                    + 2 * H * config["v_head_dim"])) \
            if on else 0.0
        self.mean_context = prompt + output / 2.0
        self.n_active = self.non_routed + self.routed * self.k / self.E

    def weight_bytes(self, batch: int) -> float:
        share = self.k / self.E if self.rule != "mechanisms" \
            else 1.0 - (1.0 - self.k / self.E) ** batch
        return self.w_bytes * (self.non_routed + self.routed * share)

    @property
    def attn_prefill_flops(self) -> float:
        return self.attn_pair * self.prompt * (self.prompt + 1) / 2.0

    @property
    def attn_flops_per_token(self) -> float:
        return self.attn_ctx * self.mean_context

    @property
    def prefill_flops(self) -> float:
        return 2.0 * self.n_active * self.prompt + self.attn_prefill_flops

    @property
    def decode_flops_per_token(self) -> float:
        return 2.0 * self.n_active + self.attn_flops_per_token

    @property
    def request_flops(self) -> float:
        return self.prefill_flops + self.decode_flops_per_token * self.output

    def decode_ai(self, batch: int) -> float:
        step = self.weight_bytes(batch) \
            + batch * self.kv_bytes_tok * self.mean_context
        return self.decode_flops_per_token * batch \
            / (step / self.cfg["models"]["bytes_per_word"])

    def counters(self, max_batch: int) -> dict:
        """The counters `correct` compares, by name."""
        out = {f"weight_bytes_B{b}": self.weight_bytes(b)
               for b in range(1, max_batch + 1)}
        out.update(kv_bytes_tok=self.kv_bytes_tok,
                   attn_flops_per_token=self.attn_flops_per_token,
                   attn_prefill_flops=self.attn_prefill_flops,
                   request_flops=self.request_flops)
        return out


# -------------------------------------------------------------- machines

def design_point(config: dict, ai: float, n_start: int) -> dict:
    """The same-performance AP/SIMD pair for a workload of arithmetic
    intensity ``ai`` anchored off DMM (i_s ~ 1/AI, the DMM per-PU
    speedup): the largest AP, halved from ``n_start``, that has one."""
    md = config["models"]
    i_s_dmm, s_apu = paper.workloads(config)["dmm"]
    i_s = i_s_dmm * md["arith_intensity"]["dmm"] / ai
    n_ap = n_start
    while n_ap >= 1024:
        s = s_apu * n_ap
        if s * i_s < 1.0:
            break
        n_ap //= 2
    else:
        raise ValueError(f"no comparable design point at AI {ai!r}")
    n_simd = 1.0 / (1.0 / s - i_s)
    area = n_simd * paper.simd_pu_area(config) + paper.simd_cache_area(config)
    simd_mm2 = area * md["a_sram_um2"] * 1e-6
    return {"i_s": i_s, "ap_n_pus": n_ap,
            "ap_area_mm2": paper.ap_area_mm2(config, n_ap),
            "ap_power_W": paper.ap_power_W(config, n_ap),
            "simd_n_pus": int(round(n_simd)), "simd_area_mm2": simd_mm2}


def ap_flops_per_s(config: dict, n_pus: int) -> float:
    md = config["models"]
    return 2.0 * n_pus * md["ap_clock_hz"] / (md["ap_cycles_fp32_mul"]
                                              + md["ap_cycles_fp32_add"])


def machine_maps(config: dict, machine: str, dp: dict, n: int):
    """(die width in mm, dynamic logic map [n, n], leakage W a cell)."""
    gamma = config["models"]["gamma_W_mm2"]
    if machine == "ap":
        w = math.sqrt(dp["ap_area_mm2"])
        pmap = paper.ap_power_map(config, n, dp["ap_power_W"], w)
        leak = gamma * w ** 2
    elif machine == "simd":
        w = math.sqrt(dp["simd_area_mm2"])
        pmap = paper.simd_power_map(config, n, dp, w)
        leak = gamma * dp["simd_area_mm2"]
    else:
        raise ValueError(f"unknown machine {machine!r}")
    return w, pmap - leak / n ** 2, leak / n ** 2


# ----------------------------------------------------------------- queue

def arrivals(traffic: dict, mean_qps: float) -> np.ndarray:
    """Per-interval arrivals of the two-state Markov-modulated Poisson
    process: the chain starts from its stationary split and its state
    rates keep the long-run mean at ``mean_qps``."""
    T = max(int(round(traffic["horizon_s"] / traffic["interval_s"])), 1)
    g = np.random.default_rng(traffic["trace_seed"])
    p_in, p_out, ratio = (traffic["p_enter"], traffic["p_exit"],
                          traffic["burst_ratio"])
    pi_hi = p_in / (p_in + p_out)
    r_lo = mean_qps / ((1.0 - pi_hi) + ratio * pi_hi)
    burst = g.random() < pi_hi
    flips = g.random(T)
    rates = np.empty(T)
    for t in range(T):
        rates[t] = r_lo * ratio if burst else r_lo
        burst = flips[t] >= p_out if burst else flips[t] < p_in
    counts = np.random.default_rng(traffic["trace_seed"] + 1).poisson(
        rates * traffic["interval_s"])
    return counts.astype(np.int64)


def fifo_finish(arr: np.ndarray, work: float, rate: np.ndarray, dt: float
                ) -> np.ndarray:
    """Finish time of each request on one FIFO server whose rate in
    interval t is ``rate[t]`` (past the horizon, the last interval's):
    a request starts once its interval has begun and the request before
    it has finished, and ends when the rate integrated from its start
    covers its ``work``."""
    T = arr.shape[0]
    out, free = [], 0.0
    for a, count in enumerate(arr):
        for _ in range(int(count)):
            pos, need = max(free, a * dt), work
            k = min(int(pos // dt), T)
            while True:
                if k >= T:
                    pos += need / rate[-1]
                    break
                end = (k + 1) * dt
                can = rate[k] * max(end - pos, 0.0)
                if can >= need and rate[k] > 0:
                    pos += need / rate[k]
                    break
                need -= can
                pos, k = end, k + 1
            out.append(pos)
            free = pos
    return np.asarray(out)


def fluid_queue(arr: np.ndarray, cost: Cost, cap: float,
                throttle: np.ndarray, dt: float, max_batch: int) -> dict:
    """Busy share and batch per interval, and each request's latency.

    Interval t serves min(backlog, f_t C dt) of the work in system, all
    of an interval's arrivals joining at its start; the batch is the
    requests in system capped at ``max_batch``.  A request's latency is
    its FIFO finish time less its arrival time (spread evenly over its
    interval), floored by its serialised decode at the batch in effect
    on arrival."""
    T = arr.shape[0]
    w = cost.request_flops
    busy, batch = np.zeros(T), np.ones(T)
    backlog = 0.0
    for t in range(T):
        backlog += arr[t] * w
        avail = throttle[t] * (cap * dt)
        s = min(backlog, avail)
        busy[t] = s / avail if avail > 0 else 0.0
        backlog -= s
        batch[t] = min(max_batch, max(1.0, math.ceil(backlog / w + arr[t])))
    if int(arr.sum()) == 0:
        return {"busy": busy, "batch": batch, "latency_s": np.zeros(0)}
    finish = fifo_finish(arr, w, throttle * cap, dt)
    t_arr = np.concatenate([t * dt + (np.arange(a) + 0.5) / a * dt
                            for t, a in enumerate(arr) if a])
    b_arr = np.repeat(batch, arr)
    floor = (cost.prefill_flops + cost.output * cost.decode_flops_per_token
             * b_arr) / cap
    return {"busy": busy, "batch": batch,
            "latency_s": np.maximum(finish - t_arr, floor)}


def traffic_bytes_per_s(config: dict, cost: Cost, q: dict, n_pus: int
                        ) -> np.ndarray:
    """DRAM demand per interval: the busy share of the AP's MAC rate over
    the decode intensity at the interval's batch."""
    cap = ap_flops_per_s(config, n_pus)
    bpw = config["models"]["bytes_per_word"]
    return np.array([b * cap / cost.decode_ai(int(B)) * bpw
                     for b, B in zip(q["busy"], q["batch"])])


def plan_invalid(reps, signals: np.ndarray, tol: float, max_merge: int,
                 slack: float) -> float:
    """1 where the plan's blocks do not tile the horizon, one is longer
    than ``max_merge``, or a signal moves more than ``tol`` (+ ``slack``)
    inside one; else 0."""
    reps = np.asarray(reps, np.int64)
    if reps.ndim != 1 or (reps < 1).any() or reps.sum() != len(signals) \
            or (reps > max_merge).any():
        return 1.0
    edges = np.concatenate([[0], np.cumsum(reps)])
    for a, b in zip(edges[:-1], edges[1:]):
        block = signals[a:b]
        if float((block.max(axis=0) - block.min(axis=0)).max()) > tol + slack:
            return 1.0
    return 0.0


# ---------------------------------------------------------------- replay

def replay(config: dict, die_w_m: float, r_convec: float, n: int,
           margin: int, frames: np.ndarray, leak0: np.ndarray,
           refresh0: np.ndarray, reps, interval_s: float, n_picard: int,
           dtype: str = "float64", lus: dict | None = None) -> dict:
    """One machine over the coarse plan ``reps``: one backward-Euler step
    of ``reps[i] * interval_s`` per interval.  ``lus`` keeps the LU
    factors by step length for further replays of the same operator.
    Returns float64 ``peak_C`` / ``min_C`` [Tc, Ld], ``duty`` [Tc],
    ``verdict_ok`` and ``judged_peak_C``."""
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
    cap = op.capacities()
    lus = {} if lus is None else lus
    Ld, nD = op.Ld, op.Ld * n * n
    b1, b2 = dr["refresh_bins_C"]
    m1, m2 = dr["refresh_multipliers"]
    x = np.zeros(op.size)
    peaks, mins, duty = [], [], []
    for frame, r in zip(np.asarray(frames, np.float64), reps):
        if r not in lus:
            lus[r] = splu((diags(cap / (r * interval_s)) + G).tocsc(),
                          permc_spec="MMD_AT_PLUS_A",
                          options={"SymmetricMode": True})
        lu = lus[r]
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
            d = q(x + q(lu.solve(q(P - G @ x))))
            settled = float(np.abs(d - xk).max()) <= PICARD_SETTLED_K
            xk = d
            if settled:
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


def stack_inputs(config: dict, machine: str, dp: dict, n: int,
                 busy_c: np.ndarray, traffic_c: np.ndarray):
    """(die width in m, frames [Tc, Ld, n, n], leak0, refresh0): logic
    layers draw the busy share of the dynamic map, DRAM layers the
    activate power of the interval's traffic striped over the dies."""
    kinds = [l["kind"] for l in config["layers"][:-1]]
    n_dram = kinds.count("dram")
    w_mm, dyn_logic, leak_cell = machine_maps(config, machine, dp, n)
    act_map = paper.dram_activate_map(config, n)
    Tc = busy_c.shape[0]
    frames = np.zeros((Tc, len(kinds), n, n))
    leak0 = np.zeros((len(kinds), n, n))
    refresh0 = np.zeros((len(kinds), n, n))
    act_W = np.array([paper.dram_activate_W(config, b, n_dram)
                      for b in traffic_c])
    for l, kind in enumerate(kinds):
        if kind == "logic":
            frames[:, l] = busy_c[:, None, None] * dyn_logic
            leak0[l] = leak_cell
        else:
            frames[:, l] = act_W[:, None, None] * act_map
            leak0[l] = paper.dram_leak_W(config, w_mm) / n ** 2
            refresh0[l] = paper.dram_refresh_map(config, n)
    return w_mm * 1e-3, frames, leak0, refresh0


def merge(reps, x: np.ndarray) -> np.ndarray:
    """Mean of ``x`` over each block of the plan."""
    edges = np.concatenate([[0], np.cumsum(reps)])
    return np.array([x[a:b].mean() for a, b in zip(edges[:-1], edges[1:])])
