"""Device-resident programs for the data-dependent workload inner loops.

The eager :class:`~repro.core.engine.APEngine` path performs a blocking
host sync (``int(bp.popcount(tag))``) after every compare/write cycle,
so data-dependent workloads (sort, knn, spmv, hist) used to run
thousands of sequential device round-trips.  The two programs here keep
the whole inner loop resident (the CoMeT interval-simulation lesson,
arXiv:2109.12405 applied at the engine layer):

* :func:`min_extract_rounds` — the MSB-first CAM min-extraction idiom
  shared by ``workloads/sort.py`` and ``workloads/knn.py``, compiled as
  ONE ``lax.scan`` over extraction rounds.  The eager "did any candidate
  respond?" branch becomes an on-device :func:`~repro.core.engine.select_state`;
  rounds after the (data-dependent) termination point are masked no-ops.
* :func:`count_probes` — a batch of response-counter COMPAREs (the
  per-bin counting of ``histogram.py``, the per-(row, bit) tag-count
  accumulation of ``spmv.py``) as one scanned program.

Both transfer their per-pass matched counts to the host ONCE per
workload phase and replay them through the engine's ``charge_*``
accounting, which makes cycles / energy / events / trace arrays
bit-identical to the eager per-cycle oracle
(tests/test_device_workloads.py pins this for every workload).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane as bp
from repro.core import isa
from repro.core import engine as E
from repro.core.bitplane import Field
from repro.core.engine import APEngine, PassSchedule, _next_pow2
from repro.kernels.ap_megakernel import ref as mk_ref
from repro.kernels.ap_megakernel import ops as mk_ops


# ---------------------------------------------------------------------------
# shared min-extraction scan (sort + knn)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MinExtractTrace:
    """Per-round matched counts of one device min-extraction program.

    Arrays are [rounds, ...]; narrowing axes run MSB -> LSB (the eager
    iteration order).  ``masked[r]`` is True for rounds after the
    data-dependent termination point (device no-ops the host never
    replays).  ``device_counters`` are the program's own on-device
    :data:`~repro.core.engine.APState` counter totals, cross-checked
    against the host replay in the tests.
    """
    copy_sched: PassSchedule
    copy_matched: np.ndarray   # [R, P_copy] per-pass counts of cand<-active
    m1: np.ndarray             # [R, m] responders of the 0-probe compare
    m2: np.ndarray             # [R, m] responders of the retire compare
    take: np.ndarray           # [R, m] bool: the eager branch was taken
    count: np.ndarray          # [R] tie-group size of the extracted min
    tie_tag: np.ndarray        # [R, n_lanes] packed tie-group TAG
    masked: np.ndarray         # [R] bool: round ran as a masked no-op
    device_counters: np.ndarray  # int32[N_COUNTERS]


@partial(jax.jit, static_argnames=("val_cols", "active_col", "cand_col",
                                   "rounds", "readout"))
def _min_extract_program(state, copy_cc, copy_ck, copy_wc, copy_wk,
                         remaining, *, val_cols, active_col, cand_col,
                         rounds, readout):
    cand = jnp.array([cand_col], jnp.int32)
    active = jnp.array([active_col], jnp.int32)
    one = jnp.array([1], jnp.uint32)
    zero = jnp.array([0], jnp.uint32)

    def body(carry, _):
        st0, done, rem = carry
        st, copy_m = E.state_run(st0, copy_cc, copy_ck, copy_wc, copy_wk)
        m1s, m2s, takes = [], [], []
        for i in reversed(range(len(val_cols))):
            cv = jnp.array([cand_col, val_cols[i]], jnp.int32)
            st_c, m1 = E.state_compare(st, cv, jnp.array([1, 0], jnp.uint32))
            # the eager branch: if any candidate has a 0 here, retire the
            # 1-candidates — on device both arms run, one is selected
            st_b, m2 = E.state_compare(st_c, cv, jnp.array([1, 1], jnp.uint32))
            st_b, _ = E.state_write(st_b, cand, zero)
            take = m1 > 0
            st = E.select_state(take, st_b, st_c)
            m1s.append(m1)
            m2s.append(m2)
            takes.append(take)
        st, count = E.state_compare(st, cand, one)
        tie_tag = st.tag
        if readout:
            # knn: sequential responder readout + re-compare + retire
            st = E.state_read_charge(st, count)
            st, _ = E.state_compare(st, cand, one)
            st, _ = E.state_write(st, active, zero)
        else:
            # sort: retire the tie group unless the active set was empty
            st_r, _ = E.state_write(st, active, zero)
            st = E.select_state(count > 0, st_r, st)
        new_rem = rem - count
        st_out = E.select_state(done, st0, st)
        rem_out = jnp.where(done, rem, new_rem)
        done_out = done | (count == 0) | (new_rem <= 0)
        ys = (copy_m, jnp.stack(m1s), jnp.stack(m2s), jnp.stack(takes),
              count, tie_tag, done)
        return (st_out, done_out, rem_out), ys

    init = (state, jnp.bool_(False), jnp.asarray(remaining, jnp.int32))
    (state, _, _), ys = jax.lax.scan(body, init, None, length=rounds)
    return state, ys


def min_extract_rounds(eng: APEngine, val: Field, active: Field, cand: Field,
                       rounds: int, remaining: int,
                       readout: bool = False) -> MinExtractTrace:
    """Run up to ``rounds`` min-extractions over ``active`` rows on device.

    One compiled program, one host transfer.  The engine adopts the final
    array state; NO cycles/energy are charged here — the caller replays
    the returned counts through :func:`replay_extract` + ``charge_*`` in
    eager order.  ``remaining`` is the termination budget (elements left
    to emit: n for sort, k for knn); ``readout`` adds knn's per-round
    responder readout + re-compare + retire to the program.
    """
    copy_sched = isa.copy(cand, active)
    state, ys = _min_extract_program(
        eng.state(),
        jnp.asarray(copy_sched.cmp_cols), jnp.asarray(copy_sched.cmp_key),
        jnp.asarray(copy_sched.w_cols), jnp.asarray(copy_sched.w_key),
        remaining,
        val_cols=tuple(val.cols()), active_col=active.col(0),
        cand_col=cand.col(0), rounds=rounds, readout=readout)
    copy_m, m1, m2, take, count, tie_tag, masked = jax.device_get(ys)
    ctr = np.asarray(jax.device_get(state.counters))
    eng.adopt(state)
    return MinExtractTrace(copy_sched, np.asarray(copy_m), np.asarray(m1),
                           np.asarray(m2), np.asarray(take),
                           np.asarray(count), np.asarray(tie_tag),
                           np.asarray(masked), ctr)


def replay_extract(eng: APEngine, tr: MinExtractTrace, r: int,
                   m: int) -> tuple[int, int]:
    """Charge round ``r``'s extraction events in eager order.

    Mirrors ``sort.extract_min`` exactly: the fused candidate copy, the
    MSB-first narrowing (second compare + retire write only where the
    branch was taken), and the final tie-group compare.  Returns
    (min_value, tie_count).
    """
    eng.charge_run(tr.copy_sched, tr.copy_matched[r])
    v = 0
    for pos, i in enumerate(reversed(range(m))):
        eng.charge_compare(2, tr.m1[r, pos])
        if tr.take[r, pos]:
            eng.charge_compare(2, tr.m2[r, pos])
            eng.charge_write(1, tr.m2[r, pos])
        else:
            v |= 1 << i
    eng.charge_compare(1, tr.count[r])
    return v, int(tr.count[r])


def tagged_rows(tag_row: np.ndarray) -> np.ndarray:
    """Row indices set in a packed TAG row (host-side unpack)."""
    shifts = np.arange(bp.LANE, dtype=np.uint32)
    bits = (np.asarray(tag_row, np.uint32)[:, None] >> shifts[None, :]) & 1
    return np.where(bits.reshape(-1))[0]


# ---------------------------------------------------------------------------
# batched response counting (hist + spmv)
# ---------------------------------------------------------------------------

@jax.jit
def _count_probes_program(state, cols, keys, real):
    def body(st0, xs):
        cc, kk, is_real = xs
        st, matched = E.state_compare(st0, cc, kk)
        st = E.select_state(is_real, st, st0)
        return st, matched

    return jax.lax.scan(body, state, (cols, keys, real))


def count_probes(eng: APEngine, cols, keys) -> np.ndarray:
    """Run a batch of COMPAREs as one device program; return responder
    counts [n_probes] (int64).

    The probe shape is padded to power-of-two buckets (padded probes are
    masked on device and sliced off here), so nearby probe batches share
    one compiled program.  The engine adopts the final state — TAG holds
    the LAST probe's responders, as after the eager loop — and every
    probe's compare cycle is charged in order.
    """
    cols = np.atleast_2d(np.asarray(cols, np.int32))
    keys = np.atleast_2d(np.asarray(keys, np.uint32))
    n_probes, k = cols.shape
    np2, k2 = _next_pow2(n_probes), _next_pow2(k)

    def pad(a):
        if k2 != k:
            a = np.concatenate(
                [a, np.repeat(a[:, :1], k2 - k, axis=1)], axis=1)
        if np2 != n_probes:
            a = np.concatenate(
                [a, np.repeat(a[-1:], np2 - n_probes, axis=0)], axis=0)
        return a

    real = np.arange(np2) < n_probes
    state, counts = _count_probes_program(
        eng.state(), jnp.asarray(pad(cols)), jnp.asarray(pad(keys)),
        jnp.asarray(real))
    counts = np.asarray(jax.device_get(counts))[:n_probes].astype(np.int64)
    eng.adopt(state)
    for i in range(n_probes):
        eng.charge_compare(k, counts[i])
    return counts


# ---------------------------------------------------------------------------
# megakernel mode: op-group device programs + bulk (vectorized) host replay
# ---------------------------------------------------------------------------
#
# The device programs above already run resident; at n_elems >= ~2048 the
# wall-clock is dominated by the *host* side — per-event charge_* Python
# loops and per-scalar trace appends.  The megakernel mode attacks both
# ends: one fused op-group program per phase on device (the whole
# min-extraction round is a single OpGroup executed by the megakernel,
# optionally shard_map-ed over the lane axis), and ONE vectorized
# charge_bulk fold on the host, built to be bit-identical to the eager
# per-event replay (see APEngine.charge_bulk for the contract; the
# property harness enforces it sample by sample).


def engine_backend(backend: str, mode: str) -> str:
    """Map a workload (backend, mode) pair to the APEngine backend.

    ``mode="megakernel"`` lowers the engine's schedule path through the
    megakernel too: jnp -> 'megakernel' (fused scan, shardable),
    pallas -> 'megakernel_pallas' (the Pallas kernel)."""
    if mode != "megakernel":
        return backend
    if backend in ("jnp", "megakernel"):
        return "megakernel"
    if backend in ("pallas", "megakernel_pallas"):
        return "megakernel_pallas"
    raise ValueError(f"unknown backend {backend!r}")


def _min_extract_group(copy_sched: PassSchedule, val: Field, active: Field,
                       cand: Field, readout: bool) -> mk_ref.OpGroup:
    """One min-extraction round as a static op group.

    Table layout (indices the trace decoder below relies on):
    [0, P_copy)            PASS     the cand <- active copy schedule
    P_copy + 3*pos + 0     CMP      probe (cand, val_bit)==(1, 0) -> m1
    P_copy + 3*pos + 1     CMP      retire probe ==(1, 1), iff m1 > 0
    P_copy + 3*pos + 2     WRITE    cand <- 0,              iff m1 > 0
    P_copy + 3*m           CMP      tie group (cand == 1) -> count
    then sort: WRITE active <- 0 iff count > 0
    or   knn: CMP cand == 1; WRITE active <- 0 (both unconditional;
    the sequential responder read rides the scan wrapper's counters).
    """
    ops = []
    for p in range(copy_sched.n_passes):
        ops.append((mk_ref.OP_PASS, 0,
                    copy_sched.cmp_cols[p].tolist(),
                    copy_sched.cmp_key[p].tolist(),
                    copy_sched.w_cols[p].tolist(),
                    copy_sched.w_key[p].tolist()))
    c0 = cand.col(0)
    for i in reversed(range(val.width)):
        cv = [c0, val.col(i)]
        ops.append((mk_ref.OP_CMP, 0, cv, [1, 0], [], []))
        ops.append((mk_ref.OP_CMP, 1, cv, [1, 1], [], []))
        ops.append((mk_ref.OP_WRITE, 2, [], [], [c0], [0]))
    ops.append((mk_ref.OP_CMP, 0, [c0], [1], [], []))
    if readout:
        ops.append((mk_ref.OP_CMP, 0, [c0], [1], [], []))
        ops.append((mk_ref.OP_WRITE, 0, [], [], [active.col(0)], [0]))
    else:
        ops.append((mk_ref.OP_WRITE, 1, [], [], [active.col(0)], [0]))
    return mk_ref.OpGroup.build(ops)


def _mk_rounds_impl(state, op, cond, cc, ck, wc, wk, remaining, rounds,
                    readout, axis_name):
    """Scan ``rounds`` op-group executions with the same termination /
    masking semantics as ``_min_extract_program`` (shard_map-able)."""
    count_idx = op.shape[0] - (3 if readout else 2)
    enabled = jnp.ones(op.shape[0], jnp.bool_)

    def body(carry, _):
        st0, done, rem = carry
        planes, tag, matched, executed = mk_ref.group_scan(
            st0.planes, st0.tag, (op, cond, cc, ck, wc, wk), enabled,
            axis_name)
        delta = mk_ref.counter_delta(op, matched, executed)
        count = matched[count_idx]
        if readout:
            delta = delta.at[E.CTR_CYCLES].add(count) \
                .at[E.CTR_READ].add(count)
        st = E.APState(planes, tag, st0.counters + delta)
        new_rem = rem - count
        st_out = E.select_state(done, st0, st)
        rem_out = jnp.where(done, rem, new_rem)
        done_out = done | (count == 0) | (new_rem <= 0)
        ys = (matched, tag, done)
        return (st_out, done_out, rem_out), ys

    init = (state, jnp.bool_(False), jnp.asarray(remaining, jnp.int32))
    (state, _, _), ys = jax.lax.scan(body, init, None, length=rounds)
    return state, ys


@partial(jax.jit, static_argnames=("rounds", "readout"))
def _mk_rounds_program(state, op, cond, cc, ck, wc, wk, remaining, *,
                       rounds, readout):
    return _mk_rounds_impl(state, op, cond, cc, ck, wc, wk, remaining,
                           rounds, readout, axis_name=None)


@functools.lru_cache(maxsize=None)
def _mk_rounds_sharded(mesh, rounds, readout):
    """jit(shard_map(...)) of the rounds program over the 'lanes' axis,
    cached per (mesh, shape) so re-runs reuse the compiled program."""
    from jax.sharding import PartitionSpec as P

    st_spec = E.APState(P(None, "lanes"), P("lanes"), P())
    rep = P()

    def body(state, op, cond, cc, ck, wc, wk, remaining):
        return _mk_rounds_impl(state, op, cond, cc, ck, wc, wk, remaining,
                               rounds, readout, axis_name="lanes")

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(st_spec, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(st_spec, (rep, P(None, "lanes"), rep)),
        check_vma=False)

    @jax.jit
    def run(state, op, cond, cc, ck, wc, wk, remaining):
        return mapped(state, op, cond, cc, ck, wc, wk, remaining)

    return run


def min_extract_rounds_mk(eng: APEngine, val: Field, active: Field,
                          cand: Field, rounds: int, remaining: int,
                          readout: bool = False) -> MinExtractTrace:
    """Megakernel counterpart of :func:`min_extract_rounds`: each round
    is ONE fused op-group execution (sharded over lanes when the engine
    has ``n_shards``), returning the identical :class:`MinExtractTrace`
    so the replay layer is shared."""
    copy_sched = isa.copy(cand, active)
    group = _min_extract_group(copy_sched, val, active, cand, readout)
    tables = tuple(jnp.asarray(t) for t in group.tables())
    if eng.mesh is not None:
        state, ys = _mk_rounds_sharded(eng.mesh, rounds, readout)(
            eng.state(), *tables, jnp.asarray(remaining, jnp.int32))
    else:
        state, ys = _mk_rounds_program(eng.state(), *tables, remaining,
                                       rounds=rounds, readout=readout)
    matched, tie_tag, masked = (np.asarray(a) for a in jax.device_get(ys))
    ctr = np.asarray(jax.device_get(state.counters))
    eng.adopt(state)
    Pc = copy_sched.n_passes
    m = val.width
    base = Pc + 3 * np.arange(m)
    m1 = matched[:, base]
    m2 = matched[:, base + 1]
    return MinExtractTrace(copy_sched, matched[:, :Pc], m1, m2, m1 > 0,
                           matched[:, Pc + 3 * m], tie_tag, masked, ctr)


def replay_extract_bulk(eng: APEngine, tr: MinExtractTrace, m: int,
                        budget: int, readout: bool = False
                        ) -> tuple[np.ndarray, np.ndarray, int]:
    """Charge every replayed round's events in ONE bulk fold.

    Replays exactly the rounds (and the per-round tails) the eager
    per-round loop would — sort: conditional tie-group retire, stop on
    a zero count; knn (``readout=True``): responder reads + re-compare
    + retire, stop when ``budget`` indices have been emitted — and
    folds them through :meth:`APEngine.charge_bulk`.  Returns
    (min_values[r_used], tie_counts[r_used], r_used); values follow
    from the recorded branch decisions (bit i of the round's minimum is
    1 iff the 0-probe at bit i had no responders).
    """
    counts = tr.count.astype(np.int64)
    R = counts.shape[0]
    r_used, out_len, tail = 0, 0, []
    if readout:
        while out_len < budget:
            out_len += min(int(counts[r_used]), budget - out_len)
            tail.append(True)
            r_used += 1
    else:
        while out_len < budget and r_used < R:
            c = int(counts[r_used])
            tail.append(c > 0)
            r_used += 1
            if c == 0:
                break
            out_len += c
    if r_used == 0:
        return np.zeros(0, np.uint64), counts[:0], 0

    Ru = r_used
    n = eng.n_words
    pw = eng.power
    sched = tr.copy_sched
    Pc = sched.n_passes
    take = tr.take[:Ru]                              # [Ru, m] bool
    cnt = counts[:Ru]
    tailp = np.asarray(tail, bool)

    # --- per-round scalar slots after the copy chunk:
    #     [cmp1, cmp2?, wr?] x m, count_cmp, then the tail
    S = 3 * m + (4 if readout else 2)
    present = np.zeros((Ru, S), bool)
    e_scal = np.zeros((Ru, S), np.float64)
    is_trace = np.ones(S, bool)
    c1, c2, wr = (3 * np.arange(m) + d for d in (0, 1, 2))
    present[:, c1] = True
    present[:, c2] = take
    present[:, wr] = take
    ci = 3 * m
    present[:, ci] = True
    if readout:
        rd, rc, rt = ci + 1, ci + 2, ci + 3
        present[:, rd:] = True
        is_trace[rd] = False                         # reads carry no event
    else:
        rt = ci + 1
        present[:, rt] = tailp
    delta = present.astype(np.int64)                 # cycles per slot
    if readout:
        delta[:, rd] = np.where(present[:, rd], cnt, 0)

    m1f = tr.m1[:Ru].astype(np.float64)
    m2f = tr.m2[:Ru].astype(np.float64)
    cf = cnt.astype(np.float64)
    e_scal[:, c1] = 2 * (pw.p_m * m1f + pw.p_mm * (n - m1f))
    e_scal[:, c2] = 2 * (pw.p_m * m2f + pw.p_mm * (n - m2f))
    e_scal[:, wr] = 1 * (pw.p_w * m2f + pw.p_mw * (n - m2f))
    e_scal[:, ci] = 1 * (pw.p_m * cf + pw.p_mm * (n - cf))
    if readout:
        e_scal[:, rc] = 1 * (pw.p_m * cf + pw.p_mm * (n - cf))
    e_scal[:, rt] = 1 * (pw.p_w * cf + pw.p_mw * (n - cf))

    # --- the copy chunk: per-pass energies exactly as charge_run
    kc = sched.kc.astype(np.float64)
    kw = sched.kw.astype(np.float64)
    mf = tr.copy_matched[:Ru].astype(np.float64)     # [Ru, Pc]
    e_pass = kc[None, :] * (pw.p_m * mf + pw.p_mm * (n - mf)) \
        + kw[None, :] * (pw.p_w * mf + pw.p_mw * (n - mf))
    chunk = e_pass.sum(axis=1)    # row-wise: identical to charge_run's 1D sum

    # --- absolute event cycles (post-increment, as eager appends them)
    round_delta = 2 * Pc + delta.sum(axis=1)
    c_start = eng.cycles + np.concatenate(
        [[0], np.cumsum(round_delta)[:-1]]).astype(np.int64)
    pass_cyc = c_start[:, None] + 2 * np.arange(1, Pc + 1, dtype=np.int64)
    scal_cyc = c_start[:, None] + 2 * Pc + np.cumsum(delta, axis=1)

    ev_present = present & is_trace[None, :]
    all_present = np.hstack([np.ones((Ru, Pc), bool), ev_present])
    trace_c = np.hstack([pass_cyc, scal_cyc])[all_present]
    trace_e = np.hstack([e_pass, e_scal])[all_present]
    terms = np.hstack([chunk[:, None], e_scal])[
        np.hstack([np.ones((Ru, 1), bool), ev_present])]

    m1s = tr.m1[:Ru].astype(np.int64)
    m2s = tr.m2[:Ru].astype(np.int64)
    n_cmp = int(present[:, c1].sum() + present[:, c2].sum()
                + present[:, ci].sum()
                + (present[:, rc].sum() if readout else 0))
    n_wr_ev = int(present[:, wr].sum() + present[:, rt].sum())
    match_sc = int(m1s.sum() + m2s[take].sum() + cnt.sum()
                   + (cnt.sum() if readout else 0))
    write_sc = int(m2s[take].sum() + cnt[tailp].sum())
    eng.charge_bulk(
        cycles=int(round_delta.sum()),
        compare_cycles=Pc * Ru + n_cmp,
        write_cycles=Pc * Ru + n_wr_ev,
        read_cycles=int(cnt.sum()) if readout else 0,
        energy_terms=terms, trace_cycles=trace_c, trace_energy=trace_e,
        match=int(mf.sum()) + match_sc,
        mismatch=(Pc * Ru + n_cmp) * n - (int(mf.sum()) + match_sc),
        write=int((kw[None, :] * mf).sum()) + write_sc,
        miswrite=int((kw[None, :] * (n - mf)).sum())
        + (n_wr_ev * n - write_sc))

    weights = np.uint64(1) << (m - 1 - np.arange(m, dtype=np.uint64))
    values = ((~take) * weights[None, :]).sum(axis=1, dtype=np.uint64)
    return values, cnt, r_used


def count_probes_mk(eng: APEngine, cols, keys) -> np.ndarray:
    """Megakernel counterpart of :func:`count_probes`: the whole probe
    batch is ONE op-group launch (CMP ops, padded probes disabled via
    the ``enabled`` mask; sharded over lanes when the engine has
    ``n_shards``), and all compare cycles are charged in one bulk fold.
    """
    cols = np.atleast_2d(np.asarray(cols, np.int32))
    keys = np.atleast_2d(np.asarray(keys, np.uint32))
    n_probes, k = cols.shape
    np2, k2 = _next_pow2(n_probes), _next_pow2(k)

    def pad(a):
        if k2 != k:
            a = np.concatenate(
                [a, np.repeat(a[:, :1], k2 - k, axis=1)], axis=1)
        if np2 != n_probes:
            a = np.concatenate(
                [a, np.repeat(a[-1:], np2 - n_probes, axis=0)], axis=0)
        return a

    group = mk_ref.OpGroup.probes(pad(cols), pad(keys))
    enabled = np.arange(np2) < n_probes
    eng.planes, eng.tag, matched = mk_ops.run_group(
        eng.planes, eng.tag, group, enabled, mesh=eng.mesh)
    counts = np.asarray(jax.device_get(matched))[:n_probes].astype(np.int64)

    cf = counts.astype(np.float64)
    e = k * (eng.power.p_m * cf + eng.power.p_mm * (eng.n_words - cf))
    eng.charge_bulk(
        cycles=n_probes, compare_cycles=n_probes,
        energy_terms=e,
        trace_cycles=eng.cycles + np.arange(1, n_probes + 1, dtype=np.int64),
        trace_energy=e,
        match=int(counts.sum()),
        mismatch=n_probes * eng.n_words - int(counts.sum()))
    return counts
