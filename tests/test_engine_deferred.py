"""Deferred matched-count accounting in ``APEngine``.

``run`` leaves each schedule's per-pass matched counts on the device and
the engine replays them when its accounting is read.  These tests drive
mixed op sequences through an engine whose every charge is mirrored,
the moment it happens, into a test-local ledger that applies the
per-schedule arithmetic eagerly (the counts fetched at once), and
require the engine's counters, event trace and power trace to equal the
ledger's bit for bit.  A second test counts the transfers a program
makes with ``repro.obs``.
"""
import types

import numpy as np
import pytest

from repro import obs
from repro.core import arith, isa
from repro.core.engine import APEngine, bin_energy_trace

N_WORDS = 4096


class _Ledger:
    """Per-schedule accounting applied eagerly, one charge at a time."""

    def __init__(self, n_words, power):
        self.n, self.pw = n_words, power
        self.ctr = dict(cycles=0, compare_cycles=0, write_cycles=0,
                        bwrite_cycles=0, read_cycles=0, energy=0.0,
                        match=0, mismatch=0, write=0, miswrite=0)
        self.trace_cycles, self.trace_energy = [], []

    def scalar(self, kind, k, m):
        c, n, pw = self.ctr, self.n, self.pw
        c["cycles"] += 1
        c[f"{kind}_cycles"] += 1
        if kind == "compare":
            e = k * (pw.p_m * m + pw.p_mm * (n - m))
            c["match"] += m
            c["mismatch"] += n - m
        else:
            e = k * (pw.p_w * m + pw.p_mw * (n - m))
            c["write"] += k * m
            c["miswrite"] += k * (n - m)
        c["energy"] += e
        self.trace_cycles.append(np.array([c["cycles"]], np.int64))
        self.trace_energy.append(np.array([e], np.float64))

    def run(self, sched, matched):
        c, n, pw = self.ctr, self.n, self.pw
        P = sched.n_passes
        c["cycles"] += 2 * P
        c["compare_cycles"] += P
        c["write_cycles"] += P
        m = np.asarray(matched, np.int64)[:P]
        kc = sched.kc.astype(np.float64)
        kw = sched.kw.astype(np.float64)
        mf = m.astype(np.float64)
        e_pass = kc * (pw.p_m * mf + pw.p_mm * (n - mf)) \
            + kw * (pw.p_w * mf + pw.p_mw * (n - mf))
        c["energy"] += float(e_pass.sum())
        self.trace_cycles.append(
            c["cycles"] - 2 * P + 2 * np.arange(1, P + 1, dtype=np.int64))
        self.trace_energy.append(e_pass)
        c["match"] += int(m.sum())
        c["mismatch"] += int(P) * n - int(m.sum())
        c["write"] += int((kw * mf).sum())
        c["miswrite"] += int((kw * (n - mf)).sum())

    def read(self, n_rows):
        self.ctr["cycles"] += n_rows
        self.ctr["read_cycles"] += n_rows

    def bulk(self, *, cycles, compare_cycles, write_cycles, read_cycles,
             energy_terms, trace_cycles, trace_energy, match, mismatch,
             write, miswrite):
        c = self.ctr
        c["cycles"] += cycles
        c["compare_cycles"] += compare_cycles
        c["write_cycles"] += write_cycles
        c["read_cycles"] += read_cycles
        for term in energy_terms:
            c["energy"] += float(term)
        self.trace_cycles.append(np.asarray(trace_cycles, np.int64))
        self.trace_energy.append(np.asarray(trace_energy, np.float64))
        for k, v in dict(match=match, mismatch=mismatch, write=write,
                         miswrite=miswrite).items():
            c[k] += v

    def trace(self):
        if not self.trace_cycles:
            return np.zeros(0, np.int64), np.zeros(0, np.float64)
        return (np.concatenate(self.trace_cycles),
                np.concatenate(self.trace_energy))


class _Mirrored(APEngine):
    """An engine that also feeds every charge to a `_Ledger` at once."""

    def reset_counters(self):
        super().reset_counters()
        self.ledger = _Ledger(self.n_words, self.power)

    def bwrite(self, cols, key):
        super().bwrite(cols, key)
        self.ledger.scalar("bwrite", len(cols), self.n_words)

    def charge_compare(self, k, matched):
        super().charge_compare(k, matched)
        self.ledger.scalar("compare", int(k), int(matched))

    def charge_write(self, k, matched):
        super().charge_write(k, matched)
        self.ledger.scalar("write", int(k), int(matched))

    def charge_read(self, n_rows):
        super().charge_read(n_rows)
        self.ledger.read(int(n_rows))

    def charge_run(self, sched, matched):
        super().charge_run(sched, matched)
        self.ledger.run(sched, np.asarray(matched))

    def charge_bulk(self, **kw):
        super().charge_bulk(**kw)
        self.ledger.bulk(**kw)


def _fields(eng):
    al = eng.alloc
    f = types.SimpleNamespace(a=al.alloc(8, "a"), b=al.alloc(8, "b"),
                              d=al.alloc(8, "d"), carry=al.alloc(1, "c"))
    g = np.random.default_rng(7)
    for field in (f.a, f.b, f.d):
        eng.load(field, g.integers(0, 256, N_WORDS, dtype=np.uint64))
    return f


def _add(eng, f):
    eng.clear(f.carry)
    eng.run(isa.add(f.a, f.b, f.carry))


def _runs_with_bwrites(eng, f, seen):
    _add(eng, f)
    eng.set_bits(f.d, 0x5A)
    eng.run(isa.copy(f.d, f.b))
    eng.clear(f.carry)
    eng.run(isa.sub(f.a, f.d, f.carry))
    eng.clear(f.d)


def _eager_between_runs(eng, f, seen):
    _add(eng, f)
    eng.compare([f.a.col(0), f.a.col(3)], [1, 0])
    eng.write([f.d.col(1)], [1])
    eng.run(isa.copy(f.d, f.a))
    eng.compare([f.d.col(2)], [1], restrict_to_tag=True)
    _add(eng, f)


def _bulk_after_runs(eng, f, seen):
    _add(eng, f)
    eng.run(isa.copy(f.d, f.b))
    c = eng.cycles
    eng.charge_bulk(cycles=5, compare_cycles=2, write_cycles=2,
                    read_cycles=1, energy_terms=[1.5, 2.25e3, 1e-3],
                    trace_cycles=[c + 1, c + 2, c + 4],
                    trace_energy=[1.5, 2.25e3, 1e-3],
                    match=3, mismatch=N_WORDS - 3, write=2, miswrite=9)
    _add(eng, f)


def _read_tagged(eng, f, seen):
    _add(eng, f)
    eng.load_tag_column(f.a.col(0))
    eng.run(isa.copy(f.d, f.b))
    eng.read_tagged(f.d)
    eng.clear(f.carry)
    eng.run(isa.sub(f.a, f.b, f.carry))
    eng.read(f.a)


def _reset_with_runs_pending(eng, f, seen):
    _add(eng, f)
    eng.run(isa.copy(f.d, f.b))
    eng.reset_counters()
    eng.run(isa.sub(f.a, f.b, f.carry))
    _add(eng, f)


def _reads_mid_program(eng, f, seen):
    _add(eng, f)
    seen.append((eng.energy, eng.ledger.ctr["energy"]))
    eng.run(isa.copy(f.d, f.a))
    eng.clear(f.carry)
    seen.append((dict(eng.events),
                 {k: eng.ledger.ctr[k]
                  for k in ("match", "mismatch", "write", "miswrite")}))
    eng.run(isa.sub(f.d, f.b, f.carry))
    seen.append((eng.energy_uJ(),
                 eng.ledger.ctr["energy"] * eng.power.p_sram_cell_uW * 1e-3))
    _add(eng, f)


@pytest.mark.parametrize("sequence", [
    _runs_with_bwrites, _eager_between_runs, _bulk_after_runs,
    _read_tagged, _reset_with_runs_pending, _reads_mid_program,
], ids=lambda fn: fn.__name__.lstrip("_"))
def test_deferred_accounting_matches_eager_replay(sequence):
    eng = _Mirrored(n_words=N_WORDS, n_bits=64)
    f = _fields(eng)
    seen = []
    sequence(eng, f, seen)
    for got, want in seen:
        assert got == want
    assert eng.counters() == eng.ledger.ctr
    cyc, e = eng.trace_events()
    want_cyc, want_e = eng.ledger.trace()
    np.testing.assert_array_equal(cyc, want_cyc)
    np.testing.assert_array_equal(e, want_e)
    interval, bins = eng.power_trace(64)
    want_interval, want_bins = bin_energy_trace(
        want_cyc, want_e, eng.ledger.ctr["cycles"], 64)
    assert interval == want_interval
    np.testing.assert_array_equal(bins, want_bins)


def _mul16(eng, al):
    a, b = al.alloc(16, "a"), al.alloc(16, "b")
    prod, carry = al.alloc(32, "prod"), al.alloc(1, "carry")
    arith.run_mul(eng, a, b, prod, carry)
    return prod


def _add16(eng, al):
    a, b, carry = al.alloc(16, "a"), al.alloc(16, "b"), al.alloc(1, "carry")
    isa.run_add(eng, a, b, carry)
    return b


@pytest.mark.parametrize("program, schedules", [(_mul16, 16), (_add16, 1)],
                         ids=["mul16", "add16"])
def test_one_transfer_per_program(program, schedules):
    eng = APEngine(n_words=N_WORDS, n_bits=128)
    with obs.scoped():
        deferred = obs.value("engine/matched/deferred")
        fetched = obs.value("engine/matched/fetch")
        result = program(eng, eng.alloc)
        assert obs.value("engine/matched/fetch") == fetched
        eng.read(result)
        eng.counters()
        assert obs.value("engine/matched/deferred") - deferred == schedules
        assert obs.value("engine/matched/fetch") - fetched <= 1
