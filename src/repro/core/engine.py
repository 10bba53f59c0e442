"""The Associative Processor machine model.

Implements the three silicon operations of the paper's AP (§2.1):

* COMPARE  — key/mask match against all rows, result into TAG (1 cycle)
* WRITE    — parallel write of key into masked columns of all TAGGED rows (1 cycle)
* BWRITE   — broadcast write into masked columns of ALL rows (1 cycle)

plus sequential row read (1 cycle / row, §2.1).

A *pass* = COMPARE cycle followed by WRITE cycle (paper Table 1 footnote).
Arithmetic routines (isa.py / arith.py / apfloat.py) compile to *pass
schedules* — static tables of (compare cols/key, write cols/key) — which this
engine executes in one fused `lax.scan`.

Bookkeeping (exact, not statistical):

* cycles     — host-side Python ints; the pass count is static so this is exact.
* energy     — per-pass matched-row counts are measured on device and folded
               into the paper's per-event energies (Table 3):
               E_cmp  = k_cmp * (p_m * matched + p_mm * (n - matched))
               E_wr   = k_wr  * (1.0 * matched + p_mw * (n - matched))
               normalized to one SRAM-cell write = 1 (§3.2, eq 16).
  This generalizes eq (16): with the adder's 1/8 match probability the
  expectation of our measured count equals the paper's closed form — tested in
  tests/test_paper_models.py.

Cycle counters update at once.  A schedule's matched counts stay on the
device: ``run`` logs them, and the energy, event and trace accounting
that needs them (with every accounting step queued behind them, so the
float64 sums keep their order) is replayed on the host when the
accounting is next read — ``counters()``, ``energy``, ``events``,
``trace_events()``, ``power_trace()``, ``energy_uJ()`` — or extended
eagerly, or when ``read`` fetches a field, whose transfer carries them.
So a program of many schedules crosses to the host once, at its result.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bitplane as bp
from repro.core.bitplane import Field, FieldAllocator


def bin_energy_trace(cycles: np.ndarray, energy: np.ndarray,
                     total_cycles: int, n_intervals: int
                     ) -> tuple[float, np.ndarray]:
    """Bin (cycle, energy) events into equal windows over [0, total_cycles].

    ``cycles`` holds 1-based completion cycles.  Energy-conserving: the
    returned bins sum to ``energy.sum()`` exactly.  Shared by
    :meth:`APEngine.power_trace` and ``cosim.trace_from_counters``.
    """
    interval = max(int(total_cycles), 1) / n_intervals
    bins = np.zeros(n_intervals, np.float64)
    cycles = np.asarray(cycles, np.int64)
    if cycles.size:
        idx = np.minimum(((cycles - 1) / interval).astype(np.int64),
                         n_intervals - 1)
        np.add.at(bins, idx, np.asarray(energy, np.float64))
    return interval, bins


@dataclasses.dataclass(frozen=True)
class PowerParams:
    """Table 3 of the paper (normalized to SRAM-cell write power = 1)."""
    p_sram_cell_uW: float = 0.5   # absolute anchor: 1 unit = 0.5 uW
    p_m: float = 0.1              # per-bit energy, matched row, compare
    p_mm: float = 0.75            # per-bit energy, mismatched row (line discharge)
    p_mw: float = 0.1             # per-bit energy, miswrite (untagged row)
    p_w: float = 1.0              # per-bit energy, true write (the unit)


PAPER_POWER = PowerParams()


@dataclasses.dataclass
class PassSchedule:
    """A static table of AP passes (compare + tagged write per row).

    Columns are padded (by repetition) to the table-wide max K; ``kc``/``kw``
    keep the true active-column counts for energy accounting.
    """
    cmp_cols: np.ndarray   # int32 [P, Kc]
    cmp_key: np.ndarray    # uint32 [P, Kc]
    w_cols: np.ndarray     # int32 [P, Kw]
    w_key: np.ndarray      # uint32 [P, Kw]
    kc: np.ndarray         # int32 [P]  true compare-column counts
    kw: np.ndarray         # int32 [P]  true write-column counts

    @property
    def n_passes(self) -> int:
        return int(self.cmp_cols.shape[0])

    @staticmethod
    def build(passes: Sequence[tuple[Sequence[int], Sequence[int],
                                     Sequence[int], Sequence[int]]]
              ) -> "PassSchedule":
        """passes: list of (cmp_cols, cmp_key, w_cols, w_key) per pass."""
        if not passes:
            raise ValueError("empty pass schedule")
        kc = np.array([len(p[0]) for p in passes], np.int32)
        kw = np.array([len(p[2]) for p in passes], np.int32)
        Kc, Kw = int(kc.max()), int(kw.max())

        def pad(vals, K):
            vals = list(vals)
            return vals + [vals[0]] * (K - len(vals))

        cc = np.array([pad(p[0], Kc) for p in passes], np.int32)
        ck = np.array([pad(p[1], Kc) for p in passes], np.uint32)
        wc = np.array([pad(p[2], Kw) for p in passes], np.int32)
        wk = np.array([pad(p[3], Kw) for p in passes], np.uint32)
        return PassSchedule(cc, ck, wc, wk, kc, kw)

    @staticmethod
    def concat(schedules: Sequence["PassSchedule"]) -> "PassSchedule":
        if not schedules:
            raise ValueError("empty schedule list")
        Kc = max(s.cmp_cols.shape[1] for s in schedules)
        Kw = max(s.w_cols.shape[1] for s in schedules)

        def padcat(arrs, K):
            out = []
            for a in arrs:
                if a.shape[1] < K:
                    a = np.concatenate(
                        [a, np.repeat(a[:, :1], K - a.shape[1], axis=1)], axis=1)
                out.append(a)
            return np.concatenate(out, axis=0)

        return PassSchedule(
            padcat([s.cmp_cols for s in schedules], Kc),
            padcat([s.cmp_key for s in schedules], Kc),
            padcat([s.w_cols for s in schedules], Kw),
            padcat([s.w_key for s in schedules], Kw),
            np.concatenate([s.kc for s in schedules]),
            np.concatenate([s.kw for s in schedules]),
        )


# ---------------------------------------------------------------------------
# functional core: APState + pure ops.  Device-resident workload programs
# (workloads/_device.py) thread an APState through lax.scan / lax.while_loop
# bodies so entire data-dependent inner loops run as ONE compiled program —
# per-pass matched counts ride along as scan outputs and cross to the host
# exactly once per workload phase (``APEngine.run``'s counts cross once per
# program: at its ``read``, or at the first accounting read).
# ---------------------------------------------------------------------------

#: APState.counters layout (int32): on-device totals mirroring the host
#: counters an eager replay would accumulate (match = matched-row compare
#: events).  Cross-checked against the host accounting in
#: tests/test_device_workloads.py.
CTR_CYCLES, CTR_COMPARE, CTR_WRITE, CTR_READ, CTR_MATCH = range(5)
N_COUNTERS = 5


@partial(jax.tree_util.register_dataclass,
         data_fields=("planes", "tag", "counters"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class APState:
    """Functional snapshot of one AP array: a pytree that scans/vmaps.

    ``counters`` is a packed int32[N_COUNTERS] accumulator updated on
    device by the ``state_*`` ops, so a device-resident program carries
    its cycle/event totals with it instead of syncing per cycle.
    """
    planes: jax.Array       # uint32[n_bits, n_lanes]
    tag: jax.Array          # uint32[n_lanes]
    counters: jax.Array     # int32[N_COUNTERS]


def state_init(n_bits: int, n_words: int) -> APState:
    return APState(bp.alloc_planes(n_bits, n_words),
                   jnp.zeros(bp.n_lanes(n_words), jnp.uint32),
                   jnp.zeros(N_COUNTERS, jnp.int32))


def select_state(pred, a: APState, b: APState) -> APState:
    """``a`` where pred else ``b`` — masks a whole op inside a scan body
    (the device-program version of an eager host-side branch)."""
    return jax.tree_util.tree_map(partial(jnp.where, pred), a, b)


def state_compare(state: APState, cols, key,
                  restrict_to_tag: bool = False) -> tuple[APState, jax.Array]:
    """COMPARE: one cycle; returns (state', matched responder count)."""
    tag = bp.compare(state.planes, cols, key,
                     state.tag if restrict_to_tag else None)
    matched = bp.popcount(tag)
    ctr = state.counters.at[CTR_CYCLES].add(1).at[CTR_COMPARE].add(1) \
        .at[CTR_MATCH].add(matched)
    return APState(state.planes, tag, ctr), matched


def state_write(state: APState, cols, key) -> tuple[APState, jax.Array]:
    """WRITE into tagged rows: one cycle; returns (state', matched)."""
    planes = bp.tagged_write(state.planes, state.tag, cols, key)
    matched = bp.popcount(state.tag)
    ctr = state.counters.at[CTR_CYCLES].add(1).at[CTR_WRITE].add(1)
    return APState(planes, state.tag, ctr), matched


def state_read_charge(state: APState, n_rows) -> APState:
    """Charge ``n_rows`` sequential read cycles (read_tagged on device:
    the data itself is already host-resident or rides the final ys)."""
    ctr = state.counters.at[CTR_CYCLES].add(n_rows).at[CTR_READ].add(n_rows)
    return APState(state.planes, state.tag, ctr)


def state_run(state: APState, cmp_cols, cmp_key, w_cols,
              w_key) -> tuple[APState, jax.Array]:
    """Run a static pass table functionally; returns (state', matched[P]).

    Mirrors :meth:`APEngine.run`: the TAG register is left untouched
    (the fused scan keeps its per-pass tags internal).
    """
    planes, matched = _run_schedule_body(state.planes, cmp_cols, cmp_key,
                                         w_cols, w_key)
    P = cmp_cols.shape[0]
    ctr = state.counters.at[CTR_CYCLES].add(2 * P).at[CTR_COMPARE].add(P) \
        .at[CTR_WRITE].add(P).at[CTR_MATCH].add(matched.sum())
    return APState(planes, state.tag, ctr), matched


def _run_schedule_body(planes, cmp_cols, cmp_key, w_cols, w_key):
    def body(planes, xs):
        cc, ck, wc, wk = xs
        tag = bp.compare(planes, cc, ck)
        matched = jax.lax.population_count(tag).astype(jnp.int32).sum()
        planes = bp.tagged_write(planes, tag, wc, wk)
        return planes, matched

    return jax.lax.scan(body, planes, (cmp_cols, cmp_key, w_cols, w_key))


@partial(jax.jit, donate_argnums=(0,))
def _run_schedule(planes: jax.Array, cmp_cols, cmp_key, w_cols, w_key):
    """Execute a pass schedule; returns planes and per-pass matched counts.

    The ``obs`` counters increment at TRACE time only — one per compiled
    shape bucket, never per execution — so ``engine/retrace/run_schedule``
    counts distinct compiles (the compiles-once test pins a bucket hit
    against it; per-bucket variants carry the ``[P=..,Kc=..,Kw=..]``
    label suffix)."""
    obs.count("engine/retrace/run_schedule")
    obs.count(f"engine/retrace/run_schedule[P={cmp_cols.shape[0]},"
              f"Kc={cmp_cols.shape[1]},Kw={w_cols.shape[1]}]")
    return _run_schedule_body(planes, cmp_cols, cmp_key, w_cols, w_key)


def _next_pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


#: jitted broadcast write — the un-jitted scatter dispatch costs ~1 ms
#: per call on CPU, which dominated field clears between fused schedules
@jax.jit
def _broadcast_write_jit(planes, cols, key):
    obs.count("engine/retrace/bwrite")
    return bp.broadcast_write(planes, cols, key)


def bucket_schedule(sched: "PassSchedule"
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad a schedule's (P, Kc, Kw) to power-of-two buckets so nearby
    schedule shapes share one compiled program instead of retracing.

    Extra key columns repeat column 0 — idempotent for both compare
    (re-ANDing an identical XNOR term) and write (re-storing the same
    value).  Extra passes are no-ops: compare column 0 against key 0,
    then write 0 back into column 0 of the rows that matched — the
    planes are unchanged whatever they hold.  Padded passes' matched
    counts are sliced off before accounting, so they contribute zero
    energy and zero events.
    """
    cc, ck, wc, wk = sched.cmp_cols, sched.cmp_key, sched.w_cols, sched.w_key
    P, Kc = cc.shape
    Kw = wc.shape[1]
    if P == 0:
        raise ValueError(
            "empty pass schedule (P=0): nothing to bucket — build "
            "schedules via PassSchedule.build, which rejects empty input")
    Kc2, Kw2, P2 = _next_pow2(Kc), _next_pow2(Kw), _next_pow2(P)

    def pad_cols(a, K2):
        if a.shape[1] == K2:
            return a
        return np.concatenate(
            [a, np.repeat(a[:, :1], K2 - a.shape[1], axis=1)], axis=1)

    cc, ck = pad_cols(cc, Kc2), pad_cols(ck, Kc2)
    wc, wk = pad_cols(wc, Kw2), pad_cols(wk, Kw2)
    if P2 != P:
        cc = np.concatenate([cc, np.zeros((P2 - P, Kc2), cc.dtype)])
        ck = np.concatenate([ck, np.zeros((P2 - P, Kc2), ck.dtype)])
        wc = np.concatenate([wc, np.zeros((P2 - P, Kw2), wc.dtype)])
        wk = np.concatenate([wk, np.zeros((P2 - P, Kw2), wk.dtype)])
    return cc, ck, wc, wk


class APEngine:
    """One Associative Processing array: n_words PUs x n_bits columns."""

    BACKENDS = ("jnp", "pallas", "megakernel", "megakernel_pallas")

    def __init__(self, n_words: int, n_bits: int = 256,
                 power: PowerParams = PAPER_POWER, collect_stats: bool = True,
                 backend: str = "jnp", n_shards: int | None = None):
        if backend not in self.BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        if n_shards is not None:
            if backend != "megakernel":
                raise ValueError(
                    "n_shards requires backend='megakernel' (lane sharding "
                    "is a megakernel execution mode)")
            if bp.n_lanes(n_words) % n_shards != 0:
                raise ValueError(
                    f"n_lanes={bp.n_lanes(n_words)} not divisible by "
                    f"n_shards={n_shards}; pick n_words a multiple of "
                    f"{bp.LANE * n_shards}")
        self.n_words = n_words
        self.n_bits = n_bits
        self.power = power
        self.collect_stats = collect_stats
        self.backend = backend
        self.n_shards = n_shards
        self.planes = bp.alloc_planes(n_bits, n_words)
        self.tag = jnp.zeros(bp.n_lanes(n_words), jnp.uint32)
        self.alloc = FieldAllocator(n_bits)
        self.reset_counters()

    @property
    def mesh(self):
        """The 1D 'lanes' device mesh when sharded, else None (cached
        per shard count so jitted sharded runners are reused)."""
        if self.n_shards is None:
            return None
        from repro.parallel.sharding import ap_mesh
        return ap_mesh(self.n_shards)

    # ----------------------------------------------------------------- state
    def reset_counters(self):
        self.cycles = 0
        self.compare_cycles = 0
        self.write_cycles = 0
        self.bwrite_cycles = 0
        self.read_cycles = 0
        self._energy = 0.0            # normalized (SRAM write = 1)
        self._events = {"match": 0, "mismatch": 0, "write": 0, "miswrite": 0}
        # power trace: per accounted event, the cycle it completed on and its
        # energy (exact same accounting as `energy` — binned by cosim.py)
        self._trace_cycles: list = []     # ints or int64 arrays
        self._trace_energy: list = []     # floats or float64 arrays
        # deferred accounting, replayed in order by _flush: (cycle, sched,
        # matched) per run, (cycle, None, k) per broadcast write
        self._pending: list = []

    @property
    def energy(self) -> float:
        """Accounted energy, normalized (SRAM write = 1)."""
        self._flush()
        return self._energy

    @property
    def events(self) -> dict:
        """Accounted match / mismatch / write / miswrite bit events."""
        self._flush()
        return self._events

    def counters(self) -> dict:
        out = dict(cycles=self.cycles, compare_cycles=self.compare_cycles,
                   write_cycles=self.write_cycles, bwrite_cycles=self.bwrite_cycles,
                   read_cycles=self.read_cycles, energy=self.energy)
        out.update(self._events)
        return out

    # ------------------------------------------------------------- data I/O
    def load(self, field: Field, values) -> None:
        """Host-side load of per-word integer values into a field (not an AP op)."""
        if field.width > 64:
            raise ValueError(
                f"cannot load a {field.width}-bit field from uint64 host "
                f"words (max 64); split the value across fields")
        vals = np.asarray(values, np.uint64)
        if vals.shape != (self.n_words,):
            raise ValueError(f"expected ({self.n_words},), got {vals.shape}")
        with obs.span("engine/load", width=field.width):
            with obs.span("engine/pack"):
                self.planes = bp.load_words(self.planes, vals, field.start,
                                            field.width)

    def read(self, field: Field, signed: bool = False) -> np.ndarray:
        """Host-side readback of a field for all words (charges n read cycles)."""
        with obs.span("engine/read", width=field.width):
            self.charge_read(self.n_words)
            halves = self._flush(bp.field_words(self.planes, field.start,
                                                field.width), "sync/read")
            with obs.span("engine/unpack"):
                vals = bp.widen_words(*halves)
        if signed and field.width < 64:
            sign = vals >> (field.width - 1)
            vals = vals.astype(np.int64) - (sign.astype(np.int64) << field.width)
        return vals

    def peek(self, field: Field) -> np.ndarray:
        """Readback WITHOUT charging cycles (debug / test oracle only)."""
        return bp.widen_words(*jax.device_get(
            bp.field_words(self.planes, field.start, field.width)))

    def read_tagged(self, field: Field) -> tuple[np.ndarray, np.ndarray]:
        """Sequential readout of ``field`` for the currently TAGGED rows.

        Charges 1 read cycle per tagged row (§2.1) — the associative
        "read responders" loop.  Returns (row_indices, values), both
        host numpy, ordered by row index.
        """
        rows = np.where(np.asarray(bp.unpack_bits(self.tag)))[0]
        self.charge_read(len(rows))
        return rows, self.peek(field)[rows]

    # ------------------------------------------------------ silicon ops
    def compare(self, cols: Sequence[int], key: Sequence[int],
                restrict_to_tag: bool = False) -> None:
        """COMPARE: one cycle; TAG <- match(key @ cols) [& TAG].

        Eager (per-cycle host sync when stats are on) — the oracle path.
        Data-dependent inner loops should run device-resident instead
        (``workloads/_device.py``) and replay through ``charge_*``.
        """
        tag_in = self.tag if restrict_to_tag else None
        self.tag = bp.compare(self.planes, jnp.asarray(cols, jnp.int32),
                              jnp.asarray(key, jnp.uint32), tag_in)
        matched = int(bp.popcount(self.tag)) if self.collect_stats else 0
        self.charge_compare(len(cols), matched)

    def write(self, cols: Sequence[int], key: Sequence[int]) -> None:
        """WRITE: one cycle; key -> masked cols of all TAGGED rows."""
        self.planes = bp.tagged_write(self.planes, self.tag,
                                      jnp.asarray(cols, jnp.int32),
                                      jnp.asarray(key, jnp.uint32))
        matched = int(bp.popcount(self.tag)) if self.collect_stats else 0
        self.charge_write(len(cols), matched)

    def bwrite(self, cols: Sequence[int], key: Sequence[int]) -> None:
        """Broadcast write (all rows): one cycle."""
        with obs.span("engine/bwrite", width=len(cols)):
            self.planes = _broadcast_write_jit(
                self.planes, jnp.asarray(cols, jnp.int32),
                jnp.asarray(key, jnp.uint32))
        self.cycles += 1
        self.bwrite_cycles += 1
        if self.collect_stats:
            self._pending.append((self.cycles, None, len(cols)))

    # ----------------------------------------- accounting without executing
    # Device-resident programs compute per-pass matched counts on device,
    # transfer them ONCE per workload phase, and replay them through these
    # chargers — producing cycle/energy/event/trace accounting bit-identical
    # to the eager per-cycle path (tests/test_device_workloads.py).
    # ``charge_run`` defers: it logs the counts where they are (on device
    # after ``run``) and ``_flush`` fetches every logged array in one
    # transfer when the accounting is read or extended eagerly.

    def charge_compare(self, k: int, matched: int) -> None:
        """Account one COMPARE cycle (k active columns, matched rows)."""
        self._flush()
        self.cycles += 1
        self.compare_cycles += 1
        if self.collect_stats:
            self._account_compare(int(k), int(matched), self.cycles)

    def charge_write(self, k: int, matched: int) -> None:
        """Account one tagged-WRITE cycle (k active columns, matched rows)."""
        self._flush()
        self.cycles += 1
        self.write_cycles += 1
        if self.collect_stats:
            self._account_write(int(k), int(matched), self.cycles)

    def charge_read(self, n_rows: int) -> None:
        """Account ``n_rows`` sequential read cycles (1 cycle/row, §2.1)."""
        self.read_cycles += int(n_rows)
        self.cycles += int(n_rows)

    def charge_run(self, sched: PassSchedule, matched) -> None:
        """Account a full pass schedule from its per-pass matched counts.

        ``matched`` (host or device) holds at least ``n_passes`` counts;
        a bucketed run's padded tail is ignored.  Cycles count at once;
        the rest waits in the log for :meth:`_flush`.
        """
        P = sched.n_passes
        self.cycles += 2 * P           # each pass = compare + write
        self.compare_cycles += P
        self.write_cycles += P
        if self.collect_stats:
            obs.count("engine/matched/deferred")
            self._pending.append((self.cycles, sched, matched))

    def _flush(self, also=None, sync: str = "sync/matched"):
        """Replay the deferred accounting log, in order.

        Every logged device array crosses in ONE ``jax.device_get``,
        which also carries ``also`` (a pytree of device arrays, returned
        on the host) so ``read`` ends a program with one transfer.
        """
        if not self._pending and also is None:
            return None
        pending, self._pending = self._pending, []
        counts = [m for _, sched, m in pending if sched is not None]
        on_device = any(isinstance(m, jax.Array) for m in counts)
        with obs.span("engine/charge", schedules=len(counts)):
            if on_device or also is not None:
                if on_device:
                    obs.count("engine/matched/fetch")
                with obs.span(sync):
                    counts, also = jax.device_get((counts, also))
            counts = iter(counts)
            for cycle, sched, k in pending:
                if sched is None:
                    self._account_write(k, self.n_words, cycle)
                else:
                    self._account_run(sched, next(counts), cycle)
        return also

    def charge_bulk(self, *, cycles: int = 0, compare_cycles: int = 0,
                    write_cycles: int = 0, read_cycles: int = 0,
                    energy_terms=None, trace_cycles=None, trace_energy=None,
                    match: int = 0, mismatch: int = 0, write: int = 0,
                    miswrite: int = 0) -> None:
        """Fold a precomputed bulk replay block into the accounting.

        The vectorized counterpart of a ``charge_*`` call sequence
        (megakernel replay uses it to retire thousands of events in one
        call).  Bit-identity contract the callers uphold and the
        property harness enforces:

        * ``energy_terms`` (float64[n]) lists the scalar values the
          equivalent charge sequence would have added to ``energy``, in
          order — one term per scalar event, one PRE-SUMMED term per
          ``charge_run`` chunk (``np.sum`` is pairwise, so chunk sums
          must be taken per chunk, never globally).  The fold here is a
          seeded ``np.cumsum``, which accumulates float64 strictly
          sequentially — identical to the scalar ``+=`` loop.
        * ``trace_cycles``/``trace_energy`` are the absolute-cycle /
          per-event energy arrays in eager append order; they land as
          ONE trace chunk, which concatenates to the same flat arrays.
        * counter/event deltas are exact ints.
        """
        self._flush()
        self.cycles += int(cycles)
        self.compare_cycles += int(compare_cycles)
        self.write_cycles += int(write_cycles)
        self.read_cycles += int(read_cycles)
        if not self.collect_stats:
            return
        if energy_terms is not None and len(energy_terms):
            self._energy = float(np.cumsum(np.concatenate(
                [[self._energy], np.asarray(energy_terms, np.float64)]))[-1])
        if trace_cycles is not None and len(trace_cycles):
            self._trace_cycles.append(np.asarray(trace_cycles, np.int64))
            self._trace_energy.append(np.asarray(trace_energy, np.float64))
        self._events["match"] += int(match)
        self._events["mismatch"] += int(mismatch)
        self._events["write"] += int(write)
        self._events["miswrite"] += int(miswrite)

    def clear(self, field: Field) -> None:
        self.bwrite(field.cols(), [0] * field.width)

    def set_bits(self, field: Field, value: int) -> None:
        """Broadcast an immediate constant into a field (1 cycle)."""
        key = [(value >> i) & 1 for i in range(field.width)]
        self.bwrite(field.cols(), key)

    def load_tag_column(self, col: int) -> None:
        """TAG <- column ``col`` (a 1-column compare against key=1)."""
        self.compare([col], [1])

    def tag_count(self) -> int:
        return int(bp.popcount(self.tag))

    # ------------------------------------------------------ fused schedules
    def run(self, sched: PassSchedule) -> None:
        """Execute a static pass schedule as one fused scan on device.

        The schedule shape is padded to a power-of-two bucket
        (:func:`bucket_schedule`) so two schedules of nearby shapes share
        one compiled program; the padded no-op passes' matched counts are
        sliced off before accounting.
        """
        P = sched.n_passes
        with obs.span("engine/run", passes=P, backend=self.backend):
            cc, ck, wc, wk = bucket_schedule(sched)
            if self.backend == "pallas":
                from repro.kernels.ap_match import ops as _ap_ops
                self.planes, matched = _ap_ops.run_schedule(
                    self.planes, cc, ck, wc, wk, backend="pallas")
            elif self.backend in ("megakernel", "megakernel_pallas"):
                from repro.kernels.ap_megakernel import OpGroup, ops as _mk_ops
                mk_backend = ("pallas" if self.backend == "megakernel_pallas"
                              else "jnp")
                self.planes, self.tag, matched = _mk_ops.run_group(
                    self.planes, self.tag,
                    OpGroup.from_schedule(cc, ck, wc, wk),
                    backend=mk_backend, mesh=self.mesh)
            else:
                self.planes, matched = _run_schedule(
                    self.planes, jnp.asarray(cc), jnp.asarray(ck),
                    jnp.asarray(wc), jnp.asarray(wk))
        self.charge_run(sched, matched)

    # -------------------------------------------------- functional bridge
    def state(self) -> APState:
        """Snapshot (planes, tag, zeroed counters) for a device program."""
        return APState(self.planes, self.tag,
                       jnp.zeros(N_COUNTERS, jnp.int32))

    def adopt(self, state: APState) -> None:
        """Adopt a device program's final array state.

        Counters are NOT folded in: the caller replays its per-pass
        matched counts through the ``charge_*`` methods so energy/event/
        trace accounting stays event-exact (the device-side
        ``state.counters`` exist to cross-check those replays).
        """
        self.planes = state.planes
        self.tag = state.tag

    # ------------------------------------------------------ energy helpers
    # ``cycle`` is the cycle the event completed on (the trace's x).
    def _account_compare(self, k: int, matched: int, cycle: int) -> None:
        n = self.n_words
        pw = self.power
        e = k * (pw.p_m * matched + pw.p_mm * (n - matched))
        self._energy += e
        self._trace_cycles.append(cycle)
        self._trace_energy.append(e)
        self._events["match"] += matched
        self._events["mismatch"] += n - matched

    def _account_write(self, k: int, matched: int, cycle: int) -> None:
        n = self.n_words
        pw = self.power
        e = k * (pw.p_w * matched + pw.p_mw * (n - matched))
        self._energy += e
        self._trace_cycles.append(cycle)
        self._trace_energy.append(e)
        self._events["write"] += k * matched
        self._events["miswrite"] += k * (n - matched)

    def _account_run(self, sched: PassSchedule, matched, cycle: int) -> None:
        """A schedule's passes, ending on ``cycle``, from host counts."""
        P = sched.n_passes
        m = np.asarray(matched, np.int64)[:P]
        n = self.n_words
        kc = sched.kc.astype(np.float64)
        kw = sched.kw.astype(np.float64)
        mf = m.astype(np.float64)
        pw = self.power
        e_pass = kc * (pw.p_m * mf + pw.p_mm * (n - mf)) \
            + kw * (pw.p_w * mf + pw.p_mw * (n - mf))
        self._energy += float(e_pass.sum())
        self._trace_cycles.append(
            cycle - 2 * P + 2 * np.arange(1, P + 1, dtype=np.int64))
        self._trace_energy.append(e_pass)
        self._events["match"] += int(m.sum())
        self._events["mismatch"] += int(P) * n - int(m.sum())
        self._events["write"] += int((kw * mf).sum())
        self._events["miswrite"] += int((kw * (n - mf)).sum())

    # ------------------------------------------------------ power trace
    def trace_events(self) -> tuple[np.ndarray, np.ndarray]:
        """All accounted energy events so far: (cycle, energy) arrays.

        ``cycle`` is the 1-based cycle each event completed on; ``energy``
        is normalized (SRAM write = 1) and sums exactly to ``self.energy``.
        Cycle spans with no events (host loads, sequential reads) simply
        contribute zero-energy intervals when binned.
        """
        self._flush()
        if not self._trace_cycles:
            return (np.zeros(0, np.int64), np.zeros(0, np.float64))
        cyc = np.concatenate([np.atleast_1d(np.asarray(c, np.int64))
                              for c in self._trace_cycles])
        e = np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                            for v in self._trace_energy])
        return cyc, e

    def power_trace(self, n_intervals: int) -> tuple[float, np.ndarray]:
        """Bin the event trace into ``n_intervals`` equal cycle windows.

        Returns (interval_cycles, energy_per_interval[n_intervals]); the
        bins cover [0, self.cycles] and conserve total energy exactly.
        """
        cyc, e = self.trace_events()
        return bin_energy_trace(cyc, e, self.cycles, n_intervals)

    # ------------------------------------------------------ reporting
    def energy_uJ(self) -> float:
        """Absolute energy in microjoules, using the Table 3 SRAM anchor.

        1 normalized unit = P_sram-cell * 1 cycle.  With the paper's ~0.5 uW
        at ~1 GHz-class operation this is ~0.5 fJ/bit-event; we report
        energy = events * 0.5e-9 uJ (documented anchor, used consistently).
        """
        return self.energy * self.power.p_sram_cell_uW * 1e-3  # 1 ns cycles
