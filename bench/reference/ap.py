"""Plain reference for word-parallel AP arithmetic and its accounting.

Imports nothing of the program.  The result of each program is plain
integer arithmetic on the words.  Cycles, events and energy follow from
the paper's algorithms (section 2.2, Table 1): a bit of an in-place add
b,c <- a + b + c is four compare/write passes, one per input pattern
(c, b, a) in {011, 100, 110, 001} that the write changes; a pass first
matches the rows holding its pattern, then writes the tagged rows.  In
the order Table 1 gives, no pass writes a pattern a later pass of that
bit matches, so each pass matches exactly the rows that held its pattern
before the bit began: the counts come from the carries of the sum.  A
conditional add (the multiplier bit as an extra compare column) matches
only rows whose condition bit is 1; a carry ripple through a bit with no
addend is two passes, (c, b) in {10, 11}.  A broadcast clear of k
columns is one cycle and k true writes on every row.  Reading a field
back is one cycle per row.

Energy per pass (Table 3, normalised to one SRAM-cell write):
    kc * (p_m * matched + p_mm * (n - matched))
  + kw * (p_w * matched + p_mw * (n - matched))
with kc compare and kw write columns.
"""
from __future__ import annotations

import numpy as np

#: programs: (operand widths a, b, result width, result is a + b,
#:            acc + a*b or a*b)
PROGRAMS = {"add16": (16, 16, 16), "mac8": (8, 8, 16), "mul16": (16, 16, 32)}
INT_COUNTERS = ("cycles", "compare_cycles", "write_cycles", "bwrite_cycles",
                "read_cycles", "match", "mismatch", "write", "miswrite")


def _bit(x: np.ndarray, i) -> np.ndarray:
    return (x >> np.uint64(i)) & np.uint64(1)


class Tally:
    """Cycle, event and energy counts of one program on ``n`` rows."""

    def __init__(self, n: int, energy: dict, energy_dtype=np.float64):
        self.n = n
        self.e = energy
        self.c = {k: 0 for k in INT_COUNTERS}
        self.dtype = energy_dtype
        self.energy = energy_dtype(0.0)

    def _charge(self, joules: float) -> None:
        self.energy = self.dtype(self.energy + self.dtype(joules))

    def clear(self, k: int) -> None:
        self.c["cycles"] += 1
        self.c["bwrite_cycles"] += 1
        self.c["write"] += k * self.n
        self._charge(k * self.e["p_w"] * self.n)

    def passes(self, kc: int, kw: int, matched) -> None:
        n, e = self.n, self.e
        for m in matched:
            m = int(m)
            self.c["cycles"] += 2
            self.c["compare_cycles"] += 1
            self.c["write_cycles"] += 1
            self.c["match"] += m
            self.c["mismatch"] += n - m
            self.c["write"] += kw * m
            self.c["miswrite"] += kw * (n - m)
            self._charge(kc * (e["p_m"] * m + e["p_mm"] * (n - m))
                         + kw * (e["p_w"] * m + e["p_mw"] * (n - m)))

    def read(self) -> None:
        self.c["cycles"] += self.n
        self.c["read_cycles"] += self.n


def _add_passes(t: Tally, w: np.ndarray, a: np.ndarray, n_add: int,
                n_bits: int, cond: np.ndarray | None) -> None:
    """Passes of w += a over ``n_bits`` bits (full adders on the first
    ``n_add`` bits, carry ripple above), only on rows where ``cond``."""
    carry_in = (w + a) ^ w ^ a                  # bit i: carry into bit i
    if cond is not None:
        keep = cond.astype(bool)
        carry_in, w, a = carry_in[keep], w[keep], a[keep]
    kc_extra = 0 if cond is None else 1
    for i in range(n_bits):
        c, b = _bit(carry_in, i), _bit(w, i)
        if i < n_add:                           # patterns (c, b, a)
            code = (c << np.uint64(2)) | (b << np.uint64(1)) | _bit(a, i)
            counts = np.bincount(code.astype(np.intp), minlength=8)
            t.passes(3 + kc_extra, 2, counts[[0b011, 0b100, 0b110, 0b001]])
        else:                                   # patterns (c, b)
            code = (c << np.uint64(1)) | b
            counts = np.bincount(code.astype(np.intp), minlength=4)
            t.passes(2 + kc_extra, 2, counts[[0b10, 0b11]])


def run(program: str, operands: dict, n_words: int, energy: dict,
        narrow: bool = False, energy_dtype=np.float64
        ) -> tuple[np.ndarray, dict]:
    """Result words and counters of one program on ``operands``.

    The controls: ``narrow=True`` runs the same algorithm on a datapath of
    half the stated widths, which breaks the guarantee of exact words;
    ``energy_dtype=np.float32`` accumulates the energy one precision below
    the stated float64.
    """
    wa, wb, wr = PROGRAMS[program]
    if narrow:
        wa, wb, wr = wa // 2, wb // 2, wr // 2
    a = operands["a"].astype(np.uint64) & np.uint64((1 << wa) - 1)
    b = operands["b"].astype(np.uint64) & np.uint64((1 << wb) - 1)
    mask = np.uint64((1 << wr) - 1)
    t = Tally(n_words, energy, energy_dtype)
    if program == "add16":
        t.clear(1)
        _add_passes(t, b, a, wa, wa, None)
        out = (a + b) & mask
    else:
        if program == "mac8":
            acc = operands["acc"].astype(np.uint64) & mask
        else:
            t.clear(wr)
            acc = np.zeros(n_words, np.uint64)
        for j in range(wb):
            bj = _bit(b, j)
            t.clear(1)
            window = acc >> np.uint64(j)
            top = wr - j if program == "mac8" else wa + 1
            _add_passes(t, window, a, wa, top, bj)
            acc = (acc + ((a * bj) << np.uint64(j))) & mask
        out = acc
    t.read()
    counters = dict(t.c)
    counters["energy"] = float(t.energy)
    return out, counters
