"""Packed bit-plane representation of the Associative Processing Array.

The AP (paper Fig. 1) is an array of ``n_words`` rows x ``n_bits`` columns of
associative bit cells.  A word-row is a Processing Unit (PU).  Compare and
tagged-write operate on *columns* (selected by MASK) across *all rows* at once,
so the natural TPU/JAX layout is **column-major bit planes**:

    planes : uint32[n_bits, n_words // 32]

plane ``i`` holds bit-column ``i`` for every word, packed 32 words per lane.
One AP pass (a 3-column compare + a 2-column tagged write) is then a handful of
bitwise VPU ops over contiguous lanes — the same re-blocking a TPU port of the
CAM would use (HBM->VMEM streaming over the word axis, all active bit-columns
resident; see kernels/ap_match).

The TAG register is a packed ``uint32[n_words // 32]`` vector.
"""
from __future__ import annotations

import dataclasses
from functools import partial, reduce

import jax
import jax.numpy as jnp
import numpy as np

LANE = 32  # words packed per uint32 lane
_U32 = jnp.uint32
FULL = jnp.uint32(0xFFFFFFFF)


def n_lanes(n_words: int) -> int:
    if n_words % LANE != 0:
        raise ValueError(f"n_words must be a multiple of {LANE}, got {n_words}")
    return n_words // LANE


def alloc_planes(n_bits: int, n_words: int) -> jax.Array:
    """All-zero associative array."""
    return jnp.zeros((n_bits, n_lanes(n_words)), dtype=_U32)


# ---------------------------------------------------------------------------
# word <-> bit-plane transposes.  The host only casts: uint64 words split
# into uint32 halves (JAX runs without x64, so >32-bit fields cross as a
# low and a high half).  The transpose itself runs on the device, one
# compiled program per field width; the field's start column is a traced
# operand, so every field of one width shares that program.
# ---------------------------------------------------------------------------

def _check_width(n_bits: int) -> None:
    if n_bits > 64:
        raise ValueError(
            f"fields wider than 64 bits do not fit uint64 host words "
            f"(got width {n_bits}); split the value across fields")


def split_words(values, n_bits: int) -> tuple[np.ndarray, np.ndarray | None]:
    """Host cast of integer words to uint32 halves for the device.

    Returns ``(lo, hi)``: the low 32 bits of every word, and the high 32
    bits where ``n_bits > 32`` (else ``None``).  Bits at and above
    ``n_bits`` are ignored by the transpose.
    """
    _check_width(n_bits)
    vals = np.asarray(jax.device_get(values))
    if vals.dtype != np.uint64:
        vals = vals.astype(np.uint64)
    lo = vals.astype(np.uint32)
    hi = (vals >> np.uint64(32)).astype(np.uint32) if n_bits > 32 else None
    return lo, hi


def widen_words(lo: np.ndarray, hi: np.ndarray | None = None) -> np.ndarray:
    """Host inverse of :func:`split_words`: uint32 halves -> uint64 words."""
    vals = np.asarray(lo).astype(np.uint64)
    if hi is not None:
        vals |= np.asarray(hi).astype(np.uint64) << np.uint64(32)
    return vals


def _pack_u32(words: jax.Array, n_bits: int) -> jax.Array:
    """uint32 words [n_words] -> planes of their low ``n_bits`` bits."""
    w = words.reshape(n_lanes(words.shape[0]), LANE).T        # [LANE, nl]
    bit = jnp.arange(n_bits, dtype=_U32)[:, None, None]
    pos = jnp.arange(LANE, dtype=_U32)[None, :, None]
    return (((w[None] >> bit) & 1) << pos).sum(axis=1, dtype=_U32)


def _unpack_u32(planes: jax.Array) -> jax.Array:
    """Planes [k <= 32, nl] -> uint32 words [nl * 32]."""
    pos = jnp.arange(LANE, dtype=_U32)[:, None, None]
    bit = jnp.arange(planes.shape[0], dtype=_U32)[None, :, None]
    w = (((planes[None] >> pos) & 1) << bit).sum(axis=1, dtype=_U32)
    return w.T.reshape(-1)                                     # [nl * LANE]


def _words_to_planes(lo, hi, n_bits: int) -> jax.Array:
    if n_bits <= 32:
        return _pack_u32(lo, n_bits)
    return jnp.concatenate([_pack_u32(lo, 32), _pack_u32(hi, n_bits - 32)])


_pack_device = jax.jit(_words_to_planes, static_argnames=("n_bits",))


@partial(jax.jit, static_argnames=("n_bits",))
def _load_device(planes, lo, hi, start, n_bits: int) -> jax.Array:
    sub = _words_to_planes(lo, hi, n_bits)
    return jax.lax.dynamic_update_slice(planes, sub, (start, 0))


@partial(jax.jit, static_argnames=("n_bits",))
def _read_device(planes, start, n_bits: int):
    sub = jax.lax.dynamic_slice_in_dim(planes, start, n_bits)
    if n_bits <= 32:
        return _unpack_u32(sub), None
    return _unpack_u32(sub[:32]), _unpack_u32(sub[32:])


def load_words(planes: jax.Array, values, start: int,
               n_bits: int) -> jax.Array:
    """``planes`` with words ``values[n_words]`` stored in bit-columns
    ``[start, start + n_bits)``: one host cast, one upload of uint32
    halves and one device program (transpose fused with the store).  The
    result keeps ``planes``' sharding."""
    lo, hi = split_words(values, n_bits)
    return _load_device(planes, lo, hi, start, n_bits=n_bits)


def field_words(planes: jax.Array, start: int, n_bits: int):
    """Device-side read of bit-columns ``[start, start + n_bits)`` as
    uint32 word halves ``(lo, hi)`` (``hi`` None up to 32 bits); bring
    them to the host with one ``jax.device_get`` and :func:`widen_words`."""
    _check_width(n_bits)
    return _read_device(planes, start, n_bits=n_bits)


def pack_words(values: np.ndarray | jax.Array, n_bits: int) -> jax.Array:
    """Pack integer words ``values[n_words]`` into bit planes [n_bits, n_words/32].

    Bit ``i`` of word ``w`` lands in ``planes[i, w // 32]`` at lane-bit ``w % 32``.
    The host casts the words to uint32 halves (:func:`split_words`); the
    transpose runs on the device.
    """
    lo, hi = split_words(values, n_bits)
    return _pack_device(lo, hi, n_bits=n_bits)


def unpack_words(planes: jax.Array, out_dtype=np.uint64) -> np.ndarray:
    """Inverse of :func:`pack_words` -> integer words [n_words] (host numpy).

    The transpose runs on the device; the host widens uint32 halves.
    """
    planes = jnp.asarray(planes)
    halves = jax.device_get(field_words(planes, 0, planes.shape[0]))
    return widen_words(*halves).astype(out_dtype, copy=False)


def pack_bits(bitvec: np.ndarray | jax.Array) -> jax.Array:
    """Pack a boolean vector [n_words] into a packed tag row [n_words/32]."""
    bitvec = jnp.asarray(bitvec).astype(_U32)
    nl = n_lanes(bitvec.shape[0])
    bits = bitvec.reshape(nl, LANE)
    shifts = jnp.arange(LANE, dtype=_U32)
    return (bits << shifts[None, :]).sum(axis=-1, dtype=_U32)


def unpack_bits(row: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_bits` -> bool [n_words]."""
    shifts = jnp.arange(LANE, dtype=_U32)
    bits = (row[:, None] >> shifts[None, :]) & 1
    return bits.reshape(-1).astype(jnp.bool_)


def popcount(row: jax.Array) -> jax.Array:
    """Number of set word-bits in a packed row (e.g. matched PUs in TAG)."""
    return jax.lax.population_count(row).astype(jnp.int32).sum()


# ---------------------------------------------------------------------------
# the three silicon primitives: COMPARE, tagged WRITE, broadcast WRITE
# Each is ONE AP cycle regardless of the number of active columns (columns act
# in parallel on the match line / word line) — cycle cost lives in the engine.
# ---------------------------------------------------------------------------

def compare(planes: jax.Array, cols: jax.Array, key: jax.Array,
            tag_in: jax.Array | None = None) -> jax.Array:
    """Match ``key`` against columns ``cols`` of every word -> packed TAG.

    cols : int32[K] column indices (the unmasked columns)
    key  : uint32[K] key bits (0/1) for those columns
    tag_in : optional packed row; if given the result is ANDed into it
             (models compare restricted to previously tagged rows).
    """
    sel = planes[cols]                                    # [K, nl] gather
    keyb = (key.astype(_U32) * FULL)[:, None]             # 0x0 / 0xFFFFFFFF
    eq = ~(sel ^ keyb)                                    # per-bit XNOR
    tag = reduce(jnp.bitwise_and, [eq[i] for i in range(eq.shape[0])])
    if tag_in is not None:
        tag = tag & tag_in
    return tag


def tagged_write(planes: jax.Array, tag: jax.Array, cols: jax.Array,
                 key: jax.Array) -> jax.Array:
    """Parallel write of ``key`` into columns ``cols`` of all tagged words."""
    keyb = (key.astype(_U32) * FULL)[:, None]
    old = planes[cols]
    new = (old & ~tag[None, :]) | (keyb & tag[None, :])
    return planes.at[cols].set(new)


def broadcast_write(planes: jax.Array, cols: jax.Array, key: jax.Array) -> jax.Array:
    """Write ``key`` into columns ``cols`` of ALL words (tag = all ones)."""
    keyb = (key.astype(_U32) * FULL)[:, None]
    nl = planes.shape[1]
    return planes.at[cols].set(jnp.broadcast_to(keyb, (cols.shape[0], nl)))


def write_column_bits(planes: jax.Array, col: int, bits: jax.Array) -> jax.Array:
    """Host-side load of a full per-word bit column (data load, not an AP op)."""
    return planes.at[col].set(bits)


# ---------------------------------------------------------------------------
# Field: a named range of bit-columns.  Shifts are free on the AP — "shift is
# implemented by activating different bit columns" (§2.2) — so a shifted view
# is just a new Field with offset column indices.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Field:
    start: int
    width: int

    def col(self, i: int) -> int:
        if not 0 <= i < self.width:
            raise IndexError(f"bit {i} out of field width {self.width}")
        return self.start + i

    def cols(self) -> list[int]:
        return list(range(self.start, self.start + self.width))

    def bit(self, i: int) -> "Field":
        return Field(self.col(i), 1)

    def slice(self, lo: int, width: int) -> "Field":
        if lo + width > self.width:
            raise IndexError("slice outside field")
        return Field(self.start + lo, width)

    def shifted(self, k: int) -> "Field":
        """View of this field shifted left by k columns (zero-cost AP shift)."""
        return Field(self.start + k, self.width)


class FieldAllocator:
    """Trivial bump allocator for bit-columns of the associative word."""

    def __init__(self, n_bits: int):
        self.n_bits = n_bits
        self._next = 0

    def alloc(self, width: int, name: str = "") -> Field:
        if self._next + width > self.n_bits:
            raise MemoryError(
                f"associative word overflow allocating {width} cols for {name!r}: "
                f"{self._next}/{self.n_bits} used")
        f = Field(self._next, width)
        self._next += width
        return f

    @property
    def used(self) -> int:
        return self._next
