"""Device word <-> bit-plane transposes against the host formula they replace.

``pack_words``/``unpack_words`` and ``APEngine.load``/``read`` cast words
to uint32 halves on the host and transpose on the device.  The oracle
below is the plain numpy formula over uint64 bit matrices; the device
path must match it bit for bit at every width up to 64, including words
whose top bit is set and words with bits above the field's width.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane as bp
from repro.core.engine import APEngine

WIDTHS = (1, 8, 16, 31, 32, 33, 48, 64)
N_WORDS = (32, 256, 4096)


def _oracle_pack(values: np.ndarray, n_bits: int) -> np.ndarray:
    values = np.asarray(values).astype(np.uint64)
    nl = values.shape[0] // bp.LANE
    bits = (values[None, :] >> np.arange(n_bits, dtype=np.uint64)[:, None]) & 1
    bits = bits.astype(np.uint32).reshape(n_bits, nl, bp.LANE)
    shifts = np.arange(bp.LANE, dtype=np.uint32)
    return (bits << shifts[None, None, :]).sum(axis=-1, dtype=np.uint32)


def _oracle_unpack(planes: np.ndarray) -> np.ndarray:
    pl = np.asarray(planes)
    n_bits, nl = pl.shape
    shifts = np.arange(bp.LANE, dtype=np.uint32)
    bits = (pl[:, :, None] >> shifts[None, None, :]) & 1
    bits = bits.reshape(n_bits, nl * bp.LANE).astype(np.uint64)
    weights = np.uint64(1) << np.arange(n_bits, dtype=np.uint64)
    return (bits * weights[:, None]).sum(axis=0, dtype=np.uint64)


def _words(rng, n: int) -> np.ndarray:
    """Full 64-bit words; every other word has bit 63 set."""
    v = rng.integers(0, 1 << 63, n, dtype=np.uint64)
    v[::2] |= np.uint64(1) << np.uint64(63)
    return v


@pytest.mark.parametrize("n_words", N_WORDS)
@pytest.mark.parametrize("n_bits", WIDTHS)
def test_pack_unpack_match_host_formula(n_bits, n_words):
    rng = np.random.default_rng(1000 * n_bits + n_words)
    v = _words(rng, n_words)
    v[1::2] |= np.uint64(1) << np.uint64(n_bits - 1)   # the field's top bit
    planes = bp.pack_words(v, n_bits)
    want = _oracle_pack(v, n_bits)
    assert planes.dtype == np.uint32
    np.testing.assert_array_equal(np.asarray(planes), want)
    got = bp.unpack_words(planes)
    assert got.dtype == np.uint64
    np.testing.assert_array_equal(got, _oracle_unpack(want))
    mask = np.uint64((1 << n_bits) - 1)
    np.testing.assert_array_equal(got, v & mask)


def test_unpack_words_rejects_width_over_64():
    with pytest.raises(ValueError, match="64"):
        bp.unpack_words(np.zeros((65, 1), np.uint32))


@pytest.mark.parametrize("width", [17, 40])
def test_engine_load_read_roundtrip_keeps_other_columns(width):
    n = 256
    rng = np.random.default_rng(width)
    eng = APEngine(n_words=n, n_bits=128)
    below = eng.alloc.alloc(9, "below")
    f = eng.alloc.alloc(width, "f")
    above = eng.alloc.alloc(128 - 9 - width, "above")
    before = np.concatenate([_oracle_pack(_words(rng, n), 64)
                             for _ in range(2)])          # every column set
    eng.planes = jnp.asarray(before)

    v = _words(rng, n) & np.uint64((1 << width) - 1)
    v[::3] |= np.uint64(1) << np.uint64(width - 1)           # negative as signed
    eng.load(f, v)
    after = np.asarray(eng.planes)
    np.testing.assert_array_equal(after[:below.width], before[:below.width])
    np.testing.assert_array_equal(after[above.start:], before[above.start:])
    np.testing.assert_array_equal(after[f.start:f.start + width],
                                  _oracle_pack(v, width))

    np.testing.assert_array_equal(eng.read(f), v)
    np.testing.assert_array_equal(eng.peek(f), v)
    signed = eng.read(f, signed=True)
    want = v.astype(np.int64) - ((v >> np.uint64(width - 1)).astype(np.int64)
                                 << width)
    assert signed.dtype == np.int64 and (signed < 0).any()
    np.testing.assert_array_equal(signed, want)
    assert eng.read_cycles == 2 * n                         # peek charges none


_SHARDED = r"""
import numpy as np
from repro.core import bitplane as bp, isa
from repro.core.engine import APEngine

eng = APEngine(n_words=512, n_bits=48, backend="megakernel", n_shards=4)
a, b, c = eng.alloc.alloc(8), eng.alloc.alloc(8), eng.alloc.alloc(1)
w = eng.alloc.alloc(31)
rng = np.random.default_rng(0)
x = rng.integers(0, 256, 512, dtype=np.uint64)
y = rng.integers(0, 256, 512, dtype=np.uint64)
eng.load(a, x)
eng.load(b, y)
isa.run_add(eng, a, b, c)                  # planes now sharded over lanes
sharding = eng.planes.sharding
assert not sharding.is_fully_replicated, sharding
z = rng.integers(0, 1 << 31, 512, dtype=np.uint64)
eng.load(w, z)
assert eng.planes.sharding == sharding, eng.planes.sharding
lo, hi = bp.split_words(z, w.width)
hlo = bp._load_device.lower(eng.planes, lo, hi, w.start,
                            n_bits=w.width).compile().as_text()
assert not any(op in hlo for op in ("all-gather", "all-reduce",
                                    "collective-permute", "all-to-all")), hlo
np.testing.assert_array_equal(eng.read(b), (x + y) & 255)
np.testing.assert_array_equal(eng.read(w), z)
print("SHARDED-LOAD-OK")
"""


def test_sharded_load_keeps_lane_sharding_subprocess():
    """A load into a 4-shard megakernel engine keeps the planes sharded
    over lanes and its program holds no collective."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _SHARDED],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED-LOAD-OK" in proc.stdout
