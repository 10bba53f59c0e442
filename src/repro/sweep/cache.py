"""Content-hashed on-disk result cache for sweeps.

One sweep = one ``sweep_<hash>[_s<N>].npz`` under the cache directory
(``$REPRO_SWEEP_CACHE`` or ``.sweep_cache/``), where ``<hash>`` is
:meth:`SweepSpec.content_hash` — a SHA-256 digest of the spec's
canonical JSON plus a schema version (DESIGN.md §8) — and ``_s<N>``
names the shard count of a sharded replay (DESIGN.md §7.6: sharded
results agree with the unsharded ones to a stated tolerance, not bit
for bit, so they are cached apart).  The npz holds the
per-record result arrays verbatim (float32/float64, so reloads are
bit-identical) and a JSON manifest with the full canonical spec, which
:func:`load` verifies against the requesting spec so a truncated-hash
collision can never serve wrong results.  Stack geometry is NOT stored:
it is deterministic from the point (``dram_on_logic(n_dram)``) and is
rebuilt on load.

A corrupt or truncated cache file (interrupted writer on a different
filesystem, disk-full, bit rot) is treated as a MISS, not an error: the
sweep recomputes and overwrites it.  Hits, misses, corrupt files, and
stores are counted under ``sweep/cache/*`` when :mod:`repro.obs` is
enabled.
"""
from __future__ import annotations

import json
import os
import zipfile
import zlib
from pathlib import Path

import numpy as np

from repro import obs
from repro.stack import dram, feedback
from repro.stack.spec import dram_on_logic
from repro.sweep.engine import SweepRecord, SweepResult, resolve_fb
from repro.sweep.spec import SweepPoint, SweepSpec

_ARRAYS = ("peak_C", "min_C", "residual_C", "throttle", "refresh_W",
           "leak_W", "dyn_W")

#: everything a damaged npz can throw while being opened/read: not a
#: zip at all, zip ok but members truncated/absent, manifest not JSON
_CORRUPT_ERRORS = (zipfile.BadZipFile, zlib.error, KeyError, ValueError,
                   EOFError, OSError, json.JSONDecodeError)


def default_cache_dir() -> Path:
    return Path(os.environ.get("REPRO_SWEEP_CACHE", ".sweep_cache"))


def path_for(spec: SweepSpec, cache_dir=None, n_shards=None) -> Path:
    base = Path(cache_dir) if cache_dir is not None else default_cache_dir()
    shards = f"_s{n_shards}" if n_shards else ""
    return base / f"sweep_{spec.content_hash()}{shards}.npz"


def store(result: SweepResult, cache_dir=None, n_shards=None) -> Path:
    """Persist a sweep result replayed over ``n_shards`` devices
    (None/0 = the plain vmap); returns the written path."""
    path = path_for(result.spec, cache_dir, n_shards)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload: dict[str, np.ndarray] = {}
    for i, rec in enumerate(result.records):
        for name in _ARRAYS:
            payload[f"r{i}_{name}"] = getattr(rec.report, name)
    manifest = {
        "spec": result.spec.canonical(),
        "records": [{"machine": r.machine,
                     "point": [r.point.workload, r.point.size,
                               r.point.n_dram, r.point.fb_mode,
                               r.point.policy]}
                    for r in result.records],
    }
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, manifest=np.array(json.dumps(manifest)), **payload)
    os.replace(tmp, path)
    obs.count("sweep/cache/store")
    if obs.is_enabled():
        obs.count("sweep/cache/bytes_written", path.stat().st_size)
    return path


def load(spec: SweepSpec, cache_dir=None, n_shards=None
         ) -> SweepResult | None:
    """Load a cached sweep for ``spec`` replayed over ``n_shards``
    devices; None on miss, manifest mismatch (hash-collision guard), or
    a corrupt/truncated file (recompute and overwrite rather than fail
    the sweep)."""
    path = path_for(spec, cache_dir, n_shards)
    if not path.exists():
        obs.count("sweep/cache/miss")
        return None
    try:
        result = _read(spec, path)
    except _CORRUPT_ERRORS:
        obs.count("sweep/cache/corrupt")
        obs.count("sweep/cache/miss")
        return None
    if result is None:
        obs.count("sweep/cache/miss")
        return None
    obs.count("sweep/cache/hit")
    if obs.is_enabled():
        obs.count("sweep/cache/bytes_read", path.stat().st_size)
    return result


def _read(spec: SweepSpec, path: Path) -> SweepResult | None:
    with np.load(path, allow_pickle=False) as z:
        manifest = json.loads(str(z["manifest"]))
        if manifest["spec"] != spec.canonical():
            return None
        interval_dt = spec.t_end / spec.n_intervals
        records = []
        for i, meta in enumerate(manifest["records"]):
            w, size, n_dram, fb_mode, policy = meta["point"]
            point = SweepPoint(w, int(size), int(n_dram), fb_mode,
                               policy)
            stack_spec = dram_on_logic(int(n_dram))
            base_ref = dram.DRAMFloorplan(die_w_mm=1.0).base_refresh_W() \
                * int(n_dram)
            arrays = {name: z[f"r{i}_{name}"] for name in _ARRAYS}
            report = feedback.StackReport(
                label=f"{point.label}/{meta['machine']}",
                interval_s=interval_dt, spec=stack_spec,
                base_refresh_W=base_ref,
                tol_C=resolve_fb(fb_mode, policy=policy).picard_tol_C,
                **arrays)
            records.append(SweepRecord(point=point,
                                       machine=meta["machine"],
                                       report=report))
    return SweepResult(spec=spec, records=tuple(records), from_cache=True)
