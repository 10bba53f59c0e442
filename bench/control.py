"""Readings of the numbers `correct` compares, for the program and its control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

One process builds the cell's system once, then for each seed runs a
closed-loop window of ``--seconds`` at the cell's own size and load and
compares the same sample of its answers twice with the plain reference:
once the program's answers (the lower readings of each limit) and once
the control's, the reference computed one precision or width step below
what the configuration states (the upper readings).  Prints one JSON
line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common, registry, run  # noqa: E402


def readings(cell: registry.Cell, seeds, seconds: float, devices):
    """Yield one dict of program and control readings per seed."""
    from repro import compile_cache
    import jax
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    mod = cell.job_module
    job = mod.Job(cell.config, cell.traffic, devices)
    run.log(f"setup_s={time.perf_counter() - _T0!r}")
    for seed in seeds:
        records, outputs, _, _ = run._run_window(job, seed, seconds)
        g_seed = (seed, 0x5A17)
        prog = mod.check(cell.config, cell.traffic, seed, records, outputs,
                         common.rng(*g_seed))
        ctl = mod.check(cell.config, cell.traffic, seed, records, outputs,
                        common.rng(*g_seed), control=True)
        yield {"seed": seed, "jobs": len(records),
               "failed": sum(1 for r in records if r.get("failed")),
               "program": prog, "control": ctl, "limits": cell.limits}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = registry.Cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        run.log(f"needs {cell.chips} TPU chip(s); found {devices}")
        return run.EXIT_NO_CHIP
    for line in readings(cell, args.seeds, args.seconds,
                         devices[:cell.chips]):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
