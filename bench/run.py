"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (imports, device start, building the
system, one job of every shape the window runs) counts as `setup_s`.
Then one client runs whole jobs in a closed loop: the next job is
submitted once the previous job's outputs are on the host, and a new job
starts while the elapsed time is under ``--seconds``.  Rates divide all
the work by the time to the end of the last job; tails are over every
job of the window.  After the window the run reads the peak device
memory, frees the system and compares a sample of the window's answers,
drawn from the seed, with the plain reference.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
the same loop under the profiler for at most `TRACE_SECONDS` and prints
its per-layer metrics, the device's busy and window seconds and a
breakdown (a traffic file may shorten the traced window further with
``trace_seconds``).  The last line of
standard output is one JSON object; the numbers compared for `correct`
close both it (key ``checks``) and standard error.  With no TPU, or fewer
chips than the cell asks for, the run exits 3 and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common, registry, trace_reduce  # noqa: E402

EXIT_BAD_CELL = 2
EXIT_NO_CHIP = 3
#: the profiler's device buffer on a v5e holds about 30 s of the steady
#: cell's ops and silently drops the rest, so a traced window is shorter;
#: a traffic file whose jobs run more ops a second sets ``trace_seconds``
TRACE_SECONDS = 15.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _run_window(job, seed: int, seconds: float):
    """Closed loop of whole jobs; returns (records, outputs, t0, t_end)."""
    from jax.profiler import TraceAnnotation
    records, outputs = [], []
    t0 = time.perf_counter()
    with TraceAnnotation(trace_reduce.WINDOW_SPAN):
        i = 0
        while time.perf_counter() - t0 < seconds:
            try:
                rec, out = job.run(seed, i)
            except Exception as e:  # a job that raises counts as failed
                rec, out = {"failed": True, "error": repr(e)[:300]}, None
                log(f"job {i} failed: {rec['error']}")
            rec["t_end"] = time.perf_counter()
            records.append(rec)
            outputs.append(out)
            i += 1
    return records, outputs, t0, time.perf_counter()


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             devices, t0: float) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    from repro import compile_cache, obs

    log(f"compilation cache: {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if obs.is_enabled():
        raise RuntimeError("repro.obs must stay disabled in a benchmark run")
    job = cell.job_module.Job(cell.config, cell.traffic, devices)
    setup_s = time.perf_counter() - t0
    log(f"setup_s={setup_s!r}")

    counter = common.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        seconds = min(seconds, TRACE_SECONDS,
                      cell.traffic.get("trace_seconds", TRACE_SECONDS))
        jax.profiler.start_trace(trace_dir)
    counter.on = True
    try:
        records, outputs, w0, w1 = _run_window(job, seed, seconds)
    finally:
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
    log(f"compiles_in_window={counter.total} {json.dumps(counter.counts)}")
    window_s = max(records[-1]["t_end"] - w0, 1e-9) if records else w1 - w0
    device = _device_info(devices)
    failed = sum(1 for r in records if r.get("failed"))
    lat = sorted(r["latency_s"] for r in records if "latency_s" in r)
    if lat:
        log(f"jobs={len(records)} failed={failed} window_s={window_s!r} "
            f"latency_s min={lat[0]!r} median={lat[len(lat) // 2]!r} "
            f"max={lat[-1]!r}")

    result = {"correct": False, "attempted": len(records), "failed": failed}
    ctx = {"records": [r for r in records if not r.get("failed")],
           "window_s": window_s, "seconds": seconds, "trace": None}
    if trace:
        try:
            summary = trace_reduce.reduce(trace_reduce.from_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = summary
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        log(f"trace: window_s={summary['window_s']!r} busy_s="
            f"{summary['busy_s']!r} idle_pct={summary['idle_pct']!r} "
            f"device ops from {summary['ops_from_s']!r} s to "
            f"{summary['ops_to_s']!r} s of the window")
        for name, secs in summary["modules"]:
            log(f"trace module {name}: {secs!r} s")
        metrics = {}
        for m in cell.per_layer:
            value = cell.layer_readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary["ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                metrics[m["name"]] = {
                    "value": cell.e2e_readers[m["name"]].read(ctx),
                    "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device

    job.close()
    del job
    t_check = time.perf_counter()
    checks = cell.job_module.check(
        cell.config, cell.traffic, seed, records, outputs,
        common.rng(seed, 0x5A17))
    log(f"check_s={time.perf_counter() - t_check!r}")
    ok = bool(checks) and failed == 0
    for name, value in checks.items():
        limit = cell.limits[name]
        ok = ok and math.isfinite(value) and value <= limit
    result["correct"] = ok
    result["checks"] = {name: {"value": value, "limit": cell.limits[name]}
                        for name, value in checks.items()}
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = registry.Cell(args.workload)
    except (registry.BenchError, OSError, ValueError) as e:
        log(f"cannot load cell {args.workload!r}: {e}")
        return EXIT_BAD_CELL
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"no program under {src}")
        return EXIT_BAD_CELL
    sys.path.insert(0, str(src))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        return EXIT_NO_CHIP
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], _T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
