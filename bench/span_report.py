"""Put a cell's device-idle time down to the program's spans, on the chip.

    python3 bench/span_report.py --workload <cell> --seed <n> [--out FILE]

From the root of a checkout.  Set-up is `bench/run.py`'s.  Then three
windows of the same closed loop of whole jobs, each as long as a
`bench/run.py --trace 1` window (`run.TRACE_SECONDS`, or the traffic's
``trace_seconds``): one without the profiler, one under it, one without
it again.  Prints one JSON object (and writes it to ``--out``):

- ``windows``: per window, the jobs and the cell's end-to-end metrics,
  so the traced window against the other two is what tracing costs
  while it records;
- ``trace``: `trace_reduce.reduce`'s summary of the traced window plus
  `program_spans.reduce`'s ``idle_by_span``, ``span_self_s`` and
  ``span_counts``;
- ``layer``: every per-layer reader of `bench/layer_metrics/` that finds
  something in the traced window: the cell's metrics in
  `BENCHMARK.json` and the program-span readers of `SPAN_METRICS`.

With no TPU, or fewer chips than the cell asks for, it exits 3.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program_spans, registry, run, trace_reduce  # noqa: E402

#: readers of `bench/layer_metrics/` that read the program's spans
SPAN_METRICS = ("steady_host_ms.solve", "host_syncs_per_solve.solve",
                "ap_host_us_per_cycle.ap", "host_syncs_per_job.ap",
                "assemble_ms_per_case.sweep")


def _window(cell, job, seed: int, seconds: float) -> tuple[dict, dict]:
    """One closed-loop window: (its summary, the readers' ctx)."""
    records, _, w0, w1 = run._run_window(job, seed, seconds)
    done = [r for r in records if not r.get("failed")]
    window_s = max(records[-1]["t_end"] - w0, 1e-9) if records else w1 - w0
    ctx = {"records": done, "window_s": window_s, "seconds": seconds,
           "trace": None}
    summary = {"jobs": len(records), "failed": len(records) - len(done),
               "window_s": window_s}
    for name, reader in cell.e2e_readers.items():
        summary[name] = reader.read(ctx) if done else None
    return summary, ctx


def report(cell: registry.Cell, seed: int, devices) -> dict:
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t0 = time.perf_counter()
    job = cell.job_module.Job(cell.config, cell.traffic, devices)
    out = {"workload": cell.name, "seed": seed,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices)},
           "setup_s": time.perf_counter() - t0, "windows": {}}
    seconds = min(run.TRACE_SECONDS,
                  cell.traffic.get("trace_seconds", run.TRACE_SECONDS))
    out["windows"]["untraced_1"], _ = _window(cell, job, seed, seconds)
    trace_dir = tempfile.mkdtemp(prefix="span_report_")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            out["windows"]["traced"], ctx = _window(cell, job, seed,
                                                    seconds)
        finally:
            jax.profiler.stop_trace()
        trace = program_spans.from_xplane(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["windows"]["untraced_2"], _ = _window(cell, job, seed, seconds)
    job.close()
    ctx["trace"] = dict(trace_reduce.reduce(trace),
                        **program_spans.reduce(trace))
    out["trace"] = ctx["trace"]
    readers = dict(cell.layer_readers)
    for name in SPAN_METRICS:
        readers[name] = registry.find_metric_reader(
            registry.BENCH_DIR / "layer_metrics", name)
    out["layer"] = {}
    for name, reader in readers.items():
        value = reader.read(ctx)
        if value is not None:
            out["layer"][name] = value
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    cell = registry.Cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        run.log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
                f"{devices[0].platform} device(s)")
        return run.EXIT_NO_CHIP
    out = report(cell, args.seed, devices[:cell.chips])
    for label, secs in out["trace"]["idle_by_span"].items():
        run.log(f"idle {label}: {secs!r} s")
    text = json.dumps(out)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
