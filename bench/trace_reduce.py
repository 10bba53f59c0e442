"""Reduce a profiler trace to device busy time, time per module and labelled gaps.

A trace is first normalised to plain tuples, so the reduction can be
checked on a small recorded trace without a chip:

    device_ops: [(device, start_ns, end_ns, module, op), ...]
    host_spans: [(start_ns, end_ns, name), ...]   the benchmark's annotations

`from_xplane` reads the `.xplane.pb` that `jax.profiler` writes; `reduce`
does the arithmetic:

- busy: the union of the intervals in which an op ran on a device, clipped
  to the window, averaged over the devices;
- time per XLA module (the union of its ops) and per op (the summed
  durations of the innermost ops: a `while` that encloses its body's ops
  is left out of the op ranking);
- idle gaps: every stretch of the window in which no op ran on a device,
  named by the benchmark's host span that covers most of it (`gen_inputs`,
  `submit`, `fetch`), else `other`.

On a TPU the trace's device clock runs about a millisecond from the
host's, so the window's edges and a gap's label are exact to that.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "window"
PHASES = ("gen_inputs", "submit", "fetch")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def from_xplane(path: str) -> dict:
    """Normalise the `.xplane.pb` under ``path`` (a file or a trace dir)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name in OP_LINES:
                    for e in line.events:
                        ops.append((plane.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    e.name.split(" = ")[0]))
                elif line.name in MODULE_LINES:
                    for e in line.events:
                        modules.append((plane.name, e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        re.sub(r"\(\d+\)$", "", e.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in PHASES:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return {"device_ops": _attach_modules(ops, modules), "host_spans": host}


def _attach_modules(ops, modules):
    """Give each op the name of the module event that encloses it."""
    by_dev = defaultdict(list)
    for dev, s, e, name in modules:
        by_dev[dev].append((s, e, name))
    for lst in by_dev.values():
        lst.sort()
    out = []
    for dev, s, e, op in sorted(ops, key=lambda o: (o[0], o[1])):
        mods = by_dev.get(dev, [])
        module = ""
        lo, hi = 0, len(mods)
        while lo < hi:                      # last module starting <= s
            mid = (lo + hi) // 2
            if mods[mid][0] <= s:
                lo = mid + 1
            else:
                hi = mid
        if lo and mods[lo - 1][1] >= s:
            module = mods[lo - 1][2]
        out.append((dev, s, e, module, op))
    return out


def _union(intervals):
    """Merge (start, end) pairs into disjoint sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle seconds, time per module and op, idle gaps by label.

    The window is the host span named ``window``; without one it runs
    from the first to the last device op.  Returns a dict with
    ``window_s``, ``busy_s`` (mean over devices), ``idle_pct``,
    ``n_devices``, ``modules`` and ``ops`` ([name, seconds], largest
    first), ``idle_gaps`` ([label, seconds] summed per label), and
    ``ops_from_s``/``ops_to_s``: where the first device op starts and the
    last ends, from the window's start (a trace whose buffer overflowed
    ends early).
    """
    ops = trace["device_ops"]
    spans = trace["host_spans"]
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    elif ops:
        w0, w1 = min(o[1] for o in ops), max(o[2] for o in ops)
    else:
        return {"window_s": 0.0, "busy_s": 0.0, "idle_pct": None,
                "n_devices": 0, "modules": [], "ops": [], "idle_gaps": [],
                "ops_from_s": None, "ops_to_s": None}
    window_ns = max(w1 - w0, 1)
    devices = sorted({o[0] for o in ops})
    inside = [o for o in ops if o[2] > w0 and o[1] < w1]
    busy_ns, gaps = 0, []
    modules, op_time = defaultdict(int), defaultdict(int)
    for dev in devices:
        mine = sorted((max(s, w0), min(e, w1), m or "?", op)
                      for d, s, e, m, op in ops
                      if d == dev and e > w0 and s < w1)
        merged = _union([(s, e) for s, e, _, _ in mine])
        busy_ns += sum(e - s for s, e in merged)
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        per_module = defaultdict(list)
        for i, (s, e, m, op) in enumerate(mine):
            per_module[m].append((s, e))
            inner = mine[i + 1] if i + 1 < len(mine) else None
            if not (inner and inner[0] < e and inner[1] <= e):
                op_time[f"{m}/{op}"] += e - s
        for m, iv in per_module.items():
            modules[m] += sum(e - s for s, e in _union(iv))
    phase_spans = sorted((s, e, n) for s, e, n in spans if n in PHASES)
    starts = [s for s, _, _ in phase_spans]
    longest = max((e - s for s, e, _ in phase_spans), default=0)
    by_label = defaultdict(int)
    for g0, g1 in gaps:
        best, label = 0, "other"
        lo = bisect.bisect_left(starts, g0 - longest)
        for s, e, n in phase_spans[lo:bisect.bisect_left(starts, g1)]:
            ov = _overlap(g0, g1, s, e)
            if ov > best:
                best, label = ov, n
        by_label[label] += g1 - g0
    n_dev = max(len(devices), 1)
    busy_s = busy_ns / n_dev / 1e9

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": window_ns / 1e9, "busy_s": busy_s,
            "idle_pct": 100.0 * (1.0 - busy_s / (window_ns / 1e9)),
            "n_devices": len(devices), "modules": ranked(modules),
            "ops_from_s": (min(o[1] for o in inside) - w0) / 1e9
            if inside else None,
            "ops_to_s": (max(o[2] for o in inside) - w0) / 1e9
            if inside else None,
            "ops": ranked(op_time),
            "idle_gaps": [[k, v / 1e9 / n_dev] for k, v in sorted(
                by_label.items(), key=lambda kv: -kv[1])[:top]]}
