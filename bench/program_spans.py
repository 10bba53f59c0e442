"""Put the device's idle time down to the program's own spans.

`repro.obs.span` writes a `jax.profiler.TraceAnnotation` for each span
while a profiler session records, so the program's host spans sit in
the `.xplane.pb` on the device trace's clock.  `from_xplane` adds them
to what `trace_reduce.from_xplane` reads, as plain tuples:

    program_spans: [(start_ns, end_ns, name, thread), ...]

every host event whose name starts with one of `PREFIXES`, on the host
thread (`thread`, the profiler's line name) that opened it.  Spans of
one thread nest; a span's path is its ancestors' names and its own,
joined by `SEP` ("thermal/steady > thermal/steady/solve").  `reduce`
returns, per path:

- ``idle_by_span``: seconds of the window in which no op ran on a
  device (`trace_reduce`'s gaps), split at span edges, each piece put
  down to the innermost program span open over it (the deepest over
  threads), else to the benchmark's phase span open over it
  (`trace_reduce.PHASES`), else ``other``; averaged over devices like
  `trace_reduce`'s busy time;
- ``span_self_s``: each span's duration in the window less the part its
  child spans cover, summed;
- ``span_counts``: spans that start in the window.  Spans named
  ``sync/<what>`` each wrap one blocking device-to-host transfer, so
  their count is the program's count of host syncs.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

from bench import trace_reduce

PREFIXES = ("thermal/", "engine/", "sweep/", "feedback/", "sync/")
SEP = " > "
OTHER = "other"


def from_xplane(path: str) -> dict:
    """`trace_reduce.from_xplane` plus ``program_spans``."""
    from jax.profiler import ProfileData
    trace = trace_reduce.from_xplane(path)
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name, line.name))
    trace["program_spans"] = spans
    return trace


def nest(spans) -> list[tuple[int, int, tuple, str, int]]:
    """Each span as (start, end, path, thread, parent index or -1),
    sorted by thread and start; a span lies in the latest-starting
    span of its thread that is still open when it starts."""
    out = []
    for s, e, name, thread in sorted(spans, key=lambda x: (x[3], x[0],
                                                           -x[1])):
        parent = len(out) - 1
        while parent >= 0 and (out[parent][3] != thread
                               or out[parent][1] <= s):
            parent = out[parent][4]
        path = (out[parent][2] if parent >= 0 else ()) + (name,)
        out.append((s, e, path, thread, parent))
    return out


def _labels(nested, phases, w0, w1):
    """Disjoint (start, end, label) pieces covering [w0, w1]."""
    events = [(s, 1, i) for i, (s, _, _, _, _) in enumerate(nested)]
    events += [(e, 0, i) for i, (_, e, _, _, _) in enumerate(nested)]
    events += [(s, 1, -1 - j) for j, (s, _, _) in enumerate(phases)]
    events += [(e, 0, -1 - j) for j, (_, e, _) in enumerate(phases)]
    events.sort()
    open_spans, open_phases = set(), set()
    pieces, edge = [], w0

    def label():
        if open_spans:
            deepest = max(open_spans,
                          key=lambda i: (len(nested[i][2]), nested[i][0]))
            return SEP.join(nested[deepest][2])
        if open_phases:
            return phases[min(open_phases)][2]
        return OTHER

    for t, opens, i in events + [(w1, 0, None)]:
        t = min(max(t, w0), w1)
        if t > edge:
            pieces.append((edge, t, label()))
            edge = t
        if i is None:
            break
        target = open_spans if i >= 0 else open_phases
        key = i if i >= 0 else -1 - i
        if opens:
            target.add(key)
        else:
            target.discard(key)
    return pieces


def _gaps(ops, w0, w1):
    """Per device, the sorted stretches of [w0, w1] with no op."""
    by_dev = defaultdict(list)
    for dev, s, e, _m, _op in ops:
        if e > w0 and s < w1:
            by_dev[dev].append((max(s, w0), min(e, w1)))
    out = []
    for dev in sorted(by_dev):
        edge, gaps = w0, []
        for s, e in trace_reduce._union(by_dev[dev]) + [[w1, w1]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        out.append(gaps)
    return out


def reduce(trace: dict) -> dict:
    """``idle_by_span``, ``span_self_s`` and ``span_counts`` of a trace
    that `from_xplane` read (module docstring); each maps a path (or a
    phase label, or ``other``) to seconds or a count, largest first.
    The window is the host span ``window``, else the device ops' reach;
    a trace with neither reads empty."""
    ops, host = trace["device_ops"], trace["host_spans"]
    win = [(s, e) for s, e, n in host if n == trace_reduce.WINDOW_SPAN]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    elif ops:
        w0, w1 = min(o[1] for o in ops), max(o[2] for o in ops)
    else:
        return {"idle_by_span": {}, "span_self_s": {}, "span_counts": {}}
    nested = nest(trace.get("program_spans", ()))
    phases = sorted((s, e, n) for s, e, n in host
                    if n in trace_reduce.PHASES)
    pieces = _labels(nested, phases, w0, w1)
    device_gaps = _gaps(ops, w0, w1)
    idle = defaultdict(int)
    for gaps in device_gaps:
        k = 0
        for g0, g1 in gaps:
            while k < len(pieces) and pieces[k][1] <= g0:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < g1:
                p0, p1, lab = pieces[j]
                idle[lab] += min(p1, g1) - max(p0, g0)
                j += 1
    n_dev = max(len(device_gaps), 1)
    inside = defaultdict(int)
    counts = defaultdict(int)
    for s, e, path, _thread, parent in nested:
        dur = max(0, min(e, w1) - max(s, w0))
        inside[SEP.join(path)] += dur
        if parent >= 0:
            inside[SEP.join(nested[parent][2])] -= dur
        if w0 <= s < w1:
            counts[SEP.join(path)] += 1

    def ranked(d):
        return sorted(d.items(), key=lambda kv: -kv[1])

    return {"idle_by_span": {k: v / n_dev / 1e9 for k, v in ranked(idle)},
            "span_self_s": {k: v / 1e9 for k, v in ranked(inside)},
            "span_counts": dict(ranked(counts))}


def under(table: dict, root: str):
    """The (path, value) items of ``table`` whose path starts with a span
    named ``root``, or, where ``root`` ends in "/", with one whose name
    starts so."""
    for path, v in table.items():
        head = path.split(SEP, 1)[0]
        if head == root or (root.endswith("/") and head.startswith(root)):
            yield path, v


def is_sync(path: str) -> bool:
    return path.rsplit(SEP, 1)[-1].startswith("sync/")
