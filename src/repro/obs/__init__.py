"""``repro.obs`` — the repo-wide observability layer.

One process-local metrics registry (counters / gauges / histograms
with p50/p95/p99 summaries, :mod:`repro.obs.registry`) plus scoped
spans.  Everything funnels through this module's functions so call
sites stay one line::

    from repro import obs

    obs.count("sweep/cache/hit")
    obs.observe("serving/request_latency_s", 0.132)
    with obs.span("feedback/replay", cases=24):
        ...

A span goes to two sinks, each only while it is on:

* a ``jax.profiler`` session that is recording: the span is a
  ``jax.profiler.TraceAnnotation`` of the same name (its keyword
  arguments become the event's metadata), so it lands in the
  profiler's ``.xplane.pb`` on the device trace's clock — whether or
  not the registry is enabled;
* the registry, when :func:`is_enabled`: a Chrome trace-event
  (:mod:`repro.obs.trace`, loadable in Perfetto) and a ``span/<name>``
  duration histogram.

**Disabled mode is a strict no-op**: when :func:`is_enabled` is False
(the default; enable with ``REPRO_OBS=1`` or :func:`enable`), every
recording function returns immediately without touching the registry,
and with no profiler recording :func:`span` hands back a shared null
context manager — no allocation, no clock read.  Neither sink changes
a compiled program or adds a host sync.  The scripts under
``benchmarks/`` enable obs (``benchmarks/_record.Recorder`` does it on
construction) and gate the enabled-vs-disabled overhead at ≤ 1.05× in
``baseline.json``.

**jit-safety rules** (docs/observability.md):

* :func:`count` may be called inside a jitted function — it then runs
  at *trace time* only, which is exactly how the retrace counters work
  (``engine/retrace/*``: one increment per compiled shape bucket).
* :func:`observe`/:func:`gauge` take host numbers; forcing a device
  value with ``float(x)`` blocks, so do it where the value is already
  being synced.
* :func:`span` must never wrap code *inside* a traced function (it
  would time tracing once and vanish from the compiled program); around
  jitted calls it measures host wall clock — dispatch plus blocking
  transfers — like every bench in this repo.
"""
from __future__ import annotations

import os
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

from repro.obs.registry import Registry
from repro.obs.trace import Tracer

__all__ = [
    "enable", "disable", "is_enabled", "scoped", "reset",
    "count", "value", "values_by_prefix", "gauge", "observe",
    "observe_many",
    "span", "snapshot", "trace_events", "write_trace",
]

_registry = Registry()
_tracer = Tracer()
_tracer._on_close = lambda name, dur_s: \
    _registry.histogram(f"span/{name}").observe(dur_s)

_enabled = os.environ.get("REPRO_OBS", "").lower() in ("1", "true",
                                                       "yes", "on")


# ---------------------------------------------------------------- control

def is_enabled() -> bool:
    return _enabled


def enable(reset: bool = False) -> None:
    """Turn collection on (optionally wiping prior metrics/spans)."""
    global _enabled
    if reset:
        globals()["reset"]()
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


@contextmanager
def scoped(on: bool = True):
    """Temporarily force the enabled state (tests / A-B timing)."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    try:
        yield
    finally:
        _enabled = prev


def reset() -> None:
    """Wipe all metrics and spans (the trace clock restarts at 0)."""
    _registry.reset()
    _tracer.reset()


# ---------------------------------------------------------------- metrics

def count(name: str, n: int = 1) -> None:
    """Add ``n`` to a counter.  Safe inside jit: runs at trace time."""
    if _enabled:
        _registry.counter(name).inc(n)


def value(name: str) -> int:
    """Current value of a counter (0 if it never fired)."""
    c = _registry.counters.get(name)
    return 0 if c is None else c.value


def values_by_prefix(prefix: str) -> dict[str, int]:
    """All counters under a name prefix, e.g. ``policy/dvfs-22nm/`` —
    how the policy bench collects per-operating-point residency without
    knowing a table's labels up front (docs/observability.md)."""
    return {name: c.value for name, c in sorted(_registry.counters.items())
            if name.startswith(prefix)}


def gauge(name: str, v: float) -> None:
    """Set a last-write-wins gauge."""
    if _enabled:
        _registry.gauge(name).set(v)


def observe(name: str, v: float) -> None:
    """Add one sample to a histogram."""
    if _enabled:
        _registry.histogram(name).observe(v)


def observe_many(name: str, vs) -> None:
    """Add a batch of samples (any iterable of numbers) to a histogram."""
    if _enabled:
        _registry.histogram(name).extend(vs)


# ---------------------------------------------------------------- spans

class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NULL_SPAN = _NullSpan()


class _BothSpans:
    """A profiler annotation and a registry span, opened together."""
    __slots__ = ("_annotation", "_span")

    def __init__(self, annotation, span):
        self._annotation, self._span = annotation, span

    def __enter__(self):
        self._annotation.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._annotation.__exit__(*exc)


def span(name: str, **args):
    """Scoped span, written to every sink that is on (module docstring).

    While a ``jax.profiler`` session records, it is a
    ``TraceAnnotation`` named ``name`` with ``args`` as metadata.  With
    the registry enabled, nested spans stack per thread and each
    completed span becomes a Chrome trace event AND feeds the
    ``span/<name>`` duration histogram (so p50/p95/p99 of any span
    show up in :func:`snapshot`); extra keyword arguments land in the
    event's ``args``.  With neither on it returns the shared null
    span.

    A span named ``sync/<what>`` wraps one blocking device-to-host
    transfer: their count in a trace is the program's count of host
    syncs (docs/observability.md).
    """
    if TraceAnnotation.is_enabled():
        annotation = TraceAnnotation(name, **args)
        if not _enabled:
            return annotation
        return _BothSpans(annotation, _tracer.span(name, **args))
    if not _enabled:
        return _NULL_SPAN
    return _tracer.span(name, **args)


# ---------------------------------------------------------------- export

def snapshot() -> dict:
    """JSON-serializable registry state (see
    :meth:`repro.obs.registry.Registry.snapshot`)."""
    return _registry.snapshot()


def trace_events() -> dict:
    """The Chrome trace-event JSON object for all completed spans."""
    return _tracer.trace_object()


def write_trace(path: str) -> str:
    """Write the span trace to ``path`` (open it in
    https://ui.perfetto.dev or ``chrome://tracing``)."""
    return _tracer.write(path)
