"""Helpers shared by the harness and the job kinds."""
from __future__ import annotations

import statistics

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator fixed by ``seed`` and a stream of indices.

    ``seed`` may be any whole number, also one wider than 32 or 64 bits
    or below 0: it is folded into an unsigned 64-bit word first.
    """
    return np.random.default_rng([seed & (2 ** 64 - 1)]
                                 + [s & (2 ** 64 - 1) for s in stream])


def percentile(values, q: int) -> float:
    """The ``q``-th percentile by `statistics.quantiles` (inclusive)."""
    vals = list(values)
    if len(vals) == 1:
        return float(vals[0])
    return float(statistics.quantiles(vals, n=100, method="inclusive")[q - 1])


class CompileCounter:
    """Counts jaxpr traces, backend compiles and persistent-cache loads.

    Any of these inside the measured window means a shape was not warmed
    in set-up.
    """

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.on = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        self._event(event)

    def _event(self, event, **_kw):
        if self.on and event in self.counts:
            self.counts[event] += 1

    @property
    def total(self) -> int:
        return sum(self.counts.values())


def sample(records, outputs, k: int, g: np.random.Generator) -> list[int]:
    """Up to ``k`` indices of the window's finished jobs, drawn by ``g``."""
    done = [i for i, (r, o) in enumerate(zip(records, outputs))
            if o is not None and not r.get("failed")]
    k = min(k, len(done))
    return sorted(g.choice(done, size=k, replace=False).tolist()) if k else []
