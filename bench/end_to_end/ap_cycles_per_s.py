"""Emulated AP cycles per wall second over the window.

The cycles are the engine's compare, write and broadcast-write cycles of
every finished job (its counters, without the modelled one-cycle-per-row
readout); the time runs from the window's start to the end of its last job.
"""


def read(ctx: dict):
    cycles = sum(r["cycles"] for r in ctx["records"])
    return cycles / ctx["window_s"] if cycles else None
