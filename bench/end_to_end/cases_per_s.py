"""Closed-loop (workload, machine) cases replayed per wall second.

Every case of every finished job of the window, over the time from the
window's start to the end of its last job.
"""


def read(ctx: dict):
    cases = sum(r["cases"] for r in ctx["records"])
    return cases / ctx["window_s"] if cases else None
