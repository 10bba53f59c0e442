"""Device-idle time under the `engine/*` spans per emulated AP cycle, in us.

Reads ``idle_by_span`` (`bench.program_spans`): the idle stretches of
the traced window put down to an `engine/*` span or a span inside one,
over the emulated cycles of the window's jobs.
"""
from bench import program_spans


def read(ctx: dict):
    t = ctx["trace"]
    cycles = sum(r.get("cycles", 0) for r in ctx["records"])
    idle = [v for _, v in program_spans.under(
        (t or {}).get("idle_by_span", {}), "engine/")]
    if not cycles or not idle:
        return None
    return sum(idle) * 1e6 / cycles
