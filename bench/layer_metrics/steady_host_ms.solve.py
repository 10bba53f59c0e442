"""Device-idle time under the `thermal/steady` span per solve, in ms.

Reads ``idle_by_span`` (`bench.program_spans`): the idle stretches of
the traced window put down to `thermal/steady` or a span inside it,
over the window's solves.
"""
from bench import program_spans


def read(ctx: dict):
    t = ctx["trace"]
    n = len(ctx["records"])
    idle = [v for _, v in program_spans.under(
        (t or {}).get("idle_by_span", {}), "thermal/steady")]
    if not n or not idle:
        return None
    return sum(idle) * 1e3 / n
