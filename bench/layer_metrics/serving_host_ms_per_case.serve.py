"""Device-idle time under the serving path's host spans per case, in ms.

Reads ``idle_by_span`` (`bench.program_spans`): the idle stretches of
the traced window put down to a `serving/*` span or a span inside it,
over the (scenario, machine) cases of the window's jobs.  The blocking
`sync/replay` transfer is left out: the host waits there on a busy
device, and once the profiler's buffer has dropped the replay's ops
(see `replay_device_ms_per_case.serve`) the trace shows that wait as
idle.
"""
from bench import program_spans


def read(ctx: dict):
    t = ctx["trace"]
    cases = sum(r.get("cases", 0) for r in ctx["records"])
    idle = [v for path, v in program_spans.under(
        (t or {}).get("idle_by_span", {}), "serving/")
        if not program_spans.is_sync(path)]
    if not cases or not idle:
        return None
    return sum(idle) * 1e3 / cases
