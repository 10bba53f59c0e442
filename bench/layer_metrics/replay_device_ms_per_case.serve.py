"""Device time of the serving path's closed-loop replay per case, in ms.

The union of the device ops of every XLA module whose name holds
``closed_loop_replay`` (`stack.feedback.closed_loop_replay`, which
every macro-round of `run_serving_cosim` dispatches).  The replay runs
more device ops a job than the profiler's buffer holds, so the device
trace stops before the traced window ends (at ``ops_to_s``).  The
reading is therefore a sample of the window's first part: the replay's
share of that part, times the window's wall time per (scenario,
machine) case.  It assumes the dropped rest runs as the sampled part
does; it sees no idle there.
"""


def read(ctx: dict):
    t = ctx["trace"]
    cases = sum(r.get("cases", 0) for r in ctx["records"])
    if not t or not cases or not t.get("ops_to_s"):
        return None
    secs = sum(s for name, s in t["modules"] if "closed_loop_replay" in name)
    if secs <= 0:
        return None
    return secs / t["ops_to_s"] * t["window_s"] * 1e3 / cases
