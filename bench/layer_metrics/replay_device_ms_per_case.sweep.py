"""Device time of the closed-loop replay program per case, in ms.

The union of the device ops of every XLA module whose name holds
``closed_loop`` in the traced window, over the cases the window's jobs
replayed.
"""


def read(ctx: dict):
    t = ctx["trace"]
    cases = sum(r.get("cases", 0) for r in ctx["records"])
    if not t or not cases:
        return None
    secs = sum(s for name, s in t["modules"] if "closed_loop" in name)
    return secs * 1e3 / cases if secs > 0 else None
