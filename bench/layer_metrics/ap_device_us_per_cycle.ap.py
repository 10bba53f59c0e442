"""Device busy time per emulated AP cycle in the traced window, in us."""


def read(ctx: dict):
    t = ctx["trace"]
    cycles = sum(r.get("cycles", 0) for r in ctx["records"])
    if not t or not cycles or t["busy_s"] <= 0:
        return None
    return t["busy_s"] * 1e6 / cycles
