"""Device busy time per steady solve in the traced window, in ms."""


def read(ctx: dict):
    t = ctx["trace"]
    n = len(ctx["records"])
    if not t or not n or t["busy_s"] <= 0:
        return None
    return t["busy_s"] * 1e3 / n
