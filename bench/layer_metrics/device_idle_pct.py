"""Share of the traced window in which no op ran on the device, in %."""


def read(ctx: dict):
    t = ctx["trace"]
    if not t or not t["n_devices"] or t["busy_s"] <= 0:
        return None
    return t["idle_pct"]
