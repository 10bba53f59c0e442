"""95th percentile of the job latency in ms, over every job of the window."""
from bench import common


def read(ctx: dict):
    lat = [r["latency_s"] * 1e3 for r in ctx["records"]]
    return common.percentile(lat, 95) if lat else None
