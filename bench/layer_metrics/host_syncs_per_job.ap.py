"""Blocking device-to-host transfers of the AP engine per job.

The `sync/*` spans inside `engine/*` spans in the traced window
(``span_counts``, `bench.program_spans`), over the window's jobs.
"""
from bench import program_spans


def read(ctx: dict):
    t = ctx["trace"]
    n = len(ctx["records"])
    syncs = [c for path, c in program_spans.under(
        (t or {}).get("span_counts", {}), "engine/")
        if program_spans.is_sync(path)]
    if not n or not syncs:
        return None
    return sum(syncs) / n
