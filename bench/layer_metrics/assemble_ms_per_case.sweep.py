"""Host time of the sweep's case assembly per case, in ms.

The self time of every `sweep/assemble` span in the traced window
(``span_self_s``, `bench.program_spans`), over the cases of the
window's jobs.
"""
from bench import program_spans


def read(ctx: dict):
    t = ctx["trace"]
    cases = sum(r.get("cases", 0) for r in ctx["records"])
    secs = [v for path, v in (t or {}).get("span_self_s", {}).items()
            if path.rsplit(program_spans.SEP, 1)[-1] == "sweep/assemble"]
    if not cases or not secs:
        return None
    return sum(secs) * 1e3 / cases
