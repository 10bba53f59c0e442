"""Coarse intervals the serving replay steps through per case.

The program's coarse plan (`cosim.coarsen_plan`, padded by
`CoarsePlan.pad_to`) of every case of the window's jobs, as each job
record carries it (``n_coarse``): fewer intervals, fewer replay steps.
"""


def read(ctx: dict):
    counts = [n for r in ctx["records"] for n in r.get("n_coarse", ())]
    return sum(counts) / len(counts) if counts else None
