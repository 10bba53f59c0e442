"""Mean multigrid V-cycles per steady solve, from the solver's own count."""


def read(ctx: dict):
    its = [r["iterations"] for r in ctx["records"] if "iterations" in r]
    return sum(its) / len(its) if its else None
