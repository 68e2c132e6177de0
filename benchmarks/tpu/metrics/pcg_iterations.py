"""pcg_iterations: mean CGResult.iterations over the window's solves (counter)."""


def read(rec):
    return sum(s[0] for s in rec.stats) / len(rec.stats) if rec.stats else None
