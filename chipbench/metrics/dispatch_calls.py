"""Device program launches per round in the traced window (executor:
tier-chunk dispatch, fold, finalize, histogram, eval)."""


def read(ctx):
    progs = ctx["trace"]["programs"]
    if not progs:
        return None
    return sum(p["calls"] for p in progs.values()) / ctx["rounds"]
