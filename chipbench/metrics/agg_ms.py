"""Device milliseconds per round of server aggregation: the in-process
engine's association-fixed upload fold and finalizer, and the wire
engine's aggregator (``update`` and ``final`` of ``fl/robust.py``)."""

PROGRAMS = ("weighted_row_fold", "finalize", "update", "final")


def read(ctx):
    progs = ctx["trace"]["programs"]
    hit = [progs[p]["seconds"] for p in PROGRAMS if p in progs]
    if not hit:
        return None
    return 1e3 * sum(hit) / ctx["rounds"]
