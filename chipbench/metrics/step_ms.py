"""Device milliseconds per round of the round step, ``tier_chunk_defer``:
each participant's download, local SGD and upload."""

PROGRAMS = ("tier_chunk_defer",)


def read(ctx):
    progs = ctx["trace"]["programs"]
    hit = [progs[p]["seconds"] for p in PROGRAMS if p in progs]
    if not hit:
        return None
    return 1e3 * sum(hit) / ctx["rounds"]
