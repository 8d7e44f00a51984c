"""Device milliseconds per round of the three Pallas kernels
(``magnitude_histogram``, ``hybrid_compress``, ``recover``)."""


def read(ctx):
    ks = ctx["trace"]["kernels"]
    if not ks:
        return None
    return 1e3 * sum(k["seconds"] for k in ks.values()) / ctx["rounds"]
