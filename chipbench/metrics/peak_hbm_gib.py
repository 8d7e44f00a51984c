"""Peak device memory of the run, ``memory_stats()["peak_bytes_in_use"]``
on the fullest chip, read once the window has closed."""


def read(ctx):
    peak = ctx["prog"]["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
