"""Share of the traced window, the span from the first to the last
program on the device, in which no operation ran on the device."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
