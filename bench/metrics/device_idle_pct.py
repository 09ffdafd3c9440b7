"""Share of the traced window, in %, in which no operation ran on the
device (1 - union of operation intervals / window), averaged over chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s() / ctx.trace_window_s)
