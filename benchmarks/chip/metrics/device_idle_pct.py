"""Share of the traced window in which no op ran on the device: 1 minus the
union of the op intervals over the window, averaged over the cell's chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
