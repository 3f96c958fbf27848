"""Device milliseconds a step in collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all), whether or not other ops
ran beside them, averaged over the cell's chips; ``collective_exposed_ms``
is the part of it with nothing else running.  Nothing to read where no
collective runs."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps or not t.collective_s:
        return None
    return 1e3 * t.collective_s / t.steps
