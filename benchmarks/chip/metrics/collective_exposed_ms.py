"""Device milliseconds a step in collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) during which no other op
ran on that device, averaged over the cell's chips.  Nothing to read where
no collective runs."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps or not t.collective_s:
        return None
    return 1e3 * t.collective_exposed_s / t.steps
