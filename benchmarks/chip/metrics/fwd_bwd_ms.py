"""Device milliseconds a step in ops under the runtime's named scope
``fwd_bwd`` (forward and backward), from the device trace, averaged over
the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps or not t.scope_s.get("fwd_bwd"):
        return None
    return 1e3 * t.scope_s["fwd_bwd"] / t.steps
