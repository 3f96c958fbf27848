"""Device milliseconds a step in ops under the runtime's named scope
``optimizer`` (clipping, AdamW, the ZeRO constraints), from the device
trace, averaged over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps or not t.scope_s.get("optimizer"):
        return None
    return 1e3 * t.scope_s["optimizer"] / t.steps
