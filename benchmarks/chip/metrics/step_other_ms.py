"""Device milliseconds a step that the device is busy outside ops under
``fwd_bwd`` and ``optimizer``: ops whose HLO carries neither scope (or no
``op_name`` at all), and the stretches inside a loop's own event between
its body's ops, which the busy time counts and no op does.  Busy time
less ``fwd_bwd_ms`` and ``optimizer_ms``, from the device trace,
averaged over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps or not t.scope_s.get("fwd_bwd"):
        return None
    scoped = t.scope_s["fwd_bwd"] + t.scope_s.get("optimizer", 0.0)
    return 1e3 * (t.busy_s - scoped) / t.steps
