"""Device milliseconds a step under ``fwd_bwd`` and under none of the
model's layer scopes (``layer_scopes.LAYERS``): the layer scan's slicing
and stacking of saved activations, casts, residual adds, the loss's
seeding of the backward.  ``fwd_bwd_ms`` less the layer metrics; a
negative value would mean two layer scopes overlap.  Nothing to read where
the program names no layer scope."""
import layer_scopes


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps or not t.scope_s.get("fwd_bwd"):
        return None
    layers = layer_scopes.layer_s(t)
    if not any(layers.values()):
        return None
    return 1e3 * (t.scope_s["fwd_bwd"] - sum(layers.values())) / t.steps
