"""Device milliseconds a step in ops under the model's named scope
``attention``: dense blocks' attention: q/k/v projections, qk-norm,
RoPE, the core (ring, flash or reference) and the o-projection, forward,
recompute and backward.  From the labels of the device trace's ops
(``layer_scopes``), averaged over the cell's chips.  Nothing to read
where the program names no such scope."""
import layer_scopes


def read(ctx):
    return layer_scopes.layer_ms(ctx, "attention")
