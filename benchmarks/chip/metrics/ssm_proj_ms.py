"""Device milliseconds a step in ops under the model's named scope
``ssm_proj``: Mamba2's five in-projections (z, x, B, C, dt) and its
out-projection, forward, recompute and backward.  From the labels of the
device trace's ops (``layer_scopes``), averaged over the cell's chips.
Nothing to read where the program names no such scope."""
import layer_scopes


def read(ctx):
    return layer_scopes.layer_ms(ctx, "ssm_proj")
