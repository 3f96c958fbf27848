"""Device milliseconds a step in ops under the model's named scope
``norm``: every RMSNorm: the blocks' norms, Mamba2's gated norm
``rmsnorm(y * silu(z))`` and the final norm, forward, recompute and
backward.  From the labels of the device trace's ops (``layer_scopes``),
averaged over the cell's chips.  Nothing to read where the program names
no such scope."""
import layer_scopes


def read(ctx):
    return layer_scopes.layer_ms(ctx, "norm")
