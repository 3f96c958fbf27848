"""Device milliseconds a step in ops under the model's named scope ``ssd``:
the SSD: discretization (A, dt), the chunked scan (``ssd_ops.ssd``, any
``impl``) and the D skip, forward, recompute and backward.  From the
labels of the device trace's ops (``layer_scopes``), averaged over the
cell's chips.  Nothing to read where the program names no such scope."""
import layer_scopes


def read(ctx):
    return layer_scopes.layer_ms(ctx, "ssd")
