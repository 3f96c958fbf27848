"""Device milliseconds a step in ops under the model's named scope
``head``: the LM head's matmul (``embedding.lm_head``, tied or not) and
the fp32 softmax cross-entropy (``softmax_xent``), forward and backward.
From the labels of the device trace's ops (``layer_scopes``), averaged
over the cell's chips.  Nothing to read where the program names no such
scope."""
import layer_scopes


def read(ctx):
    return layer_scopes.layer_ms(ctx, "head")
