"""Device milliseconds a step in ops under the model's named scope
``embed``: the token gather (``embedding.embed_tokens``) and, in the
backward, its scatter-add into the embedding's gradient.  From the
labels of the device trace's ops (``layer_scopes``), averaged over the
cell's chips.  Nothing to read where the program names no such scope."""
import layer_scopes


def read(ctx):
    return layer_scopes.layer_ms(ctx, "embed")
