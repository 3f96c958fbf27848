"""Seconds in ``jit_train_step().lower(...).compile()``: an XLA compile, or
a load from the persistent compilation cache."""


def read(ctx):
    return ctx.spans.seconds("compile")
