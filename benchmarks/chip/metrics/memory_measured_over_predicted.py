"""The device memory peak the traced window reads (``Summary.memory_peak_bytes``:
the largest over the cell's chips of the bytes allocated plus reserved) over
the bytes a device the cost model predicts for the plan
(``plan.predicted_memory``), the number ``plan_check`` holds to the chip's
memory.  Nothing to read where the plan carries no prediction (the
one-device plan is not costed) or the trace holds no allocator events."""
import math


def read(ctx):
    t = ctx.trace
    if t is None or not t.memory_peak_bytes:
        return None
    p = ctx.plan.predicted_memory
    if not (p > 0 and math.isfinite(p)):
        return None
    return t.memory_peak_bytes / p
