"""The cost model's predicted step time (``plan.predicted_step_time``) over
the window's median fenced step time.  Nothing to read where the plan
carries no prediction (the one-device plan is not costed)."""
import math


def read(ctx):
    p = ctx.plan.predicted_step_time
    if not (p > 0 and math.isfinite(p)):
        return None
    return p / ctx.median_step_s()
