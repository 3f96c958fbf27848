"""Seconds the planner took: the harness's host span around
``SearchEngine.search`` and ``plan_check.check_plan``.  Nothing to read where
the cell's plan is not searched."""


def read(ctx):
    return ctx.spans.seconds("search")
