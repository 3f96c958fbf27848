"""Host milliseconds a step of the window spent in Python's garbage
collector: the ``gc_gen<N>`` spans that ``repro.obs`` records on its
default tracer (``time.perf_counter``, the clock of the harness's spans),
clipped to the window, from its first ``dispatch`` span to its last
``fence`` span.  Nothing to read where the program has not installed the
collection hook."""
import gc
import sys


def read(ctx):
    obs = sys.modules.get("repro.obs")
    if getattr(obs, "gc_hook", None) not in gc.callbacks:
        return None
    dispatch = [(t0, t1) for n, t0, t1 in ctx.spans.records if n == "dispatch"]
    fence = [t1 for n, _, t1 in ctx.spans.records if n == "fence"]
    if not dispatch or not fence:
        return None
    w0, w1 = dispatch[0][0], fence[-1]
    gc_s = sum(max(0.0, min(r.t1, w1) - max(r.t0, w0))
               for r in obs.default_tracer().records
               if r.name.startswith("gc_gen") and r.t1 is not None)
    return 1e3 * gc_s / len(dispatch)
