"""repro.obs — runtime telemetry: step timing, trace spans (host garbage
collection among them), run sink, drift.

Stdlib-only at import time (jax is reached lazily, only to fence timers
and enter profiler annotations), so the lint lane and the offline report
renderer (``scripts/render_run.py``) can import it without an accelerator
stack on the path.  Importing it installs :func:`gc_hook`.
"""
from repro.obs.drift import (DEFAULT_SUSTAIN_STEPS, DEFAULT_WARMUP_STEPS,
                             DRIFT_RATIO_THRESHOLD, DriftMonitor,
                             DriftVerdict)
from repro.obs.metrics import StepRecord, StepTimer, fence
from repro.obs.sink import (SCHEMA_VERSION, CorruptRunLogError, NullSink,
                            RunSink, StaleRunLogError, format_live_line,
                            read_run)
from repro.obs.trace import SpanRecord, Tracer, default_tracer, gc_hook, span

__all__ = [
    "StepRecord", "StepTimer", "fence",
    "SpanRecord", "Tracer", "default_tracer", "gc_hook", "span",
    "SCHEMA_VERSION", "RunSink", "NullSink", "read_run",
    "format_live_line", "CorruptRunLogError", "StaleRunLogError",
    "DriftMonitor", "DriftVerdict", "DRIFT_RATIO_THRESHOLD",
    "DEFAULT_SUSTAIN_STEPS", "DEFAULT_WARMUP_STEPS",
]
