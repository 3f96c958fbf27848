"""Nested trace spans with a pure-Python fallback timeline.

``span("fwd")`` is a context manager that (a) records a
:class:`SpanRecord` into an in-process timeline — name, start/end, depth,
parent — and (b) enters a ``jax.profiler.TraceAnnotation`` so the same
span shows up in a real JAX profile when one is being captured.  On hosts
without jax the annotation degrades to a no-op and the Python timeline is
the whole story.

Spans nest per-thread: the active-span stack is thread-local, so serving
worker threads each get a coherent parent chain.

Host garbage collection is recorded on the same clock: importing
``repro.obs`` installs :func:`gc_hook` in ``gc.callbacks``, which makes
each collection a span ``gc_gen<N>`` (N the generation collected) on the
default tracer and, while a profiler runs, an annotation of the same name
on the profiler's host plane.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time
from typing import Callable, Iterator, Optional

import contextlib


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation``, or a nullcontext without jax.

    Imported lazily so ``repro.obs`` stays importable without jax (the
    lint lane and ``scripts/render_run.py`` both need that)."""
    try:
        import jax.profiler
    except ImportError:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class SpanRecord:
    """One closed (or still-open) span in the fallback timeline."""

    name: str
    t0: float
    t1: Optional[float] = None
    depth: int = 0
    parent: Optional[str] = None

    @property
    def duration_s(self) -> float:
        if self.t1 is None:
            raise ValueError(f"span {self.name!r} is still open")
        return self.t1 - self.t0

    def as_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "depth": self.depth, "parent": self.parent}


class Tracer:
    """Collects a timeline of nested spans.

    Records are appended at span *start*, so the timeline reads in
    chronological-open order (parents before children) and an open span
    left behind by a crash is still visible with ``t1=None``."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._local = threading.local()
        self._lock = threading.Lock()
        self.records: list[SpanRecord] = []

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def begin(self, name: str) -> SpanRecord:
        """Open a span whose end comes as a separate call (:meth:`end`), for
        callbacks that see a start and a stop.  Its parent is the span open
        on this thread; it is not pushed on the stack itself.  The record is
        appended without the lock (one ``list.append``, atomic under the
        GIL): a garbage collection may call this while the lock is held."""
        stack = self._stack()
        rec = SpanRecord(name=name, t0=self._clock(), depth=len(stack),
                         parent=stack[-1].name if stack else None)
        self.records.append(rec)
        return rec

    def end(self, rec: SpanRecord) -> None:
        rec.t1 = self._clock()

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[SpanRecord]:
        rec = self.begin(name)
        stack = self._stack()
        stack.append(rec)
        try:
            with _annotation(name):
                yield rec
        finally:
            stack.pop()
            self.end(rec)

    def timeline(self) -> list[dict]:
        """JSON-serializable chronological timeline of all recorded spans."""
        with self._lock:
            return [r.as_dict() for r in self.records]

    def total(self, name: str) -> float:
        """Summed duration of every *closed* span with this name."""
        with self._lock:
            return sum(r.duration_s for r in self.records
                       if r.name == name and r.t1 is not None)

    def clear(self) -> None:
        with self._lock:
            self.records.clear()


# Module-level default tracer: instrumentation call sites use
# ``obs.span("...")`` without threading a Tracer handle everywhere; tests
# and the launchers that want an isolated timeline construct their own.
_DEFAULT = Tracer()


def default_tracer() -> Tracer:
    return _DEFAULT


def span(name: str):
    """``with obs.span("compile"): ...`` on the default tracer."""
    return _DEFAULT.span(name)


# --------------------------------------------------------------------------
# host garbage collection
# --------------------------------------------------------------------------

_GC_NAMES = tuple(f"gc_gen{g}" for g in range(3))
_GC_OPEN: list = [None, None]   # the collection running: [record, annotation]


def gc_hook(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry: one span ``gc_gen<N>`` on the default tracer
    per collection, and a ``TraceAnnotation`` of that name while a profiler
    runs (so the pause shows on the trace's host plane).  Without a
    profiler it costs two clock readings and a list append.  Collections do
    not nest, so one slot holds the open one."""
    if phase == "start":
        name = _GC_NAMES[info["generation"]]
        prof = sys.modules.get("jax.profiler")
        ann = None
        if prof is not None and prof.TraceAnnotation.is_enabled():
            ann = prof.TraceAnnotation(name)
            ann.__enter__()
        _GC_OPEN[:] = (_DEFAULT.begin(name), ann)
    elif _GC_OPEN[0] is not None:
        rec, ann = _GC_OPEN
        _GC_OPEN[:] = (None, None)
        _DEFAULT.end(rec)
        if ann is not None:
            ann.__exit__(None, None, None)


if gc_hook not in gc.callbacks:
    gc.callbacks.append(gc_hook)
