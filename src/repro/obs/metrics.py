"""Step timing: the step-loop instrument and its fence.

Stdlib-only (the CI lint lane imports without jax/numpy): jax is touched
lazily and only to fence (``jax.block_until_ready``) before a wall-time
reading.  :class:`StepTimer` fences on the step's outputs, times the step,
works out tokens-per-second and MFU, and hands back the
:class:`StepRecord` that the run sink's ``step`` event and the live line
are made from.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional


def fence(outputs: Any) -> None:
    """Block until ``outputs`` (any pytree of jax arrays) are computed, so a
    following wall-clock reading measures finished work, not dispatch.  A
    no-op for ``None`` and on hosts without jax."""
    if outputs is None:
        return
    try:
        import jax
    except Exception:  # pragma: no cover - jax is present in the repo env
        return
    jax.block_until_ready(outputs)


@dataclasses.dataclass
class StepRecord:
    """One timed step, as handed to the sink / live formatter."""

    step: int
    step_time_s: float
    tokens_per_sec: float = 0.0
    mfu: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class StepTimer:
    """Per-step wall-time instrument for the training loop.

    ``start()`` stamps the clock; ``stop(outputs)`` fences on the step's
    outputs (``jax.block_until_ready`` — without the fence an async backend
    would credit the step with dispatch time only), counts the step in
    ``steps`` and returns the :class:`StepRecord`.

    * ``tokens_per_step`` enables tokens/sec.
    * ``flops_per_step`` (e.g. ``ModelProfile.model_flops_per_token()`` ×
      tokens — the 6N fwd+bwd basis) together with ``peak_flops`` (cluster
      peak × device count) enables MFU.
    * ``clock`` is injectable for tests (fake clock).
    """

    def __init__(self, *,
                 tokens_per_step: int = 0,
                 flops_per_step: float = 0.0,
                 peak_flops: float = 0.0,
                 clock: Callable[[], float] = time.perf_counter,
                 fence_fn: Callable[[Any], None] = fence):
        self.tokens_per_step = tokens_per_step
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops
        self._clock = clock
        self._fence = fence_fn
        self._t0: Optional[float] = None
        self.steps = 0

    def start(self) -> None:
        self._t0 = self._clock()

    def stop(self, step: int, outputs: Any = None) -> StepRecord:
        if self._t0 is None:
            raise RuntimeError("StepTimer.stop() without start()")
        self._fence(outputs)
        dt = max(self._clock() - self._t0, 1e-12)
        self._t0 = None
        rec = StepRecord(step=step, step_time_s=dt)
        if self.tokens_per_step:
            rec.tokens_per_sec = self.tokens_per_step / dt
        if self.flops_per_step and self.peak_flops:
            rec.mfu = self.flops_per_step / dt / self.peak_flops
        self.steps += 1
        return rec
