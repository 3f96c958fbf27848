"""``repro.serving`` — the supported serving entry point.

The serving surface is a frozen :class:`ServeConfig` (model / plan / cache /
scheduler / SLO sections, statically validated against the GALV08x plan-check
codes in ``__post_init__``) plus one constructor::

    from repro import serving

    config = serving.ServeConfig(
        arch="qwen2.5-3b", reduced=True,
        cache=serving.CacheConfig(max_context=256, page_size=16),
        scheduler=serving.SchedulerConfig(num_slots=8, prefill_chunk=32),
        slo=serving.SLOConfig(ttft_s=0.5, tpot_s=0.05))
    engine = serving.build(config)

    stream = engine.submit(serving.Request(prompt=ids, max_new=64))
    for token in stream:          # drives engine.tick() under the hood
        ...
    engine.stats()                # queue depth, free pages, tokens out …

``build`` returns a :class:`ServeSession` wrapping the continuous-batching
scheduler (``repro.runtime.scheduler``) over the paged KV cache
(``repro.runtime.kv_cache``).  The older step-level ``ServingEngine`` remains
available for mesh-sharded prefill/decode, but constructing it directly is
lint-banned outside this package — go through :func:`step_engine`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

from repro.analysis import plan_check as pc
from repro.configs.arch import arch_of
from repro.configs.registry import ModelConfig, get_config
from repro.core.cluster import TPU_V5E_POD, ClusterSpec
from repro.core.strategy import ExecutionPlan, LayerStrategy
from repro.runtime.kv_cache import CacheOOM, PagedCacheConfig
from repro.runtime.scheduler import (ContinuousBatchingScheduler, Request,
                                     TokenStream)

__all__ = [
    "CacheConfig", "SchedulerConfig", "SLOConfig", "ServeConfig",
    "ServeSession", "Request", "TokenStream", "CacheOOM",
    "build", "step_engine", "single_device_plan",
]


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Paged-pool geometry.  ``num_pages=None`` fully provisions every slot
    (no oversubscription, the scheduler never evicts)."""

    max_context: int = 512         # per-request ceiling: prompt + new tokens
    page_size: int = 16            # tokens per cache page
    num_pages: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Continuous-batching knobs."""

    num_slots: int = 4             # concurrent decode streams
    prefill_chunk: int = 32        # prompt tokens prefilled per tick
    temperature: float = 0.0       # default for submitted requests (<=0 greedy)
    seed: int = 0                  # base seed for temperature sampling


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """Latency / load targets.  ``None`` leaves a dimension unconstrained;
    the search (``SearchEngine.search_serve``) and the Poisson benchmark
    read these — the runtime does not enforce them."""

    ttft_s: Optional[float] = None        # p50 time-to-first-token target
    tpot_s: Optional[float] = None        # p50 time-per-output-token target
    request_rate: Optional[float] = None  # offered load, requests/second


def single_device_plan(cfg: ModelConfig, shape: str = "serve") -> ExecutionPlan:
    """The trivial 1-device plan every CPU-scale serving path uses."""
    strat = LayerStrategy()
    return ExecutionPlan(arch=cfg.name, shape=shape, mesh_axes=("data",),
                         mesh_shape=(1,),
                         layer_strategies=[strat] * cfg.num_layers,
                         default_strategy=strat)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Everything needed to stand up a serving engine, in one frozen value.

    ``__post_init__`` statically validates the cache geometry against the
    GALV08x plan-check codes (page size divides the context window, pool +
    weights fit the cluster's HBM, enough pages for the slots) — an invalid
    config raises ``ValueError`` carrying the diagnostic table, before any
    device memory is touched.
    """

    arch: str = "qwen2.5-3b"
    reduced: bool = True           # CPU-scale .reduced() variant of the arch
    plan: Optional[ExecutionPlan] = None   # None: trivial single-device plan
    cluster: Optional[ClusterSpec] = None  # None: TPU_V5E_POD
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    scheduler: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig)
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)
    init_seed: int = 0             # PRNG seed for build()'s param init

    def __post_init__(self):
        report = self.check()
        if not report.ok():
            raise ValueError("invalid ServeConfig:\n" + report.format_table())

    # ------------------------------------------------------------ derived
    def model_config(self) -> ModelConfig:
        cfg = get_config(self.arch)
        return cfg.reduced() if self.reduced else cfg

    def resolved_cluster(self) -> ClusterSpec:
        return self.cluster if self.cluster is not None else TPU_V5E_POD

    def resolved_plan(self) -> ExecutionPlan:
        if self.plan is not None:
            return self.plan
        return single_device_plan(self.model_config())

    def serve_spec(self) -> pc.ServeSpec:
        """The plan-check view of this config's cache geometry."""
        plan = self.resolved_plan()
        return pc.ServeSpec(num_slots=self.scheduler.num_slots,
                            page_size=self.cache.page_size,
                            max_context=self.cache.max_context,
                            num_pages=self.cache.num_pages,
                            tp=plan.default_strategy.tp)

    def cache_config(self) -> PagedCacheConfig:
        return PagedCacheConfig.for_model(
            self.model_config(), num_slots=self.scheduler.num_slots,
            page_size=self.cache.page_size,
            max_context=self.cache.max_context,
            num_pages=self.cache.num_pages)

    def check(self) -> pc.PlanReport:
        """The GALV08x report (plus full plan diagnostics when a non-trivial
        plan was supplied)."""
        cfg = self.model_config()
        if self.plan is not None:
            return pc.check_plan(self.plan, self.resolved_cluster(), cfg,
                                 seq_len=self.cache.max_context,
                                 serve=self.serve_spec())
        return pc.check_serve(self.serve_spec(), self.resolved_cluster(), cfg)


class ServeSession:
    """A built serving engine: ``submit(request) -> stream`` / ``tick()`` /
    ``stats()`` over a continuous-batching scheduler.  Construct with
    :func:`build`."""

    def __init__(self, config: ServeConfig,
                 scheduler: ContinuousBatchingScheduler, model: Any,
                 params: Any):
        self.config = config
        self.scheduler = scheduler
        self.model = model
        self.params = params

    def submit(self, request: Request) -> TokenStream:
        """Queue one request; returns a stream yielding its tokens (iterating
        the stream drives ``tick()`` as needed)."""
        if request.temperature == 0.0 and self.config.scheduler.temperature:
            request.temperature = self.config.scheduler.temperature
        if request.seed == 0:
            request.seed = self.config.scheduler.seed
        return self.scheduler.submit(request)

    def tick(self) -> dict:
        """One scheduling quantum: admit / prefill a chunk / decode a token."""
        return self.scheduler.tick()

    def stats(self) -> dict:
        return self.scheduler.stats()

    def run_until_drained(self, max_ticks: int = 100_000) -> None:
        self.scheduler.run_until_drained(max_ticks)


def build(config: ServeConfig, *, model: Any = None, params: Any = None,
          sink: Any = None,
          sample_fn: Optional[Callable] = None,
          clock: Optional[Callable[[], float]] = None) -> ServeSession:
    """Stand up a :class:`ServeSession` from a validated :class:`ServeConfig`.

    ``model`` / ``params`` default to a fresh ``build_model`` +
    ``init(PRNGKey(config.init_seed))`` in the serving dtype; pass trained
    params to serve real weights.  ``sink`` (a RunSink) receives the
    per-request JSONL events."""
    import jax

    from repro.models import build_model
    from repro.models.common import cast_tree

    cfg = config.model_config()
    if not arch_of(cfg).paged_serving:
        raise NotImplementedError(
            f"paged serving supports the dense cache layout; {cfg.name}'s "
            "architecture still goes through step_engine()")
    if model is None:
        model = build_model(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(config.init_seed))
    import jax.numpy as jnp

    params = cast_tree(params, jnp.bfloat16)
    kw = {} if clock is None else {"clock": clock}
    scheduler = ContinuousBatchingScheduler(
        model, params, config.cache_config(),
        prefill_chunk=config.scheduler.prefill_chunk,
        sample_fn=sample_fn, sink=sink, **kw)
    return ServeSession(config, scheduler, model, params)


def step_engine(model: Any, plan: ExecutionPlan, mesh=None, *, batch: int = 0,
                max_len: int = 0, unroll: bool = False):
    """The sanctioned constructor for the step-level ``ServingEngine``
    (mesh-sharded prefill/decode, dry-run lowering).  Direct
    ``ServingEngine(...)`` construction outside ``repro.serving`` is
    lint-banned — new code should prefer :func:`build`."""
    from repro.runtime.serve import ServingEngine

    return ServingEngine(model, plan, mesh, batch=batch, max_len=max_len,
                         unroll=unroll)
