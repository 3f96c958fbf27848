"""Serving runtime: prefill + batched decode with sharded KV caches.

``decode_32k`` / ``long_500k`` lower ``decode_step`` (one new token against a
cache of ``seq_len``).  Attention caches are sharded on the *sequence* dim
over the model axis (flash-decode style — zero padding waste for any kv-head
count); SSM states shard on heads.  Parameters follow the plan's strategy
(tp on the model axis; zero-3 additionally shards over DP for models that do
not fit replicated).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import Mesh, NamedSharding, P
from repro.configs.arch import arch_of
from repro.core.strategy import ExecutionPlan
from repro.parallel import sharding as shd
from repro.parallel.axes import axis_rules


@dataclasses.dataclass
class ServingEngine:
    model: Any
    plan: ExecutionPlan
    mesh: Optional[Mesh] = None
    batch: int = 0                 # request batch (for divisibility checks)
    max_len: int = 0               # cache capacity
    unroll: bool = False           # dry-run: unroll layer loops for exact FLOPs

    def __post_init__(self):
        self.param_specs = shd.param_spec_tree(self.model, self.plan, self.mesh, kind="param")
        self.cache_specs = shd.cache_spec_tree(
            self.model, self.plan, self.mesh, self.batch, self.max_len)
        self._rules = shd.act_rules(self.plan, self.plan.default_strategy, self.mesh)

    def abstract_params(self):
        """Serving-dtype (bf16) abstract params — no fp32 masters at inference."""
        from repro.models.common import abstract_params

        tree = abstract_params(self.model.param_defs())
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    def cast_params(self, params):
        from repro.models.common import cast_tree

        return cast_tree(params, jnp.bfloat16)

    # ------------------------------------------------------------ steps
    def prefill_step(self, params, tokens, extras=None):
        """extras: optional dict of side inputs (vis_embeds / frames) — kept
        positional because jit(in_shardings=...) forbids kwargs."""
        kwargs = dict(extras or {})
        if self.unroll:
            kwargs["unroll"] = True
        with axis_rules(self._rules):
            logits, cache = self.model.forward_prefill(
                params, tokens, max_len=self.max_len or None, **kwargs)
            if self.mesh is not None:
                cache = jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x, NamedSharding(self.mesh, s)),
                    cache, self.cache_specs)
        return logits, cache

    def decode_step(self, params, tokens, cache, cache_index, kv_len=None):
        with axis_rules(self._rules):
            logits, new_cache = self.model.forward_decode(
                params, tokens, cache, cache_index, kv_len=kv_len,
                unroll=self.unroll)
            if self.mesh is not None:
                new_cache = jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x, NamedSharding(self.mesh, s)),
                    new_cache, self.cache_specs)
        return logits, new_cache

    # ------------------------------------------------------------ jit
    def _sh(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def jit_decode_step(self, donate: bool = True):
        if self.mesh is None:
            return compat.jit(self.decode_step, donate_argnums=(2,) if donate else ())
        bspec = NamedSharding(
            self.mesh, shd.batch_spec(self.plan, self.batch or None, self.mesh))
        return compat.jit(
            self.decode_step,
            in_shardings=(self._sh(self.param_specs), bspec,
                          self._sh(self.cache_specs), None, None),
            donate_argnums=(2,) if donate else (),
        )

    def jit_prefill_step(self):
        if self.mesh is None:
            return compat.jit(self.prefill_step)
        bspec = NamedSharding(
            self.mesh, shd.batch_spec(self.plan, self.batch or None, self.mesh))
        return compat.jit(
            self.prefill_step,
            in_shardings=(self._sh(self.param_specs), bspec, None),
        )

    # ------------------------------------------------------------ simple loop
    def greedy_generate(self, params, prompt_tokens, max_new: int,
                        max_len: int, *, paged: Optional[bool] = None):
        """Greedy generation for one static batch of equal-length prompts.

        ``paged=None`` auto-routes: unsharded dense models go through the
        paged KV cache (``repro.runtime.kv_cache``) as the trivial
        B-requests-at-once case of the continuous-batching scheduler — no
        ``batch × max_len`` padded cache is ever allocated.  Mesh-sharded or
        non-dense models (and ``paged=False``) take
        :meth:`greedy_generate_reference`, the slow, obviously-correct
        synchronous loop that stays the oracle for the scheduler's
        token-for-token equivalence tests (same twin discipline as
        checkpointing)."""
        if paged is None:
            cfg = getattr(self.model, "cfg", None)
            paged = (self.mesh is None and cfg is not None
                     and arch_of(cfg).paged_serving)
        if not paged:
            return self.greedy_generate_reference(params, prompt_tokens,
                                                  max_new, max_len)
        import numpy as np

        from repro.runtime.kv_cache import PagedCacheConfig
        from repro.runtime.scheduler import (ContinuousBatchingScheduler,
                                             Request)

        B, S = prompt_tokens.shape
        cache_cfg = PagedCacheConfig.for_model(
            self.model.cfg, num_slots=B,
            page_size=min(16, max(S, 1)), max_context=max_len)
        sched = ContinuousBatchingScheduler(self.model, params, cache_cfg)
        prompts = np.asarray(prompt_tokens, np.int32)
        reqs = [sched.submit(Request(prompt=prompts[b], max_new=max_new)).request
                for b in range(B)]
        sched.run_until_drained()
        return jnp.asarray(np.stack([r.tokens for r in reqs]), jnp.int32)

    def greedy_generate_reference(self, params, prompt_tokens, max_new: int,
                                  max_len: int):
        """Reference generation loop (tests / oracle; not perf-critical):
        one padded ``batch × max_len`` cache, one synchronous decode step per
        token.  The paged path must match this token-for-token."""
        B, S = prompt_tokens.shape
        self.max_len = max_len
        self.__post_init__()
        logits, cache = self.prefill_step(params, prompt_tokens)
        out = [jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)]
        kv_len = jnp.full((B,), S, jnp.int32)
        for i in range(max_new - 1):
            tok = out[-1][:, None]
            logits, cache = self.decode_step(
                params, tok, cache, jnp.int32(S + i), kv_len=kv_len + i + 1)
            out.append(jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32))
        return jnp.stack(out, axis=1)
