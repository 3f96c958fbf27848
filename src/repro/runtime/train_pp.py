"""Pipeline-parallel training path (plan.pp > 1), schedule-aware.

Embedding / lm-head / loss run data-parallel on every stage (replicated over
the pipe axis — cheap relative to the block stack and charged by the cost
model); the block stack is staged over the "pod" axis with the schedule from
``plan.pp_schedule`` driving :mod:`repro.parallel.pipeline`.  Supports the
stacked-block families (dense / vlm / ssm) with a uniform per-stage strategy.

Schedules:

* **gpipe** — one forward over all M = max(grad_accum, pp) microbatches,
  one backward; all M microbatch activations are live at the fwd/bwd
  boundary (what the memory model charges as M in flight).
* **1f1b** — the M microbatches are windowed into M/S rounds of S; each
  round runs forward+backward inside a ``lax.scan`` step that carries only
  the gradient accumulator, so at most S = min(pp, M) microbatch
  activations are ever live.  Gradients are token-weighted across windows,
  which makes the result bitwise-equal in exact arithmetic to the gpipe
  full-batch gradient (each window's loss is its own token-mean; the
  accumulator re-weights by window token count).
* **interleaved** — every physical stage holds ``plan.pp_interleave``
  non-contiguous layer chunks; activations traverse the ring v times.  Uses
  the same windowed grad loop as 1f1b, so the in-flight bound here is ≤ S —
  below the pp·(1+(v-1)/v) warm-up the cost model conservatively charges for
  the overlap-scheduled variant on real hardware.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import Mesh, NamedSharding, P
from repro.configs.arch import arch_of
from repro.core.dynamic_programming import (interleave_realizable,
                                            schedule_windowable)
from repro.core.strategy import ExecutionPlan
from repro.parallel import sharding as shd
from repro.parallel.axes import axis_rules
from repro.parallel.pipeline import pipeline_forward, stage_stack, unstage_stack
from repro.parallel.remat import apply_remat
from repro.runtime import checkpoint as ckpt_lib
from repro.runtime import optimizer as opt_lib
from repro.runtime.train import key_compile_cache_on_metadata, softmax_xent
from repro.models import embedding as emb_lib
from repro.models.common import scoped
from repro.models.norms import rmsnorm


@dataclasses.dataclass
class PipelineTrainer:
    model: Any
    plan: ExecutionPlan
    mesh: Mesh
    opt_cfg: opt_lib.AdamWConfig = dataclasses.field(default_factory=opt_lib.AdamWConfig)
    pipe_axis: str = "pod"

    def __post_init__(self):
        assert self.plan.pp > 1
        assert arch_of(self.model.cfg).stacked, \
            "PP path needs a stacked-block architecture"
        if self.model.cfg.num_experts:
            # XLA's SPMD partitioner check-fails on the MoE dispatch scatter
            # inside a partial-manual shard_map region (tracked upstream); MoE
            # archs use the GSPMD path with the pod axis folded into DP.
            raise NotImplementedError("pipeline runtime does not support MoE; "
                                      "use the GSPMD path (pod axis -> DP)")
        self.num_stages = self.plan.pp
        self.schedule = self.plan.pp_schedule
        self.interleave = self.plan.pp_interleave if self.schedule == "interleaved" else 1
        L = self.model.cfg.num_layers
        if self.interleave > 1 and not interleave_realizable(
                L, self.num_stages, self.interleave):
            raise ValueError(
                f"{L} layers do not split into {self.num_stages} stages × "
                f"{self.interleave} virtual chunks")
        if L % self.num_stages != 0:
            raise ValueError(f"{L} layers do not split into "
                             f"{self.num_stages} stages")
        self.strategy = self.plan.default_strategy
        self._rules = shd.act_rules(self.plan, self.strategy, self.mesh)
        base = shd.param_spec_tree(self.model, _uniform(self.plan), self.mesh, kind="param")
        self.param_specs = _stage_specs(base, self.pipe_axis, self.interleave)
        self.grad_specs = _stage_specs(
            shd.param_spec_tree(self.model, _uniform(self.plan), self.mesh, kind="grad"),
            self.pipe_axis, self.interleave)
        self.opt_specs = _stage_specs(
            shd.param_spec_tree(self.model, _uniform(self.plan), self.mesh, kind="opt"),
            self.pipe_axis, self.interleave)

    # ------------------------------------------------------------ params
    def stage_params(self, params):
        out = dict(params)
        out["blocks"] = stage_stack(params["blocks"], self.num_stages,
                                    self.interleave)
        return out

    # group/ungroup mirror HybridParallelModel so the checkpoint/driver code
    # can treat both trainers uniformly (canonical checkpoints are unstaged).
    def group(self, params):
        return self.stage_params(params)

    def ungroup(self, params):
        out = dict(params)
        out["blocks"] = unstage_stack(params["blocks"], self.interleave)
        return out

    def init_params(self, key):
        return self.stage_params(self.model.init(key))

    def abstract_params(self):
        # derive staged shapes from stage_stack itself so the abstract tree
        # can never drift from the real staging layout
        flat = self.model.abstract()
        out = dict(flat)
        out["blocks"] = jax.eval_shape(
            lambda b: stage_stack(b, self.num_stages, self.interleave),
            flat["blocks"])
        return out

    def init_opt_state(self, params):
        return opt_lib.adamw_init(params, self.opt_cfg)

    def abstract_opt_state(self):
        return opt_lib.abstract_adamw_state(self.abstract_params(), self.opt_cfg)

    # rebuild-from-state entry points, mirroring HybridParallelModel so the
    # resize/restore paths can treat both trainers uniformly: canonical
    # (unstaged) trees in, this trainer's staged+sharded layout out.
    def place_params(self, canonical_params):
        staged = self.group(jax.tree.map(jnp.asarray, canonical_params))
        return jax.device_put(staged, self.shardings(self.param_specs))

    def place_opt_state(self, canonical_opt: opt_lib.AdamWState) -> opt_lib.AdamWState:
        place = lambda tree, specs: jax.device_put(
            self.group(jax.tree.map(jnp.asarray, tree)), self.shardings(specs))
        step = jax.device_put(jnp.asarray(canonical_opt.step),
                              NamedSharding(self.mesh, P()))
        return opt_lib.AdamWState(step=step,
                                  m=place(canonical_opt.m, self.opt_specs),
                                  v=place(canonical_opt.v, self.opt_specs))

    def checkpoint_state(self, params, opt_state=None):
        """Canonical-state handoff to the checkpoint writer, mirroring
        HybridParallelModel: unstaged trees with device→host copies already
        started for the async writer."""
        return ckpt_lib.canonical_checkpoint_state(self, params, opt_state)

    def shardings(self, specs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _constrain(self, tree, specs):
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, s))
            if hasattr(x, "shape") else x, tree, specs)

    # ------------------------------------------------------------ loss
    def _num_micro(self) -> int:
        return max(self.plan.grad_accum, self.num_stages)

    def _num_windows(self) -> int:
        """Fwd+bwd rounds per step (1 = single full-batch forward/backward).

        Both 1F1B and interleaved window the step into M/S rounds of S
        microbatches so at most one round's activations are live — the
        memory bound the cost model charges them for (interleaved's
        pp·(1+(v-1)/v) warm-up charge is then an upper bound on the ≤S this
        lowering actually holds).  GPipe, by definition, does not window."""
        M, S = self._num_micro(), self.num_stages
        if (self.schedule in ("1f1b", "interleaved") and M > S
                and schedule_windowable(S, self.plan.grad_accum)):
            return M // S
        return 1

    @scoped("model")
    def _forward_loss(self, params, tokens, labels, vis_embeds, n_micro):
        """Loss over one batch slice run as ``n_micro`` pipeline microbatches,
        under the named scope ``model`` (see ``HybridParallelModel.loss_fn``)."""
        model, cfg = self.model, self.model.cfg
        B = tokens.shape[0]
        mb = B // n_micro

        x = emb_lib.embed_tokens(params["embed"], tokens, jnp.bfloat16)
        if vis_embeds is not None:
            x = jnp.concatenate([vis_embeds.astype(x.dtype), x], axis=1)
        seq, D = x.shape[1], x.shape[2]
        x_micro = x.reshape(n_micro, mb, seq, D)

        def apply_block(bp, h):
            out = self.model.block_apply(bp, h, mode="train")
            return out[0]  # (x, cache, extra) -> activations only (PP drops aux)

        def stage_fn(local_blocks, h):
            def body(carry, lp):
                return apply_remat(apply_block, self.strategy.remat)(lp, carry), None

            out, _ = jax.lax.scan(body, h, local_blocks)
            return out

        outs = pipeline_forward(params["blocks"], x_micro, stage_fn,
                                mesh=self.mesh, axis=self.pipe_axis,
                                schedule=self.schedule,
                                num_virtual=self.interleave)
        h = outs.reshape(B, seq, D)
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = emb_lib.lm_head(params["embed"], h, cfg)
        off = self.model.text_offset()
        if off:
            logits = logits[:, off:, :]
        loss, metrics = softmax_xent(logits, labels)
        return loss, metrics

    def loss_fn(self, params, batch):
        return self._forward_loss(params, batch["tokens"], batch["labels"],
                                  batch.get("vis_embeds"), self._num_micro())

    # ------------------------------------------------------------ grads
    def _loss_and_grads(self, params, batch):
        W = self._num_windows()
        if W <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads

        # 1F1B/interleaved: scan over W windows of S microbatches; each scan
        # step runs the window's forward AND backward, so only that window's
        # activations are live — at most min(pp, M) microbatches in flight.
        # Token-weighted accumulation keeps the result equal to the
        # full-batch gradient.
        S = self.num_stages
        B = batch["tokens"].shape[0]
        Bw = B // W

        def window(x):
            return x.reshape((W, Bw) + x.shape[1:])

        xs = {k: window(batch[k]) for k in ("tokens", "labels")}
        if "vis_embeds" in batch:
            xs["vis_embeds"] = window(batch["vis_embeds"])

        def one_window(p, wb):
            return self._forward_loss(p, wb["tokens"], wb["labels"],
                                      wb.get("vis_embeds"), S)

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def acc(carry, wb):
            g_sum, l_sum, d_sum = carry
            (l, mets), g = jax.value_and_grad(one_window, has_aux=True)(params, wb)
            w = mets["tokens"]
            g_sum = jax.tree.map(lambda a, b: a + w * b.astype(jnp.float32),
                                 g_sum, g)
            g_sum = self._constrain(g_sum, self.grad_specs)
            return (g_sum, l_sum + w * l, d_sum + w), mets

        (g_sum, l_sum, d_sum), mets_seq = jax.lax.scan(
            acc, (g0, jnp.float32(0.0), jnp.float32(0.0)), xs)
        denom = jnp.maximum(d_sum, 1.0)
        grads = jax.tree.map(lambda g: g / denom, g_sum)
        # whole-batch metrics, same meaning as the gpipe path: token counts
        # sum, everything else is a token-weighted mean over the windows
        toks = mets_seq["tokens"]
        metrics = {k: (jnp.sum(v) if k == "tokens"
                       else jnp.sum(v * toks) / denom)
                   for k, v in mets_seq.items()}
        return l_sum / denom, metrics, grads

    # ------------------------------------------------------------ step
    def train_step(self, params, opt_state, batch):
        with axis_rules(self._rules):
            with jax.named_scope("fwd_bwd"):
                loss, metrics, grads = self._loss_and_grads(params, batch)
            with jax.named_scope("optimizer"):
                grads = self._constrain(grads, self.grad_specs)
                new_params, new_opt, stats = opt_lib.adamw_update(
                    params, grads, opt_state, self.opt_cfg)
                new_params = self._constrain(new_params, self.param_specs)
            metrics = dict(metrics)
            metrics["loss"] = loss
            metrics.update(stats)
        return new_params, new_opt, metrics

    def jit_train_step(self, donate: bool = True):
        key_compile_cache_on_metadata()
        ps = self.shardings(self.param_specs)
        os_ = opt_lib.AdamWState(
            step=NamedSharding(self.mesh, P()),
            m=self.shardings(self.opt_specs), v=self.shardings(self.opt_specs))
        return compat.jit(self.train_step, in_shardings=(ps, os_, None),
                          donate_argnums=(0, 1) if donate else ())


def _uniform(plan: ExecutionPlan) -> ExecutionPlan:
    """Plan with uniform strategy (PP path applies one strategy per stage)."""
    return dataclasses.replace(
        plan, layer_strategies=[plan.default_strategy] * len(plan.layer_strategies))


def _stage_specs(spec_tree: dict, pipe_axis: str, interleave: int = 1) -> dict:
    """Prepend the pipe-axis sharding to every blocks spec (staged dim0);
    interleaved stacks carry an extra unsharded virtual-chunk dim1."""
    out = dict(spec_tree)
    lead = (None, None) if interleave > 1 else (None,)

    def add(s: P) -> P:
        parts = tuple(s)
        # original dim0 is "layers" (never sharded) -> replace by (pipe, ...)
        return P(pipe_axis, *(lead + parts[1:] if parts else lead))

    out["blocks"] = jax.tree.map(
        lambda s: add(s), spec_tree["blocks"], is_leaf=lambda x: isinstance(x, P))
    return out
