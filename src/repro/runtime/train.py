"""Hybrid-parallel training runtime.

``construct_hybrid_parallel_model`` (named after the paper's API) takes a
model + :class:`ExecutionPlan` and returns a bundle with:

* grouped/sharded parameter structure (per-layer-group strategies),
* a jit-able ``train_step(params, opt_state, batch)`` whose internals apply
  the plan: per-group axis rules, remat policies, gradient-accumulation,
  ZeRO-driven sharding constraints on grads/optimizer state,
* the sharding trees needed for ``jax.jit(in_shardings=...)`` / checkpointing.

The per-group ``lax.scan`` chains keep compiled-HLO size O(#groups), not
O(#layers) — essential for the 40-cell dry-run compile budget.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro import obs  # noqa: F401 -- importing it installs obs.gc_hook
from repro.compat import Mesh, NamedSharding, P
from repro.configs.arch import arch_of
from repro.core.strategy import ExecutionPlan
from repro.models.common import scoped
from repro.parallel import sharding as shd
from repro.parallel.axes import axis_rules
from repro.parallel.remat import apply_remat
from repro.parallel import zero_head as zh_lib
from repro.runtime import checkpoint as ckpt_lib
from repro.runtime import optimizer as opt_lib

AUX_LOSS_WEIGHT = 0.01
Z_LOSS_WEIGHT = 1e-4


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

@scoped("head")
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray):
    """logits (B,S,V) fp32; labels (B,S) int32, -1 = masked.  Returns
    (mean nll + z-loss, metrics dict).

    The label log-prob is extracted with an iota-masked reduction rather than
    ``take_along_axis``: a gather over the vocab-sharded logits would make
    GSPMD all-gather the full fp32 logits per device, while the masked
    reduce partitions cleanly along the vocab axis (one psum of (B,S))."""
    valid = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    ll = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
    nll = (lse - ll) * valid
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    loss = jnp.sum(nll) / denom
    zloss = Z_LOSS_WEIGHT * jnp.sum(jnp.square(lse) * valid) / denom
    return loss + zloss, {"nll": loss, "zloss": zloss, "tokens": jnp.sum(valid)}


#: The checkout's root: source paths in op metadata are taken relative to it.
_ROOT = pathlib.Path(__file__).resolve().parents[3]


def key_compile_cache_on_metadata() -> None:
    """Make JAX's persistent compilation cache key on op metadata too.  By
    default the key leaves metadata out, so an executable cached by a
    program that differs from this one only in named scopes is loaded with
    that program's op names, and a profile of this step is read with stale
    scopes.  Source paths in the metadata are made relative to the
    checkout, so the key stays the same when the checkout moves.  Called
    before the train step is jitted."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{_ROOT}{os.sep}"))


# --------------------------------------------------------------------------
# layer runner (per-group strategies + remat)
# --------------------------------------------------------------------------

def make_layer_runner(plan: ExecutionPlan, mesh: Optional[Mesh], unroll: bool = False):
    from repro.models.common import scan_or_unroll

    groups = plan.groups()

    def runner(blocks, x, apply_block):
        if isinstance(blocks, dict) and not plan.uniform() and any(
                k.startswith("g") for k in blocks):
            items = [(blocks[f"g{i:03d}"], g.strategy) for i, g in enumerate(groups)]
        else:
            strat = plan.layer_strategies[0] if plan.layer_strategies else plan.default_strategy
            items = [(blocks, strat)]

        extra = jnp.float32(0.0)
        for stacked_params, strat in items:
            rules = shd.act_rules(plan, strat, mesh)
            with axis_rules(rules):
                fn = apply_remat(apply_block, strat.remat)

                def body(carry, lp, fn=fn):
                    h, ex = carry
                    h2, e2 = fn(lp, h)
                    return (h2, ex + e2), None

                (x, extra), _ = scan_or_unroll(body, (x, extra), stacked_params,
                                               unroll=unroll)
        return x, extra

    return runner


# --------------------------------------------------------------------------
# hybrid parallel model bundle
# --------------------------------------------------------------------------

@dataclasses.dataclass
class HybridParallelModel:
    model: Any
    plan: ExecutionPlan
    mesh: Optional[Mesh]
    opt_cfg: opt_lib.AdamWConfig
    unroll: bool = False           # dry-run: unroll layer loops for exact FLOPs

    # filled by construct_hybrid_parallel_model
    param_specs: Any = None
    grad_specs: Any = None
    opt_specs: Any = None
    batch_spec: Any = None
    zero_head: Optional[zh_lib.ZeroHead] = None   # the head weight's ZeRO-3 layout

    # ------------------------------------------------------------ params
    @property
    def _supports_grouping(self) -> bool:
        return arch_of(self.model.cfg).stacked

    def group(self, params):
        return shd.group_blocks(params, self.plan, self._supports_grouping)

    def ungroup(self, params):
        return shd.ungroup_blocks(params, self.plan, self._supports_grouping)

    def init_params(self, key):
        return self.group(self.model.init(key))

    def abstract_params(self):
        return self.group(self.model.abstract())

    def init_opt_state(self, params):
        return opt_lib.adamw_init(params, self.opt_cfg)

    def abstract_opt_state(self):
        return opt_lib.abstract_adamw_state(self.abstract_params(), self.opt_cfg)

    # rebuild-from-state entry points (live elastic resize / restore): take
    # the *canonical* (ungrouped) trees and lay them out for THIS trainer's
    # plan and mesh — the counterpart of init_params for migrated state.
    def place_params(self, canonical_params):
        grouped = self.group(jax.tree.map(jnp.asarray, canonical_params))
        if self.mesh is None:
            return grouped
        return jax.device_put(grouped, self.shardings(self.param_specs))

    def place_opt_state(self, canonical_opt: opt_lib.AdamWState) -> opt_lib.AdamWState:
        place = lambda tree, specs: (
            jax.tree.map(jnp.asarray, self.group(tree)) if self.mesh is None
            else jax.device_put(self.group(jax.tree.map(jnp.asarray, tree)),
                                self.shardings(specs)))
        step = jnp.asarray(canonical_opt.step)
        if self.mesh is not None:
            step = jax.device_put(step, NamedSharding(self.mesh, P()))
        return opt_lib.AdamWState(step=step,
                                  m=place(canonical_opt.m, self.opt_specs),
                                  v=place(canonical_opt.v, self.opt_specs))

    def checkpoint_state(self, params, opt_state=None):
        """Canonical-state handoff to the checkpoint writer: the ungrouped
        trees with device→host copies already started, so an async save
        overlaps its transfers with the next step's compute."""
        return ckpt_lib.canonical_checkpoint_state(self, params, opt_state)

    def opt_state_specs(self):
        return opt_lib.AdamWState(step=P(), m=self.opt_specs, v=self.opt_specs)

    def shardings(self, tree_of_specs):
        if self.mesh is None:
            return None
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), tree_of_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _constrain(self, tree, specs):
        if self.mesh is None:
            return tree
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, s))
            if hasattr(x, "shape") else x,
            tree, specs)

    # ------------------------------------------------------------ steps
    @scoped("model")
    def loss_fn(self, params, batch):
        """Forward and loss under the named scope ``model``, so that the
        model's layer scopes stay plain path components of the backward's
        op names (``fwd_bwd/transpose(jvp(model))/.../ssd/...``)."""
        runner = make_layer_runner(self.plan, self.mesh, unroll=self.unroll)
        kwargs = {}
        if "vis_embeds" in batch:
            kwargs["vis_embeds"] = batch["vis_embeds"]
        if "frames" in batch:
            kwargs["frames"] = batch["frames"]
        if self.unroll and not self._supports_grouping:
            kwargs["unroll"] = True
        if self.zero_head is not None:
            kwargs["zero_head"] = self.zero_head
        logits, extra = self.model.forward_train(
            params, batch["tokens"], layer_runner=runner, **kwargs)
        off = self.model.text_offset()
        if off:
            logits = logits[:, off:, :]
        loss, metrics = softmax_xent(logits, batch["labels"])
        loss = loss + AUX_LOSS_WEIGHT * extra
        metrics["aux"] = extra
        return loss, metrics

    def train_step(self, params, opt_state, batch):
        """One optimizer step over the global batch (with grad accumulation)."""
        plan = self.plan
        default_rules = shd.act_rules(plan, plan.default_strategy, self.mesh)
        with axis_rules(default_rules):
            k = max(plan.grad_accum, 1)
            # named_scope labels the fwd+bwd vs optimizer phases in HLO and
            # captured profiles (the in-jit counterpart of obs host spans)
            with jax.named_scope("fwd_bwd"):
                if k == 1:
                    (loss, metrics), grads = jax.value_and_grad(
                        self.loss_fn, has_aux=True)(params, batch)
                else:
                    micro = jax.tree.map(
                        lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)
                    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

                    def acc(carry, mb):
                        g_sum, l_sum = carry
                        (l, mets), g = jax.value_and_grad(self.loss_fn, has_aux=True)(params, mb)
                        g_sum = jax.tree.map(
                            lambda a, b: a + b.astype(jnp.float32), g_sum, g)
                        if self.mesh is not None:
                            g_sum = self._constrain(g_sum, self.grad_specs)
                        return (g_sum, l_sum + l), mets

                    (grads, loss_sum), mets_seq = jax.lax.scan(
                        acc, (g0, jnp.float32(0.0)), micro)
                    grads = jax.tree.map(lambda g: g / k, grads)
                    loss = loss_sum / k
                    metrics = jax.tree.map(lambda m: m[-1], mets_seq)

            with jax.named_scope("optimizer"):
                grads = self._constrain(grads, self.grad_specs)
                opt_state = opt_lib.AdamWState(
                    step=opt_state.step,
                    m=self._constrain(opt_state.m, self.opt_specs),
                    v=self._constrain(opt_state.v, self.opt_specs),
                )
                new_params, new_opt, stats = opt_lib.adamw_update(
                    params, grads, opt_state, self.opt_cfg)
                new_params = self._constrain(new_params, self.param_specs)
                new_opt = opt_lib.AdamWState(
                    step=new_opt.step,
                    m=self._constrain(new_opt.m, self.opt_specs),
                    v=self._constrain(new_opt.v, self.opt_specs),
                )
            metrics = dict(metrics)
            metrics["loss"] = loss
            metrics.update(stats)
        return new_params, new_opt, metrics

    def jit_train_step(self, donate: bool = True):
        """jit with explicit in/out shardings (None mesh -> plain jit)."""
        key_compile_cache_on_metadata()
        if self.mesh is None:
            return compat.jit(self.train_step, donate_argnums=(0, 1) if donate else ())
        ps = self.shardings(self.param_specs)
        os_ = opt_lib.AdamWState(
            step=NamedSharding(self.mesh, P()),
            m=self.shardings(self.opt_specs),
            v=self.shardings(self.opt_specs))
        return compat.jit(
            self.train_step,
            in_shardings=(ps, os_, None),
            donate_argnums=(0, 1) if donate else (),
        )


def construct_hybrid_parallel_model(
    model,
    plan: ExecutionPlan,
    mesh: Optional[Mesh] = None,
    opt_cfg: Optional[opt_lib.AdamWConfig] = None,
    unroll: bool = False,
) -> HybridParallelModel:
    """The paper's runtime entry point (Fig. 2 line 13)."""
    hp = HybridParallelModel(model=model, plan=plan, mesh=mesh,
                             opt_cfg=opt_cfg or opt_lib.AdamWConfig(), unroll=unroll)
    hp.param_specs = shd.param_spec_tree(model, plan, mesh, kind="param")
    hp.grad_specs = shd.param_spec_tree(model, plan, mesh, kind="grad")
    hp.opt_specs = shd.param_spec_tree(model, plan, mesh, kind="opt")
    hp.zero_head = zh_lib.zero_head(hp.param_specs, mesh)
    hp.batch_spec = shd.batch_spec(plan)
    return hp
