"""LayerStrategy -> GSPMD sharding rules.

Two rule sets per strategy:

* **activation rules** — consumed by ``lc()`` inside model code.  ``batch``
  maps to the DP axes; ``seq`` maps to the model axis only under sequence
  parallelism (block boundaries — Megatron-SP semantics: inside the TP region
  activations are head-/ff-sharded and full-sequence, so inner ``lc`` calls
  pass ``None`` for seq); head/ff axes map to the model axis under TP.

* **parameter rules** — used to build ``in_shardings`` for params, grads and
  optimizer state.  TP shards head/ff/vocab dims on the model axis; ZeRO-3
  additionally shards the ``embed``/``norm`` dims over the DP axes.  ZeRO-1/2
  keep params replicated but shard optimizer state (and grads for ZeRO-2)
  with the ZeRO-3 layout — GSPMD then emits exactly the reduce-scatter +
  all-gather schedule ZeRO prescribes.

Non-divisible dims (e.g. 40 query heads on a 16-wide model axis) are left to
GSPMD's uneven-sharding padding; the search engine's cost model penalizes the
padding with ceil() arithmetic, so such strategies lose the search unless
they are genuinely worth it.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.compat import Mesh, NamedSharding, P
from repro.configs.arch import arch_of
from repro.core.strategy import ExecutionPlan, LayerStrategy
from repro.models.common import ParamDef
from repro.parallel.axes import MeshRules

# logical axes that tensor parallelism shards over the model axis
_TP_PARAM_AXES = ("q_heads", "kv_heads", "ff", "vocab", "ssm_inner", "ssm_heads")
_TP_ACT_AXES = ("q_heads", "kv_heads", "ff", "vocab", "ssm_inner", "ssm_heads")


def act_rules(plan: ExecutionPlan, strategy: LayerStrategy, mesh: Optional[Mesh]) -> MeshRules:
    dp = plan.dp_axes_for(strategy)
    tp = plan.tp_axis if strategy.tp > 1 else None
    cp = plan.cp_axis if strategy.cp > 1 and "cp" in plan.mesh_axes else None
    rules: dict = {"batch": dp}
    seq_targets = tuple(t for t in (cp, tp if strategy.sp else None) if t)
    if seq_targets:
        # boundary seq: cp shards it everywhere, sp additionally over tp
        rules["seq"] = seq_targets if len(seq_targets) > 1 else seq_targets[0]
    if cp:
        # inner (TP-region) seq stays cp-sharded — ring attention consumes it
        rules["cp_seq"] = cp
    if tp:
        for ax in _TP_ACT_AXES:
            rules[ax] = tp
    if strategy.ep > 1:
        rules["experts"] = "data"
    rules["moe_capacity"] = dp          # spec() dedup resolves overlaps
    return MeshRules(rules=rules, mesh=mesh, ring=cp)


def param_rules(
    plan: ExecutionPlan,
    strategy: LayerStrategy,
    mesh: Optional[Mesh],
    *,
    zero_sharded: bool,        # True => apply the ZeRO dp-sharding layout
) -> MeshRules:
    # params replicate over cp (only activations shard their seq dim), so the
    # ZeRO layout may spread states over dp·cp — state_axes_for adds "cp"
    dp = plan.state_axes_for(strategy)
    rules: dict = {}
    if strategy.tp > 1:
        for ax in _TP_PARAM_AXES:
            rules[ax] = plan.tp_axis
    if strategy.ep > 1:
        rules["experts"] = "data"
    if zero_sharded:
        # shard the "other" dim of matrices + 1-D scales over the DP axes;
        # under EP the data axis is already taken by experts for expert
        # weights — MeshRules.spec() resolves the collision (expert dim wins).
        rules["embed"] = dp
        rules["norm"] = dp
    return MeshRules(rules=rules, mesh=mesh)


# --------------------------------------------------------------------------
# param/grad/opt-state spec trees
# --------------------------------------------------------------------------

def _specs_from_defs(defs_tree, rules: MeshRules):
    """ParamDef tree -> PartitionSpec tree (divisibility-checked per shape)."""

    def walk(sub):
        return {
            k: (rules.spec_for_shape(v.logical_axes, v.shape)
                if isinstance(v, ParamDef) else walk(v))
            for k, v in sub.items()
        }

    return walk(defs_tree)


def group_blocks(tree: dict, plan: ExecutionPlan, supports_grouping: bool = True) -> dict:
    """Split the stacked ``blocks`` subtree into per-strategy groups.

    {"blocks": stacked(L)} -> {"blocks": {"g000": stacked(n0), ...}}.
    Group keys sort lexicographically in layer order.
    """
    if "blocks" not in tree or plan.uniform() or not supports_grouping:
        return tree

    def _slice(a, start, stop):
        if isinstance(a, jax.ShapeDtypeStruct):   # abstract params (dry-run)
            return jax.ShapeDtypeStruct((stop - start,) + a.shape[1:], a.dtype)
        return a[start:stop]

    out = dict(tree)
    groups = plan.groups()
    out["blocks"] = {
        f"g{i:03d}": jax.tree.map(lambda a, g=g: _slice(a, g.start, g.stop), tree["blocks"])
        for i, g in enumerate(groups)
    }
    return out


def ungroup_blocks(tree: dict, plan: ExecutionPlan, supports_grouping: bool = True) -> dict:
    import jax.numpy as jnp

    if ("blocks" not in tree or plan.uniform() or not supports_grouping
            or not isinstance(tree.get("blocks"), dict)
            or not any(k.startswith("g") for k in tree.get("blocks", {}))):
        return tree
    out = dict(tree)
    parts = [tree["blocks"][k] for k in sorted(tree["blocks"])]
    out["blocks"] = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    return out


def param_spec_tree(
    model,
    plan: ExecutionPlan,
    mesh: Optional[Mesh],
    *,
    kind: str = "param",      # param | grad | opt
) -> dict:
    """PartitionSpec pytree matching ``group_blocks(params, plan)``.

    kind="param": ZeRO dp-sharding only at stage 3.
    kind="grad" : at stages >= 2.   kind="opt": at stages >= 1.
    """
    threshold = {"param": 3, "grad": 2, "opt": 1}[kind]
    grouped_mode = not plan.uniform() and arch_of(model.cfg).stacked
    defs = model.param_defs()

    def rules_for(strategy: LayerStrategy) -> MeshRules:
        return param_rules(plan, strategy, mesh, zero_sharded=strategy.zero >= threshold)

    out: dict = {}
    for key, sub in defs.items():
        if key == "blocks" and grouped_mode:
            # specs are invariant to slicing dim0 ("layers" never shards), so
            # derive per-group specs from the full stacked defs + group strategy
            out[key] = {
                f"g{i:03d}": _specs_from_defs(sub, rules_for(g.strategy))
                for i, g in enumerate(plan.groups())
            }
        else:
            strat = (plan.layer_strategies[0] if key == "blocks" and plan.layer_strategies
                     else plan.default_strategy)
            out[key] = _specs_from_defs(sub, rules_for(strat))
    return out


def named_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_spec(plan: ExecutionPlan, global_batch: Optional[int] = None,
               mesh: Optional[Mesh] = None) -> P:
    """tokens/labels (B, S): batch over the DP axes (replicated if indivisible,
    e.g. long_500k's global_batch=1)."""
    dp = plan.dp_axes_for(plan.default_strategy)
    if global_batch is not None and mesh is not None:
        n = 1
        for a in dp:
            n *= mesh.shape[a]
        if global_batch % n != 0:
            return P(None, None)
    return P(dp if len(dp) > 1 else dp[0], None)


def cache_spec_tree(model, plan: ExecutionPlan, mesh: Optional[Mesh],
                    batch: int = 0, max_len: int = 0) -> dict:
    """KV/SSM cache specs for serving: batch over DP; attention-cache seq over
    the model axis (ring/flash-decode style — no padding waste for any
    kv-head count); SSM state heads over the model axis.  Divisibility-checked
    against the concrete cache shapes when batch/max_len are given."""
    logical = model.cache_logical_axes()
    strategy = plan.default_strategy
    rules_map: dict = {"batch": plan.dp_axes_for(strategy)}
    if strategy.tp > 1:
        rules_map["seq"] = plan.tp_axis
        rules_map["ssm_heads"] = plan.tp_axis
        rules_map["ssm_inner"] = plan.tp_axis
    rules = MeshRules(rules=rules_map, mesh=mesh)
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x)

    if batch and max_len:
        abstract = model.abstract_cache(batch, max_len)
        return jax.tree.map(
            lambda axes, arr: rules.spec_for_shape(axes, arr.shape),
            logical, abstract, is_leaf=is_axes)
    return jax.tree.map(lambda axes: rules.spec(axes), logical, is_leaf=is_axes)
