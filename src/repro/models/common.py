"""Parameter-definition machinery shared by every model family.

Models are pure-functional: a model object holds only its (frozen) config and
exposes ``param_defs()`` — a nested dict of :class:`ParamDef` — plus forward
functions that consume the matching nested dict of arrays.

Each ``ParamDef`` carries *logical axis names* (``"embed"``, ``"heads"``,
``"ff"`` …).  The parallel runtime maps logical axes to mesh axes according to
the per-layer :class:`~repro.core.strategy.LayerStrategy`, which is how one
model definition serves every hybrid-parallel strategy Galvatron's search
engine can emit.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# Logical axis vocabulary. The sharding rules in repro.parallel.sharding key on
# these names; adding a new one requires a rule there.
LOGICAL_AXES = (
    "layers",      # stacked-layer leading dim (scanned)
    "vocab",       # vocabulary dim of embeddings / lm head
    "embed",       # d_model
    "q_heads",     # query heads (tensor-parallel)
    "kv_heads",    # key/value heads (tensor-parallel, may be < tp degree)
    "head_dim",    # per-head dim (never sharded)
    "ff",          # feed-forward hidden dim (tensor-parallel)
    "experts",     # MoE expert dim (expert-parallel)
    "ssm_inner",   # mamba2 expanded inner dim (tensor-parallel)
    "ssm_heads",   # mamba2 value heads (tensor-parallel)
    "ssm_state",   # SSD state dim (never sharded)
    "ssm_groups",  # B/C projection groups
    "conv",        # conv kernel width (never sharded)
    "norm",        # 1-D norm scales (zero-3 shardable only)
    "stages",      # pipeline-stage leading dim (pipeline runtime only)
)


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declarative description of one parameter tensor."""

    shape: tuple[int, ...]
    logical_axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones | small_normal
    scale: float | None = None    # stddev override for normal inits
    dtype: Any = jnp.float32      # master weights are fp32; cast to bf16 in fwd

    def __post_init__(self):
        if len(self.shape) != len(self.logical_axes):
            raise ValueError(
                f"shape {self.shape} vs logical_axes {self.logical_axes} rank mismatch"
            )
        for ax in self.logical_axes:
            if ax is not None and ax not in LOGICAL_AXES:
                raise ValueError(f"unknown logical axis {ax!r}")

    def num_params(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def materialize(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        fan_in = self.shape[-2] if len(self.shape) >= 2 else max(self.shape[-1], 1)
        std = self.scale if self.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
        if self.init == "small_normal":
            std = 0.02
        return (std * jax.random.normal(key, self.shape)).astype(self.dtype)


ParamTree = dict  # nested dict[str, ParamDef | ParamTree] / dict[str, Array | ...]


def tree_paths(defs: ParamTree, prefix: tuple[str, ...] = ()) -> list[tuple[tuple[str, ...], ParamDef]]:
    out = []
    for k in sorted(defs):
        v = defs[k]
        if isinstance(v, ParamDef):
            out.append((prefix + (k,), v))
        else:
            out.extend(tree_paths(v, prefix + (k,)))
    return out


def init_params(defs: ParamTree, key: jax.Array) -> ParamTree:
    """Materialize a nested dict of ParamDefs into arrays (deterministic per path)."""
    flat = tree_paths(defs)
    keys = jax.random.split(key, max(len(flat), 1))
    values = {path: d.materialize(k) for (path, d), k in zip(flat, keys)}

    def build(sub: ParamTree, prefix: tuple[str, ...]) -> ParamTree:
        out = {}
        for k, v in sub.items():
            if isinstance(v, ParamDef):
                out[k] = values[prefix + (k,)]
            else:
                out[k] = build(v, prefix + (k,))
        return out

    return build(defs, ())


def abstract_params(defs: ParamTree) -> ParamTree:
    """ShapeDtypeStruct pytree matching ``init_params`` — used by the dry-run
    so no host memory is ever allocated for full-size models."""

    def build(sub: ParamTree) -> ParamTree:
        return {
            k: (jax.ShapeDtypeStruct(v.shape, v.dtype) if isinstance(v, ParamDef) else build(v))
            for k, v in sub.items()
        }

    return build(defs)


def logical_axes_tree(defs: ParamTree) -> ParamTree:
    """Same-structure pytree of logical-axis tuples (consumed by sharding rules)."""

    def build(sub: ParamTree) -> ParamTree:
        return {
            k: (v.logical_axes if isinstance(v, ParamDef) else build(v))
            for k, v in sub.items()
        }

    return build(defs)


def count_params(defs: ParamTree) -> int:
    return sum(d.num_params() for _, d in tree_paths(defs))


def cast_tree(params: ParamTree, dtype) -> ParamTree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, params
    )


def stacked(defs: ParamTree, num: int) -> ParamTree:
    """Prepend a scanned ``layers`` dim of size ``num`` to every ParamDef."""

    def add(v):
        if isinstance(v, ParamDef):
            return dataclasses.replace(
                v, shape=(num,) + v.shape, logical_axes=("layers",) + v.logical_axes
            )
        return {k: add(sv) for k, sv in v.items()}

    return {k: add(v) for k, v in defs.items()}


def take_layer(params: ParamTree, idx) -> ParamTree:
    """Slice one layer out of a stacked param tree (inside lax.scan)."""
    return jax.tree.map(lambda x: x[idx], params)


def slice_layers(params: ParamTree, start: int, stop: int) -> ParamTree:
    return jax.tree.map(lambda x: x[start:stop], params)


Initializer = Callable[[jax.Array], ParamTree]


#: Named scopes of the model's layers.  None is opened inside another, so
#: every op of the step's HLO lies under at most one of them; the runtime's
#: ``model`` scope around the loss keeps them plain path components of the
#: backward's op names too (``transpose(jvp(model))/.../ssd/...``).
LAYER_SCOPES = ("embed", "norm", "ssm_proj", "ssm_conv", "ssd", "attention",
                "mlp", "head")


def scoped(name: str):
    """Run the decorated function under ``jax.named_scope(name)``.  A fresh
    context manager is entered per call: a shared one is not re-entrant."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def scan_or_unroll(body, carry, xs, *, unroll: bool = False, length: int | None = None):
    """``lax.scan`` or an explicit python loop over the leading dim.

    XLA's cost analysis counts while-loop bodies once (not × trip count);
    the dry-run lowers an *unrolled* variant of each step to obtain exact
    FLOP/byte totals for the roofline (never compiled — lowering only).
    """
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = length if length is not None else jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs) if xs is not None else None
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys
