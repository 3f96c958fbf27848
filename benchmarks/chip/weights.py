"""Seeded weights, made on the device from ``--seed``.

A reference module describes its parameters as a nested dict of
:class:`Leaf` (shape and how to draw it), keyed as the program keys its own
parameter tree.  :func:`make` draws every leaf from one key: leaf ``i`` of
the sorted paths from ``fold_in(key, i)``, so the program's copy and the
reference's copy of a seed are the same numbers, drawn twice.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    init: str = "normal"        # normal | ones | zeros | a_log | dt_bias
    std: float = 1.0


def seed_key(seed: int):
    """A PRNG key for any whole seed up to 2**62 (split into two words)."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    hi, lo = divmod(int(seed), 2**31)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def paths(spec: dict, prefix: tuple = ()) -> list[tuple[tuple, Leaf]]:
    out = []
    for k in sorted(spec):
        v = spec[k]
        if isinstance(v, Leaf):
            out.append((prefix + (k,), v))
        else:
            out.extend(paths(v, prefix + (k,)))
    return out


def _draw(leaf: Leaf, key):
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, jnp.float32)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, jnp.float32)
    if leaf.init == "normal":
        return leaf.std * jax.random.normal(key, leaf.shape, jnp.float32)
    if leaf.init == "a_log":
        # Mamba2: A = -exp(A_log), A drawn uniformly from [1, 16]
        return jnp.log(jax.random.uniform(key, leaf.shape, jnp.float32, 1.0, 16.0))
    if leaf.init == "dt_bias":
        # Mamba2: dt log-uniform in [1e-3, 1e-1], floor 1e-4; the bias is
        # softplus^-1(dt)
        u = jax.random.uniform(key, leaf.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {leaf.init!r}")


def make(spec: dict, key) -> dict:
    """The nested dict of float32 arrays that ``spec`` describes."""
    flat = paths(spec)
    values = {p: _draw(leaf, jax.random.fold_in(key, i))
              for i, (p, leaf) in enumerate(flat)}

    def build(sub, prefix):
        return {k: values[prefix + (k,)] if isinstance(v, Leaf)
                else build(v, prefix + (k,)) for k, v in sub.items()}

    return build(spec, ())


def abstract(spec: dict) -> dict:
    """``jax.ShapeDtypeStruct`` tree of ``spec`` (float32 leaves)."""
    def build(sub):
        return {k: jax.ShapeDtypeStruct(v.shape, jnp.float32)
                if isinstance(v, Leaf) else build(v) for k, v in sub.items()}
    return build(spec)
