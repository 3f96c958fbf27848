"""The LM head under ZeRO-3 (``parallel/zero_head.py``): when the runtime
builds the chunked, overlapped head and when it keeps the plain einsum, how
many chunks it cuts, and that the chunked head trains as the plain one
does, for an untied and a tied head on the cell's tp2-sp-z3 strategy."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro import compat, obs
from repro.configs.registry import get_config
from repro.core.strategy import LayerStrategy, uniform_plan
from repro.launch.train import single_device_plan
from repro.models import build_model
from repro.parallel import sharding as shd
from repro.parallel import zero_head as zh_lib
from repro.runtime.train import construct_hybrid_parallel_model
from tests._mp import run_with_devices

CFG = dataclasses.replace(get_config("qwen3-14b").reduced(), num_kv_heads=2)


def _head_spans() -> list:
    return [r.name for r in obs.default_tracer().records
            if r.name.startswith("head_")]


@pytest.mark.parametrize("mesh_shape,strategy,tied,expect", [
    ((2, 2), LayerStrategy(tp=2, sp=True, zero=3), False, (("data",), ("model",))),
    ((2, 2), LayerStrategy(tp=2, sp=True, zero=3), True, (("data",), ("model",))),
    ((2, 2), LayerStrategy(tp=1, zero=3), False, (("data", "model"), ())),
    ((2, 2), LayerStrategy(tp=2, sp=True, zero=2), False, None),
    ((2, 2), LayerStrategy(tp=2, sp=True, zero=1), True, None),
    ((1, 4), LayerStrategy(tp=4, sp=True, zero=3), False, None),
])
def test_zero_head_engages_only_on_a_sharded_contraction(mesh_shape, strategy,
                                                         tied, expect):
    """The head's layout is read from the plan's parameter specs: it is
    found only where ZeRO-3 shards the weight's contracting dim over more
    than one device; under ZeRO <= 2 or with one data-parallel rank the
    plain einsum stays."""
    cfg = dataclasses.replace(CFG, tie_embeddings=tied)
    mesh = compat.abstract_mesh(mesh_shape, ("data", "model"))
    plan = uniform_plan(cfg.name, "train", mesh_shape, ("data", "model"),
                        cfg.num_layers, strategy)
    zh = zh_lib.zero_head(shd.param_spec_tree(build_model(cfg), plan, mesh),
                          mesh)
    assert (zh and (zh.zero_axes, zh.vocab_axes)) == expect


def test_one_device_keeps_the_plain_head():
    """No mesh: no ZeRO-3 layout, and the step traces the plain einsum,
    which the head's span names."""
    hp = construct_hybrid_parallel_model(
        build_model(CFG), single_device_plan(CFG, "none"), None)
    assert hp.zero_head is None
    batch = {k: jnp.zeros((2, 16), jnp.int32) for k in ("tokens", "labels")}
    before = len(_head_spans())
    jax.eval_shape(hp.loss_fn, hp.abstract_params(), batch)
    assert _head_spans()[before:] == ["head_plain"]


@pytest.mark.parametrize("vocab_local,k", [
    (75968, 4),     # qwen3-14b's 151,936 rows at tp 2: chunks of 18,992
    (256, 4), (6, 3), (10, 2), (7, 1), (1, 1),
])
def test_head_chunks_divide_the_local_vocabulary(vocab_local, k):
    assert zh_lib.head_chunks(vocab_local) == k


def test_chunks_for_falls_back_to_plain_on_indivisible_shapes():
    mesh = compat.abstract_mesh((2, 2), ("data", "model"))
    zh = zh_lib.ZeroHead(mesh, ("data",), ("model",))
    assert zh_lib.chunks_for(zh, (4, 64, 128), 512) == 4
    assert zh_lib.chunks_for(zh, (3, 64, 128), 512) == 0     # batch
    assert zh_lib.chunks_for(zh, (4, 63, 128), 512) == 0     # sequence
    assert zh_lib.chunks_for(None, (4, 64, 128), 512) == 0


_EQUIV = """
import dataclasses, json
import jax, numpy as np
from repro import compat, obs
from repro.configs.registry import get_config
from repro.core.strategy import LayerStrategy, uniform_plan
from repro.models import build_model
from repro.parallel import sharding as shd
from repro.parallel.axes import axis_rules
from repro.runtime.train import construct_hybrid_parallel_model

cfg = dataclasses.replace(get_config("qwen3-14b").reduced(), num_kv_heads=2,
                          tie_embeddings={tied})
model = build_model(cfg)
plan = uniform_plan(cfg.name, "train", (2, 2), ("data", "model"),
                    cfg.num_layers, LayerStrategy(tp=2, sp=True, zero=3))
mesh = compat.make_mesh(plan.mesh_shape, plan.mesh_axes)
chunked = construct_hybrid_parallel_model(model, plan, mesh)
plain = dataclasses.replace(chunked, zero_head=None)
params = model.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0, cfg.vocab_size)
batch = {{"tokens": toks[:, :-1], "labels": toks[:, 1:]}}

def run(hp):
    def step(p, b):
        with axis_rules(shd.act_rules(hp.plan, hp.plan.default_strategy, mesh)):
            (loss, _), grads = jax.value_and_grad(hp.loss_fn, has_aux=True)(p, b)
            kw = {{"zero_head": hp.zero_head}} if hp.zero_head else {{}}
            logits, _ = hp.model.forward_train(p, b["tokens"], **kw)
        return loss, grads, logits
    loss, grads, logits = compat.jit(
        step, in_shardings=(hp.shardings(hp.param_specs), None))(
            hp.place_params(params), batch)
    return (float(loss), np.asarray(logits),
            jax.tree.map(lambda a: np.asarray(a, np.float64), hp.ungroup(grads)))

loss1, logits1, g1 = run(chunked)
spans = [r.name for r in obs.default_tracer().records if r.name.startswith("head_")]
loss0, logits0, g0 = run(plain)
rel = {{jax.tree_util.keystr(k): float(np.linalg.norm(a - b) / np.linalg.norm(b))
        for (k, a), b in zip(jax.tree_util.tree_flatten_with_path(g1)[0],
                             jax.tree.leaves(g0))}}
print(json.dumps({{"spans": spans, "loss": [loss1, loss0],
                  "logits_equal": bool(np.array_equal(logits1, logits0)),
                  "rel": rel}}))
"""


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_chunked_head_matches_plain_einsum(tied):
    """tp2-sp-z3 on a (2, 2) mesh of CPU devices at small widths: the
    chunked head against the plain einsum on the same mesh, weights and
    batch.  The logits and the loss are the same to the last bit; so is
    every gradient leaf but two, which differ by summation order alone: the
    final norm's scale (a few 1e-8), and a tied embedding, whose head part
    the chunked path reduces over the data axis in bf16 before adding the
    embedding's own gradient (0.7e-3 measured)."""
    out = json.loads(run_with_devices(_EQUIV.format(tied=tied), n_devices=4)
                     .strip().splitlines()[-1])
    # the step traces the head twice (the loss, and the logits beside it)
    assert out["spans"] == ["head_zero_overlap/4"] * 2
    assert out["logits_equal"]
    assert out["loss"][0] == out["loss"][1]
    rel = out["rel"]
    assert len(rel) >= 13
    loose = {"['final_norm']['scale']": 1e-6, "['embed']['tok']": 3e-3 if tied else 0.0}
    for leaf, r in rel.items():
        assert r <= loose.get(leaf, 0.0), (leaf, r)


_RING = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.compat import P
from repro.parallel.zero_head import ring_reduce_scatter

for shape, axes in (((2, 2), ("data",)), ((4, 1), ("data",)),
                    ((2, 2), ("data", "model"))):
    mesh = compat.make_mesh(shape, ("data", "model"))
    n = int(np.prod([mesh.shape[a] for a in axes]))
    # whole numbers, so any summation order is exact
    x = jnp.asarray(np.random.default_rng(0).integers(-8, 8, (4 * n, 8, 6)),
                    jnp.float32)
    for dim in (1, 2) if 6 % n == 0 else (1,):
        f = lambda fn: compat.shard_map(
            fn, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
            axis_names={{"data", "model"}}, check_vma=False)(x)
        ring = f(lambda a: ring_reduce_scatter(a, axes, n, dim))
        ref = f(lambda a: jax.lax.psum_scatter(a, axes, scatter_dimension=dim,
                                               tiled=True))
        assert np.array_equal(np.asarray(ring), np.asarray(ref)), (shape, axes, dim)
print("ok")
"""


def test_ring_reduce_scatter_matches_psum_scatter():
    """The collective-permute ring gives what ``psum_scatter`` gives, over
    two and four devices and over two mesh axes at once."""
    assert run_with_devices(_RING.format(), n_devices=4).strip().endswith("ok")
