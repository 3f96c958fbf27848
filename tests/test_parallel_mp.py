"""Multi-device (8 fake CPU devices, subprocess) equivalence tests:
GSPMD hybrid strategies and the shard_map pipeline vs single-device math."""
import pytest

from tests._mp import run_with_devices

_COMMON = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.registry import get_config
from repro.models import build_model
from repro.core.strategy import LayerStrategy, ExecutionPlan
from repro.runtime.train import construct_hybrid_parallel_model
from repro.runtime.data import SyntheticDataset

def single_device_loss(arch, batch, ga=1):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    plan = ExecutionPlan(arch=arch, shape="t", mesh_axes=("data",), mesh_shape=(1,),
                         grad_accum=ga, layer_strategies=[LayerStrategy()]*cfg.num_layers,
                         default_strategy=LayerStrategy())
    hp = construct_hybrid_parallel_model(model, plan, mesh=None)
    p = hp.init_params(jax.random.PRNGKey(0))
    o = hp.init_opt_state(p)
    _, _, m = hp.jit_train_step(donate=False)(p, o, batch)
    return float(m["loss"])
"""


@pytest.mark.parametrize("arch,strat_kw", [
    ("qwen3-14b", dict(tp=4, sp=True, zero=3, remat="selective")),
    ("llama3.2-1b", dict(tp=2, zero=2)),
    ("moonshot-v1-16b-a3b", dict(tp=4, zero=3, ep=2)),
    ("mamba2-2.7b", dict(tp=4, zero=1, remat="full")),
])
def test_gspmd_equivalence(arch, strat_kw):
    code = _COMMON + f"""
arch = {arch!r}
cfg = get_config(arch).reduced()
model = build_model(cfg)
mesh = compat.make_mesh((2, 4), ("data", "model"))
strat = LayerStrategy(**{strat_kw!r})
plan = ExecutionPlan(arch=arch, shape="t", mesh_axes=("data","model"), mesh_shape=(2,4),
                     grad_accum=2, layer_strategies=[strat]*cfg.num_layers,
                     default_strategy=strat)
hp = construct_hybrid_parallel_model(model, plan, mesh)
params = hp.init_params(jax.random.PRNGKey(0))
opt = hp.init_opt_state(params)
ds = SyntheticDataset(cfg, seq_len=32, global_batch=4)
b = {{k: jnp.asarray(v) for k, v in ds.batch(0).items()}}
_, _, m = hp.jit_train_step(donate=False)(params, opt, b)
ref = single_device_loss(arch, b, ga=2)
d = abs(float(m["loss"]) - ref)
assert d < 5e-2, (float(m["loss"]), ref)
print("OK", d)
"""
    out = run_with_devices(code, n_devices=8)
    assert "OK" in out


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-2.7b"])
def test_pipeline_equivalence(arch):
    code = _COMMON + f"""
from repro.runtime.train_pp import PipelineTrainer
arch = {arch!r}
cfg = get_config(arch).reduced()
model = build_model(cfg)
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
strat = LayerStrategy(tp=2, zero=1)
plan = ExecutionPlan(arch=arch, shape="t", mesh_axes=("pod","data","model"),
                     mesh_shape=(2,2,2), pp=2, grad_accum=4,
                     layer_strategies=[strat]*cfg.num_layers, default_strategy=strat)
tr = PipelineTrainer(model, plan, mesh)
params = tr.init_params(jax.random.PRNGKey(0))
opt = tr.init_opt_state(params)
ds = SyntheticDataset(cfg, seq_len=32, global_batch=8)
b = {{k: jnp.asarray(v) for k, v in ds.batch(0).items()}}
_, _, m = tr.jit_train_step(donate=False)(params, opt, b)
ref = single_device_loss(arch, b, ga=1)
d = abs(float(m["loss"]) - ref)
assert d < 5e-2, (float(m["loss"]), ref)
print("OK", d)
"""
    out = run_with_devices(code, n_devices=8)
    assert "OK" in out


def test_pipeline_schedule_equivalence():
    """GPipe vs 1F1B vs interleaved vs the single-device reference: identical
    losses and gradients (up to bf16 reduction-order noise)."""
    code = _COMMON + """
import dataclasses
from repro.runtime.train_pp import PipelineTrainer

arch = "llama3.2-1b"
cfg = dataclasses.replace(get_config(arch).reduced(), num_layers=4)
model = build_model(cfg)
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
strat = LayerStrategy(tp=2, zero=1)
ds = SyntheticDataset(cfg, seq_len=32, global_batch=8)
b = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}

def flat(tree):
    return np.concatenate([np.asarray(x, np.float32).ravel()
                           for x in jax.tree.leaves(tree)])

# single-device reference loss + grads (same initial params)
from repro.runtime.train import construct_hybrid_parallel_model
plan1 = ExecutionPlan(arch=arch, shape="t", mesh_axes=("data",), mesh_shape=(1,),
                      grad_accum=1, layer_strategies=[LayerStrategy()]*cfg.num_layers,
                      default_strategy=LayerStrategy())
hp = construct_hybrid_parallel_model(model, plan1, mesh=None)
p_ref = hp.init_params(jax.random.PRNGKey(0))
(ref_loss, _), ref_g = jax.value_and_grad(hp.loss_fn, has_aux=True)(p_ref, b)
ref_flat = flat(ref_g)

results = {}
for sched, v in [("gpipe", 1), ("1f1b", 1), ("interleaved", 2)]:
    plan = ExecutionPlan(arch=arch, shape="t", mesh_axes=("pod","data","model"),
                         mesh_shape=(2,2,2), pp=2, pp_schedule=sched,
                         pp_interleave=v, grad_accum=4,
                         layer_strategies=[strat]*cfg.num_layers,
                         default_strategy=strat)
    tr = PipelineTrainer(model, plan, mesh)
    params = tr.stage_params(p_ref)
    # staging must be a bijection (checkpoints are canonical/unstaged)
    for a, bb in zip(jax.tree.leaves(tr.ungroup(params)), jax.tree.leaves(p_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
    loss, mets, grads = jax.jit(tr._loss_and_grads)(params, b)
    results[sched] = (float(loss), flat(tr.ungroup(dict(grads))))

def rel(a, bvec):
    return float(np.linalg.norm(a - bvec) / (np.linalg.norm(bvec) + 1e-12))

for sched, (loss, g) in results.items():
    assert abs(loss - float(ref_loss)) < 5e-2, (sched, loss, float(ref_loss))
    assert rel(g, ref_flat) < 5e-2, (sched, rel(g, ref_flat))
for sched in ("1f1b", "interleaved"):
    d = rel(results[sched][1], results["gpipe"][1])
    assert d < 5e-2, (sched, d)
print("OK")
"""
    out = run_with_devices(code, n_devices=8)
    assert "OK" in out


def test_pipeline_rejects_moe():
    code = _COMMON + """
from repro.runtime.train_pp import PipelineTrainer
cfg = get_config("moonshot-v1-16b-a3b").reduced()
model = build_model(cfg)
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
strat = LayerStrategy(tp=2)
plan = ExecutionPlan(arch="m", shape="t", mesh_axes=("pod","data","model"),
                     mesh_shape=(2,2,2), pp=2, grad_accum=4,
                     layer_strategies=[strat]*cfg.num_layers, default_strategy=strat)
try:
    PipelineTrainer(model, plan, mesh)
    print("NO-RAISE")
except NotImplementedError:
    print("OK")
"""
    out = run_with_devices(code, n_devices=8)
    assert "OK" in out


def test_serving_sharded_decode_matches_single_device():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.registry import get_config
from repro.models import build_model
from repro.core.strategy import LayerStrategy, ExecutionPlan
from repro.runtime.serve import ServingEngine

cfg = get_config("qwen2.5-3b").reduced()
model = build_model(cfg)
mesh = compat.make_mesh((2, 4), ("data", "model"))
strat = LayerStrategy(tp=4, zero=0)
B, S = 4, 32
plan = ExecutionPlan(arch="q", shape="t", mesh_axes=("data","model"), mesh_shape=(2,4),
                     layer_strategies=[strat]*cfg.num_layers, default_strategy=strat)
eng = ServingEngine(model, plan, mesh, batch=B, max_len=S + 4)
params = model.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
lg, cache = eng.jit_prefill_step()(params, toks, None)
lg2, _ = eng.jit_decode_step(donate=False)(params, toks[:, :1], cache,
                                           jnp.int32(S), jnp.full((B,), S + 1, jnp.int32))
# single device reference
plan1 = ExecutionPlan(arch="q", shape="t", mesh_axes=("data",), mesh_shape=(1,),
                      layer_strategies=[LayerStrategy()]*cfg.num_layers,
                      default_strategy=LayerStrategy())
eng1 = ServingEngine(model, plan1, mesh=None, batch=B, max_len=S + 4)
lg_1, cache1 = eng1.prefill_step(params, toks)
lg2_1, _ = eng1.decode_step(params, toks[:, :1], cache1, jnp.int32(S),
                            jnp.full((B,), S + 1, jnp.int32))
# bf16 reduction-order noise across 8 shards (fp32 agrees to 5e-5 — verified
# during bring-up); random-init logits have near-ties, so compare values,
# not greedy token ids
np.testing.assert_allclose(np.asarray(lg2, np.float32), np.asarray(lg2_1, np.float32),
                           atol=0.4, rtol=0.4)
np.testing.assert_allclose(np.max(np.asarray(lg2[:, -1], np.float32), -1),
                           np.max(np.asarray(lg2_1[:, -1], np.float32), -1), atol=0.4)
print("OK")
"""
    out = run_with_devices(code, n_devices=8)
    assert "OK" in out
