"""Context parallelism: ring flash-attention numerics (fwd + grads vs the
single-device flash reference), zig-zag layout invariants, search-space
properties (cp·tp·pp ≤ devices, cp | seq), the memory-cap acceptance
scenario (search picks cp>1 once a long sequence makes cp=1 infeasible) and
elastic replans retaining cp."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._prop import given, settings, st

from repro.configs.registry import get_config
from repro.core.cluster import TPU_V5E_POD
from repro.core.decision_tree import candidate_strategies, cp_candidates
from repro.core.search import SearchEngine, evaluate_uniform
from repro.core.strategy import ExecutionPlan, LayerStrategy
from repro.kernels.flash_attention.ref import attention_reference
from repro.parallel.context import (inverse_permutation, ring_attention,
                                    validate_cp, zigzag_permutation)

ATOL = 3e-5          # fp32 online-softmax vs dense reference
GRAD_ATOL = 3e-4


def _qkv(rng, B=2, S=64, H=2, hd=16, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(ks[i], (B, S, H, hd), dtype) for i in range(3))


# ---------------------------------------------------------------- numerics
@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_flash_reference(cp, causal, rng):
    q, k, v = _qkv(rng)
    out = ring_attention(q, k, v, causal=causal, cp=cp)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL, rtol=ATOL)


@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_grads_match_reference(cp, causal, rng):
    q, k, v = _qkv(rng)
    g = jax.random.normal(jax.random.fold_in(rng, 7), q.shape)

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) * g)

    ring = jax.grad(loss(lambda *a: ring_attention(*a, causal=causal, cp=cp)),
                    argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(lambda *a: attention_reference(*a, causal=causal)),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ring, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=GRAD_ATOL, rtol=GRAD_ATOL)


def test_ring_flash_kernel_partials_match(rng):
    """The Pallas-kernel partial path (positional masking + (m,l) residual
    merge) agrees with the jnp ring — forward-only oracle, interpret mode."""
    q, k, v = _qkv(rng, S=256, hd=32)
    for causal in (True, False):
        out = ring_attention(q, k, v, causal=causal, cp=4,
                             use_flash=True, interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


def test_odd_remainders_rejected(rng):
    q, k, v = _qkv(rng, S=60)
    with pytest.raises(ValueError):
        ring_attention(q, k, v, causal=True, cp=4)      # 60 % 8 != 0
    with pytest.raises(ValueError):
        validate_cp(100, 4)                             # 100 % 8 != 0
    with pytest.raises(ValueError):
        validate_cp(64, 0)
    validate_cp(64, 4)                                  # realizable: no raise


# ---------------------------------------------------------------- layout
@settings(max_examples=20, deadline=None)
@given(logc=st.integers(2, 10), cp=st.sampled_from([1, 2, 4, 8]))
def test_zigzag_permutation_properties(logc, cp):
    S = (2 ** logc) * 2 * cp
    perm = zigzag_permutation(S, cp)
    assert sorted(perm) == list(range(S))               # a true permutation
    inv = inverse_permutation(perm)
    assert (perm[inv] == np.arange(S)).all()
    # balance: every rank's shard holds exactly one early and one late chunk
    c = S // (2 * cp)
    for r in range(cp):
        shard = perm[r * 2 * c:(r + 1) * 2 * c]
        assert shard[:c].max() < S // 2 and shard[c:].min() >= S // 2


# ---------------------------------------------------------------- search space
@settings(max_examples=20, deadline=None)
@given(seq=st.sampled_from([192, 512, 2048, 4096]),
       batch=st.sampled_from([8, 16]),
       cp_axis=st.sampled_from([2, 4]))
def test_searched_plans_satisfy_cp_invariants(seq, batch, cp_axis):
    """Acceptance property: every searched plan keeps cp·tp·pp ≤ devices and
    cp dividing the sequence (2·cp for the zig-zag split)."""
    cfg = dataclasses.replace(get_config("llama3.2-1b").reduced(), num_layers=2)
    devices = cp_axis * 2
    res = SearchEngine(cfg).search(
        seq, batch, mesh_shape=(cp_axis, 2, 1),
        mesh_axes=("cp", "data", "model"), pp_options=[1])
    plan = res.plan
    for s in plan.layer_strategies:
        assert s.cp * s.tp * plan.pp <= devices
        assert seq % s.cp == 0
        if s.cp > 1:
            assert seq % (2 * s.cp) == 0


def test_cp_candidates_gates():
    dense = get_config("llama3.2-1b")
    assert cp_candidates(dense, 8, seq_len=4096, mesh_constrained_cp=4) == [1, 4]
    # zig-zag indivisible => cp stays 1
    assert cp_candidates(dense, 8, seq_len=4092, mesh_constrained_cp=4) == [1]
    # non-dense families and non-attention kinds stay cp=1
    ssm = get_config("mamba2-2.7b")
    assert cp_candidates(ssm, 8, seq_len=4096, mesh_constrained_cp=4) == [1]
    assert cp_candidates(dense, 8, seq_len=4096, layer_kind="moe_block",
                         mesh_constrained_cp=4) == [1]
    # free mode enumerates powers of two under max_cp
    assert cp_candidates(dense, 8, seq_len=4096, max_cp=4) == [1, 2, 4]
    # no seq_len => conservative cp=1 (legacy call sites)
    cands = candidate_strategies(dense, 8, mesh_constrained_tp=2)
    assert all(s.cp == 1 for s in cands)


def test_strategy_cp_validation_and_roundtrip():
    with pytest.raises(ValueError):
        LayerStrategy(cp=0)
    s = LayerStrategy(tp=2, cp=4, zero=3)
    assert "cp4" in s.short()
    assert "cp" not in LayerStrategy(tp=2).short()
    plan = ExecutionPlan(arch="a", shape="t", mesh_axes=("cp", "data", "model"),
                         mesh_shape=(4, 2, 1), layer_strategies=[s],
                         default_strategy=s)
    back = ExecutionPlan.from_json(plan.to_json())
    assert back.default_strategy.cp == 4
    # cp axis carries states (ZeRO) but never batch for cp>1 layers
    assert "cp" not in plan.dp_axes_for(s)
    assert "cp" in plan.state_axes_for(s)
    assert "cp" in plan.dp_axes_for(LayerStrategy(tp=2))     # absorbed at cp=1


# ---------------------------------------------------------------- memory cap
def _load_cp_bench():
    """benchmarks/context_parallel.py owns the calibrated memory-cap scenario
    (shared with the CI smoke); load it by path — benchmarks/ is not a
    package."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / \
        "context_parallel.py"
    spec = importlib.util.spec_from_file_location("_context_parallel_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_search_picks_cp_under_long_seq_memory_cap():
    """Acceptance: once the sequence pushes every cp=1 plan over the memory
    cap, the search must return a cp>1 ring plan (and the same cap without a
    cp mesh axis must be infeasible)."""
    r = _load_cp_bench().check(verbose=False)
    assert r["m_cp1"] > r["m_cp4"]
    assert not r["no_cp"].feasible
    best = r["best"]
    assert best.feasible and best.plan.default_strategy.cp > 1
    assert best.plan.predicted_memory <= r["cap"] < r["m_cp1"]


# ---------------------------------------------------------------- elastic
def test_elastic_replan_retains_cp_on_shrunk_mesh():
    """A long-context run that needed cp to fit must get cp back after a
    membership change: with 3 layers pp cannot stage (3 % 2 != 0), so the
    ring is the only rescuer under the calibrated cap."""
    from repro.runtime.elastic import (ElasticEvent, replan,
                                       replan_cp_candidates)

    cfg = dataclasses.replace(get_config("llama3.2-1b").reduced(), num_layers=3)
    seq, batch, devices = 8192, 8, 8
    assert replan_cp_candidates(cfg, seq, devices) == [1, 2, 4]
    assert replan_cp_candidates(cfg, 512, devices) == [1]       # short context
    assert replan_cp_candidates(get_config("mamba2-2.7b"), seq, devices) == [1]

    frugal = LayerStrategy(zero=3, remat="full")
    m_cp1 = min(m for m in (
        evaluate_uniform(cfg, TPU_V5E_POD, seq, batch, devices,
                         dataclasses.replace(frugal, tp=tp),
                         grad_accum=ga, opt_bytes=ob)[1]
        for tp in (1, 2, 4, 8) for ga in (1, 2, 4, 8) for ob in (8.0, 4.0))
        if math.isfinite(m))
    m_cp = min(m for m in (
        evaluate_uniform(cfg, TPU_V5E_POD, seq, batch, devices,
                         dataclasses.replace(frugal, tp=tp, cp=cp),
                         grad_accum=ga)[1]
        for cp, tps in ((2, (1, 4)), (4, (1, 2))) for tp in tps
        for ga in (1, 2, 4, 8)) if math.isfinite(m))
    assert m_cp1 > 1.05 * m_cp, (m_cp1, m_cp)
    cap = (m_cp1 + m_cp) / 2.0
    tight = dataclasses.replace(TPU_V5E_POD, hbm_bytes=cap)
    plan = replan(cfg, ElasticEvent(16, devices, "node-failure"), seq, batch,
                  cluster=tight)
    assert plan.default_strategy.cp > 1, plan.default_strategy.short()
    assert "cp" in plan.mesh_axes
    assert "elastic replan" in plan.notes
    assert math.prod(plan.mesh_shape) <= devices


# ---------------------------------------------------------------- multi-device
_MP_COMMON = """
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


def test_ring_gspmd_lowering_matches_serial():
    """Sharded ring (GSPMD explicit-dim lowering on a cp mesh) == the serial
    reference ring == dense attention, values and grads."""
    from tests._mp import run_with_devices

    code = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.parallel.context import ring_attention
from repro.models.attention import dense_attention

mesh = compat.make_mesh((4, 2), ("cp", "data"))
B,S,H,hd = 2, 64, 2, 16
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q,k,v = (jax.random.normal(ks[i], (B,S,H,hd), jnp.float32) for i in range(3))
ref = dense_attention(q,k,v,causal=True)
out = jax.jit(lambda q,k,v: ring_attention(q,k,v,causal=True,mesh=mesh))(q,k,v)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)
g1 = jax.grad(lambda q_: jnp.sum(ring_attention(q_,k,v,causal=True,mesh=mesh)**2))(q)
g2 = jax.grad(lambda q_: jnp.sum(dense_attention(q_,k,v,causal=True)**2))(q)
np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=3e-4, rtol=3e-4)
print("OK")
"""
    assert "OK" in run_with_devices(code, n_devices=8)


def test_ring_shard_map_lowering_matches_serial():
    from tests._mp import run_with_devices

    code = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.parallel.context import ring_attention
from repro.models.attention import dense_attention

mesh = compat.make_mesh((4, 2), ("cp", "data"))
B,S,H,hd = 2, 64, 2, 16
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q,k,v = (jax.random.normal(ks[i], (B,S,H,hd), jnp.float32) for i in range(3))
ref = dense_attention(q,k,v,causal=True)
out = jax.jit(lambda q,k,v: ring_attention(q,k,v,causal=True,mesh=mesh,
                                           lowering="shard_map"))(q,k,v)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)
print("OK")
"""
    assert "OK" in run_with_devices(code, n_devices=8)


def test_cp_train_step_matches_single_device():
    """Full hybrid runtime on a (cp, data, model) mesh: one train step's loss
    equals the single-device reference (ring attention engaged via the plan's
    cp strategy)."""
    from tests._mp import run_with_devices

    code = _MP_COMMON + """
arch = "llama3.2-1b"
cfg = get_config(arch).reduced()
model = build_model(cfg)
ds = SyntheticDataset(cfg, seq_len=64, global_batch=4)
b = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
mesh = compat.make_mesh((2, 2, 2), ("cp", "data", "model"))
strat = LayerStrategy(tp=2, cp=2, zero=2)
plan = ExecutionPlan(arch=arch, shape="t", mesh_axes=("cp","data","model"),
                     mesh_shape=(2,2,2), grad_accum=2,
                     layer_strategies=[strat]*cfg.num_layers, default_strategy=strat)
hp = construct_hybrid_parallel_model(model, plan, mesh)
params = hp.init_params(jax.random.PRNGKey(0))
opt = hp.init_opt_state(params)
_, _, m = hp.jit_train_step(donate=False)(params, opt, b)
ref = single_device_loss(arch, b, ga=2)
d = abs(float(m["loss"]) - ref)
assert d < 5e-2, (float(m["loss"]), ref)
print("OK", d)
"""
    assert "OK" in run_with_devices(code, n_devices=8)


def test_pipeline_with_cp_matches_single_device():
    """PipelineTrainer on a (pod, cp, data, model) mesh: cp composes with the
    pipeline's shard_map lowering."""
    from tests._mp import run_with_devices

    code = _MP_COMMON + """
from repro.runtime.train_pp import PipelineTrainer
arch = "llama3.2-1b"
cfg = get_config(arch).reduced()
model = build_model(cfg)
ds = SyntheticDataset(cfg, seq_len=64, global_batch=8)
b = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
mesh = compat.make_mesh((2, 2, 2, 1), ("pod", "cp", "data", "model"))
strat = LayerStrategy(cp=2, zero=1)
plan = ExecutionPlan(arch=arch, shape="t", mesh_axes=("pod","cp","data","model"),
                     mesh_shape=(2,2,2,1), pp=2, grad_accum=4,
                     layer_strategies=[strat]*cfg.num_layers, default_strategy=strat)
tr = PipelineTrainer(model, plan, mesh)
params = tr.init_params(jax.random.PRNGKey(0))
opt = tr.init_opt_state(params)
_, _, m = tr.jit_train_step(donate=False)(params, opt, b)
ref = single_device_loss(arch, b, ga=1)
d = abs(float(m["loss"]) - ref)
assert d < 5e-2, (float(m["loss"]), ref)
print("OK", d)
"""
    assert "OK" in run_with_devices(code, n_devices=8)
