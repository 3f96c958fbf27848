"""qwen3-14b as the four-chip benchmark cell trains it: the registry entry
against the cell's configuration file, the plan the search picks on a v5e
2x2 host, and that plan's strategy on four CPU devices against the
one-device program, gradient leaf by gradient leaf."""
import importlib
import json
import pathlib

import pytest

from repro.configs.registry import get_config
from tests._mp import run_with_devices

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
CONF = json.loads((CHIP / "configs" / "qwen3-14b.json").read_text())
WORKLOAD = json.loads(
    (CHIP / "workloads" / "qwen3-14b.train-2k.4chip.json").read_text())
V5E_HBM = 16 * 2**30


def test_registry_matches_published_config(monkeypatch):
    """Every field the dense reference fixes from the published config,
    depth aside, is the registry's own: no ``set`` of the cell's file is
    needed for the widths, heads, eps or rope."""
    monkeypatch.syspath_prepend(str(CHIP))
    dense = importlib.import_module("references.dense")
    want = dense.program_fields(CONF["config"])
    want.pop("num_layers")
    cfg = get_config(CONF["program"]["arch"])
    assert {k: getattr(cfg, k) for k in want} == want
    assert cfg.source.startswith("hf:Qwen/Qwen3-14B")


def test_cell_plan_is_feasible_and_checked(monkeypatch):
    """The benchmark's own search on the cell's (2, 2) data x model mesh
    (``harness.search_plan``: refused unless feasible and passed by
    ``plan_check``) finds a plan whose predicted memory fits a v5e."""
    monkeypatch.syspath_prepend(str(CHIP))
    harness = importlib.import_module("harness")
    cell = harness.find_cell("qwen3-14b.train-2k.4chip")
    plan = harness.search_plan(harness.program_config(cell), cell)
    assert plan.num_devices == 4
    assert 0 < plan.predicted_memory < V5E_HBM
    assert plan.predicted_step_time > 0


_GRADS = """
import dataclasses, json
import jax, numpy as np
from repro import compat
from repro.configs.registry import get_config
from repro.core.search import SearchEngine
from repro.launch.train import single_device_plan
from repro.models import build_model
from repro.runtime.optimizer import AdamWConfig
from repro.runtime.train import construct_hybrid_parallel_model

big = dataclasses.replace(get_config("qwen3-14b"), num_layers={layers})
plan = SearchEngine(big).search({seq}, {batch}, mesh_shape={mesh_shape},
                                mesh_axes={mesh_axes}).plan
# small widths, every mechanism kept: GQA (2 KV heads over tp 2), qk-norm,
# the untied head
cfg = dataclasses.replace(get_config("qwen3-14b").reduced(), num_kv_heads=2,
                          num_layers={layers})
assert cfg.qk_norm and not cfg.tie_embeddings and cfg.num_kv_heads < cfg.num_heads
model = build_model(cfg)
# no clipping: Adam's first moment after one step is (1 - b1) * gradient
opt = AdamWConfig(grad_clip=0.0)
mesh = compat.make_mesh(plan.mesh_shape, plan.mesh_axes)
hp4 = construct_hybrid_parallel_model(model, plan, mesh, opt_cfg=opt)
# the same recomputation on one device: remat alone moves bf16 gradients
hp1 = construct_hybrid_parallel_model(
    model, single_device_plan(cfg, plan.default_strategy.remat), None,
    opt_cfg=opt)
params = model.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), ({batch}, 65), 0, cfg.vocab_size)
batch = {{"tokens": toks[:, :-1], "labels": toks[:, 1:]}}

def grads(hp):
    p = hp.place_params(params)
    _, o, m = hp.jit_train_step(donate=False)(p, hp.init_opt_state(p), batch)
    g = jax.tree.map(lambda x: np.asarray(x, np.float64) / (1 - opt.b1),
                     hp.ungroup(o.m))
    return float(m["loss"]), g

loss4, g4 = grads(hp4)
loss1, g1 = grads(hp1)
rel = {{jax.tree_util.keystr(k): float(np.linalg.norm(a - b) / np.linalg.norm(b))
        for (k, a), b in zip(jax.tree_util.tree_flatten_with_path(g4)[0],
                             jax.tree.leaves(g1))}}
assert len(rel) == len(jax.tree.leaves(g1)) == len(jax.tree.leaves(g4))
print(json.dumps({{"plan": plan.default_strategy.short(),
                  "loss": [loss4, loss1], "rel": rel}}))
"""


def test_cell_plan_gradients_match_one_device():
    """The searched plan's strategy (tp2-sp-z3 today) on a (2, 2) mesh of
    CPU devices, at small widths, against the one-device program from the
    same weights and batch: the first step's gradient, every leaf."""
    mesh = WORKLOAD["plan"]
    code = _GRADS.format(layers=CONF["config"]["num_hidden_layers"],
                         seq=WORKLOAD["seq_len"], batch=WORKLOAD["global_batch"],
                         mesh_shape=tuple(mesh["mesh_shape"]),
                         mesh_axes=tuple(mesh["mesh_axes"]))
    out = json.loads(run_with_devices(code, n_devices=4).strip().splitlines()[-1])
    assert out["plan"].startswith("tp2"), out["plan"]
    loss4, loss1 = out["loss"]
    assert loss4 == pytest.approx(loss1, rel=1e-3)
    # Both sides keep bfloat16 activations and matmul operands with float32
    # accumulation; the four-device side adds its tensor-parallel partial
    # sums and sequence-parallel gathers in another order.  At these widths
    # that moves a leaf's gradient by 0.6e-2 to 1.7e-2 (L2 of the difference
    # over L2 of the leaf), as much as recomputing the layers does on one
    # device (1.4e-2); the loss over half of the batch moves every leaf by
    # 0.55 to 1.0.
    assert len(out["rel"]) >= 12, out["rel"]
    worst = max(out["rel"], key=out["rel"].get)
    assert out["rel"][worst] < 5e-2, (worst, out["rel"])
