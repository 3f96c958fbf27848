"""HLO collective parser: loop trip-count multiplication (the scan-once fix),
replica-group decoding, per-mesh-axis attribution, and the real-compiled
dp×tp census (tp all-reduces distinguished from the dp gradient all-reduce).

The parser proper lives in ``repro.analysis.hlo_stats``;
``repro.launch.hlo_stats`` is the compatibility re-export and both import
paths are exercised here on purpose."""

from repro.launch.hlo_stats import collective_stats, _shape_bytes
from tests._mp import run_with_devices


def test_launch_shim_reexports_analysis_module():
    from repro.analysis import hlo_stats as analysis_mod
    from repro.launch import hlo_stats as launch_mod

    assert launch_mod.collective_stats is analysis_mod.collective_stats
    assert launch_mod.axis_census is analysis_mod.axis_census
    assert launch_mod.AxisCensus is analysis_mod.AxisCensus


def test_shape_bytes():
    assert _shape_bytes("f32[64,64]{1,0}") == 64 * 64 * 4
    assert _shape_bytes("bf16[8]{0}") == 16
    assert _shape_bytes("(f32[4]{0}, s32[2]{0})") == 16 + 8


def test_parse_replica_groups_forms():
    from repro.analysis.hlo_stats import parse_replica_groups

    explicit = parse_replica_groups("... replica_groups={{0,2},{1,3}} ...")
    assert explicit == [[0, 2], [1, 3]]
    iota = parse_replica_groups("... replica_groups=[2,2]<=[4] ...")
    assert iota == [[0, 1], [2, 3]]
    transposed = parse_replica_groups("... replica_groups=[2,2]<=[2,2]T(1,0)")
    assert transposed == [[0, 2], [1, 3]]
    assert parse_replica_groups("no groups here") is None


def test_classify_axes_labels():
    """(2,2) ("data","model") mesh, row-major ids: 0=(0,0) 1=(0,1) 2=(1,0)
    3=(1,1) — model groups vary the trailing coordinate, data the leading."""
    from repro.analysis.hlo_stats import classify_axes

    shape, axes = (2, 2), ("data", "model")
    model = "%ar = f32[8]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}"
    data = "%ar = f32[8]{0} all-reduce(%x), replica_groups={{0,2},{1,3}}"
    both = "%ar = f32[8]{0} all-reduce(%x), replica_groups={{0,1,2,3}}"
    assert classify_axes(model, shape, axes) == "model"
    assert classify_axes(data, shape, axes) == "data"
    assert classify_axes(both, shape, axes) == "data+model"
    perm = ("%cp = f32[8]{0} collective-permute(%x), "
            "source_target_pairs={{0,2},{2,0},{1,3},{3,1}}")
    assert classify_axes(perm, shape, axes) == "data"
    self_copy = ("%cp = f32[8]{0} collective-permute(%x), "
                 "source_target_pairs={{0,0},{1,1}}")
    assert classify_axes(self_copy, shape, axes) == "none"
    outside = "%ar = f32[8]{0} all-reduce(%x), replica_groups={{0,9}}"
    assert classify_axes(outside, shape, axes) == "other"


def test_synthetic_while_multiplication():
    text = """
HloModule jit_f, entry_computation_layout={()->f32[8]{0}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %ar = f32[8]{0} all-reduce(%gte), replica_groups=[1,4]<=[4], to_apply=%add
  ROOT %t = (s32[], f32[8]{0}) tuple(%c, %ar)
}

%cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %c10 = s32[] constant(10)
  ROOT %cmp = pred[] compare(%i, %c10), direction=LT
}

ENTRY %main () -> f32[8] {
  %init = (s32[], f32[8]{0}) tuple(%zero, %zeros)
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""
    stats = collective_stats(text)
    assert stats.bytes_by_kind["all-reduce"] == 10 * 8 * 4
    assert stats.counts_by_kind["all-reduce"] == 10


def test_nested_while_trips_multiply_through():
    """An inner loop's collectives count outer×inner times; the census keeps
    the axis attribution through the call graph."""
    from repro.analysis.hlo_stats import axis_census

    text = """
HloModule jit_f

%inner_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %ar = f32[8]{0} all-reduce(%gte), replica_groups={{0,1},{2,3}}, to_apply=%add
  ROOT %t = (s32[], f32[8]{0}) tuple(%c, %ar)
}

%inner_cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %c5 = s32[] constant(5)
  ROOT %cmp = pred[] compare(%i, %c5), direction=LT
}

%outer_body (q: (s32[], f32[8])) -> (s32[], f32[8]) {
  %q = (s32[], f32[8]{0}) parameter(0)
  %w2 = (s32[], f32[8]{0}) while(%q), condition=%inner_cond, body=%inner_body
  ROOT %t2 = (s32[], f32[8]{0}) tuple(%c2, %gte2)
}

%outer_cond (q.1: (s32[], f32[8])) -> pred[] {
  %q.1 = (s32[], f32[8]{0}) parameter(0)
  %c3 = s32[] constant(3)
  ROOT %cmp2 = pred[] compare(%j, %c3), direction=LT
}

ENTRY %main () -> f32[8] {
  %init = (s32[], f32[8]{0}) tuple(%zero, %zeros)
  %w = (s32[], f32[8]{0}) while(%init), condition=%outer_cond, body=%outer_body
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""
    stats = collective_stats(text)
    assert stats.counts_by_kind["all-reduce"] == 3 * 5
    assert stats.bytes_by_kind["all-reduce"] == 3 * 5 * 8 * 4
    assert stats.unresolved_loops == 0
    census = axis_census(text, (2, 2), ("data", "model"))
    assert census.entries[("model", "all-reduce")] == (3 * 5 * 8 * 4, 3 * 5)


def test_compiled_dp_tp_census_separates_axes():
    """Real compiled train step on a (2,2) dp×tp mesh: the per-axis census
    must attribute tp activation all-reduces to "model" and the gradient
    all-reduce to "data" (plus any dp+model global reductions separately) —
    the measurement half of the GALV090 audit."""
    out = run_with_devices("""
import dataclasses
import jax
from repro.analysis.hlo_stats import axis_census
from repro.configs.registry import get_config
from repro.configs.shapes import SHAPES
from repro.core.cost_model import GRAD_BYTES
from repro.core.strategy import LayerStrategy, uniform_plan
from repro.launch import mesh as mesh_lib
from repro.models import build_model
from repro.runtime.data import input_specs
from repro.runtime.train import construct_hybrid_parallel_model

cfg = get_config("llama3.2-1b").reduced()
seq, batch = 64, 8
strat = LayerStrategy(tp=2, zero=0)
plan = uniform_plan(cfg.name, "t", (2, 2), ("data", "model"),
                    cfg.num_layers, strat)
mesh = mesh_lib.make_mesh((2, 2), ("data", "model"))
hp = construct_hybrid_parallel_model(build_model(cfg), plan, mesh)
spec = dataclasses.replace(
    [s for s in SHAPES.values() if s.kind == "train"][0],
    seq_len=seq, global_batch=batch)
specs = input_specs(cfg, spec, hp.model)
args = (hp.abstract_params(), hp.abstract_opt_state(), specs)
hlo = hp.jit_train_step(donate=False).lower(*args).compile().as_text()

census = axis_census(hlo, (2, 2), ("data", "model"))
assert census.unresolved_loops == 0, census.rows()
model_b = census.bytes_on("model")
data_ar = census.bytes_on("data", "all-reduce")
assert model_b > 0, census.rows()      # tp activation collectives
assert data_ar > 0, census.rows()      # dp gradient all-reduce
# the dp gradient reduction moves >= the tp-sharded fp32 grads and the
# two are attributed to DIFFERENT labels (no conflation of tp with dp)
n_params = sum(p.size for p in jax.tree.leaves(hp.abstract_params()))
assert data_ar >= n_params / 2 * GRAD_BYTES * 0.5, (data_ar, n_params)
assert census.bytes_on("data", "all-gather") == 0   # zero=0: no resharding
print("OK", int(model_b), int(data_ar))
""", n_devices=4)
    assert "OK" in out


def test_compiled_scan_collectives_counted_with_trips():
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_stats import collective_stats
mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
def f(x):
    def body(c, _):
        y = jax.lax.with_sharding_constraint(c @ c, NamedSharding(mesh, P("x")))
        y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P(None, "x")))
        return y, None
    return jax.lax.scan(body, x, None, length=5)[0]
c = jax.jit(f, in_shardings=NamedSharding(mesh, P("x"))).lower(
    jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
s = collective_stats(c.as_text())
ag = s.bytes_by_kind.get("all-gather", 0)
assert abs(ag - 5 * 64 * 64 * 4 / 8) < 1, s.bytes_by_kind   # operand = result/8, x5
assert s.unresolved_loops == 0
print("OK")
""", n_devices=8)
    assert "OK" in out
