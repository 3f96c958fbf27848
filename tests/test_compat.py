"""Compat module: lc() under the abstract-mesh context, shard_map manual
axes, mesh factories and their axis types — on a 1-device mesh."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.compat import P
from repro.parallel.axes import MeshRules, axis_rules, lc


def _mesh():
    return compat.make_mesh((1,), ("model",))


def _lc_once(mesh, monkeypatch):
    """Run lc once, recording the NamedSharding it builds (the compiled
    output sharding normalizes to replicated on a 1-device mesh, so the
    constraint must be captured at trace time)."""
    import repro.parallel.axes as axes_mod

    built = []
    real = compat.NamedSharding

    def recorder(m, spec):
        s = real(m, spec)
        built.append(s)
        return s

    monkeypatch.setattr(axes_mod, "NamedSharding", recorder)
    rules = MeshRules(rules={"embed": "model"}, mesh=mesh)
    with axis_rules(rules):
        out = jax.jit(lambda x: lc(x, "batch", "embed"))(jnp.ones((2, 4)))
    monkeypatch.setattr(axes_mod, "NamedSharding", real)
    return out, built


def _simulate_abstract_ctx(monkeypatch, ctx):
    """Pretend the abstract-mesh context is ``ctx``."""
    monkeypatch.setattr(jax.sharding, "get_abstract_mesh", lambda: ctx)


# ------------------------------------------------------------ lc() paths

def test_lc_noop_outside_rules():
    x = jnp.ones((2, 4))
    np.testing.assert_array_equal(np.asarray(lc(x, "batch", "embed")), np.asarray(x))


def test_lc_new_path_manual_axis_dropped(monkeypatch):
    """A Manual-typed mesh axis in the abstract-mesh context must be dropped
    from the rules (shard_map already applied it)."""
    mesh = _mesh()

    class _ManualCtx:
        empty = False
        axis_names = ("model",)
        axis_types = (compat.AxisType.Manual,)

    _simulate_abstract_ctx(monkeypatch, _ManualCtx())
    out, built = _lc_once(mesh, monkeypatch)
    # every rule target was manual -> spec is empty -> lc must degrade to a
    # no-op instead of raising or constraining on the dead axis
    assert built == []
    np.testing.assert_array_equal(np.asarray(out), np.ones((2, 4)))


# ------------------------------------------------------------ shard_map

def test_shard_map_reports_manual_axes_inside_body():
    """current_mesh_context must see the manual axis while the body traces —
    the invariant lc() relies on."""
    mesh = compat.make_mesh((1,), ("x",))
    seen = {}

    def body(a):
        _, manual = compat.current_mesh_context(mesh)
        seen["manual"] = manual
        return jax.lax.psum(a, "x")

    out = compat.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P(),
                           axis_names={"x"}, check_vma=False)(jnp.arange(4.0))
    assert seen["manual"] == frozenset({"x"})
    np.testing.assert_array_equal(np.asarray(out), np.arange(4.0))
    # and the bookkeeping must not leak past the call
    _, manual = compat.current_mesh_context(mesh)
    assert "x" not in manual


def test_shard_map_default_axis_names_fully_manual():
    """axis_names=None means manual over every mesh axis."""
    mesh = compat.make_mesh((1,), ("x",))
    seen = {}

    def body(a):
        _, manual = compat.current_mesh_context(mesh)
        seen["manual"] = manual
        return a * 2

    out = compat.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                           check_vma=False)(jnp.ones((4,)))
    assert seen["manual"] == frozenset({"x"})
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4,)))


# ------------------------------------------------------------ jit / mesh

def test_jit_keeps_real_flags():
    f = compat.jit(lambda x, y: x + y, donate_argnums=(1,))
    assert float(f(jnp.float32(1.0), jnp.float32(2.0))) == 3.0


def test_make_mesh_and_abstract_mesh_agree():
    m = compat.make_mesh((1,), ("data",))
    assert tuple(m.axis_names) == ("data",)
    am = compat.abstract_mesh((4, 2), ("data", "model"))
    assert tuple(am.axis_names) == ("data", "model")
    assert am.shape["data"] == 4 and am.shape["model"] == 2


def test_make_mesh_axes_are_auto():
    """``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` rejects; the runtime's meshes must be Auto,
    with or without an explicit device subset."""
    auto = compat.AxisType.Auto
    assert compat.make_mesh((1,), ("data",)).axis_types == (auto,)
    m = compat.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    assert m.axis_types == (auto, auto)
    assert compat.abstract_mesh((4, 2), ("data", "model")).axis_types == (auto, auto)


# ------------------------------------------------------------ cost_analysis

class _FakeComputation:
    def __init__(self, ret):
        self._ret = ret

    def cost_analysis(self):
        return self._ret


def test_cost_analysis_normalizes_list_dict_and_empty():
    metrics = {"flops": 1.0, "bytes accessed": 2.0}
    assert compat.cost_analysis(_FakeComputation(dict(metrics))) == metrics
    # nothing reported
    assert compat.cost_analysis(_FakeComputation({})) == {}
    assert compat.cost_analysis(_FakeComputation(None)) == {}


def test_cost_analysis_on_real_compiled():
    compiled = jax.jit(lambda x: x * 2 + 1).lower(jnp.ones((8,))).compile()
    ca = compat.cost_analysis(compiled)
    assert isinstance(ca, dict)
    assert ca.get("flops", 0.0) >= 0.0
