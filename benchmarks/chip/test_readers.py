"""The per-layer readers of the model's scopes and of host collections,
on hand-built trace summaries and host spans."""
from __future__ import annotations

import gc
import importlib.util
import types

import pytest

import harness
import layer_scopes
import trace_reduce as tr
from conftest import HERE

LAYERS = layer_scopes.LAYERS
#: op_name prefixes the compiled step gives its ops: the forward, the
#: remat recompute inside the backward, and a remat path that repeats the
#: forward's stack (the final norm's).
PATHS = ("jit(train_step)/fwd_bwd/jvp(model)/while/body/closed_call/{}/mul",
         "jit(train_step)/fwd_bwd/transpose(jvp(model))/while/body/"
         "closed_call/checkpoint/rematted_computation/{}/closed_call/while/"
         "body/closed_call/bihn,bhnp->bihp/dot_general",
         "jit(train_step)/fwd_bwd/transpose(jvp(model))/{0}/fwd_bwd/"
         "jvp(model)/{0}/checkpoint/rematted_computation/convert_element_type")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _label(name, path):
    return tr.Op(name, "fusion", 0.0, 1.0, path).label()


def _ctx(scope_s, busy_s=0.5, steps=2, spans=None):
    """A context whose trace has ``fwd_bwd`` and ``optimizer`` from
    ``scope_s``, and each layer's seconds there split over ops of the
    paths in ``PATHS``, beside ops under no layer."""
    top = [(_label("fusion.0", "jit(train_step)/fwd_bwd/transpose(jvp(model))"
                   "/while/body/closed_call/dynamic_update_slice"), 0.001),
           (_label("copy.1", ""), 0.002)]
    for layer in LAYERS:
        if layer in scope_s:
            top += [(_label(f"{layer}.{i}", p.format(layer)),
                     scope_s[layer] / len(PATHS)) for i, p in enumerate(PATHS)]
    summary = tr.Summary(window_s=1.0, busy_s=busy_s, steps=steps,
                         scope_s={s: scope_s.get(s, 0.0) for s in tr.SCOPES},
                         collective_s=0.0, collective_exposed_s=0.0,
                         top_ops=top, idle_gaps=[], memory_peak_bytes=None)
    return harness.ReadContext(spans=spans or harness.Spans(), trace=summary,
                               step_times=[0.25] * steps, plan=None, chips=1)


def test_scopes_are_the_programs_layers():
    """The readers know the program's layer scopes, and the trace
    reduction's own scopes stay the runtime's two."""
    from repro.models.common import LAYER_SCOPES

    assert LAYERS == LAYER_SCOPES
    assert tr.SCOPES == ("fwd_bwd", "optimizer")


@pytest.mark.parametrize("path,layer", [
    (PATHS[1].format("ssd"), "ssd"),
    (PATHS[2].format("norm"), "norm"),
    ("fwd_bwd/jvp(model)/head/dot_general", "head"),
    # cut inside the component "rmsnorm": its tail "norm" is not the scope
    ("fwd_bwd/transpose(jvp(model))/while/body/closed_call/checkpoint/"
     "x_rmsnorm/" + "a" * 75, None),
    ("fwd_bwd/jvp(model)/while/body/closed_call/dynamic_slice", None),
    ("", None),
])
def test_label_layer(path, layer):
    """The layer scope is read from the label the trace reduction makes,
    cut to its last ``TAIL`` characters."""
    label = _label("fusion.7", path)
    assert len(label) <= len("fusion.7 []") + layer_scopes.TAIL
    assert layer_scopes.label_layer(label) == layer


@pytest.mark.parametrize("family,layers", [
    ("mamba2", {"embed": 0.02, "norm": 0.03, "ssm_proj": 0.10,
                "ssm_conv": 0.01, "ssd": 0.15, "head": 0.05}),
    ("dense", {"embed": 0.02, "norm": 0.03, "attention": 0.14, "mlp": 0.12,
               "head": 0.05}),
])
def test_layer_metrics_sum_to_fwd_bwd(family, layers):
    """Layer scopes plus ``model_other_ms`` make ``fwd_bwd_ms``; a scope the
    family lacks reads nothing; busy time less ``fwd_bwd`` and
    ``optimizer`` is ``step_other_ms``."""
    ctx = _ctx({"fwd_bwd": 0.40, "optimizer": 0.02, **layers}, busy_s=0.5,
               steps=2)
    got = {n: _reader(f"{n}_ms")(ctx) for n in LAYERS}
    assert {n for n, v in got.items() if v is not None} == set(layers)
    assert all(got[n] == pytest.approx(1e3 * v / 2) for n, v in layers.items())
    other = _reader("model_other_ms")(ctx)
    assert other == pytest.approx(1e3 * (0.40 - sum(layers.values())) / 2)
    assert sum(v for v in got.values() if v) + other == pytest.approx(
        _reader("fwd_bwd_ms")(ctx))
    assert _reader("step_other_ms")(ctx) == pytest.approx(1e3 * 0.08 / 2)


def test_layer_metrics_silent_without_scopes():
    """A program that names no layer scope (the parent of this reader) reads
    nothing under any layer metric, while ``step_other_ms`` still reads."""
    ctx = _ctx({"fwd_bwd": 0.4, "optimizer": 0.02})
    for n in LAYERS + ("model_other",):
        assert _reader(f"{n}_ms")(ctx) is None
    assert _reader("step_other_ms")(ctx) == pytest.approx(40.0)
    assert _reader("model_other_ms")(_ctx({})) is None
    untraced = harness.ReadContext(spans=harness.Spans(), trace=None,
                                   step_times=[0.25], plan=None, chips=1)
    for n in LAYERS + ("model_other", "step_other"):
        assert _reader(f"{n}_ms")(untraced) is None


def test_host_gc_ms_reads_collections_inside_the_window(monkeypatch):
    from repro import obs

    tracer = obs.Tracer(clock=iter([0.5, 0.7, 1.2, 1.3, 2.9, 3.4]).__next__)
    for _ in range(3):           # before, inside, and across the window's end
        tracer.end(tracer.begin("gc_gen2"))
    spans = harness.Spans()
    spans.records = [("compile", 0.0, 0.9), ("dispatch", 1.0, 1.1),
                     ("fence", 1.1, 2.0), ("dispatch", 2.0, 2.1),
                     ("fence", 2.1, 3.0)]
    monkeypatch.setattr(obs.trace, "_DEFAULT", tracer)
    read = _reader("host_gc_ms")
    assert read(_ctx({}, spans=spans)) == pytest.approx(1e3 * (0.1 + 0.1) / 2)
    monkeypatch.setattr(gc, "callbacks", [])
    assert read(_ctx({}, spans=spans)) is None          # no hook, no reading
    monkeypatch.setitem(__import__("sys").modules, "repro.obs",
                        types.ModuleType("repro.obs"))
    assert read(_ctx({}, spans=spans)) is None          # a parent's obs
