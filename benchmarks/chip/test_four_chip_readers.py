"""The readers of the metrics that only the cell across chips reports
(``memory_measured_over_predicted``, ``collective_ms``), on hand-built
trace summaries and plans, and on the trace recorded on one chip."""
from __future__ import annotations

import importlib.util

import pytest

import harness
import trace_reduce as tr
from conftest import HERE
from repro.core.strategy import ExecutionPlan

GIB = 2**30


def _read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _ctx(peak_bytes=None, predicted_bytes=0.0, traced=True, collective_s=0.0,
         exposed_s=0.0, summary=None):
    summary = summary or tr.Summary(
        window_s=1.0, busy_s=0.9, steps=4, scope_s={s: 0.0 for s in tr.SCOPES},
        collective_s=collective_s, collective_exposed_s=exposed_s, top_ops=[],
        idle_gaps=[], memory_peak_bytes=peak_bytes)
    plan = ExecutionPlan(arch="a", shape="t", mesh_axes=("data", "model"),
                         mesh_shape=(2, 2), predicted_memory=predicted_bytes)
    return harness.ReadContext(spans=harness.Spans(),
                               trace=summary if traced else None,
                               step_times=[0.2] * 4, plan=plan, chips=4)


def test_reads_measured_over_predicted():
    read = lambda ctx: _read("memory_measured_over_predicted", ctx)
    assert read(_ctx(14 * GIB, 16 * GIB)) == pytest.approx(0.875)
    assert read(_ctx(int(19.5 * GIB), 13 * GIB)) == pytest.approx(1.5)


@pytest.mark.parametrize("peak,predicted,traced", [
    (14 * GIB, 0.0, True),               # the one-device plan: not costed
    (14 * GIB, float("inf"), True),
    (14 * GIB, float("nan"), True),
    (None, 14 * GIB, True),              # no allocator events in the window
    (14 * GIB, 14 * GIB, False),         # an untraced run
])
def test_reads_nothing_without_both(peak, predicted, traced):
    assert _read("memory_measured_over_predicted",
                 _ctx(peak, predicted, traced)) is None


def test_collective_ms_reads_whole_collective_time_a_step():
    ctx = _ctx(collective_s=0.2, exposed_s=0.05)
    assert _read("collective_ms", ctx) == pytest.approx(50.0)
    assert _read("collective_exposed_ms", ctx) == pytest.approx(12.5)


@pytest.mark.parametrize("traced", [True, False])
def test_collective_ms_reads_nothing_without_collectives(traced):
    assert _read("collective_ms", _ctx(traced=traced)) is None


def test_collective_ms_silent_on_the_one_chip_trace():
    d = HERE / "testdata" / "trace_1chip"
    summary = tr.summarize(tr.load(d), chips=1,
                           hlo_text=(d / "step.hlo.txt").read_text())
    assert _read("collective_ms", _ctx(summary=summary)) is None
