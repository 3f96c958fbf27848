"""The trace reduction, on a small trace recorded on one chip and committed
under ``testdata/`` (``testdata/record_trace.py``: four steps of a jitted
step with ``fwd_bwd`` and ``optimizer`` scopes inside the harness's host
spans; recorded on four chips it adds a cross-chip reduction, and a
``trace_4chip`` directory beside it is read the same way), and on
intervals made up by hand."""
from __future__ import annotations

import pytest

import trace_reduce as tr
from conftest import HERE

TRACES = {c: HERE / "testdata" / f"trace_{c}chip" for c in (1, 4)
          if (HERE / "testdata" / f"trace_{c}chip").is_dir()}


def _summary(chips):
    d = TRACES[chips]
    return tr.summarize(tr.load(d), chips=chips,
                        hlo_text=(d / "step.hlo.txt").read_text())


def test_union_intersect_length():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tr.length(u) == 6
    assert tr.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]


def test_leaves_drop_enclosing_events():
    op = lambda n, a, b: tr.Op(n, "fusion", a, b, "")
    ops = [op("while", 0, 10), op("a", 1, 3), op("b", 4, 9), op("c", 5, 6),
           op("d", 11, 12)]
    assert [o.name for o in tr.leaves(ops)] == ["a", "c", "d"]


def test_hlo_scopes_and_opcodes():
    text = ('  %dot.1 = f32[8,8]{1,0} dot(%x, %w), metadata={op_name='
            '"jit(train_step)/fwd_bwd/dot_general" stack_frame_id=3}\n'
            '  ROOT %ag = f32[8]{0} all-gather-start(%p), metadata={op_name='
            '"jit(train_step)/optimizer/all_gather"}\n')
    scopes = tr.hlo_scopes(text)
    assert scopes == {"dot.1": "jit(train_step)/fwd_bwd/dot_general",
                      "ag": "jit(train_step)/optimizer/all_gather"}

    class Ev:
        def __init__(self, name):
            self.name, self.start_ns, self.end_ns = name, 0.0, 1.0

    dot = tr._op(Ev("%dot.1 = f32[8,8]{1,0:T(8,128)} dot(f32[8,8] %x)"), scopes)
    ag = tr._op(Ev("%ag = (f32[8]{0:T(128)}, u32[]) all-gather-start(%p)"), scopes)
    fus = tr._op(Ev("%fusion.9 = f32[] fusion(f32[] %all-reduce.3), kind=kLoop"), {})
    assert (dot.opcode, dot.in_scope("fwd_bwd"), dot.collective) == ("dot", True, False)
    assert (ag.opcode, ag.in_scope("optimizer"), ag.collective) == (
        "all-gather-start", True, True)
    assert (fus.opcode, fus.collective) == ("fusion", False)


def test_one_chip_trace_numbers():
    """Read off by hand from the trace: four steps of five 2048^3 matmuls
    (four under fwd_bwd, one under optimizer) at ~90 us each, ~4.5 ms
    apart on the host.  The device clock runs ~0.4 ms ahead of the host's,
    so the first step's ops fall partly before the window and are clipped."""
    s = _summary(1)
    assert s.window_s == pytest.approx(0.018117588, rel=1e-9)
    assert s.busy_s == pytest.approx(0.001494746, rel=1e-9)
    assert s.scope_s["fwd_bwd"] == pytest.approx(0.001087967, rel=1e-9)
    assert s.scope_s["optimizer"] == pytest.approx(0.00030416, rel=1e-9)
    assert s.top_ops[0][0] == "fusion [optimizer/dot_general]"
    assert [g for g, _ in s.idle_gaps[:4]] == ["data"] * 4
    # the allocator's events: 70,421,504 bytes allocated, none reserved
    assert s.memory_peak_bytes == 70421504


@pytest.mark.parametrize("chips", sorted(TRACES))
def test_recorded_trace_reduces(chips):
    s = _summary(chips)
    assert s.steps == 4
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
    assert s.scope_s["fwd_bwd"] > s.scope_s["optimizer"] > 0
    assert s.scope_s["fwd_bwd"] + s.scope_s["optimizer"] <= s.busy_s
    assert s.top_ops and all(v > 0 for _, v in s.top_ops)
    assert {g for g, _ in s.idle_gaps} <= {"data", "dispatch", "fence", "other"}
    assert s.collective_exposed_s <= s.collective_s
    assert s.memory_peak_bytes > 0
    assert (s.collective_s > 0) == (chips > 1)
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
