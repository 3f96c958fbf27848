"""Runtime telemetry subsystem: step timing, nested trace spans, host
garbage-collection spans, the JSONL run sink (schema + crash tolerance), cost-model drift detection,
the drift->replan advisory signal, and the end-to-end driver run log that
``scripts/render_run.py`` renders."""
import json
import pathlib
import subprocess
import sys

import pytest

from repro import obs
from repro.obs.sink import SCHEMA_VERSION

REPO = pathlib.Path(__file__).resolve().parents[1]


class FakeClock:
    """Deterministic clock: returns the scripted times, then keeps ticking."""

    def __init__(self, start=0.0, tick=1.0):
        self.now = start
        self.tick = tick

    def __call__(self):
        t = self.now
        self.now += self.tick
        return t

    def advance(self, dt):
        self.now += dt


# ---------------------------------------------------------------- metrics

def test_step_timer_fences_and_computes_rates():
    clock = FakeClock(tick=0.0)
    fenced = []
    timer = obs.StepTimer(tokens_per_step=1000, flops_per_step=4e12,
                          peak_flops=1e14, clock=clock,
                          fence_fn=fenced.append)
    timer.start()
    clock.advance(0.5)
    rec = timer.stop(7, outputs="the-step-outputs")
    assert fenced == ["the-step-outputs"]         # fenced before the reading
    assert rec.step == 7 and rec.step_time_s == pytest.approx(0.5)
    assert rec.tokens_per_sec == pytest.approx(2000.0)
    assert rec.mfu == pytest.approx(4e12 / 0.5 / 1e14)
    assert timer.steps == 1
    assert rec.as_dict()["mfu"] == rec.mfu
    with pytest.raises(RuntimeError):
        timer.stop(8)                             # stop without start


# ------------------------------------------------------------------ spans

def test_span_nesting_order_depth_and_parents():
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("step"):
        with tr.span("fwd_bwd"):
            pass
        with tr.span("optimizer"):
            pass
    names = [r["name"] for r in tr.timeline()]
    assert names == ["step", "fwd_bwd", "optimizer"]   # chronological-open
    by = {r["name"]: r for r in tr.timeline()}
    assert by["step"]["depth"] == 0 and by["step"]["parent"] is None
    assert by["fwd_bwd"]["depth"] == 1 and by["fwd_bwd"]["parent"] == "step"
    assert by["optimizer"]["parent"] == "step"
    # parent closes after its children (FakeClock ticks 1s per reading)
    assert by["step"]["t1"] > by["optimizer"]["t1"]


def test_span_totals_and_open_span_visibility():
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("ckpt"):
        pass
    with tr.span("ckpt"):
        pass
    assert tr.total("ckpt") > 0
    # a span left open (crash) is recorded with t1=None and excluded from
    # total(); duration_s refuses to guess
    cm = tr.span("crashed")
    cm.__enter__()
    rec = tr.records[-1]
    assert rec.t1 is None and tr.total("crashed") == 0.0
    with pytest.raises(ValueError, match="still open"):
        _ = rec.duration_s
    tr.clear()
    assert tr.timeline() == []


def test_module_level_span_uses_default_tracer():
    tr = obs.default_tracer()
    before = len(tr.timeline())
    with obs.span("unit-test-span"):
        pass
    assert any(r["name"] == "unit-test-span" for r in tr.timeline()[before:])


def test_begin_end_pair_for_callbacks():
    """A callback's span: opened and closed by two calls, parented to the
    span open on the thread, and not itself a parent of later spans."""
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("step"):
        rec = tr.begin("cb")
        with tr.span("inner"):
            pass
        assert rec.t1 is None
        tr.end(rec)
    by = {r["name"]: r for r in tr.timeline()}
    assert by["cb"]["parent"] == "step" and by["cb"]["depth"] == 1
    assert by["inner"]["parent"] == "step"
    assert by["cb"]["t0"] < by["inner"]["t0"] < by["cb"]["t1"]
    assert tr.total("cb") == by["cb"]["t1"] - by["cb"]["t0"]


# -------------------------------------------------------------- gc spans

def test_gc_collect_leaves_closed_gc_gen2_span():
    import gc

    assert obs.gc_hook in gc.callbacks          # installed on import
    tr = obs.default_tracer()
    before = len(tr.records)
    with obs.span("window"):
        gc.collect()
    new = tr.records[before:]
    gen2 = [r for r in new if r.name == "gc_gen2"]
    assert gen2 and all(r.t1 is not None and r.t1 >= r.t0 for r in gen2)
    window = next(r for r in new if r.name == "window")
    assert gen2[-1].parent == "window"
    assert window.t0 <= gen2[-1].t0 <= gen2[-1].t1 <= window.t1


def test_gc_span_annotates_the_profiler_host_plane(tmp_path):
    """While a profiler runs, a collection is also an annotation of the same
    name on the trace's host plane, on the device trace's clock."""
    import gc

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(pb)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert "gc_gen2" in names


# ------------------------------------------------------------------- sink

def test_sink_roundtrip_schema_and_order(tmp_path):
    clock = FakeClock(start=100.0)
    with obs.RunSink.create(tmp_path / "r1", clock=clock,
                            meta={"arch": "llama"}) as sink:
        sink.emit("step", step=0, loss=2.5)
        sink.emit("run_end", steps=1)
    records = obs.read_run(tmp_path / "r1" / "run.jsonl")
    assert [r["event"] for r in records] == ["run_start", "step", "run_end"]
    assert records[0]["schema"] == SCHEMA_VERSION
    assert records[0]["run_id"] == "r1" and records[0]["arch"] == "llama"
    assert records[1]["loss"] == 2.5 and records[1]["ts"] >= 100.0


def test_sink_coerces_numpy_scalars(tmp_path):
    np = pytest.importorskip("numpy")
    with obs.RunSink.create(tmp_path) as sink:
        sink.emit("step", loss=np.float32(1.5), n=np.int64(3))
    rec = obs.read_run(tmp_path / "run.jsonl")[1]
    assert rec["loss"] == 1.5 and rec["n"] == 3
    assert isinstance(rec["loss"], float) and isinstance(rec["n"], int)


def test_truncated_final_line_skipped_with_warning(tmp_path):
    with obs.RunSink.create(tmp_path) as sink:
        sink.emit("step", step=0)
        sink.emit("step", step=1)
    path = tmp_path / "run.jsonl"
    raw = path.read_text()
    path.write_text(raw + '{"event": "step", "st')    # mid-write crash
    with pytest.warns(UserWarning, match="truncated final line"):
        records = obs.read_run(path)
    assert [r.get("step") for r in records[1:]] == [0, 1]


def test_midfile_garbage_is_corrupt_not_truncated(tmp_path):
    with obs.RunSink.create(tmp_path) as sink:
        sink.emit("step", step=0)
    path = tmp_path / "run.jsonl"
    lines = path.read_text().splitlines()
    lines.insert(1, "not json at all")
    path.write_text("\n".join(lines) + "\n")
    with pytest.raises(obs.CorruptRunLogError, match="line 2"):
        obs.read_run(path)
    # a complete line that parses but isn't an event record is corrupt too
    path.write_text('{"event": "run_start", "schema": %d}\n[1, 2]\n'
                    % SCHEMA_VERSION)
    with pytest.raises(obs.CorruptRunLogError, match="not an event record"):
        obs.read_run(path)


def test_stale_schema_and_missing_run_start_rejected(tmp_path):
    path = tmp_path / "run.jsonl"
    path.write_text(json.dumps(
        {"event": "run_start", "schema": SCHEMA_VERSION + 1}) + "\n")
    with pytest.raises(obs.StaleRunLogError) as ei:
        obs.read_run(path)
    assert ei.value.found == SCHEMA_VERSION + 1
    path.write_text('{"event": "step", "step": 0}\n')
    with pytest.raises(obs.CorruptRunLogError, match="not run_start"):
        obs.read_run(path)


def test_sink_reopen_appends_without_second_run_start(tmp_path):
    with obs.RunSink.create(tmp_path) as sink:
        sink.emit("step", step=0)
    with obs.RunSink.create(tmp_path) as sink:      # resume same log
        sink.emit("step", step=1)
    events = [r["event"] for r in obs.read_run(tmp_path / "run.jsonl")]
    assert events == ["run_start", "step", "step"]


def test_null_sink_and_live_line():
    sink = obs.NullSink()
    assert sink.emit("step", step=1)["step"] == 1
    sink.close()
    line = obs.format_live_line(
        {"step": 12, "loss": 2.3456, "grad_norm": 1.5,
         "tokens_per_sec": 12345.6, "mfu": 0.417, "step_time_s": 0.0213})
    assert "step    12" in line and "loss 2.3456" in line
    assert "gnorm 1.50" in line and "tok/s 12,346" in line
    assert "mfu 41.7%" in line and "dt 21.3ms" in line


def test_obs_importable_without_jax(tmp_path):
    """The sink/metrics/drift stack must work where only stdlib exists
    (render_run on a laptop, the CI lint lane)."""
    code = (
        "import sys; sys.modules['jax'] = None; sys.modules['numpy'] = None\n"
        "sys.path.insert(0, 'src')\n"
        "from repro import obs\n"
        f"s = obs.RunSink.create(r'{tmp_path}')\n"
        "s.emit('step', step=0); s.close()\n"
        "obs.fence(None)\n"
        f"print(len(obs.read_run(r'{tmp_path}' + '/run.jsonl')))\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "2"


# ------------------------------------------------------------------ drift

def test_drift_monitor_warmup_band_and_sustain():
    mon = obs.DriftMonitor(0.1, warmup_steps=2, sustain_steps=3,
                           ema_alpha=1.0, clock=FakeClock())
    assert mon.observe(0, 0.5) is None and mon.observe(1, 0.5) is None
    v = mon.observe(2, 0.11)                      # in band: ratio 1.1
    assert v.drifting is False and v.sustained is False
    for step in range(3, 6):
        v = mon.observe(step, 0.5)                # 5x the prediction
        assert v.drifting is True
    assert v.sustained is True                    # 3rd diverged step sustains
    assert mon.observe(6, 0.1).sustained is False  # back in band: clears
    assert mon._diverged_streak == 0


def test_drift_monitor_is_two_sided_and_reset():
    mon = obs.DriftMonitor(1.0, warmup_steps=0, sustain_steps=1,
                           ema_alpha=1.0)
    fast = mon.observe(0, 0.1)                    # 10x faster than predicted
    assert fast.drifting and fast.ratio == pytest.approx(0.1)
    mon.reset(0.1)                                # replan: new prediction
    assert mon.ema is None
    v = mon.observe(1, 0.1)
    assert v is not None and not v.drifting and v.ratio == pytest.approx(1.0)
    # a plan with no prediction yields no verdict at all
    mon.reset(0.0)
    assert mon.observe(2, 0.1) is None
    with pytest.raises(ValueError):
        obs.DriftMonitor(0.1, threshold=0.9)


def test_drift_ema_smooths_single_spikes():
    mon = obs.DriftMonitor(0.1, warmup_steps=0, sustain_steps=2)
    for step in range(20):
        v = mon.observe(step, 0.1)
    spike = mon.observe(20, 1.0)                  # one 10x outlier
    assert spike.drifting is False                # EMA absorbs it
    assert spike.measured_ema < 0.4


class _ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event, **fields):
        self.events.append({"event": event, **fields})
        return self.events[-1]


def test_drift_replan_advisor_cooldown_and_rearm():
    from repro.runtime.elastic import DriftReplanAdvisor

    def verdict(step, *, drifting, sustained):
        return obs.DriftVerdict(step=step, measured_ema=0.5, predicted=0.1,
                                ratio=5.0, drifting=drifting,
                                sustained=sustained)

    clock = FakeClock(tick=0.0)
    sink = _ListSink()
    adv = DriftReplanAdvisor(sink, cooldown_s=100.0, clock=clock)
    assert adv.observe(None) is False
    assert adv.observe(verdict(1, drifting=True, sustained=False)) is False
    assert adv.observe(verdict(2, drifting=True, sustained=True)) is True
    clock.advance(50.0)                           # inside cooldown: silent
    assert adv.observe(verdict(3, drifting=True, sustained=True)) is False
    clock.advance(60.0)                           # cooldown expired
    assert adv.observe(verdict(4, drifting=True, sustained=True)) is True
    # drift clears -> advisor re-arms immediately
    assert adv.observe(verdict(5, drifting=False, sustained=False)) is False
    assert adv.observe(verdict(6, drifting=True, sustained=True)) is True
    assert adv.signals_emitted == 3
    sig = sink.events[0]
    assert sig["event"] == "replan_signal" and sig["code"] == "GALV070"
    assert sig["step"] == 2 and "no auto-replan" in sig["action"]


# ------------------------------------------------- end-to-end driver run log

@pytest.fixture(scope="module")
def run_log(tmp_path_factory):
    """One reduced single-device training run with --run-dir; shared by the
    log-shape and render tests below."""
    from repro.launch.train import main

    run_dir = tmp_path_factory.mktemp("obs-e2e") / "run0"
    main(["--arch", "llama3.2-1b", "--reduced", "--steps", "4", "--seq", "32",
          "--batch", "4", "--log-every", "2", "--run-dir", str(run_dir)])
    return run_dir


def test_driver_emits_valid_run_log(run_log):
    records = obs.read_run(run_log / "run.jsonl")
    by = {}
    for r in records:
        by.setdefault(r["event"], []).append(r)
    assert records[0]["event"] == "run_start"
    assert records[0]["schema"] == SCHEMA_VERSION
    plan = by["plan"][0]
    assert plan["reason"] == "search"
    assert "predicted_breakdown" in plan
    steps = by["step"]
    assert len(steps) == 3           # steps 0, 2 (log-every) + 3 (final)
    for s in steps:
        assert s["step_time_s"] > 0 and s["tokens_per_sec"] > 0
        assert "loss" in s and "grad_norm" in s and s["mfu"] >= 0
    end = by["run_end"][0]
    assert end["steps"] == 4 and end["tokens"] == 4 * 4 * 32
    compile_ev = by["compile"][0]
    assert compile_ev["t0"] < compile_ev["t1"]
    assert compile_ev["seconds"] == pytest.approx(
        compile_ev["t1"] - compile_ev["t0"])
    assert "ckpt_stall_seconds" in end and end["drift_sustained"] is False


def test_render_run_reports_from_log(run_log):
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "render_run.py"),
         str(run_log)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = proc.stdout
    assert "run report:" in out
    assert "p50" in out and "p99" in out and "MFU" in out
    assert "drift verdict:" in out and "GALV070" not in out
    assert "predicted split" in out and "compute" in out and "comm" in out


def test_render_run_missing_log_exits_nonzero(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "render_run.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert proc.returncode == 2
    assert "no run log" in proc.stdout
