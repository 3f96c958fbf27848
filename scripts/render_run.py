#!/usr/bin/env python
"""Render a human run report from a JSONL run log (repro.obs RunSink).

    PYTHONPATH=src python scripts/render_run.py results/runs/<run_id>
    PYTHONPATH=src python scripts/render_run.py results/runs/<run_id>/run.jsonl

Stdlib-only (imports repro.obs.sink, which needs no jax/numpy), so reports
render anywhere the log file can be copied — no accelerator stack required.
Sections: run header, step-time percentiles + tokens/sec + MFU, the plan's
predicted comm-vs-compute split, checkpoint stalls, resize events, serving
request percentiles (TTFT/TPOT + queue depth from the continuous-batching
scheduler's request_start/first_token/request_end events), and the
cost-model drift verdict (GALV070 signals included).
"""
from __future__ import annotations

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.obs.sink import read_run  # noqa: E402


def _pct(values: list[float], p: float) -> float:
    if not values:
        return float("nan")
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    rank = (p / 100.0) * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def _ms(x: float) -> str:
    return f"{x * 1e3:.1f} ms"


def render(records: list[dict]) -> str:
    by = {}
    for rec in records:
        by.setdefault(rec.get("event"), []).append(rec)

    lines: list[str] = []
    start = by.get("run_start", [{}])[0]
    run_id = start.get("run_id", "<unknown>")
    lines.append(f"run report: {run_id}")
    head_bits = [f"{k}={start[k]}" for k in ("arch", "seq", "batch", "steps",
                                             "devices", "mode") if k in start]
    if head_bits:
        lines.append("  " + "  ".join(head_bits))
    lines.append("")

    # ---- steps ---------------------------------------------------------
    steps = by.get("step", [])
    if steps:
        times = [r["step_time_s"] for r in steps if "step_time_s" in r]
        toks = [r["tokens_per_sec"] for r in steps if r.get("tokens_per_sec")]
        mfus = [r["mfu"] for r in steps if r.get("mfu")]
        losses = [r["loss"] for r in steps if "loss" in r]
        lines.append(f"steps logged: {len(steps)}")
        if times:
            lines.append(
                f"  step time   p50 {_ms(_pct(times, 50))}   "
                f"p90 {_ms(_pct(times, 90))}   p99 {_ms(_pct(times, 99))}   "
                f"max {_ms(max(times))}")
        if toks:
            lines.append(f"  tokens/sec  mean {sum(toks) / len(toks):,.0f}   "
                         f"last {toks[-1]:,.0f}")
        if mfus:
            lines.append(f"  MFU         mean {100 * sum(mfus) / len(mfus):.2f}%   "
                         f"last {100 * mfus[-1]:.2f}%")
        else:
            lines.append("  MFU         not measured (the device has no "
                         "peak in repro.core.cluster)")
        if losses:
            lines.append(f"  loss        first {losses[0]:.4f}   "
                         f"last {losses[-1]:.4f}")
    else:
        lines.append("steps logged: 0")
    lines.append("")

    # ---- plan / predicted split ---------------------------------------
    for plan in by.get("plan", []):
        lines.append(f"plan[{plan.get('reason', '?')}]: "
                     f"{plan.get('strategy', '?')} "
                     f"mesh={tuple(plan.get('mesh_shape', ()))} "
                     f"ga={plan.get('grad_accum', '?')}")
        pred = plan.get("predicted_step_time_s") or 0.0
        if pred:
            lines.append(f"  predicted step time {_ms(pred)}")
        bd = plan.get("predicted_breakdown") or {}
        comp, comm = bd.get("compute_s", 0.0), bd.get("comm_s", 0.0)
        if comp or comm:
            tot = comp + comm
            lines.append(
                f"  predicted split     compute {_ms(comp)} "
                f"({100 * comp / tot:.0f}%)   comm {_ms(comm)} "
                f"({100 * comm / tot:.0f}%)")
    if by.get("plan"):
        lines.append("")

    # ---- memory --------------------------------------------------------
    mems = [r.get("peak_hbm_bytes", 0) for r in by.get("memory", [])]
    if any(mems):
        lines.append(f"peak HBM (AOT memory_analysis): "
                     f"{max(mems) / 1e9:.3f} GB/device")
        lines.append("")

    # ---- checkpoints ---------------------------------------------------
    ckpts = by.get("ckpt", [])
    queued = [r for r in ckpts if r.get("phase") == "queued"]
    written = [r for r in ckpts if r.get("phase") == "written"]
    run_end = by.get("run_end", [{}])[-1]
    stall = run_end.get("ckpt_stall_seconds")
    if stall is None:
        stall = sum(r.get("stall_seconds", 0.0) for r in ckpts)
    if ckpts or stall:
        lines.append(f"checkpoints: {len(queued)} queued, "
                     f"{len(written)} written, "
                     f"total step-loop stall {_ms(stall or 0.0)}")
        lines.append("")

    # ---- resize --------------------------------------------------------
    for r in by.get("resize", []):
        lines.append(f"resize @ step {r.get('step', '?')}: "
                     f"{r.get('old_devices', '?')} -> "
                     f"{r.get('new_devices', '?')} devices "
                     f"({r.get('path', '?')}, "
                     f"{_ms(r.get('seconds', 0.0))}, "
                     f"{r.get('bytes_moved', 0) / 1e6:.1f} MB)")
    if by.get("resize"):
        lines.append("")

    # ---- serving requests ---------------------------------------------
    ends = by.get("request_end", [])
    starts = by.get("request_start", [])
    if starts or ends:
        lines.append(f"serving: {len(starts)} request(s) submitted, "
                     f"{len(ends)} completed, "
                     f"{len(by.get('request_evicted', []))} evicted")
        ttfts = [r["ttft_s"] for r in ends if "ttft_s" in r]
        tpots = [r["tpot_s"] for r in ends if "tpot_s" in r]
        if ttfts:
            lines.append(
                f"  ttft        p50 {_ms(_pct(ttfts, 50))}   "
                f"p90 {_ms(_pct(ttfts, 90))}   p99 {_ms(_pct(ttfts, 99))}")
        if tpots:
            lines.append(
                f"  tpot        p50 {_ms(_pct(tpots, 50))}   "
                f"p90 {_ms(_pct(tpots, 90))}   p99 {_ms(_pct(tpots, 99))}")
        gen = sum(r.get("generated_tokens", 0) for r in ends)
        total = [r.get("total_s", 0.0) for r in ends]
        if gen and total:
            lines.append(f"  tokens      {gen:,} generated; request total "
                         f"p50 {_ms(_pct(total, 50))}   "
                         f"p99 {_ms(_pct(total, 99))}")
        depths = [r["queue_depth"] for r in starts + ends
                  if "queue_depth" in r]
        if depths:
            lines.append(f"  queue depth mean {sum(depths) / len(depths):.1f}"
                         f"   max {max(depths)}")
        lines.append("")

    # ---- drift verdict -------------------------------------------------
    drifts = by.get("drift", [])
    signals = by.get("replan_signal", [])
    sustained = (run_end.get("drift_sustained")
                 or any(d.get("sustained") for d in drifts))
    if sustained:
        last = next((d for d in reversed(drifts) if d.get("sustained")),
                    drifts[-1] if drifts else {})
        lines.append(
            f"drift verdict: DRIFTING (GALV070) — measured EMA "
            f"{_ms(last.get('measured_ema', 0.0))} vs predicted "
            f"{_ms(last.get('predicted', 0.0))} "
            f"(ratio {last.get('ratio', float('nan')):.2f}); "
            f"{len(signals)} replan signal(s) logged — re-profile and "
            f"re-search recommended")
    elif drifts:
        lines.append(f"drift verdict: transient divergence on "
                     f"{len(drifts)} step(s), never sustained — OK")
    else:
        lines.append("drift verdict: OK (measured step time within the "
                     "cost model's threshold band, or no prediction to "
                     "compare against)")

    if run_end:
        ws = run_end.get("wall_seconds")
        if ws is not None:
            lines.append(f"wall time: {ws:.2f} s for "
                         f"{run_end.get('steps', '?')} steps, "
                         f"{run_end.get('tokens', 0):,} tokens")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Render a run report from a repro.obs JSONL run log.")
    ap.add_argument("run", help="run directory (containing run.jsonl) or the "
                                "run.jsonl path itself")
    args = ap.parse_args(argv)
    path = pathlib.Path(args.run)
    if path.is_dir():
        path = path / "run.jsonl"
    if not path.exists():
        print(f"render_run: no run log at {path}")
        return 2
    records = read_run(path)
    print(render(records))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
