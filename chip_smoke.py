#!/usr/bin/env python3
"""Chip smoke: the training main path on a TPU, at published widths.

Drives ``repro.launch.train.main`` — search, plan verifier,
``construct_hybrid_parallel_model``, the jitted step — in this one process,
for llama3.2-1b at its published widths (d_model 2048, 32 query / 8 KV heads,
d_ff 8192, vocab 128256), cut in depth only, with random weights from PRNG
key 0 and synthetic batches from seed 0.

    python3 chip_smoke.py             one chip (the first, even where more
                                      are visible)
    python3 chip_smoke.py --chips 4   the searched plan on four chips, against
                                      the same run on one chip

It exits non-zero, with no ``"ok": true`` line, when the repository is not
beside it, when JAX finds no TPU, when the chip's kind has no entry in the
peaks table, when a loss or grad norm is not finite, when the step-0 loss is
outside its band, when the two runs disagree, or when any phase raises.  On
success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": <chips>}}``.
Run logs go to ``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"

#: llama3.2-1b at published widths, 2 of its 16 layers.  With the step's
#: params and Adam state held twice (the loop does not donate), arguments +
#: outputs + temporaries of the compiled step come to about 12 GiB of the
#: chip's 15.75 GiB (AOT memory analysis for a described v5e).
SMOKE = {"arch": "llama3.2-1b", "layers": 2, "seq": 2048, "batch": 4,
         "steps": 6, "remat": "full"}

#: At random init the logits are near-uniform, so the step-0 loss sits near
#: ln(vocab).  The tied embedding (std 0.02) over d_model 2048 gives logits of
#: std ~0.9 after the final RMSNorm, which lifts the cross-entropy by about
#: sigma^2/2 = 0.4; the z-loss term adds ~0.015.  A band of 1.0 holds that
#: and catches a model whose logits are not near-uniform at init.
LOSS0_BAND = 1.0

#: Four chips against one: the same params, batches and math; only the split
#: and order of the bf16 partial sums differ (tp splits the head and FFN
#: contractions, dp the batch).  Each token's loss carries a few bf16
#: roundings (2^-8 relative), and a step's loss averages 8192 tokens, so a
#: gap above half a bf16 rounding unit, 2e-3 relative, means the two plans
#: compute different math.  The digest sums (|params|, |Adam m|) sum 0.4 G
#: elements and are held to the same bound.
AGREE_RTOL = 2e-3

_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    pass


def train_argv(smoke: dict, run_dir: pathlib.Path, devices: int,
               steps: int) -> list[str]:
    return ["--arch", smoke["arch"], "--layers", str(smoke["layers"]),
            "--seq", str(smoke["seq"]), "--batch", str(smoke["batch"]),
            "--steps", str(steps), "--remat", smoke["remat"],
            "--devices", str(devices), "--log-every", "1", "--digest",
            "--run-dir", str(run_dir)]


def run_training(smoke: dict, name: str, devices: int, steps: int) -> dict:
    """One ``train.main`` run; returns what its run log recorded."""
    from repro import obs
    from repro.launch import train

    run_dir = OUT / name
    shutil.rmtree(run_dir, ignore_errors=True)
    print(f"== {name}: train.main on {devices} device(s), {steps} steps",
          flush=True)
    train.main(train_argv(smoke, run_dir, devices, steps))
    by: dict = {}
    for rec in obs.read_run(run_dir / "run.jsonl"):
        by.setdefault(rec["event"], []).append(rec)
    compile_span = [s for s in by["run_end"][0]["spans"]
                    if s["name"] == "compile"][-1]
    return {"name": name, "devices": devices,
            "plan": by["plan"][0], "steps": by["step"],
            "compile_s": by["compile"][0]["seconds"],
            "compile_window": (compile_span["t0"], compile_span["t1"]),
            "peak_hbm": by.get("memory", [{}])[0].get("peak_hbm_bytes", 0.0),
            "digest": by["digest"][0]}


def check_run(run: dict, vocab: int) -> None:
    """Finite losses and grad norms; step-0 loss within its band."""
    for s in run["steps"]:
        if not (math.isfinite(s["loss"]) and math.isfinite(s["grad_norm"])):
            raise SmokeFailure(f"{run['name']}: step {s['step']} loss "
                               f"{s['loss']} grad norm {s['grad_norm']}")
    loss0 = run["steps"][0]["loss"]
    if abs(loss0 - math.log(vocab)) > LOSS0_BAND:
        raise SmokeFailure(f"{run['name']}: step-0 loss {loss0:.4f} is more "
                           f"than {LOSS0_BAND} from ln({vocab}) = "
                           f"{math.log(vocab):.4f}")


def compare_runs(a: dict, b: dict, rtol: float = AGREE_RTOL) -> list[str]:
    """Per-step loss and digest agreement of two runs; returns the lines
    that disagree (empty when they agree)."""
    bad = []
    pairs = [(f"step {sa['step']} loss", sa["loss"], sb["loss"])
             for sa, sb in zip(a["steps"], b["steps"])]
    pairs += [(f"digest {k}", a["digest"][k], b["digest"][k])
              for k in ("params", "opt_m")]
    for what, x, y in pairs:
        gap = abs(x - y) / max(abs(y), 1e-30)
        line = f"   {what}: {x:.6f} vs {y:.6f}  rel gap {gap:.2e}"
        print(line)
        if not gap <= rtol:
            bad.append(line)
    if len(a["steps"]) != len(b["steps"]):
        bad.append(f"   step counts differ: {len(a['steps'])} vs "
                   f"{len(b['steps'])}")
    return bad


def report(run: dict) -> None:
    plan = run["plan"]
    print(f"-- {run['name']}: plan {plan['strategy']} mesh "
          f"{tuple(plan['mesh_shape'])} axes {tuple(plan['mesh_axes'])} "
          f"grad_accum {plan['grad_accum']}; compile {run['compile_s']:.1f} s; "
          f"peak (memory analysis) {run['peak_hbm'] / 2**30:.2f} GiB")
    for s in run["steps"]:
        print(f"   step {s['step']}: loss {s['loss']:.6f} grad_norm "
              f"{s['grad_norm']:.4f} step time {s['step_time_s'] * 1e3:.1f} ms "
              f"tokens/s {s['tokens_per_sec']:,.0f} mfu {s['mfu']:.4f}")
    d = run["digest"]
    print(f"   digest params {d['params']:.6e} opt_m {d['opt_m']:.6e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository beside {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev0.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"{len(devices)} visible", file=sys.stderr)
        return 2

    from repro.configs.registry import get_config
    from repro.core.cluster import PEAK_FLOPS_BY_DEVICE_KIND
    from repro.launch.compile_cache import enable_compile_cache

    if dev0.device_kind not in PEAK_FLOPS_BY_DEVICE_KIND:
        print(f"chip_smoke: device kind {dev0.device_kind!r} has no entry in "
              "the peaks table (repro.core.cluster)", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    hits: list[float] = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(time.perf_counter())
        if event == _CACHE_HIT else None)

    print(f"jax {jax.__version__}")
    for d in devices:
        print(f"device {d.id}: platform {d.platform} kind {d.device_kind}")
    print(f"compile cache: {cache_dir}")
    vocab = get_config(SMOKE["arch"]).vocab_size

    if args.chips == 1:
        run = run_training(SMOKE, "one_chip", 1, SMOKE["steps"])
        report(run)
        check_run(run, vocab)
        stats = dev0.memory_stats() or {}
        print(f"peak_bytes_in_use (device 0): "
              f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
        # the same program again: its compile should come from the cache,
        # and the same inputs on the same chip give the same step-0 loss
        rerun = run_training(SMOKE, "one_chip_rerun", 1, 1)
        t0, t1 = rerun["compile_window"]
        hit = any(t0 <= t <= t1 for t in hits)
        print(f"second compile of the step hit the cache: {hit} "
              f"({rerun['compile_s']:.1f} s against {run['compile_s']:.1f} s; "
              f"{len(hits)} cache hits in the run)")
        l0, r0 = run["steps"][0]["loss"], rerun["steps"][0]["loss"]
        if l0 != r0:
            raise SmokeFailure(f"rerun step-0 loss {r0!r} != {l0!r}")
    else:
        multi = run_training(SMOKE, "four_chips", args.chips, SMOKE["steps"])
        report(multi)
        check_run(multi, vocab)
        for d in devices[:args.chips]:
            stats = d.memory_stats() or {}
            print(f"peak_bytes_in_use (device {d.id}): "
                  f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
        one = run_training(SMOKE, "one_chip_reference", 1, SMOKE["steps"])
        report(one)
        check_run(one, vocab)
        print(f"four chips against one (rtol {AGREE_RTOL}):")
        bad = compare_runs(multi, one)
        if bad:
            raise SmokeFailure("four-chip run disagrees with the one-chip "
                               "run:\n" + "\n".join(bad))
        print(f"{len(hits)} persistent-cache hits in the run")

    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
