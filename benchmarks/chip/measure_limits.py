#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 benchmarks/chip/measure_limits.py --workload <name> --seeds 12 \
        [--control-seeds 3] [--out readings.jsonl]

In one process, for each seed: the program's set-up and its first three
steps (the same ``Program`` a run builds, with no window), then the float32
reference; on the first ``--control-seeds`` seeds also the fp8 control (the
reference with fp8 matmul operands, the nearest precision below the
configuration's bfloat16) and the reference with each fault the cell can
have planted: ``half_batch`` and, across chips, ``no_exchange``.  One JSON
line per seed and reading goes to ``--out`` and stdout, then a summary: the
largest program gap (the lower reading) and the smallest control and fault
gaps (the upper readings) of each number compared.  A state left unchanged
reads 1 on the change and the gradient by construction and is not run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 100)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import os

    import jax

    import harness

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.find_cell(args.workload)
    devices = jax.devices()[:cell.chips]
    faults = ["half_batch"] + (["no_exchange"] if cell.chips > 1 else [])
    out = open(args.out, "w") if args.out else None
    rows = []

    def emit(row):
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + i
        prog = harness.Program(cell, devices, harness.Spans(), seed)
        readings = prog.check_steps()
        prog.free()
        del prog
        ref = harness.reference_readings(cell, seed, devices)
        emit({"seed": seed, "kind": "program", "loss": readings["loss"],
              "ref_loss": ref["loss"], **harness.gaps(readings, ref)})
        if i < args.control_seeds:
            for kind, kw in [("control_fp8", {"mode": "fp8"})] + [
                    (f, {"fault": f}) for f in faults]:
                r = harness.reference_readings(cell, seed, devices, **kw)
                emit({"seed": seed, "kind": kind, **harness.gaps(r, ref)})
    keys = ("loss_gap", "grad_gap", "change_gap")
    summary = {"lower": {k: max(r[k] for r in rows if r["kind"] == "program")
                         for k in keys}}
    for kind in {r["kind"] for r in rows} - {"program"}:
        summary[kind] = {k: min(r[k] for r in rows if r["kind"] == kind)
                         for k in keys}
    emit({"summary": summary})
    return 0


if __name__ == "__main__":
    sys.exit(main())
