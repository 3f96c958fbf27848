#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <window> --trace <0|1>

Runs on the machine it is started on, on the first ``chips`` devices the
cell asks for.  It exits non-zero and prints no result when the repository
is not beside it, when JAX finds no TPU or fewer chips than the cell needs,
or when the chip's kind is not in the peaks table.  Otherwise the last line
of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, each number the comparison made beside its limit; the
same numbers are the last lines of stderr.  Everything else goes to stderr.

The compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, otherwise
``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def metric_readers(cell_name: str) -> dict:
    """{metric: (unit, read)} for the per-layer metrics this cell reports,
    each read by ``metrics/<name>.py``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = importlib.util.spec_from_file_location(
            f"metric_{m['name']}", HERE / "metrics" / f"{m['name']}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[m["name"]] = (m["unit"], mod.read)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no repository beside {HERE}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    import peaks

    try:
        cell = harness.find_cell(args.workload)
    except harness.Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if not cell.limits:
        print(f"run.py: {cell.name} has no limits file: set its limits from "
              "chip readings (measure_limits.py) first", file=sys.stderr)
        return 2
    t_imports = time.perf_counter()

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    t_jax = time.perf_counter()
    devices = jax.devices()
    t_backend = time.perf_counter()
    print(f"start-up: imports (JAX among them) {t_imports - T_START:.3f} s, "
          f"cache set-up {t_jax - t_imports:.3f} s, TPU start-up "
          f"{t_backend - t_jax:.3f} s", file=sys.stderr)
    if devices[0].platform != "tpu":
        print(f"run.py: JAX found no TPU (platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, "
              f"{len(devices)} visible", file=sys.stderr)
        return 3
    try:
        peak = peaks.peak(devices[0].device_kind)["bf16_flops"]
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3

    with contextlib.redirect_stdout(sys.stderr):
        try:
            result = harness.run_cell(
                cell, seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace), devices=devices[:cell.chips],
                t_start=T_START, peak_flops=peak,
                metric_readers=metric_readers(cell.name) if args.trace else {})
        except harness.Refused as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
