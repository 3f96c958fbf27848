#!/usr/bin/env python3
"""Size a cell without the chip: AOT-compile its training step, with its
plan, for a described v5e (one chip) or v5e:2x2 (four), and print what
``memory_analysis()`` gives.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/sizing.py \
        --workload mamba2-2.7b.train-8k [--layers 6,8,10] [--remat none,full]

For each depth (and, for a one-chip cell, each recomputation level) it
prints the plan and the bytes a device holds: arguments + outputs +
temporaries - aliased (donated) buffers, against the chip's 15.75 GiB.
Nothing runs, so it says nothing about time.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
V5E_USABLE = 15.75 * 2**30


def with_depth(cell, layers: int, remat: str | None, batch: int = 0,
               seq: int = 0):
    conf = copy.deepcopy(cell.conf)
    conf["config"][cell.ref.DEPTH_KEY] = layers
    conf["program"]["set"]["num_layers"] = layers
    wl = copy.deepcopy(cell.wl)
    if remat is not None:
        wl["plan"]["remat"] = remat
    wl["global_batch"] = batch or wl["global_batch"]
    wl["seq_len"] = seq or wl["seq_len"]
    return dataclasses.replace(cell, conf=conf, wl=wl)


def size(cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import harness
    from repro.compat import NamedSharding, P

    devices = list(topo.devices)[:cell.chips]
    plan, hp = harness.build_program(cell, devices, harness.Spans())
    if hp.mesh is None:
        one = SingleDeviceSharding(devices[0])
        p_sh = jax.tree.map(lambda _: one, hp.abstract_params())
        o_sh = jax.tree.map(lambda _: one, hp.abstract_opt_state())
        b_sh = one
    else:
        p_sh = hp.shardings(hp.param_specs)
        o_sh = type(hp.abstract_opt_state())(
            step=NamedSharding(hp.mesh, P()), m=hp.shardings(hp.opt_specs),
            v=hp.shardings(hp.opt_specs))
        b_sh = NamedSharding(hp.mesh, P())
    place = lambda tree, sh: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, sh)
    tok = jax.ShapeDtypeStruct((cell.batch, cell.seq), jnp.int32, sharding=b_sh)
    compiled = hp.jit_train_step().lower(
        place(hp.abstract_params(), p_sh), place(hp.abstract_opt_state(), o_sh),
        {"tokens": tok, "labels": tok}).compile()
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return {"plan": plan.default_strategy.short(),
            "grad_accum": plan.grad_accum,
            "groups": [g.strategy.short() for g in plan.groups()],
            "predicted_step_time_s": plan.predicted_step_time,
            "argument_gib": ma.argument_size_in_bytes / 2**30,
            "output_gib": ma.output_size_in_bytes / 2**30,
            "temp_gib": ma.temp_size_in_bytes / 2**30,
            "alias_gib": ma.alias_size_in_bytes / 2**30,
            "held_gib": held / 2**30,
            "fits": held < V5E_USABLE}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", default="",
                    help="comma-separated depths to try (default: the file's)")
    ap.add_argument("--remat", default="",
                    help="comma-separated remat levels for a one-chip plan")
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch to try (default: the workload file's)")
    ap.add_argument("--seq", type=int, default=0,
                    help="sequence length to try (default: the file's)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies

    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.find_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    depths = [int(x) for x in args.layers.split(",") if x] or [
        cell.conf["config"][cell.ref.DEPTH_KEY]]
    remats = [x for x in args.remat.split(",") if x] or [None]
    for layers in depths:
        for remat in remats:
            c = with_depth(cell, layers, remat, args.batch, args.seq)
            try:
                r = size(c, topo)
            except Exception as e:  # noqa: BLE001 — report and go on
                r = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            print(f"{args.workload} layers {layers} batch {c.batch} seq "
                  f"{c.seq} remat "
                  f"{c.wl['plan'].get('remat', 'searched')}: {r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
