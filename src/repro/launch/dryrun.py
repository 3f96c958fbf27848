import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh) cell.

For each cell this driver:
  1. obtains an ExecutionPlan — SearchEngine (mesh-constrained) for train
     cells, the serving heuristic for prefill/decode cells;
  2. lowers and COMPILES the step function against ShapeDtypeStruct inputs
     with full in_shardings on the production mesh (the required proof that
     the distribution config is coherent);
  3. records ``compiled.memory_analysis()`` / ``compiled.cost_analysis()``,
     and collective bytes parsed from the partitioned HLO with while-loop
     trip-count correction (XLA counts scan bodies once — see
     repro.analysis.hlo_stats); with ``--audit``, every train cell is
     additionally checked by the compiled-artifact auditor
     (repro.analysis.hlo_audit, GALV090-094) and audit errors fail the cell;
  4. additionally lowers an UNROLLED ga=1 variant (never compiled) whose
     ``cost_analysis`` gives exact global FLOPs/bytes for the roofline.

Results land in ``results/dryrun/<arch>__<shape>__<mesh>.json``; the roofline
benchmark (benchmarks/roofline.py) consumes them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod] [--skip-unrolled]
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import numpy as np

from repro import compat
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, supports_shape
from repro.analysis.hlo_stats import collective_stats
from repro.core.search import SearchEngine, serving_plan
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import build_model
from repro.runtime.data import input_specs
from repro.serving import step_engine
from repro.runtime.train import construct_hybrid_parallel_model

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


FAKE_DEVICES = 512                      # matches the XLA_FLAGS override above


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def _axis_mesh(degree: int, axis: str, flag: str) -> tuple[tuple, str]:
    """(degree, data, model=16) mesh shape + result tag for a cell with a
    leading staged/ring axis ("pod" for --pp cells, "cp" for --cp cells)."""
    if FAKE_DEVICES % (degree * 16) != 0 or degree > 32:
        raise ValueError(
            f"{flag} {degree} does not tile the {FAKE_DEVICES}-device "
            f"dry-run host (need {flag.lstrip('-')}*16 | {FAKE_DEVICES}, "
            f"{flag.lstrip('-')} <= 32)")
    shape = (degree, FAKE_DEVICES // (degree * 16), 16)
    return shape, axis + "x".join(map(str, shape))


def _pp_mesh(pp: int) -> tuple[tuple, str]:
    return _axis_mesh(pp, "pod", "--pp")


def _cp_mesh(cp: int) -> tuple[tuple, str]:
    return _axis_mesh(cp, "cp", "--cp")


def _plan_for(cfg, spec, mesh_shape, mesh_axes, arch, shape_id,
              pp: int = 1, pp_schedule: str | None = None,
              pp_interleave: int = 2, cp: int = 1):
    if spec.kind == "train":
        eng = SearchEngine(cfg)
        sched_opts = None
        if pp > 1 and pp_schedule:
            v = pp_interleave if pp_schedule == "interleaved" else 1
            sched_opts = [(pp_schedule, v)]
        res = eng.search(spec.seq_len, spec.global_batch,
                         mesh_shape=mesh_shape, mesh_axes=mesh_axes,
                         # pp=1 -> GSPMD path; --pp stages over the pod axis
                         pp_options=[pp],
                         pp_schedule_options=sched_opts,
                         # --cp pins the ring degree on the cp-axis mesh
                         cp_options=[cp] if cp > 1 else None,
                         arch=arch, shape_name=shape_id)
        return res.plan, {"search_seconds": res.search_seconds,
                          "search_feasible": res.feasible}
    plan = serving_plan(cfg, seq_len=spec.seq_len, batch=spec.global_batch,
                        mesh_shape=mesh_shape, mesh_axes=mesh_axes,
                        arch=arch, shape_name=shape_id)
    return plan, {"search_seconds": 0.0, "search_feasible": True}


def _memory_dict(ma) -> dict:
    return {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}


def _summarize_plan(plan) -> dict:
    ss: dict = {}
    for s in plan.layer_strategies:
        ss[s.short()] = ss.get(s.short(), 0) + 1
    return {"pp": plan.pp, "pp_schedule": plan.pp_schedule,
            "pp_interleave": plan.pp_interleave, "grad_accum": plan.grad_accum,
            "cp": plan.default_strategy.cp,
            "strategies": ss, "default": plan.default_strategy.short(),
            "predicted_step_time": plan.predicted_step_time,
            "predicted_memory": plan.predicted_memory,
            "notes": plan.notes}


def run_cell(arch: str, shape_id: str, *, multi_pod: bool = False,
             skip_unrolled: bool = False, verbose: bool = True,
             custom_mesh: tuple | None = None,
             force_strategy: str | None = None,
             force_ga: int | None = None,
             pp: int = 1, pp_schedule: str | None = None,
             pp_interleave: int = 2, cp: int = 1,
             seq_len: int | None = None,
             validate_only: bool = False,
             audit: bool = False,
             out: dict | None = None) -> dict:
    # ``out`` (when given) is mutated in place as stages complete, so a crash
    # mid-cell leaves the caller holding the stages that did succeed
    # (memory_analysis, lower/compile timings, ...) alongside the error.
    cfg = get_config(arch)
    spec = SHAPES[shape_id]
    if seq_len is not None:                          # long-context override
        spec = dataclasses.replace(spec, seq_len=seq_len)
    if pp > 1 and cp > 1:
        raise ValueError("--pp and --cp dry-run cells are separate scenarios")
    if cp > 1:                                       # ring: cp axis = seq shards
        from repro.analysis.invariants import cp_seq_divisible

        if not cp_seq_divisible(spec.seq_len, cp):
            raise ValueError(f"--cp {cp} needs seq_len % (2*cp) == 0; "
                             f"got {spec.seq_len}")
        shape, mesh_tag = _cp_mesh(cp)
        mesh = make_mesh(shape, ("cp", "data", "model"))
    elif pp > 1:                                     # staged: pod axis = stages
        shape, mesh_tag = _pp_mesh(pp)
        mesh = make_mesh(shape, ("pod", "data", "model"))
    elif custom_mesh is not None:                    # §Perf: alternative meshes
        mesh = make_mesh(tuple(custom_mesh), ("data", "model"))
        mesh_tag = "x".join(map(str, custom_mesh))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_tag = _mesh_tag(multi_pod)
    mesh_axes = tuple(mesh.axis_names)
    mesh_shape = tuple(mesh.shape[a] for a in mesh_axes)
    out = out if out is not None else {}
    out.update({"arch": arch, "shape": shape_id, "mesh": mesh_tag,
                "mesh_shape": mesh_shape, "devices": int(np.prod(mesh_shape)),
                "kind": spec.kind, "seq_len": spec.seq_len,
                "global_batch": spec.global_batch})

    ok, why = supports_shape(cfg, spec)
    if not ok:
        out["skipped"] = why
        if verbose:
            print(f"[skip] {arch} × {shape_id}: {why}")
        return out

    if (pp > 1 or cp > 1) and spec.kind != "train":
        raise ValueError(f"--pp/--cp apply to train shapes, not {spec.kind}")
    plan, search_meta = _plan_for(cfg, spec, mesh_shape, mesh_axes, arch, shape_id,
                                  pp=pp, pp_schedule=pp_schedule,
                                  pp_interleave=pp_interleave, cp=cp)
    if pp > 1 and (not search_meta["search_feasible"] or plan.pp != pp):
        # the search falls back to a pp=1 plan when nothing fits — don't file
        # a pp=1 measurement under a staged-mesh result tag
        raise ValueError(
            f"no feasible pp={pp} plan for {arch}×{shape_id} "
            f"(schedule={pp_schedule or 'searched'}; fallback pp={plan.pp})")
    if cp > 1 and (not search_meta["search_feasible"]
                   or plan.default_strategy.cp != cp):
        raise ValueError(
            f"no feasible cp={cp} plan for {arch}×{shape_id} "
            f"(needs ring attention, Arch.cp, and seq % (2*cp) == 0)")
    if force_strategy is not None:                   # §Perf: pinned variants
        from repro.core.strategy import LayerStrategy

        parts = force_strategy.split("-")
        kw: dict = {}
        for tkn in parts:
            if tkn.startswith("tp"):
                kw["tp"] = int(tkn[2:])
            elif tkn == "sp":
                kw["sp"] = True
            elif tkn.startswith("cp"):
                kw["cp"] = int(tkn[2:])
            elif tkn.startswith("z"):
                kw["zero"] = int(tkn[1:])
            elif tkn.startswith("ep"):
                kw["ep"] = int(tkn[2:])
            elif tkn in ("none", "selective", "full"):
                kw["remat"] = tkn
        strat = LayerStrategy(**kw)
        plan = dataclasses.replace(
            plan, layer_strategies=[strat] * len(plan.layer_strategies),
            default_strategy=strat,
            notes=plan.notes + f" | forced {force_strategy}")
    if force_ga is not None:
        plan = dataclasses.replace(plan, grad_accum=force_ga,
                                   notes=plan.notes + f" | forced ga{force_ga}")
    out.update(search_meta)
    out["plan"] = _summarize_plan(plan)

    if validate_only:
        # static verification only: print the diagnostic table and stop
        # before anything lowers or compiles
        from repro.analysis import plan_check as pc
        from repro.core.cluster import TPU_V5E_POD
        from repro.core.profiler_model import profile_model

        is_train = spec.kind == "train"
        report = pc.check_plan(
            plan,
            dataclasses.replace(TPU_V5E_POD, chips=out["devices"]),
            cfg, seq_len=spec.seq_len,
            global_batch=spec.global_batch if is_train else None,
            profile=profile_model(cfg, spec.seq_len) if is_train else None)
        print(report.format_table())
        out["validate_only"] = {"ok": report.ok(), "codes": report.codes()}
        if not report.ok():
            raise ValueError("plan verification failed: "
                             + ", ".join(report.error_codes()))
        return out

    model = build_model(cfg)

    # ------------------------------------------------------ build + lower
    t0 = time.perf_counter()
    if spec.kind == "train":
        opt_cfg = None
        if "bf16-adam" in plan.notes:
            import jax.numpy as jnp
            from repro.runtime.optimizer import AdamWConfig

            opt_cfg = AdamWConfig(m_dtype=jnp.bfloat16, v_dtype=jnp.bfloat16)
        if plan.pp > 1:
            from repro.runtime.train_pp import PipelineTrainer

            kw = {"opt_cfg": opt_cfg} if opt_cfg is not None else {}
            hp = PipelineTrainer(model, plan, mesh, **kw)
        else:
            hp = construct_hybrid_parallel_model(model, plan, mesh, opt_cfg=opt_cfg)
        args = (hp.abstract_params(), hp.abstract_opt_state(),
                input_specs(cfg, spec, model))
        lowered = hp.jit_train_step(donate=True).lower(*args)
    else:
        engine = step_engine(model, plan, mesh,
                             batch=spec.global_batch, max_len=spec.seq_len)
        params_abs = engine.abstract_params()      # bf16 at inference
        specs = input_specs(cfg, spec, model)
        if spec.kind == "prefill":
            fn = engine.jit_prefill_step()
            extras = {k: v for k, v in specs.items() if k != "tokens"}
            lowered = fn.lower(params_abs, specs["tokens"], extras)
        else:
            fn = engine.jit_decode_step(donate=True)
            lowered = fn.lower(params_abs, specs["tokens"], specs["cache"],
                               specs["cache_index"], specs["kv_len"])
    out["lower_seconds"] = time.perf_counter() - t0

    # ------------------------------------------------------ compile
    t0 = time.perf_counter()
    compiled = lowered.compile()
    out["compile_seconds"] = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    print(ma)                                # the required proof-of-fit output
    out["memory_analysis"] = _memory_dict(ma)
    ca = compat.cost_analysis(compiled)
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    out["xla_cost_analysis"] = {
        "flops_per_device_scanned": float(ca.get("flops", 0.0)),
        "bytes_per_device_scanned": float(ca.get("bytes accessed", 0.0)),
        "note": "XLA counts while(scan) bodies once; see unrolled + collectives",
    }
    hlo_text = compiled.as_text()
    stats = collective_stats(hlo_text)
    out["collectives"] = stats.merged()

    # ---------------------------------------------- compiled-artifact audit
    if audit and spec.kind == "train":
        import jax
        from repro.analysis.hlo_audit import audit_step

        try:
            jaxpr = jax.make_jaxpr(hp.train_step)(*args)
        except Exception:  # noqa: BLE001 — HLO-side checks still run
            jaxpr = None
        report = audit_step(plan, cfg, seq_len=spec.seq_len,
                            global_batch=spec.global_batch,
                            hlo_text=hlo_text, jaxpr=jaxpr)
        print(report.format_table())
        out["audit"] = report.to_event()
        if not report.ok():
            raise ValueError("compiled-artifact audit failed: "
                             + ", ".join(report.error_codes()))
    elif audit:
        out["audit"] = {"skipped": "census prediction covers train steps; "
                                   "prefill/decode cells are not audited"}

    # ------------------------------------------------------ unrolled lower
    if not skip_unrolled and spec.kind == "train" and plan.pp > 1:
        out["unrolled"] = {"skipped": "staged (pp>1) runs have no unrolled variant"}
    elif not skip_unrolled:
        t0 = time.perf_counter()
        try:
            if spec.kind == "train":
                plan1 = dataclasses.replace(
                    plan, grad_accum=1,
                    layer_strategies=list(plan.layer_strategies))
                hp_u = construct_hybrid_parallel_model(model, plan1, mesh, unroll=True,
                                                       opt_cfg=opt_cfg if spec.kind == "train" else None)
                args_u = (hp_u.abstract_params(), hp_u.abstract_opt_state(),
                          input_specs(cfg, spec, model))
                lowered_u = hp_u.jit_train_step(donate=True).lower(*args_u)
            else:
                engine_u = step_engine(model, plan, mesh,
                                       batch=spec.global_batch,
                                       max_len=spec.seq_len, unroll=True)
                specs = input_specs(cfg, spec, model)
                params_abs = engine_u.abstract_params()
                if spec.kind == "prefill":
                    extras = {k: v for k, v in specs.items() if k != "tokens"}
                    lowered_u = engine_u.jit_prefill_step().lower(
                        params_abs, specs["tokens"], extras)
                else:
                    lowered_u = engine_u.jit_decode_step(donate=True).lower(
                        params_abs, specs["tokens"], specs["cache"],
                        specs["cache_index"], specs["kv_len"])
            cu = compat.cost_analysis(lowered_u)
            out["unrolled"] = {
                "flops_global": float(cu.get("flops", 0.0)),
                "bytes_global_unoptimized": float(cu.get("bytes accessed", 0.0)),
                "lower_seconds": time.perf_counter() - t0,
                "note": "pre-SPMD global program, exact trip counts; bytes are "
                        "pre-fusion (upper bound)",
            }
        except Exception as e:  # noqa: BLE001 — record, don't fail the cell
            out["unrolled"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-unrolled", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    ap.add_argument("--mesh-shape", default=None,
                    help="custom single-pod mesh 'dp,tp' (hillclimb variants)")
    ap.add_argument("--force-strategy", default=None,
                    help="uniform LayerStrategy short string, e.g. tp16-sp-z2")
    ap.add_argument("--force-ga", type=int, default=None)
    ap.add_argument("--pp", type=int, default=1,
                    help=">1 stages the block stack over a pod axis (PP cell)")
    ap.add_argument("--pp-schedule", default=None,
                    choices=["gpipe", "1f1b", "interleaved"],
                    help="pin the pipeline schedule (default: searched)")
    ap.add_argument("--pp-interleave", type=int, default=2)
    ap.add_argument("--cp", type=int, default=1,
                    help=">1 rings attention over a cp axis (context-parallel "
                         "cell; needs seq %% (2*cp) == 0)")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="override the shape's sequence length (long-context "
                         "cells, e.g. --arch llama3.2-1b-long --seq-len 32768)")
    ap.add_argument("--validate-only", action="store_true",
                    help="statically verify the plan (repro.analysis."
                         "plan_check) and print the GALV diagnostic table — "
                         "nothing lowers or compiles; exit 1 on any error")
    ap.add_argument("--audit", action="store_true",
                    help="audit every compiled train cell against its plan "
                         "(repro.analysis.hlo_audit — GALV090-094: per-axis "
                         "collective census vs the cost model, dtype drift, "
                         "remat presence, host callbacks); a cell with audit "
                         "errors counts as a failure")
    ap.add_argument("--tag", default="", help="output filename suffix")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]
    if args.pp > 1 or args.cp > 1:
        meshes = [False]           # staged/ring cells build their own mesh
    elif args.both_meshes or (args.all and not args.multipod):
        meshes = [False, True]
    else:
        meshes = [args.multipod]

    custom = tuple(int(x) for x in args.mesh_shape.split(",")) if args.mesh_shape else None
    failures = 0
    for arch, shape_id in cells:
        for mp in meshes:
            if args.cp > 1:
                mtag = _cp_mesh(args.cp)[1]
            elif args.pp > 1:
                mtag = _pp_mesh(args.pp)[1]
            elif custom:
                mtag = "x".join(map(str, custom))
            else:
                mtag = _mesh_tag(mp)
            if args.seq_len:
                mtag += f"__seq{args.seq_len}"
            tag = f"{arch}__{shape_id}__{mtag}" + (f"__{args.tag}" if args.tag else "")
            path = outdir / f"{tag}.json"
            print(f"=== {tag} ===", flush=True)
            # run_cell fills res in place, so on failure the stages that did
            # succeed before the crash survive next to the error record
            res: dict = {"arch": arch, "shape": shape_id, "mesh": mtag}
            try:
                run_cell(arch, shape_id, multi_pod=mp,
                         skip_unrolled=args.skip_unrolled,
                         custom_mesh=custom,
                         force_strategy=args.force_strategy,
                         force_ga=args.force_ga,
                         pp=args.pp, pp_schedule=args.pp_schedule,
                         pp_interleave=args.pp_interleave,
                         cp=args.cp, seq_len=args.seq_len,
                         validate_only=args.validate_only, audit=args.audit,
                         out=res)
            except Exception as e:  # noqa: BLE001
                failures += 1
                res["error"] = f"{type(e).__name__}: {e}"
                res["traceback"] = traceback.format_exc()
                print(f"[FAIL] {tag}: {e}")
            path.write_text(json.dumps(res, indent=2, default=str))
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
