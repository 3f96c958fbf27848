"""End-to-end training driver: search -> construct -> train -> checkpoint.

CPU-scale by default (reduced or custom-dim configs); the same driver drives
a real pod by passing the production mesh.  Implements the paper's Fig. 2
user workflow plus the scale features: periodic atomic checkpoints, restart
from the latest step, and **live elastic resize** — ``--simulate-failure-at-step``
fires membership changes mid-run, the engine re-searches the plan for the
surviving devices, and the in-memory migration path (runtime/resize.py)
reshards params/opt-state/carry onto the replanned mesh without a restart
(``--elastic-mode checkpoint`` keeps the save/restore fallback path for
comparison — the two are bitwise equivalent).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 20 --seq 64 --batch 8
  PYTHONPATH=src python -m repro.launch.train --preset 100m --steps 300 \
      --seq 256 --batch 16 --ckpt-dir /tmp/ckpt
  # live shrink 8->4 at step 3, grow back 4->8 at step 6:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 10 --seq 32 --batch 8 \
      --simulate-failure-at-step 3,6 --resize-to 4,8
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis import plan_check
from repro.analysis.invariants import cp_seq_divisible
from repro.configs.registry import ARCH_IDS, ModelConfig, get_config
from repro.core import calibrate
from repro.core import profile_cache as pcache_lib
from repro.core.cluster import TPU_V5E_POD, device_peak_flops
from repro.core.profiler_model import profile_model
from repro.core.search import SearchEngine
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.core.strategy import ExecutionPlan, LayerStrategy, uniform_plan
from repro.models import build_model
from repro.runtime import checkpoint as ckpt_lib
from repro.runtime import resize as resize_lib
from repro.runtime.data import SyntheticDataset
from repro.runtime.elastic import (DriftReplanAdvisor, ElasticEvent, replan,
                                   replan_and_diff)
from repro.runtime.train import construct_hybrid_parallel_model
from repro.runtime.train_pp import PipelineTrainer

PRESET_100M = ModelConfig(
    name="llama-100m", family="dense", num_layers=12, d_model=640,
    num_heads=10, num_kv_heads=10, d_ff=2560, vocab_size=32_000,
    head_dim=64, mlp_type="swiglu", rope_theta=10_000.0)


def resolve_cfg(args) -> ModelConfig:
    if args.preset == "100m":
        cfg = PRESET_100M
    else:
        cfg = get_config(args.arch)
        cfg = cfg.reduced() if args.reduced else cfg
    if args.layers:
        # depth cut: the first N whole layers, every width as published
        if not 1 <= args.layers <= cfg.num_layers:
            raise SystemExit(f"--layers {args.layers}: {cfg.name} has "
                             f"{cfg.num_layers} layers")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def single_device_plan(cfg: ModelConfig, remat: str = "none",
                       grad_accum: int = 1) -> ExecutionPlan:
    """The plan a one-device run trains with (no search: nothing to shard)."""
    return uniform_plan(cfg.name, "train", (1,), ("data",), cfg.num_layers,
                        LayerStrategy(remat=remat),
                        grad_accum=max(grad_accum, 1))


def _int_list(text: str) -> list[int]:
    return [int(tok) for tok in str(text).split(",") if tok.strip()]


def _parse_events(args, n_dev: int) -> list[tuple[int, int]]:
    """[(fire_step, new_device_count), ...] from --simulate-failure-at-step /
    --resize-to, validated against the live device pool."""
    steps = _int_list(args.simulate_failure_at_step or "")
    sizes = _int_list(args.resize_to or "")
    if not steps:
        if sizes:
            raise SystemExit("--resize-to needs --simulate-failure-at-step "
                             "entries naming when each resize fires")
        return []
    if sizes and len(sizes) != len(steps):
        raise SystemExit("--resize-to needs one device count per "
                         "--simulate-failure-at-step entry")
    events = list(zip(steps, sizes)) if sizes else [(s, 0) for s in steps]
    if any(b <= a for a, b in zip(steps, steps[1:])):
        raise SystemExit("--simulate-failure-at-step entries must be "
                         "strictly increasing")
    for _, n in events:
        if sizes and n < 1:
            raise SystemExit(f"--resize-to {n} is not a device count")
        if n > n_dev:
            raise SystemExit(f"--resize-to {n} exceeds the live device pool "
                             f"({n_dev}); grow events can only reuse devices "
                             "this process already sees")
    return events


def _build_runtime(model, plan: ExecutionPlan):
    """(trainer, mesh) realizing ``plan`` on a prefix of the live devices —
    a shrunk plan leaves the departed devices out of the mesh."""
    mesh = mesh_lib.make_mesh(plan.mesh_shape, plan.mesh_axes,
                              devices=jax.devices()[:plan.num_devices])
    return resize_lib.make_trainer(model, plan, mesh), mesh


def _predicted_breakdown(plan: ExecutionPlan, cfg: ModelConfig, seq_len: int,
                         global_batch: int, calibration) -> dict:
    """Cost-model comm-vs-compute split for ``plan`` (seconds per step) —
    recorded in the plan event so the run report can compare the predicted
    split against the measured step times."""
    from repro.core import cost_model as cm

    profile = profile_model(cfg, seq_len)
    micro = max(global_batch // max(plan.grad_accum, 1), 1)
    cluster = dataclasses.replace(TPU_V5E_POD, chips=max(plan.num_devices, 1))
    env = cm.CostEnv(cluster=cluster,
                     devices=plan.num_devices // max(plan.pp, 1),
                     pp=plan.pp, micro_batch=micro,
                     grad_accum=plan.grad_accum,
                     pp_schedule=plan.pp_schedule,
                     pp_interleave=plan.pp_interleave,
                     calibration=calibration)
    if len(plan.layer_strategies) == len(profile.layers):
        strategies = list(plan.layer_strategies)
    else:
        strategies = [plan.default_strategy] * len(profile.layers)
    M = env.microbatches()
    compute = comm = 0.0
    for lp, s in zip(profile.layers, strategies):
        compute += M * cm.compute_time(lp, s, env)
        comm += M * (cm.tp_comm_time(lp, s, env)
                     + cm.cp_comm_time(lp, s, env)
                     + cm.ep_comm_time(lp, s, env))
        comm += cm.dp_comm_time(lp, s, env)
    # machine-comparable per-axis collective census — the same object the
    # compiled-artifact auditor (repro.analysis.hlo_audit) diffs against the
    # measured HLO census, recorded so run reports can replay the comparison
    census = cm.predicted_comm_census(
        profile, strategies, devices=env.devices, micro_batch=micro,
        grad_accum=plan.grad_accum, pp=plan.pp, mesh_axes=plan.mesh_axes)
    return {"compute_s": compute, "comm_s": comm,
            "predicted_step_time_s": plan.predicted_step_time,
            "comm_census": [dataclasses.asdict(e) for e in census]}


def _emit_plan(sink, reason: str, plan: ExecutionPlan, *,
               breakdown: dict | None = None,
               spec: "resize_lib.MigrationSpec | None" = None) -> None:
    """The single "here is the active plan" emitter — one structured ``plan``
    event plus one human line, shared by the initial-search, live-resize and
    legacy-replan paths (previously three near-identical print blocks)."""
    sched = f" pp={plan.pp}/{plan.pp_schedule}" + (
        f"x{plan.pp_interleave}" if plan.pp_interleave > 1 else "") \
        if plan.pp > 1 else ""
    note = plan.notes.split("|")[-1].strip() if plan.notes else ""
    line = (f"plan[{reason}]: {plan.default_strategy.short()} "
            f"ga={plan.grad_accum}{sched} mesh={plan.mesh_shape} "
            f"groups={len(plan.groups())}")
    if note:
        line += f" ({note})"
    print(line)
    if spec is not None:
        print(f"   migration spec: {spec.summary()}")
    fields = dict(
        reason=reason, strategy=plan.default_strategy.short(),
        mesh_shape=list(plan.mesh_shape), mesh_axes=list(plan.mesh_axes),
        grad_accum=plan.grad_accum, pp=plan.pp, pp_schedule=plan.pp_schedule,
        predicted_step_time_s=plan.predicted_step_time, notes=note)
    if breakdown:
        fields["predicted_breakdown"] = breakdown
    if spec is not None:
        fields["migration"] = spec.summary()
    sink.emit("plan", **fields)


def _aot_memory(step_fn, params, opt, batch):
    """(compiled step callable, peak HBM bytes) via the AOT memory_analysis
    the calibration path already uses — compiled once, then the compiled
    object IS the step function (no double compile).  The peak is what the
    step holds at once: arguments + outputs + temporaries, less the bytes
    donation aliases; 0 where the backend reports no memory analysis.  A
    compile error propagates."""
    compiled = step_fn.lower(params, opt, batch).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        return compiled, 0.0
    return compiled, float(ma.argument_size_in_bytes + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _run_audit(compiled_fn, step_fn, plan: ExecutionPlan, cfg: ModelConfig,
               args, params, opt, batch) -> None:
    """Post-compile gate for the search's winning plan: audit the compiled
    step (post-SPMD HLO + staged jaxpr) against the plan before the first
    tick, print the report, abort on audit errors."""
    from repro.analysis.hlo_audit import audit_step

    hlo_text = None
    if hasattr(compiled_fn, "as_text"):
        try:
            hlo_text = compiled_fn.as_text()
        except Exception:  # noqa: BLE001 — jaxpr-side checks still run
            hlo_text = None
    try:
        jaxpr = jax.make_jaxpr(step_fn)(params, opt, batch)
    except Exception:  # noqa: BLE001 — HLO-side checks still run
        jaxpr = None
    report = audit_step(plan, cfg, seq_len=args.seq, global_batch=args.batch,
                        hlo_text=hlo_text, jaxpr=jaxpr)
    print(report.format_table())
    if not report.ok():
        raise SystemExit("compiled-artifact audit failed: "
                         + ", ".join(report.error_codes())
                         + " — the compiled step does not match the plan")


def _apply_resize(cfg, args, event: ElasticEvent, model, hp, plan, params, opt,
                  carry: "resize_lib.CarryState", sink):
    """Replan for the survivors and migrate live state onto the new mesh.
    Returns the rebuilt (hp, plan, mesh, params, opt, carry, step_fn); the
    returned carry is the authoritative resume point for the loop."""
    new_plan, spec = replan_and_diff(cfg, event, args.seq, args.batch, plan,
                                     arch=cfg.name,
                                     profile_cache=args.profile_cache or None)
    _emit_plan(sink, "resize", new_plan, spec=spec)
    new_hp, new_mesh = _build_runtime(model, new_plan)
    if args.elastic_mode == "checkpoint":
        params, opt, carry, report = resize_lib.migrate_via_checkpoint(
            hp, new_hp, params, opt, carry, step=carry.step,
            async_write=args.ckpt_async == "on")
    else:
        params, opt, carry, report = resize_lib.migrate(
            hp, new_hp, params, opt, carry)
    print(f"   {report.summary()}")
    sink.emit("resize", step=carry.step, old_devices=event.old_devices,
              new_devices=event.new_devices, reason=event.reason,
              path=report.path, seconds=report.seconds,
              bytes_moved=report.bytes_moved, migration=spec.summary())
    return (new_hp, new_plan, new_mesh, params, opt, carry,
            new_hp.jit_train_step(donate=False))


def main(argv=None):
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "profile":
        # `train.py profile ...` — measured profiling into the on-disk cache
        from repro.launch import profile as profile_cli
        return profile_cli.main(argv[1:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-1b")
    ap.add_argument("--preset", choices=["100m"], default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the first N layers of the config and change "
                         "no width (a depth cut); 0 = every layer")
    ap.add_argument("--devices", type=int, default=0,
                    help="train on the first N visible devices; 0 = all")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", "--seq-len", dest="seq", type=int, default=128,
                    help="sequence length (--seq-len is an alias)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=0, help="0 = searched")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (>1 stages the block stack over a pod axis)")
    ap.add_argument("--cp", type=int, default=1,
                    help="context-parallel degree (>1 runs attention as a "
                         "ring over a cp mesh axis; needs seq %% (2*cp) == 0)")
    ap.add_argument("--pp-schedule", default="searched",
                    choices=["searched", "gpipe", "1f1b", "interleaved"],
                    help="pipeline schedule; 'searched' lets the engine pick")
    ap.add_argument("--pp-interleave", type=int, default=2,
                    help="virtual stages per physical stage (interleaved only)")
    ap.add_argument("--remat", default=None, choices=["none", "selective", "full"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-async", default="on", choices=["on", "off"],
                    help="'on' (default) writes checkpoints on a background "
                         "writer thread (the step loop only ever blocks on "
                         "the previous save); 'off' is the synchronous "
                         "escape hatch — byte-identical output either way")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-failure-at-step", "--simulate-failure-at",
                    dest="simulate_failure_at_step", default="",
                    help="comma-separated steps at which to fire a simulated "
                         "membership change (with --resize-to: live resize; "
                         "without: legacy replan-and-print)")
    ap.add_argument("--resize-to", default="",
                    help="comma-separated surviving device counts, one per "
                         "--simulate-failure-at-step entry; each event "
                         "replans + migrates live state onto the new mesh")
    ap.add_argument("--elastic-mode", default="live",
                    choices=["live", "checkpoint"],
                    help="how a resize event moves state: 'live' = in-memory "
                         "device_put migration; 'checkpoint' = save/restore "
                         "round trip (the fallback path / equivalence oracle)")
    ap.add_argument("--validate-only", action="store_true",
                    help="statically verify the plan (repro.analysis."
                         "plan_check) and print the GALV diagnostic table — "
                         "no params are initialized and nothing compiles; "
                         "exit 1 on any error")
    ap.add_argument("--audit", action="store_true",
                    help="audit the compiled step against the plan before "
                         "the first tick (repro.analysis.hlo_audit, "
                         "GALV090-094: per-axis collective census vs the "
                         "cost model, dtype drift, remat presence, host "
                         "callbacks); writes an `audit` event to the run "
                         "sink and aborts on audit errors")
    ap.add_argument("--digest", action="store_true",
                    help="print a deterministic state digest at the end "
                         "(params/opt sums + final loss) — lets two runs be "
                         "compared for bitwise-equivalent training state")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--run-dir", default="",
                    help="directory for the JSONL run log (repro.obs "
                         "RunSink; e.g. results/runs/<run_id>) — step "
                         "metrics, plan/resize/ckpt/drift events; render a "
                         "report with scripts/render_run.py")
    ap.add_argument("--profile-cache", default="",
                    help="path to a measured profile cache (see the `profile` "
                         "subcommand); calibrates the search's cost model — "
                         "analytic defaults when unset")
    args = ap.parse_args(argv)
    enable_compile_cache()

    calibration = calibrate.DEFAULT_CALIBRATION
    if args.profile_cache:
        try:
            calibration = calibrate.load_calibration(args.profile_cache)
        except FileNotFoundError:
            raise SystemExit(f"--profile-cache {args.profile_cache}: no such "
                             "file — run the `profile` subcommand first")
        except (pcache_lib.CorruptProfileCacheError,
                pcache_lib.StaleProfileCacheError) as e:
            raise SystemExit(f"--profile-cache: {e}")
        print(f"calibration: {calibration.source} "
              f"({args.profile_cache})")

    cfg = resolve_cfg(args)
    uncut = get_config(args.arch) if args.preset is None else PRESET_100M
    print(f"arch: {cfg.name} layers {cfg.num_layers} of {uncut.num_layers}  "
          f"d_model {cfg.d_model} heads {cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim {cfg.resolved_head_dim} d_ff {cfg.d_ff} "
          f"vocab {cfg.vocab_size}")
    model = build_model(cfg)
    if not 0 <= args.devices <= jax.device_count():
        raise SystemExit(f"--devices {args.devices}: {jax.device_count()} "
                         "visible")
    n_dev = args.devices or jax.device_count()
    events = _parse_events(args, n_dev)

    sink = (obs.RunSink.create(args.run_dir,
                               meta={"arch": cfg.name, "seq": args.seq,
                                     "batch": args.batch, "steps": args.steps,
                                     "devices": n_dev})
            if args.run_dir else obs.NullSink())

    # ---- plan: search the engine even at CPU scale (paper workflow) ------
    if args.cp > 1:
        if not cp_seq_divisible(args.seq, args.cp):
            raise SystemExit(f"--cp {args.cp} needs --seq % (2*cp) == 0 "
                             f"(zig-zag split); got seq {args.seq}")
        if cfg.family != "dense":
            raise SystemExit(f"--cp supports dense-family archs; "
                             f"{cfg.name} is {cfg.family}")
    if n_dev == 1:
        if args.cp > 1:
            print(f"warning: --cp {args.cp} ignored on a single device")
        if any(n for _, n in events):
            raise SystemExit("--resize-to needs a multi-device pool "
                             "(set XLA_FLAGS=--xla_force_host_platform_device_count)")
        plan = single_device_plan(cfg, args.remat or "none", args.grad_accum)
        mesh = None
    else:
        # staged/ring run: pod axis carries the pipeline, cp axis the
        # ring-attention sequence shards; schedule/cp searched or pinned
        try:
            shape, axes = mesh_lib.train_mesh_spec(n_dev, pp=args.pp, cp=args.cp)
        except ValueError as e:
            raise SystemExit(str(e))
        sched_opts = None
        if args.pp_schedule != "searched":
            v = args.pp_interleave if args.pp_schedule == "interleaved" else 1
            sched_opts = [(args.pp_schedule, v)]
        res = SearchEngine(cfg, calibration=calibration).search(
            args.seq, args.batch, mesh_shape=shape, mesh_axes=axes,
            pp_options=[args.pp], pp_schedule_options=sched_opts,
            cp_options=[args.cp] if args.cp > 1 else None,
            arch=cfg.name)
        if (args.pp > 1 or args.cp > 1) and (
                not res.feasible or res.plan.pp != args.pp):
            # the search falls back to a pp=1 max-sharding plan when nothing
            # fits — don't silently train something other than what was asked.
            # Plain (pp=1, cp=1) runs keep the historical best-effort
            # behavior: train the fallback plan rather than abort.
            raise SystemExit(
                f"no feasible pp={args.pp} cp={args.cp} plan for "
                f"--pp-schedule {args.pp_schedule} ({cfg.num_layers} layers, "
                f"{n_dev} devices; interleaved needs num_layers % "
                f"(pp*interleave) == 0, cp needs seq % (2*cp) == 0)")
        plan = res.plan
        mesh = mesh_lib.make_mesh(shape, axes, devices=jax.devices()[:n_dev])
    _emit_plan(sink, "search", plan,
               breakdown=_predicted_breakdown(plan, cfg, args.seq, args.batch,
                                              calibration))

    if args.validate_only:
        # static verification only: nothing below this point runs — no param
        # init, no lowering, no compile
        report = plan_check.check_plan(
            plan, dataclasses.replace(TPU_V5E_POD, chips=plan.num_devices),
            cfg, seq_len=args.seq, global_batch=args.batch,
            profile=profile_model(cfg, args.seq), calibration=calibration)
        print(report.format_table())
        raise SystemExit(0 if report.ok() else 1)

    if plan.pp > 1:
        hp = PipelineTrainer(model, plan, mesh)
    else:
        hp = construct_hybrid_parallel_model(model, plan, mesh)

    host_rng = jax.random.PRNGKey(0)     # the run's host key; rides CarryState
    params = hp.init_params(host_rng)
    opt = hp.init_opt_state(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} {n_params/1e6:.1f}M params")

    start_step = 0
    if args.resume and args.ckpt_dir and ckpt_lib.latest_step(args.ckpt_dir) is not None:
        try:
            restored = ckpt_lib.restore(
                args.ckpt_dir, params_like=hp.ungroup(params),
                opt_like=resize_lib.canonical_state(hp, params, opt)[1])
            opt = hp.place_opt_state(restored["opt"])
        except KeyError:
            # checkpoints from before live resize stored the optimizer state
            # in the trainer's grouped layout rather than the canonical one
            restored = ckpt_lib.restore(args.ckpt_dir,
                                        params_like=hp.ungroup(params),
                                        opt_like=opt)
            opt = jax.tree.map(jnp.asarray, restored["opt"])
        saved_plan = restored.get("plan")
        if saved_plan is not None:
            # GALV050: shards reshard freely across meshes, but the
            # checkpoint must describe THIS model (arch + layer count)
            incompat = plan_check.check_checkpoint_compat(saved_plan, plan)
            if incompat:
                for d in incompat:
                    print(d)
                raise SystemExit(1)
        params = hp.place_params(restored["params"])
        start_step = restored["step"]
        print(f"resumed from step {start_step}")

    ds = SyntheticDataset(cfg, seq_len=args.seq, global_batch=args.batch)
    step_fn = hp.jit_train_step(donate=False)
    writer = None
    if args.ckpt_dir and args.ckpt_async == "on":
        writer = ckpt_lib.CheckpointWriter(sink=sink)

    last_saved_step = -1
    sync_ckpt_seconds = 0.0

    def save_checkpoint(at_step: int) -> None:
        nonlocal last_saved_step, sync_ckpt_seconds
        if at_step == last_saved_step:    # final save == last periodic save
            return
        last_saved_step = at_step
        canon_p, canon_o = hp.checkpoint_state(params, opt)
        if writer is not None:
            writer.save_async(args.ckpt_dir, at_step, canon_p, canon_o, plan)
            print(f"checkpoint queued (async) step {at_step}")
        else:
            t0 = time.perf_counter()
            path = ckpt_lib.save(args.ckpt_dir, at_step, canon_p, canon_o, plan)
            dt = time.perf_counter() - t0
            sync_ckpt_seconds += dt
            sink.emit("ckpt", phase="written", step=at_step,
                      stall_seconds=dt, queue_depth=0, path=str(path))
            print(f"checkpoint -> {path}")

    # ---- telemetry: step timing / MFU / drift ---------------------------
    devices = plan.num_devices if mesh is not None else 1
    tokens_per_step = args.batch * args.seq
    flops_per_step = (profile_model(cfg, args.seq).model_flops_per_token()
                      * tokens_per_step)
    chip_peak = device_peak_flops(jax.devices()[0])   # 0 -> no MFU
    timer = obs.StepTimer(tokens_per_step=tokens_per_step,
                          flops_per_step=flops_per_step,
                          peak_flops=chip_peak * devices)
    drift = obs.DriftMonitor(plan.predicted_step_time)
    advisor = DriftReplanAdvisor(sink)
    drift_was_sustained = False
    compiled_fn = None                   # AOT-compiled step (set lazily)

    t_start = time.perf_counter()
    tokens_done = 0
    last_metrics = None
    pending = [e for e in events if e[0] >= start_step]
    if len(pending) != len(events):
        print(f"warning: dropping {len(events) - len(pending)} resize event(s) "
              f"before resumed step {start_step}")
    cur_devices = plan.num_devices if mesh is not None else 1
    step = start_step
    while step < args.steps:
        if pending and step == pending[0][0]:
            _, new_dev = pending.pop(0)
            if new_dev and mesh is not None:
                print(f"!! simulated membership change at step {step}: "
                      f"{cur_devices} -> {new_dev} devices ({args.elastic_mode})")
                event = ElasticEvent(old_devices=cur_devices,
                                     new_devices=new_dev, reason="simulated")
                carry = resize_lib.CarryState(step=step,
                                              samples_seen=step * args.batch,
                                              rng=host_rng)
                hp, plan, mesh, params, opt, carry, step_fn = _apply_resize(
                    cfg, args, event, model, hp, plan, params, opt, carry,
                    sink)
                step, host_rng = carry.step, carry.rng   # resume exactly where
                cur_devices = new_dev                    # the old trainer stopped
                compiled_fn = None                       # new plan recompiles
                drift.reset(plan.predicted_step_time)    # new prediction too
                timer.peak_flops = chip_peak * plan.num_devices
            else:
                # legacy behavior: replan for 75% capacity and report only
                print("!! simulated node failure: re-searching plan for 75% capacity")
                event = ElasticEvent(old_devices=256, new_devices=192)
                new_plan = replan(get_config(args.arch) if not args.preset else cfg,
                                  event, args.seq, args.batch)
                _emit_plan(sink, "replan-advisory", new_plan)
        batch = {k: jnp.asarray(v) for k, v in ds.batch(step).items()}
        if compiled_fn is None:
            with obs.span("compile") as compile_span:
                compiled_fn, peak_hbm = _aot_memory(step_fn, params, opt, batch)
            print(f"compiled step in {compile_span.duration_s:.1f} s, "
                  f"peak {peak_hbm / 2**30:.2f} GiB (memory analysis)")
            sink.emit("compile", step=step, seconds=compile_span.duration_s,
                      t0=compile_span.t0, t1=compile_span.t1)
            if peak_hbm:
                sink.emit("memory", step=step, peak_hbm_bytes=peak_hbm)
            if args.audit:
                # before the first tick (and after every resize recompile):
                # the compiled artifact must match the plan it was ranked by
                _run_audit(compiled_fn, step_fn, plan, cfg, args,
                           params, opt, batch)
        timer.start()
        params, opt, metrics = compiled_fn(params, opt, batch)
        rec = timer.stop(step, (params, opt, metrics))
        last_metrics = metrics       # host sync deferred to log/digest time
        tokens_done += args.batch * args.seq
        verdict = drift.observe(step, rec.step_time_s)
        if verdict is not None and (verdict.drifting or drift_was_sustained):
            sink.emit("drift", **verdict.as_dict())
            if verdict.sustained and not drift_was_sustained:
                warnings.warn(
                    f"GALV070 cost-model-drift: measured step-time EMA "
                    f"{verdict.measured_ema * 1e3:.1f} ms is "
                    f"{verdict.ratio:.2f}x the plan's predicted "
                    f"{verdict.predicted * 1e3:.1f} ms — re-profile and "
                    f"re-search recommended", stacklevel=2)
            advisor.observe(verdict)
            drift_was_sustained = verdict.sustained
        if step % args.log_every == 0 or step == args.steps - 1:
            host = jax.device_get(metrics)    # ONE device sync for the dict
            step_rec = {**rec.as_dict(), "loss": float(host["loss"]),
                        "grad_norm": float(host["grad_norm"])}
            sink.emit("step", **step_rec)
            dt = time.perf_counter() - t_start
            print(obs.format_live_line(step_rec)
                  + f"  avg tok/s {tokens_done / dt:,.0f}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(step + 1)
        step += 1
    if args.ckpt_dir:
        save_checkpoint(args.steps)
    ckpt_stall = sync_ckpt_seconds
    if writer is not None:
        path = writer.close()             # drain pending async saves
        ckpt_stall += writer.blocked_seconds
        print(f"checkpoint -> {path} "
              f"(async writer: {writer.saves_completed} saves, "
              f"{writer.blocked_seconds * 1e3:.1f} ms total step-loop stall)")
    sink.emit("run_end", steps=timer.steps, tokens=tokens_done,
              wall_seconds=time.perf_counter() - t_start,
              ckpt_stall_seconds=ckpt_stall,
              drift_sustained=drift_was_sustained)
    if args.digest:
        canon_p, canon_o = resize_lib.canonical_state(hp, params, opt)
        p_sum = sum(float(np.abs(np.asarray(jax.device_get(x), np.float64)).sum())
                    for x in jax.tree.leaves(canon_p))
        m_sum = sum(float(np.abs(np.asarray(jax.device_get(x), np.float64)).sum())
                    for x in jax.tree.leaves(canon_o.m))
        last_loss = float(last_metrics["loss"]) if last_metrics else float("nan")
        sink.emit("digest", params=p_sum, opt_m=m_sum, loss=last_loss)
        print(f"digest params={p_sum:.6e} opt_m={m_sum:.6e} loss={last_loss:.8f}")
    sink.close()
    print("done")


if __name__ == "__main__":
    main()
