"""Compiled-artifact audit benchmark: planted-defect corpus + clean plan.

``repro.analysis.hlo_audit.audit_step`` statically proves the compiled step
matches the plan (GALV090–094).  This suite pins both directions of that
contract against the *real* runtime — every artifact here is a genuinely
staged/compiled train step, not synthetic HLO text:

* **clean** — the searched llama plan on a 2×2 ``("data","model")`` mesh
  compiles and audits with zero diagnostics (the cost model's per-axis
  census predicts the partitioner's actual collectives within the band);
* **forced-f32** — a wrapper model stages the forward at f32 under a bf16
  plan → flagged **GALV091**, the unmodified twin is not;
* **remat-stripped** — the runtime stages ``remat='none'`` while the plan
  declares ``remat='selective'`` (a dropped checkpoint wrapper) → flagged
  **GALV092**, the honestly-rematted twin is not;
* **callback** — a ``jax.debug.print`` staged inside the step → flagged
  **GALV093**, the clean twin is not;
* **mis-sharded** — params force-resharded onto the data axis of a pure-DP
  plan, which GSPMD silently repairs with all-gathers → flagged **GALV090**
  as an *error*; the unconstrained twin audits without one.

``--check`` asserts every defect is flagged with exactly its expected code
and that each clean twin is not — code-for-code, so an auditor regression
that stops catching (or starts over-reporting) a defect class fails CI.
The failing/passing *unit* twins for each code live in
``tests/test_plan_verifier.py``, enforced by the ``galv-catalog`` lint rule.

jax pins its device count at first backend init, so the corpus runs in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(same pattern as ``benchmarks/elastic_resize.py`` / ``tests/_mp.py``).

Usage:
  PYTHONPATH=src python benchmarks/hlo_audit.py           # table
  PYTHONPATH=src python benchmarks/hlo_audit.py --check   # CI smoke
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

N_DEVICES = 4
SEQ = 64
BATCH = 8
_MARKER = "HLO_AUDIT_ROWS:"

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

#: (case, GALV code that must appear, must it be an *error*) — None code
#: means the case must audit with zero errors and no GALV09x diagnostics.
EXPECTATIONS = (
    ("clean", None, False),
    ("forced-f32", "GALV091", True),
    ("forced-f32-twin", None, False),
    ("remat-stripped", "GALV092", True),
    ("remat-stripped-twin", None, False),
    ("callback", "GALV093", True),
    ("mis-sharded", "GALV090", True),
    ("mis-sharded-twin", None, False),
)


# --------------------------------------------------------------------------
# in-subprocess measurement
# --------------------------------------------------------------------------

def worker() -> list[dict]:
    """Stage/compile every corpus entry and audit it; needs 4 devices."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.analysis.hlo_audit import audit_step
    from repro.configs.registry import get_config
    from repro.configs.shapes import SHAPES
    from repro.core.search import SearchEngine
    from repro.core.strategy import LayerStrategy, uniform_plan
    from repro.launch import mesh as mesh_lib
    from repro.models import build_model
    from repro.runtime.data import input_specs
    from repro.runtime.train import construct_hybrid_parallel_model

    assert jax.device_count() >= N_DEVICES, jax.device_count()
    cfg = get_config("llama3.2-1b").reduced()
    spec = dataclasses.replace(
        [s for s in SHAPES.values() if s.kind == "train"][0],
        seq_len=SEQ, global_batch=BATCH)

    def stage(plan, mesh, model=None, wrap=None, compile_hlo=False):
        """(hlo_text | None, jaxpr) for one runtime configuration."""
        hp = construct_hybrid_parallel_model(
            model if model is not None else build_model(cfg), plan, mesh)
        specs = input_specs(cfg, spec, hp.model)
        args = (hp.abstract_params(), hp.abstract_opt_state(), specs)
        step = hp.train_step if wrap is None else wrap(hp, mesh)
        jaxpr = jax.make_jaxpr(step)(*args)
        hlo = None
        if compile_hlo:
            # a wrapped step keeps the runtime's input shardings: left
            # unspecified, XLA's propagation moves the params to wherever
            # the wrapper constrains them and no repair shows in the HLO
            jit = (hp.jit_train_step(donate=False) if wrap is None
                   else compat.jit(step, in_shardings=(
                       hp.shardings(hp.param_specs),
                       hp.shardings(hp.opt_state_specs()), None)))
            hlo = jit.lower(*args).compile().as_text()
        return hlo, jaxpr

    rows: list[dict] = []

    def audit(case, plan, hlo, jaxpr):
        t0 = time.perf_counter()
        rep = audit_step(plan, cfg, seq_len=SEQ, global_batch=BATCH,
                         hlo_text=hlo, jaxpr=jaxpr)
        rows.append({
            "case": case,
            "codes": sorted(set(rep.codes())),
            "error_codes": sorted(set(rep.error_codes())),
            "n_errors": len(rep.errors),
            "n_warnings": len(rep.warnings),
            "audit_s": time.perf_counter() - t0,
            "hlo": hlo is not None,
        })

    # ---- clean: the searched plan, fully compiled --------------------------
    plan = SearchEngine(cfg).search(
        SEQ, BATCH, mesh_shape=(2, 2), mesh_axes=("data", "model"),
        pp_options=[1]).plan
    mesh22 = mesh_lib.make_mesh((2, 2), ("data", "model"))
    audit("clean", plan, *stage(plan, mesh22, compile_hlo=True))

    # ---- forced-f32: forward staged at the wrong width ---------------------
    base = build_model(cfg)

    class F32Model:
        def __getattr__(self, k):
            return getattr(base, k)

        def forward_train(self, params, tokens, *, dtype=jnp.bfloat16,
                          layer_runner=None):
            return base.forward_train(params, tokens, dtype=jnp.float32,
                                      layer_runner=layer_runner)

    strat = LayerStrategy(tp=2, sp=True, zero=2, remat="none")
    plan_bf16 = uniform_plan(cfg.name, "train", (2, 2), ("data", "model"),
                             cfg.num_layers, strat)
    audit("forced-f32", plan_bf16,
          *stage(plan_bf16, mesh22, model=F32Model()))
    audit("forced-f32-twin", plan_bf16, *stage(plan_bf16, mesh22))

    # ---- remat-stripped: plan says selective, runtime staged none ----------
    plan_remat = uniform_plan(
        cfg.name, "train", (2, 2), ("data", "model"), cfg.num_layers,
        LayerStrategy(tp=2, sp=True, zero=2, remat="selective"))
    _, jaxpr_none = stage(plan_bf16, mesh22)       # runtime remat='none'
    audit("remat-stripped", plan_remat, None, jaxpr_none)
    audit("remat-stripped-twin", plan_remat, *stage(plan_remat, mesh22))

    # ---- callback: a debug print left inside the step ----------------------
    def with_print(hp, _mesh):
        def step(params, opt, batch):
            params, opt, metrics = hp.train_step(params, opt, batch)
            jax.debug.print("loss={x}", x=metrics["loss"])
            return params, opt, metrics
        return step

    audit("callback", plan_bf16,
          *stage(plan_bf16, mesh22, wrap=with_print))

    # ---- mis-sharded: GSPMD repairs a bad constraint with all-gathers ------
    from jax.sharding import NamedSharding, PartitionSpec

    # zero=0: params/grads/opt fully replicated, so the plan predicts NO
    # all-gather traffic on the data axis — the gather rule stays armed
    # (zero>=1 legitimately re-gathers the dp-sharded optimizer update)
    plan_dp = uniform_plan(cfg.name, "train", (N_DEVICES, 1),
                           ("data", "model"), cfg.num_layers,
                           LayerStrategy(zero=0))
    mesh41 = mesh_lib.make_mesh((N_DEVICES, 1), ("data", "model"))

    def misshard(hp, mesh):
        dp_sharding = NamedSharding(mesh, PartitionSpec("data"))

        def step(params, opt, batch):
            params = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, dp_sharding)
                if getattr(x, "ndim", 0) >= 1 and x.shape[0] % N_DEVICES == 0
                else x, params)
            return hp.train_step(params, opt, batch)
        return step

    audit("mis-sharded", plan_dp,
          *stage(plan_dp, mesh41, wrap=misshard, compile_hlo=True))
    audit("mis-sharded-twin", plan_dp,
          *stage(plan_dp, mesh41, compile_hlo=True))
    return rows


def run() -> list[dict]:
    """Spawn the 4-device worker subprocess and return its audit rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEVICES}"
    env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "import json, runpy, sys; "
        f"mod = runpy.run_path({str(pathlib.Path(__file__).resolve())!r}, "
        "run_name='bench_hlo_audit'); "
        f"print({_MARKER!r} + json.dumps(mod['worker']()))"
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"hlo_audit worker failed (rc={proc.returncode})\n"
                           f"stdout:\n{proc.stdout[-2000:]}\n"
                           f"stderr:\n{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARKER):
            return json.loads(line[len(_MARKER):])
    raise RuntimeError(f"no result marker in worker output:\n{proc.stdout[-2000:]}")


def check(verbose: bool = True) -> list[dict]:
    """CI smoke: every planted defect flagged with exactly its expected
    GALV code (as an error), every clean twin free of errors and codes."""
    rows = run()
    by_case = {r["case"]: r for r in rows}
    assert set(by_case) == {c for c, _, _ in EXPECTATIONS}, sorted(by_case)
    for case, code, as_error in EXPECTATIONS:
        r = by_case[case]
        if code is None:
            assert r["n_errors"] == 0, (
                f"{case}: clean artifact raised errors {r['error_codes']}")
            assert not r["codes"], (
                f"{case}: clean artifact raised {r['codes']} — the audit "
                "band regressed (false positives on a correct program)")
        else:
            where = r["error_codes"] if as_error else r["codes"]
            assert code in where, (
                f"{case}: expected {code} in {'errors' if as_error else 'codes'}, "
                f"got codes={r['codes']} errors={r['error_codes']}")
    if verbose:
        planted = [c for c, code, _ in EXPECTATIONS if code]
        print(f"OK: {len(planted)} planted defects flagged code-for-code "
              f"({', '.join(by_case[c]['error_codes'][0] for c in planted)})")
        clean = [c for c, code, _ in EXPECTATIONS if code is None]
        print(f"OK: {len(clean)} clean artifacts audited with zero "
              f"diagnostics (incl. the searched plan, compiled)")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: planted defects flagged code-for-code, "
                         "clean twins diagnostic-free")
    args = ap.parse_args()
    if args.check:
        check()
        return
    print("case,codes,error_codes,warnings,hlo,audit_ms")
    for r in run():
        print(f"{r['case']},{'+'.join(r['codes']) or '-'},"
              f"{'+'.join(r['error_codes']) or '-'},{r['n_warnings']},"
              f"{r['hlo']},{r['audit_s'] * 1e3:.1f}")


if __name__ == "__main__":
    main()
