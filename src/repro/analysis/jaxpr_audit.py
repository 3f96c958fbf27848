"""Staged-jaxpr audit — dtype drift, remat presence, host callbacks.

The jaxpr half of the compiled-artifact auditor
(:mod:`repro.analysis.hlo_audit` orchestrates; this module owns everything
answerable *before* XLA: walk every equation of the staged train/serve step
— recursing through ``pjit``/``scan``/``while``/``cond``/``remat2``
sub-jaxprs — and check the program against the plan:

* **GALV091 dtype-drift** — a bf16 plan whose hot path runs f32×f32
  ``dot_general``/conv compute.  Only matmul-class ops are inspected, so the
  sanctioned f32 islands (rmsnorm/softmax internals, the fp32 logit/loss
  accumulators — all elementwise or reductions, and bf16-operand dots with
  f32 *accumulation*) never trip it; the rule catches a forward pass that
  was staged at the wrong width, which doubles activation memory and
  invalidates the searched plan's cost/memory ranking.
* **GALV092 remat-missing** — the plan declares ``remat != none`` but no
  checkpoint region in the jaxpr contains a matmul.  ``jax.checkpoint``
  stages a ``remat2`` equation; a policy that wraps only elementwise
  epilogues (or a remat wrapper that was dropped entirely) saves nothing,
  so the memory model's remat credit is fiction.  Empirically (JAX 0.4.37)
  the clean ``remat='none'`` step still stages small dot-free ``remat2``
  regions from library internals — hence the contains-a-dot requirement.
* **GALV093 host-callback-in-step** (jaxpr side) — ``pure_callback`` /
  ``io_callback`` / debug prints (``debug_print`` on JAX 0.9) staged inside
  the step sync the host every tick.

Verified on JAX 0.9.0: the checkpoint primitive is named ``remat2``.
"""
from __future__ import annotations

import dataclasses
from collections import Counter

from repro.analysis.plan_check import Diagnostic

#: jax.checkpoint's staged primitive
REMAT_PRIMITIVES = ("remat2",)

#: host-synchronizing primitives that must never stage inside the step
CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                       "debug_print", "host_callback_call", "outside_call",
                       "infeed", "outfeed")

#: matmul-class compute primitives inspected for dtype drift
_DOT_PRIMITIVES = ("dot_general", "conv_general_dilated")


@dataclasses.dataclass
class JaxprSummary:
    """Primitive census of one staged step function."""

    prim_counts: Counter            # primitive name -> occurrences
    dot_dtypes: Counter             # (lhs_dtype, rhs_dtype) -> dot count
    f32_dots: int                   # dots with BOTH operands f32
    total_dots: int
    remat_eqns: int                 # checkpoint regions staged
    remat_dots: int                 # matmuls inside checkpoint regions
    callbacks: list                 # callback primitive names found


def _sub_jaxprs(eqn):
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for u in vs:
            if hasattr(u, "eqns"):
                out.append(u)
            elif hasattr(u, "jaxpr"):        # ClosedJaxpr
                out.append(u.jaxpr)
    return out


def summarize_jaxpr(jaxpr) -> JaxprSummary:
    """Walk a (Closed)Jaxpr recursively and census its primitives."""
    s = JaxprSummary(Counter(), Counter(), 0, 0, 0, 0, [])

    def walk(jx, in_remat):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            s.prim_counts[name] += 1
            if name in _DOT_PRIMITIVES:
                dts = tuple(str(v.aval.dtype) for v in eqn.invars
                            if hasattr(v, "aval")
                            and getattr(v.aval, "shape", None) is not None)
                if len(dts) >= 2:
                    s.dot_dtypes[dts[:2]] += 1
                    s.total_dots += 1
                    if dts[0] == dts[1] == "float32":
                        s.f32_dots += 1
                    if in_remat:
                        s.remat_dots += 1
            if name in REMAT_PRIMITIVES:
                s.remat_eqns += 1
            if name in CALLBACK_PRIMITIVES:
                s.callbacks.append(name)
            for sub in _sub_jaxprs(eqn):
                walk(sub, in_remat or name in REMAT_PRIMITIVES)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, False)
    return s


def audit_jaxpr(jaxpr, plan, *, dtype: str = "bf16") -> list[Diagnostic]:
    """GALV091/092/093 diagnostics for one staged step against its plan.

    ``jaxpr`` is ``jax.make_jaxpr(step_fn)(*abstract_args)`` (or any
    (Closed)Jaxpr); ``dtype`` is the plan's compute dtype (the runtime's
    forward default is bf16)."""
    s = summarize_jaxpr(jaxpr)
    diags: list[Diagnostic] = []

    if dtype in ("bf16", "bfloat16") and s.f32_dots > 0:
        diags.append(Diagnostic(
            "GALV091",
            f"{s.f32_dots}/{s.total_dots} matmuls run f32×f32 in a {dtype} "
            "plan — the forward pass was staged at the wrong width "
            "(f32 rmsnorm/softmax/logit accumulators are elementwise or "
            "bf16-operand and never counted)",
            where="jaxpr"))

    declared = [i for i, st in enumerate(plan.layer_strategies)
                if st.remat != "none"]
    if declared and s.remat_dots == 0:
        pol = sorted({plan.layer_strategies[i].remat for i in declared})
        diags.append(Diagnostic(
            "GALV092",
            f"plan declares remat={'/'.join(pol)} on {len(declared)} "
            f"layer(s) but no checkpoint region in the staged step contains "
            f"a matmul ({s.remat_eqns} dot-free remat2 eqn(s) found) — "
            "nothing will be recomputed in the backward",
            where="jaxpr"))

    if s.callbacks:
        kinds = Counter(s.callbacks)
        desc = ", ".join(f"{k}×{n}" for k, n in sorted(kinds.items()))
        diags.append(Diagnostic(
            "GALV093",
            f"host callback primitive(s) staged inside the jitted step: "
            f"{desc}",
            where="jaxpr"))
    return diags
