"""Roofline analysis (deliverable g): three terms per (arch × shape × mesh)
from the dry-run artifacts in results/dryrun/.

  compute term    = FLOPs / (chips × peak_FLOP/s)
  memory term     = HBM bytes / (chips × HBM_bw)
  collective term = collective bytes / (chips × link_bw)

FLOP source: the analytic profiler (exact by construction — parameter counts
pinned to the real models within 2% in tests), cross-checked against the
dry-run's UNROLLED lowering (`xla_unrolled_frac` column).  The XLA number
undercounts the flash-attention/SSD *inner* chunk scans (cost_analysis
counts while bodies once — verified in tests), so it is a lower bound; the
two agree closely for scan-light families (MoE ffn, mamba projections).
Bytes: compiled per-device "bytes accessed", scan-corrected by depth.
Collectives: partitioned-HLO parse with while-trip multiplication (exact).
"""
from __future__ import annotations

import json
import pathlib

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
BWD_FACTOR = 2.0

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "dryrun"
OUT = pathlib.Path(__file__).resolve().parents[1] / "results" / "roofline.csv"

_REMAT_EXTRA = {"full": 1.0, "selective": 0.0, "none": 0.0}  # ×fwd recompute


def _analytic_step_flops(cfg, spec, plan: dict, *, causal_frac: float = 1.0) -> float:
    """Global FLOPs per step as the runtime executes it (baseline runtime
    computes the full S² grid => causal_frac=1.0; the causal-skip §Perf
    variant passes the triangular fraction)."""
    from repro.configs.arch import KV_KINDS, layer_slots
    from repro.core.profiler_model import profile_model

    samples = spec.global_batch
    if spec.kind == "train":
        prof = profile_model(cfg, spec.seq_len, causal_frac=causal_frac)
        # strategy mix (remat recompute factors) from the plan summary
        mix = plan.get("strategies", {})
        total_layers = max(sum(mix.values()), 1)
        fwd = 0.0
        per_layer = [lp.flops for lp in prof.layers]
        quad = [lp.flops_quadratic for lp in prof.layers]
        base_fwd = sum(per_layer)
        extra = 0.0
        for short, count in mix.items():
            share = count / total_layers
            if short.endswith("-full"):
                extra += share * base_fwd
            elif short.endswith("-selective"):
                extra += share * sum(quad)
        fwd = base_fwd + prof.head_flops
        return samples * (fwd * (1.0 + BWD_FACTOR) + extra)
    if spec.kind == "prefill":
        prof = profile_model(cfg, spec.seq_len, causal_frac=causal_frac)
        return samples * (sum(lp.flops for lp in prof.layers) + prof.head_flops)
    # decode: one token against a cache of seq_len
    prof = profile_model(cfg, 1, causal_frac=1.0)
    per_tok = sum(lp.flops for lp in prof.layers) + prof.head_flops
    # k/v reads of every layer that keeps a decode cache
    attn_layers = sum(s.kind in KV_KINDS for s in layer_slots(cfg))
    per_tok += attn_layers * 4.0 * spec.seq_len * cfg.num_heads * cfg.resolved_head_dim
    return samples * per_tok


def analyze_cell(d: dict) -> dict | None:
    if "skipped" in d or "error" in d:
        return None
    from repro.configs.registry import get_config
    from repro.configs.shapes import SHAPES

    chips = d["devices"]
    cfg = get_config(d["arch"])
    spec = SHAPES[d["shape"]]
    plan = d.get("plan", {})
    xla = d["xla_cost_analysis"]
    unrolled = d.get("unrolled", {})

    flops_analytic = _analytic_step_flops(cfg, spec, plan)
    flops_xla = unrolled.get("flops_global", 0.0)
    scanned_global = max(xla["flops_per_device_scanned"] * chips, 1.0)
    scan_corr = max(flops_analytic / scanned_global, 1.0)
    bytes_per_device = xla["bytes_per_device_scanned"] * min(scan_corr, 64.0)
    coll_bytes = d["collectives"]["collective_bytes"]          # per device

    t_compute = flops_analytic / (chips * PEAK_FLOPS)
    t_memory = bytes_per_device / HBM_BW
    t_coll = coll_bytes / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())

    from repro.core.profiler_model import profile_model

    prof = profile_model(cfg, min(spec.seq_len, 8192))
    if spec.kind == "train":
        model_flops = prof.model_flops_per_token() * spec.seq_len * spec.global_batch
    elif spec.kind == "prefill":
        model_flops = (prof.model_flops_per_token() / 3.0
                       * spec.seq_len * spec.global_batch)
    else:
        model_flops = prof.model_flops_per_token() / 3.0 * spec.global_batch
    return {
        "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"], "chips": chips,
        "plan": plan.get("default", "?"), "grad_accum": plan.get("grad_accum", 1),
        "flops_analytic": flops_analytic,
        "xla_unrolled_frac": flops_xla / flops_analytic if flops_analytic else 0.0,
        "bytes_per_device": bytes_per_device,
        "collective_bytes_per_device": coll_bytes,
        "t_compute_s": t_compute, "t_memory_s": t_memory, "t_collective_s": t_coll,
        "dominant": dominant, "roofline_bound_s": bound,
        "model_flops": model_flops,
        "useful_flops_frac": model_flops / flops_analytic if flops_analytic else 0.0,
        "temp_bytes_per_device": d["memory_analysis"]["temp_size_in_bytes"],
        "args_bytes_per_device": d["memory_analysis"]["argument_size_in_bytes"],
        "compile_seconds": d.get("compile_seconds", 0.0),
    }


def load_all(pattern: str = "*.json") -> list[dict]:
    rows = []
    for path in sorted(RESULTS.glob(pattern)):
        d = json.loads(path.read_text())
        row = analyze_cell(d)
        if row:
            rows.append(row)
    return rows


def check() -> None:
    """CI smoke (hermetic): a synthetic dry-run cell must analyze to a
    well-formed roofline row, skip/error artifacts must be rejected, and any
    real artifacts on disk must also produce finite rows."""
    cell = {
        "arch": "llama3.2-1b", "shape": "train_4k", "mesh": "16x16",
        "devices": 256,
        "plan": {"default": "tp1-z3", "grad_accum": 4,
                 "strategies": {"tp1-z3": 16}},
        "xla_cost_analysis": {"flops_per_device_scanned": 1e12,
                              "bytes_per_device_scanned": 2e9},
        "unrolled": {"flops_global": 5e14},
        "collectives": {"collective_bytes": 1e9},
        "memory_analysis": {"temp_size_in_bytes": 8e9,
                            "argument_size_in_bytes": 4e9},
        "compile_seconds": 12.5,
    }
    row = analyze_cell(cell)
    assert row is not None
    terms = {"compute": row["t_compute_s"], "memory": row["t_memory_s"],
             "collective": row["t_collective_s"]}
    assert row["dominant"] in terms
    assert row["roofline_bound_s"] == max(terms.values()) > 0.0
    assert terms[row["dominant"]] == row["roofline_bound_s"]
    assert 0.0 < row["useful_flops_frac"] <= 1.5, row["useful_flops_frac"]
    assert row["flops_analytic"] > 0.0
    assert analyze_cell({"skipped": True}) is None
    assert analyze_cell({"error": "compile blew up"}) is None
    rows = load_all()
    for r in rows:
        assert r["roofline_bound_s"] > 0.0, r
        assert r["dominant"] in ("compute", "memory", "collective"), r
    print(f"roofline.check OK: synthetic cell dominant={row['dominant']}, "
          f"{len(rows)} artifact row(s)")


def main():
    rows = load_all()
    if not rows:
        print("no dry-run artifacts found; run repro.launch.dryrun first")
        return
    cols = ["arch", "shape", "mesh", "plan", "t_compute_s", "t_memory_s",
            "t_collective_s", "dominant", "useful_flops_frac", "xla_unrolled_frac"]
    print(",".join(cols))
    for r in rows:
        print(",".join(f"{r[c]:.4g}" if isinstance(r[c], float) else str(r[c])
                       for c in cols))
    OUT.parent.mkdir(parents=True, exist_ok=True)
    full_cols = list(rows[0])
    OUT.write_text("\n".join(
        [",".join(full_cols)] + [",".join(str(r[c]) for c in full_cols) for r in rows]))
    print(f"# wrote {OUT} ({len(rows)} cells)")


if __name__ == "__main__":
    main()
