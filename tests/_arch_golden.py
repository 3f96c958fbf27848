"""Golden numbers of the planner and the data layer for every registered
architecture: per-layer profiles, searched plans, cache bytes, shape
support, replan candidates and input specs.

The architecture description (``repro.configs.arch``) must leave every one
of these unchanged; ``tests/test_search_engine.py`` and
``tests/test_arch.py`` compare against the recorded file entry by entry,
floats bit for bit (their JSON text, which round-trips exactly).

Regenerate only for a change that is meant to move them:

    PYTHONPATH=src python -m tests._arch_golden
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

GOLDEN = pathlib.Path(__file__).with_name("data") / "arch_golden.json"

#: the benchmark cell qwen3-14b.train-2k.4chip: its program config and search
CELL_SET = {"num_layers": 2, "norm_eps": 1e-6}
CELL_SEARCH = dict(seq_len=2048, global_batch=4, mesh_shape=(2, 2),
                   mesh_axes=("data", "model"))


def _profile(cfg) -> dict:
    from repro.core.profiler_model import profile_model

    prof = profile_model(cfg, 4096)
    records: list = []
    layers = []
    for lp in prof.layers:
        rec = {f.name: getattr(lp, f.name) for f in dataclasses.fields(lp)
               if f.name not in ("name", "shared_group")}
        rec["flop_parts"] = [[p.flops, p.shard_dim] for p in lp.flop_parts]
        if rec not in records:
            records.append(rec)
        layers.append([lp.name, lp.shared_group, records.index(rec)])
    return {"layers": layers, "records": records,
            "embed_params": prof.embed_params, "head_flops": prof.head_flops,
            "logits_bytes": prof.logits_bytes}


def arch_entry(arch: str) -> dict:
    """Everything but the searched plans, for one registered architecture."""
    from repro.configs.registry import get_config
    from repro.configs.shapes import SHAPES, supports_shape
    from repro.core.memory_model import kv_cache_bytes
    from repro.models import build_model
    from repro.runtime.data import input_specs
    from repro.runtime.elastic import replan_cp_candidates, replan_pp_candidates

    cfg = get_config(arch)
    specs = input_specs(cfg, SHAPES["train_4k"])
    model = type(build_model(cfg))
    return {
        "model": f"{model.__module__}:{model.__name__}",
        "profile": _profile(cfg),
        "kv_cache_bytes": kv_cache_bytes(cfg, 8, 4096),
        "supports_shape": {sid: list(supports_shape(cfg, spec))
                           for sid, spec in SHAPES.items()},
        "replan_pp": replan_pp_candidates(cfg, 8),
        "replan_cp": replan_cp_candidates(cfg, 8192, 8),
        "input_specs": [[k, list(v.shape), str(v.dtype)]
                        for k, v in specs.items()],
    }


def plan_entry(res) -> dict:
    """A SearchResult's plan, layer strategies run-length encoded."""
    p = res.plan
    runs: list = []
    for s in p.layer_strategies:
        d = dataclasses.asdict(s)
        if runs and runs[-1][0] == d:
            runs[-1][1] += 1
        else:
            runs.append([d, 1])
    return {"feasible": res.feasible, "pp": p.pp, "pp_schedule": p.pp_schedule,
            "pp_interleave": p.pp_interleave, "grad_accum": p.grad_accum,
            "layer_strategies": runs,
            "default_strategy": dataclasses.asdict(p.default_strategy),
            "predicted_step_time": p.predicted_step_time,
            "predicted_memory": p.predicted_memory, "notes": p.notes}


def production_searches(arch: str) -> list:
    """The searches of ``test_search_feasible_on_production_mesh``: the
    (16, 16) pod, and for grok-1 also the two-pod mesh."""
    from repro.configs.registry import get_config
    from repro.core.search import SearchEngine

    cfg = get_config(arch)
    out = [SearchEngine(cfg).search(4096, 256, mesh_shape=(16, 16),
                                    mesh_axes=("data", "model"),
                                    pp_options=[1], arch=arch,
                                    shape_name="train_4k")]
    if arch == "grok-1-314b":
        out.append(SearchEngine(cfg).search(4096, 256, mesh_shape=(2, 16, 16),
                                            mesh_axes=("pod", "data", "model"),
                                            pp_options=[1], arch=arch))
    return out


def cell_search():
    """qwen3-14b.train-2k.4chip's search, as the benchmark harness runs it."""
    from repro.configs.registry import get_config
    from repro.core.search import SearchEngine

    cfg = dataclasses.replace(get_config("qwen3-14b"), **CELL_SET)
    c = CELL_SEARCH
    return SearchEngine(cfg).search(c["seq_len"], c["global_batch"],
                                    mesh_shape=c["mesh_shape"],
                                    mesh_axes=c["mesh_axes"], arch=cfg.name)


def record() -> dict:
    from repro.configs.registry import ARCH_IDS

    return {
        "arch": {a: arch_entry(a) for a in ARCH_IDS},
        "plans": {a: [plan_entry(r) for r in production_searches(a)]
                  for a in ARCH_IDS},
        "cell_plan": plan_entry(cell_search()),
    }


def canonical(obj) -> str:
    """JSON text of ``obj`` as stored: equal text means bit-equal floats."""
    return json.dumps(obj, sort_keys=True)


def _text(obj, depth: int = 0) -> str:
    """Dicts nested up to three deep one key a line, deeper values compact."""
    if not isinstance(obj, dict) or depth == 3:
        return canonical(obj)
    pad = " " * (depth + 1)
    body = ",\n".join(f"{pad}{json.dumps(k)}: {_text(v, depth + 1)}"
                      for k, v in sorted(obj.items()))
    return "{\n" + body + "\n" + " " * depth + "}"


def load() -> dict:
    return json.loads(GOLDEN.read_text())


if __name__ == "__main__":
    GOLDEN.parent.mkdir(exist_ok=True)
    GOLDEN.write_text(_text(record()) + "\n")
    print(f"wrote {GOLDEN}")
