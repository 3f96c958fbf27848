"""Training cells: set-up, the measured window, and the comparison with the
plain reference that decides ``correct``.

A cell is found by its name in ``BENCHMARK.json``: its configuration file
(``configs/<config>.json``: sizes, the reference module, the program's
registry id and the keys set on it) and its workload file
(``workloads/<name>.json``: sequence length, global batch, how the plan is
made) and its limits file (``limits/<name>.json``: the limit of each number
the comparison holds).  Nothing here is specific to one cell.

Set-up (``setup_s``): the plan (one-device plan, or the search on the
cell's mesh and ``plan_check``), ``construct_hybrid_parallel_model``,
weights and Adam state made on the device from the seed directly in the
runtime's shardings, the step compiled (``jit_train_step()``, donating
params and optimizer state), then the step's first three calls on batches
0..2.  Those three calls are the warm-up and the comparison's readings: the
loss of each, the first gradient's leaf norms worked out from Adam's ``m``
after one step, and the leaf norms of the parameters' change after three.
The same compiled step and state then run back to back for ``--seconds``,
each call fenced with ``block_until_ready``.  After the window the device
memory peak is read, the program's state is freed, and the float32
reference follows the same three steps from the same seed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import math
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

import data as data_lib
import flops as flops_lib
import weights as weights_lib
from references.common import ADAMW

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: A leaf whose reference gradient norm is below this share of the median
#: leaf's moves by round-off and weight decay alone; its change is not
#: compared.
TINY_GRAD = 1e-3


class Refused(Exception):
    """The run cannot be made here; nothing is printed on stdout."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    traffic: str
    conf: dict                 # the configuration file
    wl: dict                   # the workload file
    ref: object                # the reference module
    limits: dict               # the limits file; empty until set

    @property
    def seq(self) -> int:
        return self.wl["seq_len"]

    @property
    def batch(self) -> int:
        return self.wl["global_batch"]


def load_cell(entry: dict, config: dict, root: pathlib.Path = ROOT) -> Cell:
    """The cell of a ``workloads`` entry of ``BENCHMARK.json`` and the
    ``configs`` entry it names: the configuration file, the workload file
    ``workloads/<name>.json`` and the limits file ``limits/<name>.json``
    (no limits where it is missing)."""
    name = entry["name"]
    conf = json.loads((root / config["file"]).read_text())
    wl = json.loads((HERE / "workloads" / f"{name}.json").read_text())
    if wl["traffic"] != entry["traffic"]:
        raise Refused(f"{name}: workload file says traffic {wl['traffic']!r}, "
                      f"BENCHMARK.json {entry['traffic']!r}")
    lim = HERE / "limits" / f"{name}.json"
    limits = json.loads(lim.read_text()) if lim.is_file() else {}
    ref = importlib.import_module(f"references.{conf['reference']}")
    return Cell(name, entry["chips"], entry["traffic"], conf, wl, ref, limits)


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``name`` in ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    confs = {c["name"]: c for c in bench["configs"]}
    return load_cell(cells[name], confs[cells[name]["config"]], root)


class Spans:
    """Host spans on the host clock, also written into the profiler's trace
    (``TraceAnnotation``) so that device idle gaps can be labelled."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def seconds(self, name: str) -> float | None:
        d = [t1 - t0 for n, t0, t1 in self.records if n == name]
        return sum(d) if d else None


# --------------------------------------------------------------------------
# the program
# --------------------------------------------------------------------------

def program_config(cell: Cell):
    from repro.configs.registry import get_config

    prog = cell.conf["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["set"])
    want = cell.ref.program_fields(cell.conf["config"])
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise Refused(f"{cell.name}: the program's {prog['arch']} with "
                      f"{prog['set']} differs from the configuration file "
                      f"(program, file): {bad}")
    return cfg


def search_plan(cfg, cell: Cell):
    """The launcher's search on the cell's mesh, then ``plan_check``."""
    from repro.analysis import plan_check
    from repro.core.cluster import TPU_V5E_POD
    from repro.core.profiler_model import profile_model
    from repro.core.search import SearchEngine

    p = cell.wl["plan"]
    res = SearchEngine(cfg).search(
        cell.seq, cell.batch, mesh_shape=tuple(p["mesh_shape"]),
        mesh_axes=tuple(p["mesh_axes"]), arch=cfg.name)
    if not res.feasible:
        raise Refused(f"{cell.name}: the search found no feasible plan")
    report = plan_check.check_plan(
        res.plan, dataclasses.replace(TPU_V5E_POD, chips=res.plan.num_devices),
        cfg, seq_len=cell.seq, global_batch=cell.batch,
        profile=profile_model(cfg, cell.seq))
    if not report.ok():
        raise Refused(f"{cell.name}: plan_check refused the searched plan:\n"
                      + report.format_table())
    return res.plan


def build_program(cell: Cell, devices, spans: Spans):
    """(plan, hybrid-parallel model) as the launcher builds them."""
    from repro import compat
    from repro.models import build_model
    from repro.runtime.train import construct_hybrid_parallel_model

    cfg = program_config(cell)
    p = cell.wl["plan"]
    if p["kind"] == "single":
        from repro.launch.train import single_device_plan
        plan, mesh = single_device_plan(cfg, p["remat"]), None
    elif p["kind"] == "search":
        with spans("search"):
            plan = search_plan(cfg, cell)
        mesh = compat.make_mesh(plan.mesh_shape, plan.mesh_axes,
                                devices=devices[:plan.num_devices])
    else:
        raise Refused(f"{cell.name}: unknown plan kind {p['kind']!r}")
    return plan, construct_hybrid_parallel_model(build_model(cfg), plan, mesh)


def check_layout(cell: Cell, hp) -> None:
    """The program's parameter tree has the reference's paths and shapes."""
    import jax

    spec = weights_lib.abstract(cell.ref.param_spec(cell.conf["config"]))
    prog = hp.model.abstract()
    shape = lambda t: {jax.tree_util.keystr(k): tuple(v.shape) for k, v in
                       jax.tree_util.tree_flatten_with_path(t)[0]}
    if shape(spec) != shape(prog):
        raise Refused(f"{cell.name}: the program's parameter tree differs "
                      f"from the reference's:\nprogram {shape(prog)}\n"
                      f"reference {shape(spec)}")


def _norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


class Program:
    """The object set-up builds and the window drives: the compiled step
    with its state."""

    def __init__(self, cell: Cell, devices, spans: Spans, seed: int):
        import jax
        from repro import compat
        from repro.compat import NamedSharding, P
        from repro.runtime.optimizer import AdamWState

        self.cell, self.seed = cell, seed
        with spans("build"):
            self.plan, self.hp = build_program(cell, devices, spans)
            hp = self.hp
            check_layout(cell, hp)
        self.devices = (list(hp.mesh.devices.flat) if hp.mesh is not None
                        else devices[:1])
        spec = cell.ref.param_spec(cell.conf["config"])
        self.vocab = cell.ref.token_vocab(cell.conf["config"])
        p_sh = hp.shardings(hp.param_specs)
        o_sh = None if hp.mesh is None else AdamWState(
            step=NamedSharding(hp.mesh, P()), m=hp.shardings(hp.opt_specs),
            v=hp.shardings(hp.opt_specs))
        with spans("init"):
            key = weights_lib.seed_key(seed)
            init = compat.jit(lambda k: hp.group(weights_lib.make(spec, k)),
                              out_shardings=p_sh)
            self.params = init(key)
            self.opt = compat.jit(hp.init_opt_state, out_shardings=o_sh)(
                self.params)
            jax.block_until_ready((self.params, self.opt))
        self.step_no = 0
        first = self.batch(0)
        with spans("compile"):
            self.step = hp.jit_train_step().lower(
                self.params, self.opt, first).compile()
        self.step_memory = self.step.memory_analysis()
        self._m_norms = compat.jit(
            lambda opt: _norms(hp.ungroup(opt.m)))
        self._change_norms = compat.jit(
            lambda p, k: _norms(jax.tree.map(
                lambda a, b: a - b, hp.ungroup(p), weights_lib.make(spec, k))))
        self._key = key
        self._first = first

    def batch(self, step: int) -> dict:
        import jax.numpy as jnp
        b = data_lib.batch(self.seed, step, self.cell.batch, self.cell.seq,
                           self.vocab)
        return {k: jnp.asarray(v) for k, v in b.items()}

    def call(self, batch):
        self.params, self.opt, metrics = self.step(self.params, self.opt, batch)
        self.step_no += 1
        return metrics

    def check_steps(self) -> dict:
        """The first three steps, with the readings the comparison needs."""
        import jax

        losses = []
        batch = self._first
        for t in range(3):
            metrics = self.call(batch)
            host = jax.device_get(metrics)
            losses.append(float(host["loss"]))
            if t == 0:
                gnorm = float(host["grad_norm"])
                scale = min(1.0, ADAMW["grad_clip"] / (gnorm + 1e-12))
                m = np.asarray(jax.device_get(self._m_norms(self.opt)))
                grad = m / ((1.0 - ADAMW["b1"]) * scale)
            if t < 2:
                batch = self.batch(t + 1)
        change = np.asarray(jax.device_get(
            self._change_norms(self.params, self._key)))
        return {"loss": losses, "grad": grad, "change": change}

    def free(self) -> None:
        import jax
        for x in jax.tree.leaves((self.params, self.opt)):
            x.delete()
        self.params = self.opt = self.step = None


# --------------------------------------------------------------------------
# the reference
# --------------------------------------------------------------------------

def _ref_sharding(cell: Cell, devices):
    """Shardings for the reference on the cell's devices: each weight split
    along its largest dimension that the device count divides, the batch
    split by rows.  ``None`` on one device."""
    if len(devices) == 1:
        return None
    import jax
    from repro import compat
    from repro.compat import NamedSharding, P

    n = len(devices)
    mesh = compat.make_mesh((n,), ("x",), devices=devices)

    def leaf(shape):
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if shape[i] % n == 0:
                spec = [None] * len(shape)
                spec[i] = "x"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    spec = weights_lib.abstract(cell.ref.param_spec(cell.conf["config"]))
    params = jax.tree.map(lambda a: leaf(a.shape), spec)
    rows = NamedSharding(mesh, P("x")) if cell.batch % n == 0 else \
        NamedSharding(mesh, P())
    return {"params": params, "rows": rows}


def reference_readings(cell: Cell, seed: int, devices, *, mode: str = "f32",
                       fault: str = "") -> dict:
    """Three steps of the plain reference from ``seed``: the same readings
    as :meth:`Program.check_steps`.  ``mode="fp8"`` is the control;
    ``fault`` plants one of the faults the comparison must catch:
    ``half_batch`` (the loss over half of the batch) and ``no_exchange``
    (a tensor-parallel partial sum left out)."""
    import jax
    import jax.numpy as jnp
    from repro import compat
    from references import common

    conf = cell.conf["config"]
    spec = cell.ref.param_spec(conf)
    vocab = cell.ref.token_vocab(conf)
    sh = _ref_sharding(cell, devices)
    p_sh = None if sh is None else sh["params"]
    constrain = (lambda x: x) if sh is None else (
        lambda x: jax.lax.with_sharding_constraint(x, sh["rows"]))

    def loss_fn(params, tokens, labels):
        if fault == "half_batch":
            # causal: the first half of every row is computed as in the
            # whole row, and the mean is taken over it alone
            half = tokens.shape[1] // 2
            tokens, labels = tokens[:, :half], labels[:, :half]
        nll, z2 = cell.ref.loss_sums(conf, params, tokens, labels, mode=mode,
                                     fault="" if fault == "half_batch" else fault,
                                     constrain=constrain)
        return common.loss_from_sums(nll, z2, labels.size)

    key = weights_lib.seed_key(seed)
    make = compat.jit(lambda k: weights_lib.make(spec, k), out_shardings=p_sh)
    grad_fn = compat.jit(jax.value_and_grad(loss_fn))
    adam = compat.jit(common.adamw_step, static_argnums=(4,),
                      donate_argnums=(0, 2, 3))
    norms = compat.jit(_norms)
    change = compat.jit(lambda p, k: _norms(jax.tree.map(
        lambda a, b: a - b, p, weights_lib.make(spec, k))))
    params = make(key)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t in range(3):
        b = data_lib.batch(seed, t, cell.batch, cell.seq, vocab)
        tokens, labels = (jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        if sh is not None:
            tokens = jax.device_put(tokens, sh["rows"])
            labels = jax.device_put(labels, sh["rows"])
        loss, grads = grad_fn(params, tokens, labels)
        losses.append(float(loss))
        if t == 0:
            grad = np.asarray(jax.device_get(norms(grads)))
        params, m, v, _ = adam(params, grads, m, v, t + 1)
        del grads
    out = {"loss": losses, "grad": grad,
           "change": np.asarray(jax.device_get(change(params, key)))}
    for x in jax.tree.leaves((params, m, v)):
        x.delete()
    return out


# --------------------------------------------------------------------------
# the comparison
# --------------------------------------------------------------------------

def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared: the largest relative loss gap over the three
    steps; and, for the first gradient and for the change after three steps,
    the worst leaf's gap between the program's norm and the reference's, as
    a share of the larger of the reference's norm of that leaf and the
    median leaf's.  Leaves whose reference gradient is under ``TINY_GRAD``
    of the median leaf's are left out of the change."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))

    def worst(a, b, keep):
        a, b = np.asarray(a, float)[keep], np.asarray(b, float)[keep]
        floor = max(float(np.median(b)), 1e-30)
        return float(np.max(np.abs(a - b) / np.maximum(b, floor)))

    g_ref = np.asarray(ref["grad"], float)
    every = np.ones(len(g_ref), bool)
    moved = g_ref >= TINY_GRAD * float(np.median(g_ref))
    return {"loss_gap": loss_gap,
            "grad_gap": worst(prog["grad"], g_ref, every),
            "change_gap": worst(prog["change"], ref["change"], moved)}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that has a limit, beside it; ok when every one is finite
    and within.  A number without a limit is read and not compared."""
    checks = {k: {"value": values[k], "limit": lim} for k, lim in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def memory_peak(stats: dict) -> int:
    """A device's peak memory from ``memory_stats()``: the peak of the bytes
    held by arrays plus the peak of the bytes the runtime reserves for the
    programs' temporaries."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0))


def _p90(xs: list[float]) -> float:
    return float(np.percentile(np.asarray(xs), 90))


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
             t_start: float, peak_flops: float, metric_readers: dict,
             program_hook=None) -> dict:
    """One run of ``cell``; returns the result line's object.
    ``program_hook(program)``, where given, replaces parts of the program
    after set-up (the fault tests plant their faults there)."""
    import jax

    spans = Spans()
    prog = Program(cell, devices, spans, seed)
    if program_hook is not None:
        program_hook(prog)
    with spans("check_steps"):
        readings = prog.check_steps()
    chips = len(prog.devices)
    tokens_per_step = cell.batch * cell.seq

    batch = prog.batch(prog.step_no)
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    step_times, metrics_seen = [], []
    t_win = time.perf_counter()
    setup_s = t_win - t_start
    with jax.profiler.TraceAnnotation("bench_window"):
        while True:
            t0 = time.perf_counter()
            with spans("dispatch"):
                metrics = prog.call(batch)
            with spans("data"):           # the next batch, while the device runs
                batch = prog.batch(prog.step_no)
            with spans("fence"):
                jax.block_until_ready((prog.params, prog.opt, metrics))
            t1 = time.perf_counter()
            step_times.append(t1 - t0)
            metrics_seen.append(metrics["loss"])
            if t1 - t_win >= seconds:
                break
    window_s = t1 - t_win
    if trace:
        jax.profiler.stop_trace()
    losses = [float(x) for x in jax.device_get(metrics_seen)]
    failed = sum(1 for x in losses if not math.isfinite(x))
    stats = [d.memory_stats() or {} for d in prog.devices]
    peak_bytes = max(memory_peak(st) for st in stats)
    ma = prog.step_memory
    summary = None
    if trace:
        import trace_reduce
        summary = trace_reduce.summarize(trace_reduce.load(trace_dir),
                                         chips=chips,
                                         hlo_text=prog.step.as_text())
        shutil.rmtree(trace_dir, ignore_errors=True)
    plan = prog.plan
    prog.free()
    del prog

    with spans("reference"):
        ref = reference_readings(cell, seed, devices[:chips])
    values = gaps(readings, ref)
    ok, checks = judge(values, cell.limits)
    print("step_ms " + " ".join(f"{1e3 * t:.3f}" for t in step_times),
          file=sys.stderr)
    for name in ("build", "search", "init", "compile", "check_steps",
                 "reference"):
        if spans.seconds(name) is not None:
            print(f"span {name}: {spans.seconds(name):.3f} s", file=sys.stderr)
    for i, st in enumerate(stats):
        print(f"memory_stats {i}: {json.dumps(st, sort_keys=True)}",
              file=sys.stderr)
    if ma is not None:
        print("compiled step memory: " + json.dumps(
            {k: getattr(ma, k) for k in dir(ma) if k.endswith("_in_bytes")},
            sort_keys=True), file=sys.stderr)
    if summary is not None:
        print(f"trace memory peak: {summary.memory_peak_bytes}", file=sys.stderr)
    print(f"setup {setup_s:.3f} s, window {window_s:.3f} s, {len(step_times)} "
          f"steps", file=sys.stderr)
    for k in values.keys() - checks.keys():
        print(f"reading {k}: {values[k]!r} (not compared)", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)

    steps = len(step_times)
    tps = steps * tokens_per_step / window_s / chips
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak_bytes}
    out = {"correct": ok and failed == 0, "attempted": steps, "failed": failed}
    if not trace:
        fpt = flops_lib.per_token(cell.conf, cell.seq)
        out["metrics"] = {
            "tokens_per_s_per_chip": {"value": tps, "unit": "tokens/s"},
            "mfu": {"value": 100.0 * fpt * tps / peak_flops, "unit": "%"},
            "step_time_p90_ms": {"value": 1e3 * _p90(step_times), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        ctx = ReadContext(spans=spans, trace=summary, step_times=step_times,
                          plan=plan, chips=chips)
        out["metrics"] = {}
        for name, (unit, read) in metric_readers.items():
            v = read(ctx)
            if v is not None:
                out["metrics"][name] = {"value": v, "unit": unit}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["device"] = device
    out["checks"] = checks
    return out


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric's reader may read."""
    spans: Spans
    trace: object              # trace_reduce.Summary, in a traced run
    step_times: list
    plan: object
    chips: int

    def median_step_s(self) -> float:
        return statistics.median(self.step_times)
