"""The comparison that decides ``correct``, on the CPU at small widths.

* The program, the float32 reference and the fp8 control (the reference
  with every matmul's operands rounded to fp8) follow the same three steps
  from one seed; the control has to fail one of the cell's limits, on a
  number where it reads at least three times what the program reads.  (At
  these widths the program's own gaps are larger than at the cell's, so it
  is held to the cell's limits only in the dense cell's whole run below.)
* A whole run of each cell, with the harness's look for a chip skipped and
  the timed step broken underneath, has to come out ``correct: false``: a
  step that returns its state unchanged, a step over half of the batch (the
  mean taken over the rest), and, for the cell across chips, a step whose
  tensor-parallel partial sums are never added up (the reference with that
  exchange left out, put in the program's place).
* The four-device path (search, mesh, sharded init and reference) runs on
  four virtual CPU devices and comes out ``correct: true``.

The four-chip cell is not in BENCHMARK.json yet (``PENDING``: its entries
as BENCHMARK.json would give them) and has no limits file; its tests hold
it to ``TEST_LIMITS``, the one-chip cell's limits, which its small-width
readings clear by 3x.
* Without a TPU the command prints no result and exits non-zero; so it does
  in a tree that holds only ``BENCHMARK.json`` and the benchmark.

Run with ``python3 -m pytest benchmarks/chip``.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness
from conftest import HERE, ROOT, small_cell

_BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BENCH = [w["name"] for w in _BENCH["workloads"]]
PENDING = [({"name": "qwen3-14b.train-2k.4chip", "config": "qwen3-14b",
             "traffic": "train-2k", "chips": 4},
            {"name": "qwen3-14b",
             "file": "benchmarks/chip/configs/qwen3-14b.json"})]
#: {cell: (its workloads entry, its configs entry)}
ENTRIES = {w["name"]: (w, c) for w, c in [
    (w, next(c for c in _BENCH["configs"] if c["name"] == w["config"]))
    for w in _BENCH["workloads"]] + PENDING}
CELLS = {n: harness.load_cell(*e) for n, e in ENTRIES.items()}
ACROSS = [n for n, c in CELLS.items() if c.chips > 1]
DENSE = [n for n, c in CELLS.items() if c.conf["reference"] == "dense"]
TEST_LIMITS = {"grad_gap": 0.006, "change_gap": 0.006}
SEED = 2**31 + 1234


def _small(name, single=True):
    cell = CELLS[name]
    return small_cell(cell, single,
                      limits=None if cell.limits else TEST_LIMITS)


def _devices():
    import jax
    return jax.devices()


@pytest.mark.parametrize("name", sorted(CELLS))
def test_fp8_control_fails(name):
    cell = _small(name)
    devices = _devices()
    prog = harness.Program(cell, devices, harness.Spans(), SEED)
    readings = prog.check_steps()
    prog.free()
    ref = harness.reference_readings(cell, SEED, devices[:1])
    control = harness.reference_readings(cell, SEED, devices[:1], mode="fp8")
    program, fp8 = harness.gaps(readings, ref), harness.gaps(control, ref)
    ok, checks = harness.judge(fp8, cell.limits)
    assert not ok, checks
    failed = [k for k, c in checks.items() if c["value"] > c["limit"]]
    assert any(fp8[k] >= 3 * program[k] for k in failed), (program, fp8)


def _run(cell, hook=None):
    return harness.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                            devices=_devices(), t_start=time.perf_counter(),
                            peak_flops=197e12, metric_readers={},
                            program_hook=hook)


def _unchanged(prog):
    from repro import compat
    step = compat.jit(prog.hp.train_step)

    def call(params, opt, batch):
        return (params, opt) + (step(params, opt, batch)[2],)
    prog.step = call


def _half_batch(prog):
    real = prog.step

    def call(params, opt, batch):
        labels = batch["labels"]
        labels = labels.at[:, labels.shape[1] // 2:].set(-1)
        return real(params, opt, dict(batch, labels=labels))
    prog.step = call


def _no_exchange(prog):
    import jax
    from repro import compat
    from repro.runtime.optimizer import adamw_update
    from references import common

    cell, hp = prog.cell, prog.hp
    conf = cell.conf["config"]

    def loss(params, batch):
        nll, z2 = cell.ref.loss_sums(conf, params, batch["tokens"],
                                     batch["labels"], fault="no_exchange")
        return common.loss_from_sums(nll, z2, batch["labels"].size)

    def call(params, opt, batch):
        value, grads = jax.value_and_grad(loss)(params, batch)
        new_p, new_o, stats = adamw_update(params, grads, opt, hp.opt_cfg)
        return new_p, new_o, dict(stats, loss=value)
    prog.step = compat.jit(call)


def test_clean_run_is_correct():
    if not DENSE:
        pytest.skip("no dense cell")
    out = _run(_small(DENSE[0]))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_fault_makes_run_incorrect(name, fault):
    hook = {"unchanged": _unchanged, "half_batch": _half_batch}[fault]
    out = _run(_small(name), hook)
    assert not out["correct"], out["checks"]


def test_exchange_left_out_makes_run_incorrect():
    if not ACROSS:
        pytest.skip("no cell across chips")
    out = _run(_small(ACROSS[0]), _no_exchange)
    assert not out["correct"], out["checks"]


_FOUR = """
import json, sys, time
sys.path[:0] = [{here!r}, {src!r}]
import jax, conftest, harness
cell = conftest.small_cell(harness.load_cell(*{entries!r}),
                           limits={limits!r})
out = harness.run_cell(cell, seed={seed}, seconds=0.5, trace=False,
                       devices=jax.devices(), t_start=time.perf_counter(),
                       peak_flops=197e12, metric_readers={{}})
print(json.dumps(out))
"""


def test_four_device_path_is_correct():
    if not ACROSS:
        pytest.skip("no cell across chips")
    name = ACROSS[0]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(here=str(HERE), src=str(ROOT / "src"),
                        entries=ENTRIES[name], seed=SEED,
                        limits=None if CELLS[name].limits else TEST_LIMITS)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(HERE),
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]


def test_no_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        BENCH[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, str(tmp_path / "benchmarks" / "chip"
                                            / "run.py"),
                        "--workload", BENCH[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       timeout=300, cwd=str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
