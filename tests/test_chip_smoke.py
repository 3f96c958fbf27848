"""chip_smoke.py off the chip, and the trainer pieces it leans on: the
depth cut, the one-device plan, the peaks table, the AOT memory analysis and
the compile-cache placement."""
import argparse
import importlib.util
import math
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.core.cluster import PEAK_FLOPS_BY_DEVICE_KIND, device_peak_flops
from repro.launch import compile_cache, train

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_smoke(script: pathlib.Path, cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    proc = _run_smoke(ROOT / "chip_smoke.py", ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_refuses_without_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", lone)
    proc = _run_smoke(lone, tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def _fake_run(losses, digest=(100.0, 1.0), name="run"):
    return {"name": name,
            "steps": [{"step": i, "loss": l, "grad_norm": 1.0}
                      for i, l in enumerate(losses)],
            "digest": {"params": digest[0], "opt_m": digest[1]}}


def test_smoke_checks_band_and_finiteness():
    smoke = _load_smoke()
    vocab = get_config(smoke.SMOKE["arch"]).vocab_size
    ln_v = math.log(vocab)
    smoke.check_run(_fake_run([ln_v + 0.4, ln_v]), vocab)
    with pytest.raises(smoke.SmokeFailure, match="step-0 loss"):
        smoke.check_run(_fake_run([ln_v + 1.5]), vocab)
    with pytest.raises(smoke.SmokeFailure, match="step 1"):
        smoke.check_run(_fake_run([ln_v, float("nan")]), vocab)


def test_smoke_comparison_flags_disagreement():
    smoke = _load_smoke()
    a = _fake_run([12.0, 11.9], digest=(100.0, 1.0))
    close = _fake_run([12.0 * (1 + 1e-4), 11.9], digest=(100.0, 1.0 + 1e-4))
    assert smoke.compare_runs(a, close) == []
    far = _fake_run([12.0, 11.9 * 1.01], digest=(100.0, 1.0))
    bad = smoke.compare_runs(a, far)
    assert len(bad) == 1 and "step 1 loss" in bad[0]
    assert smoke.compare_runs(a, _fake_run([12.0])) != []


def _ns(**kw):
    base = dict(preset=None, arch="llama3.2-1b", reduced=False, layers=0)
    return argparse.Namespace(**{**base, **kw})


def test_layers_cut_keeps_widths():
    full = get_config("llama3.2-1b")
    cut = train.resolve_cfg(_ns(layers=2))
    assert cut.num_layers == 2
    assert cut == full.__class__(**{**full.__dict__, "num_layers": 2})
    assert train.resolve_cfg(_ns()) == full
    with pytest.raises(SystemExit, match="--layers 17"):
        train.resolve_cfg(_ns(layers=17))


def test_single_device_plan():
    cfg = train.resolve_cfg(_ns(layers=3))
    plan = train.single_device_plan(cfg, "full", grad_accum=0)
    assert plan.num_devices == 1 and plan.grad_accum == 1
    assert len(plan.layer_strategies) == 3
    assert plan.default_strategy.remat == "full"


def test_peak_flops_only_for_known_devices():
    assert PEAK_FLOPS_BY_DEVICE_KIND["TPU v5 lite"] == 197e12
    assert device_peak_flops(jax.devices()[0]) == 0.0      # cpu: no MFU


def test_aot_memory_propagates_compile_errors():
    class Broken:
        def lower(self, *args):
            raise ValueError("refused by the compiler")

    with pytest.raises(ValueError, match="refused"):
        train._aot_memory(Broken(), None, None, None)
    step = jax.jit(lambda p, o, b: (p + b, o))
    x = jnp.ones((256,), jnp.float32)
    compiled, peak = train._aot_memory(step, x, x, x)
    assert peak >= 3 * 256 * 4          # three arguments + two outputs
    assert float(compiled(x, x, x)[0][0]) == 2.0


def test_compile_cache_placement(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and JAX is
    pointed nowhere else; without it the cache goes to the fixed,
    git-ignored directory in the checkout."""
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T).lower(jnp.ones((64, 64))).compile()\n")
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(cache)] * 2
    assert any(cache.iterdir())

    env.pop("JAX_COMPILATION_CACHE_DIR")
    code = code.replace("jax.jit", "# jax.jit")      # no write into the checkout
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(ROOT / ".jax_cache")] * 2
