"""The model's layer scopes in the compiled train step: each family's
scopes name its forward and its backward, no op lies under two of them
(what lets the device trace put every op's time on one layer), each
stays in the tail of the op name that the trace's op labels keep, and a
step loaded from the persistent compilation cache keeps its own."""
import dataclasses
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.launch.train import single_device_plan
from repro.models import build_model
from repro.models.common import LAYER_SCOPES
from repro.runtime.train import construct_hybrid_parallel_model

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmarks" / "chip"))
import layer_scopes  # noqa: E402
import trace_reduce  # noqa: E402

FAMILIES = {
    "mamba2-2.7b": ({"num_layers": 2, "d_model": 64, "vocab_size": 512,
                     "ssm_state": 16, "ssm_head_dim": 16},
                    {"embed", "norm", "ssm_proj", "ssm_conv", "ssd", "head"}),
    "qwen3-14b": ({"num_layers": 2, "d_model": 64, "num_heads": 4,
                   "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                   "vocab_size": 512},
                  {"embed", "norm", "attention", "mlp", "head"}),
}
OPCODE = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = .*?\b([a-z][a-z0-9\-]*)\(',
                    re.M)


def _layers(path: str) -> set:
    return set(path.split("/")) & set(LAYER_SCOPES)


@pytest.mark.parametrize("arch", sorted(FAMILIES))
def test_layer_scopes_in_compiled_step(arch):
    sizes, expect = FAMILIES[arch]
    cfg = dataclasses.replace(get_config(arch), **sizes)
    hp = construct_hybrid_parallel_model(
        build_model(cfg), single_device_plan(cfg, "selective"), None)
    params = hp.init_params(jax.random.PRNGKey(0))
    batch = {k: jnp.zeros((1, 64), jnp.int32) for k in ("tokens", "labels")}
    text = hp.jit_train_step().lower(
        params, hp.init_opt_state(params), batch).compile().as_text()
    scopes = trace_reduce.hlo_scopes(text)
    opcodes = {m.group(1): m.group(2) for m in OPCODE.finditer(text)}
    fwd_bwd = {n: p for n, p in scopes.items() if "/fwd_bwd/" in p}

    fwd = {s for p in fwd_bwd.values() if "/jvp(model)/" in p
           for s in _layers(p)}
    bwd = {s for p in fwd_bwd.values() if "/transpose(jvp(model))/" in p
           for s in _layers(p)}
    assert fwd == expect and bwd == expect

    work = {n: p for n, p in fwd_bwd.items() if opcodes.get(n) in
            ("dot", "convolution", "gather", "scatter")}
    assert {opcodes[n] for n in work} >= {"dot", "gather", "scatter"}
    assert all(len(_layers(p)) <= 1 for p in work.values())
    assert all(len(_layers(p)) == 1 for n, p in work.items()
               if opcodes[n] == "dot")

    # the benchmark reads an op's layer from its label, which keeps only
    # the tail of the op name: every op's layer is in that tail
    for n, p in scopes.items():
        label = trace_reduce.Op(n, "", 0.0, 0.0, p).label()
        assert layer_scopes.label_layer(label) == next(
            iter(_layers(p)), None), p


def test_compile_cache_keeps_each_programs_scopes(tmp_path):
    """Two programs that differ only in a named scope, through one
    persistent compilation cache: once the trainer keys the cache on
    metadata, the second is not loaded with the first one's op names; the
    source paths in that metadata are relative to the checkout."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime.train import key_compile_cache_on_metadata\n"
        f"jax.config.update('jax_compilation_cache_dir', r'{tmp_path}')\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "def make(scope):\n"
        "    def f(x):\n"
        "        with jax.named_scope(scope):\n"
        "            return jnp.sin(x) * 2\n"
        "    return f\n"
        "x = jnp.ones((8,))\n"
        "old = jax.jit(make('scope_a')).lower(x).compile().as_text()\n"
        "key_compile_cache_on_metadata()\n"
        "new = jax.jit(make('scope_b')).lower(x).compile().as_text()\n"
        "print('/scope_a/' in old, '/scope_b/' in new, '/scope_a/' in new)\n"
        "import re\n"
        "pattern = jax.config.jax_hlo_source_file_canonicalization_regex\n"
        f"print(re.sub(pattern, '', r'{REPO / 'src' / 'x.py'}'))\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=REPO,
                          env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["True", "True", "False",
                                   str(pathlib.Path("src", "x.py"))]
