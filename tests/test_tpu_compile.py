"""AOT compiles for a described (not attached) TPU v5e chip.

The TPU compiler is installed beside the CPU backend, so these compile the
main path's Pallas kernels at real widths, and one whole training step of
``chip_smoke.py``'s configuration, exactly as the chip would — catching
tiling-rule refusals and programs that do not fit before any chip time is
spent.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module fixture (never at import, so every
pytest-xdist worker collects the same tests and only the worker given this
file loads the TPU library).  The persistent compilation cache is off around
these compiles: an entry written for a described chip cannot be read back
without one.
"""
import argparse
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: usable HBM of one v5e chip (16 GB nominal) as its allocator reports it
V5E_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel is in
    return compiled


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert len(topo.devices) == 4


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_forward_compiles(one_chip, head_dim):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd

    qkv = [_spec((1, 2048, 8, head_dim), jnp.bfloat16, one_chip)] * 3
    _compile_kernel(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
                    *qkv)


def test_flash_forward_residuals_positions_compile(one_chip):
    """The context-parallel form: explicit positions, softmax stats out."""
    from repro.kernels.flash_attention.kernel import flash_attention_fwd

    qkv = [_spec((1, 2048, 8, 128), jnp.bfloat16, one_chip)] * 3
    pos = [_spec((1, 2048), jnp.int32, one_chip)] * 2
    _compile_kernel(
        lambda q, k, v, qp, kp: flash_attention_fwd(
            q, k, v, causal=True, q_pos=qp, k_pos=kp, return_residuals=True),
        *qkv, *pos)


def test_rmsnorm_compiles(one_chip):
    from repro.kernels.rmsnorm.kernel import rmsnorm_pallas

    _compile_kernel(rmsnorm_pallas, _spec((4096, 2048), jnp.bfloat16, one_chip),
                    _spec((2048,), jnp.float32, one_chip))


def test_ssd_compiles_at_mamba2_widths(one_chip):
    from repro.kernels.ssd.kernel import ssd_pallas

    cfg = get_config("mamba2-2.7b")
    H, P, N, G, S = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, 2048
    assert (H, P, N) == (80, 64, 128)
    _compile_kernel(
        lambda x, dt, A, B, C: ssd_pallas(x, dt, A, B, C, chunk=128),
        _spec((1, S, H, P), jnp.bfloat16, one_chip),
        _spec((1, S, H), jnp.float32, one_chip),
        _spec((H,), jnp.float32, one_chip),
        _spec((1, S, G, N), jnp.bfloat16, one_chip),
        _spec((1, S, G, N), jnp.bfloat16, one_chip))


def _chip_smoke_config() -> dict:
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SMOKE


def test_chip_smoke_train_step_fits_one_chip(one_chip):
    """One whole training step of chip_smoke.py's configuration, through the
    trainer's single-device path, compiled for one v5e: it must compile and
    its arguments + outputs + temporaries must fit the chip."""
    from repro.launch import train
    from repro.models import build_model
    from repro.runtime.train import construct_hybrid_parallel_model

    smoke = _chip_smoke_config()
    cfg = train.resolve_cfg(argparse.Namespace(
        preset=None, arch=smoke["arch"], reduced=False, layers=smoke["layers"]))
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (2048, 8192, 128256)
    hp = construct_hybrid_parallel_model(
        build_model(cfg), train.single_device_plan(cfg, smoke["remat"]), None)
    place = lambda tree: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    tokens = _spec((smoke["batch"], smoke["seq"]), jnp.int32, one_chip)
    compiled = hp.jit_train_step(donate=False).lower(
        place(hp.abstract_params()), place(hp.abstract_opt_state()),
        {"tokens": tokens, "labels": tokens}).compile()
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert held < V5E_HBM_BYTES, (held / 2**30, ma)


def test_zero_head_collectives_overlap_its_matmuls(topo):
    """qwen3-14b's untied head at published widths under tp2-sp-z3 on the
    v5e 2x2, forward and backward: the compiler keeps one synchronous
    weight gather (the first chunk's) and runs the other three inside the
    chunk matmuls' async collective fusions; each chunk's gradient leaves
    as a collective-permute that a matmul runs under before it is waited
    on; the weight gradient is never all-reduced or reduce-scattered
    whole."""
    import re

    from repro import compat
    from repro.compat import NamedSharding, P
    from repro.parallel import zero_head as zh_lib

    mesh = compat.make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    zh = zh_lib.ZeroHead(mesh, ("data",), ("model",))
    x = _spec((4, 2048, 5120), jnp.bfloat16, NamedSharding(mesh, P("data", "model")))
    w = _spec((5120, 151936), jnp.bfloat16, NamedSharding(mesh, P("data", "model")))
    k = zh_lib.chunks_for(zh, x.shape, w.shape[1])
    assert k == 4

    def loss(x, w):
        logits = zh_lib.gathered_logits(x, w, zh, k, 1)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).compile().as_text()
    entry = re.search(r"^ENTRY .*?\{\n(.*?)^\}", text, re.S | re.M).group(1)
    ops = [(m.group(1), m.group(2), m.group(3), line) for line in entry.splitlines()
           for m in [re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z\-]*)\(", line)]
           if m]
    chunk = "bf16[5120,18992]"
    assert sum(op == "all-gather" and shape.startswith(chunk)
               for _, shape, op, _ in ops) == 1
    assert sum("calls=%async_collective_fusion" in line for *_, line in ops) >= 3
    assert not [name for name, shape, op, line in ops
                if (op in ("all-reduce", "reduce-scatter")
                    or "calls=%all-reduce-scatter" in line)
                and any(s in shape for s in ("5120,18992", "2560,18992", "2560,75968"))]
    pos = {name: i for i, (name, *_) in enumerate(ops)}
    starts = [name for name, shape, op, _ in ops
              if op == "collective-permute-start" and "bf16[2560,18992]" in shape]
    assert len(starts) == k
    for name, _, op, line in ops:
        if op == "collective-permute-done":
            start = re.search(r"collective-permute-done\(%(\S+?)\)", line).group(1)
            between = ops[pos[start] + 1:pos[name]]
            assert any("kind=kOutput" in ln for *_, ln in between), start
