#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 benchmarks/chip/testdata/record_trace.py <out dir>

On every visible chip: a jitted step of a few matmuls under the named scope
``fwd_bwd``, an elementwise update under ``optimizer`` and, on more than
one chip, a ``psum`` across them; four steps inside the harness's host
spans (``bench_window`` around ``dispatch``, ``fence`` and ``data``).  The
trace is a few hundred kB; the compiled step's HLO text, which maps op names
to named scopes, is saved beside it as ``step.hlo.txt``.  Needs the chip.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[3]


def main(out: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from repro import compat
    from repro.compat import NamedSharding, P

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return 3
    mesh = compat.make_mesh((len(devices),), ("x",), devices=devices)
    rows = NamedSharding(mesh, P("x"))

    def step(w, x):
        with jax.named_scope("fwd_bwd"):
            h = x
            for _ in range(4):
                h = jnp.tanh(h @ w)
            g = jnp.sum(h, axis=0, keepdims=True)
        with jax.named_scope("optimizer"):
            g = jax.lax.with_sharding_constraint(
                jnp.broadcast_to(g, x.shape), rows)
            w = w - 1e-3 * (g.T @ x) / x.shape[0]
        return w, h

    w = jnp.eye(2048, dtype=jnp.float32)
    x = jax.device_put(jnp.ones((2048 * len(devices), 2048), jnp.float32), rows)
    fn = compat.jit(step, donate_argnums=(0,)).lower(w, x).compile()
    w, h = fn(w, x)
    jax.block_until_ready((w, h))
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("dispatch"):
                w, h = fn(w, x)
            with jax.profiler.TraceAnnotation("fence"):
                jax.block_until_ready((w, h))
            with jax.profiler.TraceAnnotation("data"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    (pathlib.Path(out) / "step.hlo.txt").write_text(fn.as_text())
    files = list(pathlib.Path(out).rglob("*.xplane.pb"))
    print(f"recorded {files} ({sum(f.stat().st_size for f in files)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
