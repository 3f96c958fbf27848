"""Mamba2 SSD chunked-scan kernel (Pallas, TPU target).

Grid = (batch, heads, chunks); the chunk dim is sequential ("arbitrary") and
carries the (N, P) state in fp32 VMEM scratch — the inter-chunk recurrence.
Within a chunk everything is dense MXU work on (Q×N)/(Q×Q)/(Q×P) tiles
(state-space *duality*: the quadratic intra-chunk form), which is exactly
how the SSD paper maps the scan onto matmul hardware; chunk=Q=128 and
N/P=64..128 keep every matmul MXU-shaped.

B/C are stored per group (G ≤ H); the index map routes head h to group
h·G//H so no expanded copies are materialized in HBM.

Validated with ``interpret=True`` against ``ref.ssd_naive``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref,   # inputs
                y_ref, final_ref,                         # outputs
                state_ref,                                # scratch (N, P) fp32
                *, num_chunks: int, chunk: int):
    # Everything stays 2-D: dt arrives both as a column (Q, 1) and as a row
    # (1, Q), so no vector ever changes between sublane and lane layout
    # (Mosaic refuses such relayouts and has no cumsum).
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)                      # (Q, P)
    A = a_ref[h].astype(jnp.float32)                      # () from SMEM
    dt_c = dtc_ref[0].astype(jnp.float32)                 # (Q, 1)
    dt_r = dtr_ref[0].astype(jnp.float32)                 # (1, Q)
    Bm = b_ref[0].astype(jnp.float32)                     # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)                     # (Q, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    da_c, da_r = dt_c * A, dt_r * A
    # inclusive prefix sums of da, as a column and as a row
    cum_c = jnp.sum(jnp.where(ii >= jj, da_r, 0.0), axis=1, keepdims=True)
    cum_r = jnp.sum(jnp.where(ii <= jj, da_c, 0.0), axis=0, keepdims=True)
    total = jnp.sum(da_r, axis=1, keepdims=True)          # (1, 1)

    # ---- intra-chunk quadratic form ------------------------------------
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)      # (Q, Q)
    L = jnp.where(ii >= jj, jnp.exp(cum_c - cum_r), 0.0)
    M = CB * L * dt_r
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (Q, P)

    # ---- inter-chunk contribution ---------------------------------------
    state = state_ref[...]                                # (N, P)
    y += jax.lax.dot_general(Cm * jnp.exp(cum_c), state,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # ---- state update -----------------------------------------------------
    w = jnp.exp(total - cum_c) * dt_c                     # (Q, 1)
    contrib = jax.lax.dot_general(Bm * w, x, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = jnp.exp(total) * state + contrib

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        final_ref[0, 0] = state_ref[...].astype(final_ref.dtype)


def ssd_pallas(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False,
               initial_state=None):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); B/C: (B,S,G,N).
    Returns (y (B,S,H,P), final_state (B,H,N,P) fp32).
    ``initial_state`` must be None (kernel zero-initializes; decode uses
    ``ssd_step``)."""
    assert initial_state is None, "kernel path starts from zero state"
    Bs, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk

    # (B, S, H, P) -> (B*H, S, P) rows; dt -> (B*H, S) as column and row
    # blocks; B/C stay grouped
    xt = x.transpose(0, 2, 1, 3).reshape(Bs * H, S, P)
    dtt = dt.transpose(0, 2, 1).reshape(Bs * H, S)

    def bh(b, h):  # flatten helpers for index maps
        return b * H + h

    kernel = functools.partial(_ssd_kernel, num_chunks=nc, chunk=chunk)
    y, final = pl.pallas_call(
        kernel,
        grid=(Bs, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, h, c: (b * H + h, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b * H + h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b * H + h, 0, c)),
            # A (H,) whole in SMEM, read as a scalar per head: a (1,) VMEM
            # block breaks the TPU's 128-lane tiling rule for rank-1 blocks
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, pl.Squeezed(), N),
                         lambda b, h, c: (b, c, h * G // H, 0)),
            pl.BlockSpec((1, chunk, pl.Squeezed(), N),
                         lambda b, h, c: (b, c, h * G // H, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, h, c: (b * H + h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bs * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bs, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(xt, dtt[:, :, None], dtt[:, None, :], A, B, C)
    y = y.reshape(Bs, H, S, P).transpose(0, 2, 1, 3)
    return y, final
