"""Pure-jnp oracle for the Mamba2 SSD (state-space duality) scan.

Semantics (per batch b, head h, state n, channel p):

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * B_t[n] * x_t[p]
    y_t[p] = sum_n C_t[n] * S_t[n, p]

Heads are grouped: head h reads B/C from group ``h // (H // G)``.
``ssd_chunked`` keeps B and C at group width (B, S, G, N) and lets heads
read them through the split H = G × R, as the Pallas kernel's index map
does, so no per-head copy of B or C is ever materialized.

Two references are provided: ``ssd_naive`` (step-by-step lax.scan — the
ground truth) and ``ssd_chunked`` (the blocked SSD algorithm the Pallas
kernel mirrors — intra-chunk dense matmuls + inter-chunk recurrence).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _expand_groups(bc: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """(B, S, G, N) -> (B, S, H, N) by repeating each group H//G times."""
    G = bc.shape[2]
    rep = num_heads // G
    return jnp.repeat(bc, rep, axis=2)


def ssd_step(state, x_t, dt_t, A, B_t, C_t):
    """One recurrent step (used for decode and as the naive oracle body).

    state: (B, H, N, P); x_t: (B, H, P); dt_t: (B, H); A: (H,);
    B_t/C_t: (B, H, N) (already group-expanded).
    """
    decay = jnp.exp(dt_t * A[None, :])[..., None, None]            # (B,H,1,1)
    update = (dt_t[..., None, None] * B_t[..., :, None] * x_t[..., None, :])
    new_state = decay * state + update                              # (B,H,N,P)
    y = jnp.einsum("bhn,bhnp->bhp", C_t, new_state)
    return new_state, y


def ssd_naive(x, dt, A, B, C, initial_state=None):
    """x: (B,S,H,P) fp32; dt: (B,S,H) >0; A: (H,) <0; B/C: (B,S,G,N)."""
    Bsz, S, H, P = x.shape
    N = B.shape[-1]
    Bh = _expand_groups(B, H)
    Ch = _expand_groups(C, H)
    state0 = initial_state if initial_state is not None else jnp.zeros((Bsz, H, N, P), jnp.float32)

    def body(state, t):
        new_state, y = ssd_step(state, x[:, t], dt[:, t], A, Bh[:, t], Ch[:, t])
        return new_state, y

    final, ys = jax.lax.scan(body, state0, jnp.arange(S))
    return jnp.moveaxis(ys, 0, 1), final                            # (B,S,H,P)


def _segsum(da: jnp.ndarray) -> jnp.ndarray:
    """da: (..., Q) -> L[..., i, j] = sum_{j < m <= i} da_m (lower-tri incl diag=0)."""
    Q = da.shape[-1]
    cs = jnp.cumsum(da, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]                      # i, j
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int = 64, initial_state=None):
    """Blocked SSD: O(S·Q) intra-chunk matmuls + O(S/Q) state recurrence.

    Shapes as in ``ssd_naive``; S must be divisible by ``chunk``.
    Returns (y (B,S,H,P), final_state (B,H,N,P)).

    B and C stay at group width (B,S,G,N) throughout: the head axis is split
    as H = G × R (head h = g·R + r reads group g), so each group's C·B
    product, chunk-state input and inter-chunk read of C is computed once and
    broadcast over its R heads, never copied to H heads.
    """
    Bsz, S, H, P = x.shape
    G, N = B.shape[-2:]
    R = H // G
    assert S % chunk == 0, (S, chunk)
    nc, Q = S // chunk, chunk

    xc = x.reshape(Bsz, nc, Q, G, R, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, Q, G, R).astype(jnp.float32)
    Bc = B.reshape(Bsz, nc, Q, G, N).astype(jnp.float32)
    Cc = C.reshape(Bsz, nc, Q, G, N).astype(jnp.float32)

    da = dtc * A.reshape(G, R)                                      # (B,nc,Q,G,R)
    cum = jnp.cumsum(da, axis=2)                                    # (B,nc,Q,G,R)
    total = cum[:, :, -1]                                           # (B,nc,G,R)

    # ---- intra-chunk (the "dual" quadratic form, masked by decay) -------
    L = _segsum(jnp.moveaxis(da, 2, -1))                            # (B,nc,G,R,Q,Q)
    CB = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc)                   # once per group
    M = CB[:, :, :, None] * jnp.exp(L)
    M = M * jnp.moveaxis(dtc, 2, -1)[..., None, :]                  # × dt_j
    y_intra = jnp.einsum("bcgrij,bcjgrp->bcigrp", M, xc)

    # ---- chunk state contributions ----------------------------------------
    w = jnp.exp(total[:, :, None] - cum) * dtc                      # (B,nc,Q,G,R)
    contrib = jnp.einsum("bcjgn,bcjgr,bcjgrp->bcgrnp", Bc, w, xc)   # (B,nc,G,R,N,P)

    # ---- inter-chunk recurrence -------------------------------------------
    state0 = (initial_state.reshape(Bsz, G, R, N, P) if initial_state is not None
              else jnp.zeros((Bsz, G, R, N, P), jnp.float32))
    decay_chunk = jnp.exp(total)                                    # (B,nc,G,R)

    def body(state, c):
        y_off = (jnp.exp(cum[:, c])[..., None]
                 * jnp.einsum("bign,bgrnp->bigrp", Cc[:, c], state))
        new_state = decay_chunk[:, c][..., None, None] * state + contrib[:, c]
        return new_state, y_off

    final, y_inter = jax.lax.scan(body, state0, jnp.arange(nc))
    y_inter = jnp.moveaxis(y_inter, 0, 1)                           # (B,nc,Q,G,R,P)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y.astype(x.dtype), final.reshape(Bsz, H, N, P)
