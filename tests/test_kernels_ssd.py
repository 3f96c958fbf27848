"""SSD Pallas kernel + chunked oracle vs the naive sequential recurrence."""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest
from tests._prop import given, settings, st

from repro.kernels.ssd.kernel import ssd_pallas
from repro.kernels.ssd.ref import ssd_chunked, ssd_naive, ssd_step

SHAPES = [
    # (B, S, H, P, G, N)
    (2, 128, 4, 32, 1, 16),
    (1, 256, 4, 64, 2, 32),
    (1, 64, 2, 16, 1, 8),
]


def _inputs(rng, B, S, H, P, G, N):
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("shape", SHAPES)
def test_chunked_matches_naive(shape, rng):
    x, dt, A, Bm, Cm = _inputs(rng, *shape)
    y_ref, st_ref = ssd_naive(x, dt, A, Bm, Cm)
    for chunk in (16, 32, 64):
        y, st_ = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(st_), np.asarray(st_ref), atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("shape", SHAPES)
def test_pallas_matches_naive(shape, rng):
    x, dt, A, Bm, Cm = _inputs(rng, *shape)
    y_ref, st_ref = ssd_naive(x, dt, A, Bm, Cm)
    y, st_ = ssd_pallas(x, dt, A, Bm, Cm, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(st_ref), atol=1e-3, rtol=1e-3)


def test_decode_step_matches_scan_tail(rng):
    """ssd_step (decode) continues exactly from the prefill final state."""
    B, S, H, P, G, N = 1, 64, 2, 16, 1, 8
    x, dt, A, Bm, Cm = _inputs(rng, B, S + 1, H, P, G, N)
    y_all, _ = ssd_naive(x, dt, A, Bm, Cm)
    _, state = ssd_naive(x[:, :S], dt[:, :S], A, Bm[:, :S], Cm[:, :S])
    from repro.kernels.ssd.ref import _expand_groups

    Bh = _expand_groups(Bm, H)
    Ch = _expand_groups(Cm, H)
    _, y_last = ssd_step(state, x[:, S], dt[:, S], A, Bh[:, S], Ch[:, S])
    np.testing.assert_allclose(np.asarray(y_last), np.asarray(y_all[:, S]),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), s_chunks=st.integers(1, 4))
def test_property_state_decay_bound(seed, s_chunks):
    """|state| is bounded by sum of |dt·B·x| contributions (decay < 1)."""
    rng = jax.random.PRNGKey(seed)
    B, H, P, G, N = 1, 2, 8, 1, 4
    S = 16 * s_chunks
    x, dt, A, Bm, Cm = _inputs(rng, B, S, H, P, G, N)
    _, state = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    assert np.all(np.isfinite(np.asarray(state)))
    from repro.kernels.ssd.ref import _expand_groups

    Bh = np.asarray(_expand_groups(Bm, H))
    bound = np.sum(np.abs(np.asarray(dt))[..., None, None]
                   * np.abs(Bh)[..., :, None]
                   * np.abs(np.asarray(x))[..., None, :], axis=1)
    assert np.all(np.abs(np.asarray(state)) <= bound + 1e-4)


@pytest.mark.parametrize("G,H", [(1, 4), (2, 4), (4, 4)])
def test_chunked_grads_match_naive(G, H, rng):
    """y, final and the gradients for x, dt, A, B and C agree with the scan."""
    x, dt, A, Bm, Cm = _inputs(rng, 1, 128, H, 16, G, 32)
    w_y = jax.random.normal(jax.random.fold_in(rng, 1), x.shape)
    w_f = jax.random.normal(jax.random.fold_in(rng, 2), (1, H, 32, 16))

    def loss(fn):
        def f(*args):
            y, final = fn(*args)
            return jnp.sum(y * w_y) + jnp.sum(final * w_f)
        return f

    chunked = lambda *a: ssd_chunked(*a, chunk=32)
    y, st_ = chunked(x, dt, A, Bm, Cm)
    y_ref, st_ref = ssd_naive(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(st_ref), atol=1e-3, rtol=1e-3)
    argnums = (0, 1, 2, 3, 4)
    grads = jax.jit(jax.grad(loss(chunked), argnums))(x, dt, A, Bm, Cm)
    grads_ref = jax.jit(jax.grad(loss(ssd_naive), argnums))(x, dt, A, Bm, Cm)
    for name, g, g_ref in zip("x dt A B C".split(), grads, grads_ref):
        scale = float(jnp.max(jnp.abs(g_ref)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-3 * scale, rtol=1e-3, err_msg=name)


def test_chunked_grad_keeps_bc_at_group_width(rng):
    """No value in the B/C gradient's jaxpr is as large as a per-head copy.

    With chunk < N and P < chunk, every value the algorithm needs is smaller
    than S·H·N elements, so one that large can only be B or C (or their
    gradients) expanded to all heads.
    """
    Bsz, S, H, P, G, N = 1, 256, 8, 8, 1, 32
    x, dt, A, Bm, Cm = _inputs(rng, Bsz, S, H, P, G, N)

    def loss(Bm, Cm):
        y, final = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
        return jnp.sum(y) + jnp.sum(final)

    def sizes(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape)), str(eqn.primitive)
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)          # ClosedJaxpr -> Jaxpr
                    if isinstance(sub, jax.extend.core.Jaxpr):
                        yield from sizes(sub)

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(Bm, Cm)
    too_big = [(n, prim) for n, prim in sizes(closed.jaxpr) if n >= S * H * N]
    assert not too_big, too_big
