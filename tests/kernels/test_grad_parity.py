"""Gradient parity: jax.grad through every Pallas kernel vs its XLA ref.

Every kernel family carries a ``jax.custom_vjp`` (flash_attention's
recompute-tile backward, rglru's transpose scan, rwkv6's chunked-state
backward, conv2d from PR 2), so the SAME loss closure differentiates on
either backend.  Losses use a fixed random cotangent (``mean(out * c)``)
so both paths see identical incoming cotangents and tolerances stay
tight; the fp32 tolerances below were calibrated against the
formulation noise between chunked and sequential references.

Also here: the jaxpr walk proving the flash backward never materializes
the (S, S) score matrix, and the registry-driven parity loop that gives
any newly-registered kernel forward+grad coverage for free.
"""
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import common
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.rglru import ref as rg_ref
from repro.kernels.rglru.rglru import rglru_pallas
from repro.kernels.rwkv6 import ref as wkv_ref
from repro.kernels.rwkv6.rwkv6 import wkv_pallas


def _cotangent_loss(fn, out_shape, seed=7):
    c = jax.random.normal(jax.random.PRNGKey(seed), out_shape)

    def loss(*args):
        return jnp.mean(fn(*args) * c)
    return loss


def _assert_grads_close(g1, g2, rtol, atol):
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


# ------------------------------------------------------------------ flash ----

def _make_qkv(b, s, hkv, g, hd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, hkv, g, hd)),
            jax.random.normal(ks[1], (b, s, hkv, hd)),
            jax.random.normal(ks[2], (b, s, hkv, hd)))


@pytest.mark.parametrize("b,s,hkv,g,hd,bq,bk,causal,window", [
    (1, 128, 1, 1, 64, 64, 64, True, None),     # MHA
    (2, 128, 2, 2, 32, 64, 64, True, 32),       # GQA + sliding window
    (1, 100, 1, 2, 32, 64, 64, True, None),     # odd seq len (pad path)
    (1, 97, 2, 1, 32, 32, 32, True, 48),        # odd + window
    (1, 96, 1, 4, 32, 32, 64, False, None),     # MQA-ish, non-causal
])
def test_flash_grad_matches_ref(b, s, hkv, g, hd, bq, bk, causal, window):
    q, k, v = _make_qkv(b, s, hkv, g, hd)
    scale = hd ** -0.5

    def fl(q, k, v):
        return fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                      scale=scale, bq=bq, bk=bk)

    def rf(q, k, v):
        return fa_ref.attention_ref(q, k, v, causal=causal, window=window,
                                    scale=scale)

    out_shape = q.shape
    g1 = jax.grad(_cotangent_loss(fl, out_shape), (0, 1, 2))(q, k, v)
    g2 = jax.grad(_cotangent_loss(rf, out_shape), (0, 1, 2))(q, k, v)
    _assert_grads_close(g1, g2, rtol=2e-4, atol=1e-6)


def _collect_shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.add(tuple(aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    _collect_shapes(sub.jaxpr, out)
                elif isinstance(sub, jex_core.Jaxpr):
                    _collect_shapes(sub, out)
    return out


def test_flash_backward_never_materializes_scores():
    """No (S, S) intermediate anywhere in the fwd+bwd jaxpr at S=256."""
    s, hd = 256, 64
    q, k, v = _make_qkv(1, s, 2, 1, hd)

    def loss(q, k, v):
        return jnp.sum(fa_ops.flash_attention(
            q, k, v, causal=True, scale=hd ** -0.5, bq=64, bk=64) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)
    shapes = _collect_shapes(jaxpr.jaxpr, set())
    offenders = [sh for sh in shapes if sh.count(s) >= 2]
    assert not offenders, f"(S,S)-sized intermediates found: {offenders}"
    # the xla reference DOES materialize one (sanity-check the detector)
    jaxpr_ref = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(fa_ref.attention_ref(
            q, k, v, causal=True, scale=hd ** -0.5) ** 2), (0, 1, 2)))(q, k, v)
    shapes_ref = _collect_shapes(jaxpr_ref.jaxpr, set())
    assert any(sh.count(s) >= 2 for sh in shapes_ref)


# ------------------------------------------------------------------ rglru ----

@pytest.mark.parametrize("b,t,d,chunk", [
    (2, 128, 16, 32),
    (1, 100, 24, 64),      # odd T and D (pad path, both axes)
    (3, 64, 32, 16),
])
def test_rglru_grad_matches_sequential(b, t, d, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, t, d)) * 0.5 + 2.0)
    bb = jax.random.normal(ks[1], (b, t, d))

    def pal(a, bb):
        return rglru_pallas(a, bb, chunk=chunk)

    def seq(a, bb):
        return rg_ref.rglru_sequential(a, bb)[0]

    g1 = jax.grad(_cotangent_loss(pal, a.shape), (0, 1))(a, bb)
    g2 = jax.grad(_cotangent_loss(seq, a.shape), (0, 1))(a, bb)
    _assert_grads_close(g1, g2, rtol=3e-4, atol=1e-7)


def test_rglru_grad_strong_decay_finite():
    """The transpose-scan backward must survive a ≈ 0 (the bounded-exponent
    kernel form; the naive 1/P rescaling overflowed here)."""
    a = jnp.full((1, 128, 8), 5e-5)
    bb = jnp.ones((1, 128, 8))
    da, db = jax.grad(
        lambda a, bb: jnp.sum(rglru_pallas(a, bb, chunk=64)), (0, 1))(a, bb)
    assert np.isfinite(np.asarray(da)).all()
    assert np.isfinite(np.asarray(db)).all()


# ------------------------------------------------------------------ rwkv6 ----

def _make_wkv(b, t, h, k, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r, kk, v = (jax.random.normal(ks[i], (b, t, h, k)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, t, h, k)) * 0.5))
    u = jax.random.normal(ks[4], (h, k)) * 0.5
    return r, kk, v, w, u


@pytest.mark.parametrize("b,t,h,k,chunk", [
    (2, 128, 2, 32, 32),
    (1, 100, 1, 16, 32),       # odd T (pad path)
    (2, 64, 4, 16, 16),
])
def test_rwkv6_grad_matches_refs(b, t, h, k, chunk):
    r, kk, v, w, u = _make_wkv(b, t, h, k)

    def pal(r, kk, v, w, u):
        return wkv_pallas(r, kk, v, w, u, chunk=chunk)

    def chk(r, kk, v, w, u):
        return wkv_ref.wkv_chunked(r, kk, v, w, u, chunk=chunk)[0]

    def seq(r, kk, v, w, u):
        return wkv_ref.wkv_sequential(r, kk, v, w, u)[0]

    args = (r, kk, v, w, u)
    an = (0, 1, 2, 3, 4)
    shape = r.shape
    gp = jax.grad(_cotangent_loss(pal, shape), an)(*args)
    # tight vs the chunked ref — the backward IS the chunked-state pullback
    gc = jax.grad(_cotangent_loss(chk, shape), an)(*args)
    _assert_grads_close(gp, gc, rtol=2e-4, atol=1e-7)
    # looser vs the sequential oracle (chunked-vs-sequential fp32
    # formulation noise, same magnitude the forward parity tests carry)
    gs = jax.grad(_cotangent_loss(seq, shape), an)(*args)
    _assert_grads_close(gp, gs, rtol=3e-3, atol=1e-6)


# --------------------------------------------------------------- registry ----

@pytest.mark.parametrize("name", sorted(common.ops()))
def test_registered_op_forward_and_grad_parity(name):
    """Registering a KernelOp buys this coverage: pallas == ref within tol,
    and jax.grad agrees through both on the op's example inputs."""
    op = common.get_op(name)
    args = op.example(0)
    out = op.pallas(*args)
    exp = op.ref(*args)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=op.tol, atol=op.tol)
    if not op.differentiable:
        return
    shape = np.asarray(exp).shape
    gp = jax.grad(_cotangent_loss(op.pallas, shape), op.grad_argnums)(*args)
    gr = jax.grad(_cotangent_loss(op.ref, shape), op.grad_argnums)(*args)
    # example inputs are small; 10x the forward tol absorbs backward
    # formulation noise (chunked-state recompute vs oracle autodiff)
    _assert_grads_close(gp, gr, rtol=10 * op.tol, atol=10 * op.tol)
