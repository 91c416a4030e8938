"""Pallas LRN: fused tile kernel over (rows, channels) slabs.

LRN is memory-bound — the XLA lowering of the windowed sum reads the
activation ``n+1`` times through HBM.  The Pallas kernel stages one
``(bm, C)`` slab in VMEM, builds the size-``n`` channel-window sum from
static shifted slices of the staged tile, and writes the normalized
output in the same pass: one HBM read, one write per element.  Every
position's channel window lives inside its own row, so the grid over
row tiles is embarrassingly parallel and group/batch/space dims can all
be flattened into M.

Differentiable via ``jax.custom_vjp``; the backward is the closed form

    dx = dy * d**-b - 2*a*b * x * W(dy * x * d**-(b+1)),   d = k + a*W(x²)

(W = the symmetric window sum — channel i is in window(j) iff j is in
window(i)) lowered to XLA, mirroring the conv kernel's XLA backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.lrn import ref as lrn_ref_mod

_ROW_CAP = 512          # M-tile cap, same scale as the conv kernel's


def _lrn_kernel(x_ref, o_ref, *, n: int, alpha: float, beta: float,
                k: float):
    xv = x_ref[...].astype(jnp.float32)          # (bm, c_pad)
    sq = xv * xv
    pad = n // 2
    z = jnp.zeros((xv.shape[0], pad), jnp.float32)
    sqp = jnp.concatenate([z, sq, z], axis=1)    # channel zero-pad in VMEM
    win = sum(sqp[:, i:i + xv.shape[1]] for i in range(n))
    y = xv / jnp.power(k + alpha * win, beta)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "alpha", "beta", "k",
                                             "bm", "interpret"))
def _lrn_impl(x, n, alpha, beta, k, bm, interpret):
    shape = x.shape
    c = shape[-1]
    m = 1
    for d in shape[:-1]:
        m *= d
    xm = x.reshape(m, c)
    m_pad = -(-m // bm) * bm
    c_pad = -(-c // common.LANE) * common.LANE
    if (m_pad, c_pad) != (m, c):
        # zero channel padding is harmless: it can only ADD zeros to the
        # window sums of real channels, exactly like the oracle's pad
        xm = jnp.pad(xm, ((0, m_pad - m), (0, c_pad - c)))
    out = pl.pallas_call(
        functools.partial(_lrn_kernel, n=n, alpha=alpha, beta=beta, k=k),
        grid=(m_pad // bm,),
        in_specs=[pl.BlockSpec((bm, c_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, c_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, c_pad), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xm)
    if (m_pad, c_pad) != (m, c):
        out = out[:m, :c]
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _lrn_core(x, n, alpha, beta, k, bm, interpret):
    return _lrn_impl(x, n, alpha, beta, k, bm, interpret)


def _lrn_fwd(x, n, alpha, beta, k, bm, interpret):
    return _lrn_impl(x, n, alpha, beta, k, bm, interpret), x


def _lrn_bwd(n, alpha, beta, k, bm, interpret, x, dy):
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    d = k + alpha * lrn_ref_mod.window_sum(xf * xf, n)
    dx = (dyf * jnp.power(d, -beta)
          - 2.0 * alpha * beta * xf
          * lrn_ref_mod.window_sum(dyf * xf * jnp.power(d, -(beta + 1.0)),
                                   n))
    return (dx.astype(x.dtype),)


_lrn_core.defvjp(_lrn_fwd, _lrn_bwd)


def lrn_pallas(x, *, n: int = 5, alpha: float = 1e-4, beta: float = 0.75,
               k: float = 2.0, bm: int = None, interpret: bool = None):
    """x (..., C) -> (..., C); leading dims flattened into row tiles.
    Differentiable."""
    interpret = common.resolve_interpret(interpret)
    if bm is None:
        m = 1
        for d in x.shape[:-1]:
            m *= d
        bm = common.pow2_clip(m, _ROW_CAP)
    return _lrn_core(x, n, float(alpha), float(beta), float(k), bm,
                     interpret)
