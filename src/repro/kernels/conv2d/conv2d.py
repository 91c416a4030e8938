"""Pallas TPU conv kernels — the MXU half of the paper's hot path.

The paper's hot-spot is convolution, and its Table 1 compares conv
backends (cuda-convnet vs cuDNN R1/R2).  The TPU-native adaptation has
two Pallas programs:

``matmul_bias``
    Blocked (bm, bk) x (bk, bn) matmul staged through VMEM with an fp32
    accumulator carried across the K grid axis, bias add + optional ReLU
    fused into the final tile write (mirroring cuDNN's fused epilogue).
    This is the GEMM stage of the *reference* two-stage im2col path.

``conv2d_fused``
    Implicit-GEMM convolution: the grid walks (group, batch, N-tile,
    row-tile) output tiles and each program instance loads its input
    windows directly from the VMEM-staged ``(H, W, C)`` operand — the
    ``(B*OH*OW, K*K*C)`` im2col patch tensor never exists in HBM.  The
    wrapper turns stride > 1 into stride 1 by space-to-depth, so the
    per-tap windows are plain ``pl.ds`` loads from the ref (the TPU
    compiler refuses value-level strided gathers); the tap reduction is
    unrolled in-kernel feeding the MXU, so every grid axis is parallel.

Both are differentiable via ``jax.custom_vjp`` (Pallas calls have no
automatic AD): ``matmul_bias`` back-propagates through two more Pallas
matmuls; ``conv2d_fused`` lowers its backward to XLA's conv-transpose
kernels via ``jax.linear_transpose`` (no recomputed forward).

``interpret``/block sizes are resolved by ``tune.py``: ``interpret=None``
auto-compiles on TPU and interprets elsewhere; ``bm/bk/bn=None`` come
from the shape-keyed autotune cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SUBLANE
from repro.kernels.conv2d import tune


# ---------------------------------------------------------------------------
# blocked matmul + fused bias/ReLU epilogue (im2col reference GEMM stage)
# ---------------------------------------------------------------------------

def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_scr, *, n_k: int,
                   relu: bool):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        y = acc_scr[...] + b_ref[...].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "relu",
                                             "interpret"))
def _matmul_bias_impl(x, w, b, bm, bk, bn, relu, interpret):
    m, k = x.shape
    _, n = w.shape
    mp = -(-m // bm) * bm
    kp = -(-k // bk) * bk
    np_ = -(-n // bn) * bn
    # pad only what is misaligned — aligned calls must not pay HBM copies
    xp = x if (mp == m and kp == k) else jnp.pad(x, ((0, mp - m),
                                                     (0, kp - k)))
    wp = w if (kp == k and np_ == n) else jnp.pad(w, ((0, kp - k),
                                                      (0, np_ - n)))
    bp = (b if np_ == n else jnp.pad(b, (0, np_ - n)))[None, :]

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=kp // bk, relu=relu),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp, bp)
    return out if (mp == m and np_ == n) else out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _matmul_bias_core(x, w, b, bm, bk, bn, relu, interpret):
    return _matmul_bias_impl(x, w, b, bm, bk, bn, relu, interpret)


def _matmul_bias_fwd(x, w, b, bm, bk, bn, relu, interpret):
    y = _matmul_bias_impl(x, w, b, bm, bk, bn, relu, interpret)
    return y, (x, w, b, y)


def _matmul_bias_bwd(bm, bk, bn, relu, interpret, res, dy):
    x, w, b, y = res
    if relu:
        dy = dy * (y > 0).astype(dy.dtype)
    db = dy.sum(0).astype(b.dtype)
    # dx = dy @ w.T, dw = x.T @ dy — same MXU kernel, permuted blockings
    zk = jnp.zeros((x.shape[1],), dy.dtype)
    zn = jnp.zeros((dy.shape[1],), dy.dtype)
    dx = _matmul_bias_impl(dy, w.T, zk, bm, bn, bk, False, interpret)
    dw = _matmul_bias_impl(x.T, dy, zn, bk, bm, bn, False, interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype), db


_matmul_bias_core.defvjp(_matmul_bias_fwd, _matmul_bias_bwd)


def matmul_bias(x, w, b, *, bm: int = None, bk: int = None, bn: int = None,
                relu: bool = False, interpret: bool = None,
                autotune: bool = None):
    """(M,K) @ (K,N) + b(N,) with fused bias/ReLU epilogue.

    ``interpret=None`` auto-resolves (compiled on TPU); ``bm/bk/bn=None``
    come from the autotune cache (``autotune`` overrides measurement).
    Differentiable.
    """
    interpret = tune.resolve_interpret(interpret)
    if bm is None or bk is None or bn is None:
        m, k = x.shape
        n = w.shape[1]
        wd = None if w.dtype == x.dtype else w.dtype
        tbm, tbk, tbn = tune.matmul_blocks(m, k, n, x.dtype,
                                           interpret=interpret,
                                           autotune=autotune, w_dtype=wd)
        bm, bk, bn = bm or tbm, bk or tbk, bn or tbn
    return _matmul_bias_core(x, w, b, bm, bk, bn, relu, interpret)


# ---------------------------------------------------------------------------
# fused implicit-GEMM convolution
# ---------------------------------------------------------------------------

def _conv_fused_kernel(x_ref, w_ref, b_ref, o_ref, *, taps: int, tr: int,
                       owp: int, relu: bool):
    """One (group g, image b, N-tile j, row-tile i) output tile.

    x_ref (Hs, Ws, C) — ONE image's ONE group, padded and (for stride > 1)
    space-to-depth'd, so the conv it sees is always stride 1 with a
    ``taps``-wide window (the BlockSpec index map picks the group, so a
    program never sees the other groups' channels);
    w_ref (taps*taps, C, bn) — that group's per-tap weight slabs;
    b_ref (1, bn); o_ref (tr*owp, bn) — ``tr`` whole output rows.

    Patch rows are loaded on the fly: for each static tap (kh, kw) the
    ref window ``x[i*tr+kh : +tr, kw : kw+owp]`` IS the (tr*owp, C) slab
    of the im2col matrix belonging to that tap, so the reduction is
    taps² unrolled (tr*owp, C) @ (C, bn) MXU dots — implicit GEMM.  The
    row offset is a ``pl.ds`` on the ref's leading axis, the column
    offset a static slice, and ``owp`` is a sublane multiple, so the
    (tr, owp, C) -> (tr*owp, C) collapse is layout-free.
    """
    r0 = pl.program_id(3) * tr
    c = x_ref.shape[-1]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for kh in range(taps):
        for kw in range(taps):
            xs = x_ref[pl.ds(r0 + kh, tr), pl.ds(kw, owp), :]
            acc += jax.lax.dot(
                xs.reshape(tr * owp, c).astype(jnp.float32),
                w_ref[kh * taps + kw].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    y = acc + b_ref[...].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def _row_tiling(oh: int, ow: int, bm: int, dtype) -> tuple:
    """(tr, owp, n_tiles): M is tiled by whole output rows.  ``owp`` pads
    the output width to the dtype's sublane multiple; ``tr`` rows of it
    fill at most ``bm`` M entries, balanced so the last tile wastes less
    than one tile's worth of rows."""
    sub = SUBLANE * max(1, 4 // jnp.dtype(dtype).itemsize)
    owp = -(-ow // sub) * sub
    n_tiles = -(-oh // max(1, bm // owp))
    return -(-oh // n_tiles), owp, n_tiles


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "oh", "ow",
                                             "bm", "bn", "relu", "groups",
                                             "interpret"))
def _conv_fused_impl(x, w, bias, kernel, stride, oh, ow, bm, bn, relu,
                     groups, interpret):
    b_, hp, wp, cin = x.shape
    cout = w.shape[-1]
    s = stride
    cig = cin // groups          # input channels per group
    npg = cout // groups         # output channels per group
    npg_pad = -(-npg // bn) * bn     # pad per group so bn tiles never
    tiles_pg = npg_pad // bn         # straddle a group boundary
    taps = -(-kernel // s)           # window width after space-to-depth
    tr, owp, n_i = _row_tiling(oh, ow, bm, x.dtype)
    ohp = n_i * tr
    hs, ws = ohp + taps - 1, owp + taps - 1
    cs = s * s * cig                 # channels per group after s2d

    # pad (or crop rows no output reads) to whole s x s cells, then
    # space-to-depth with the group on the leading axis:
    # (B, hs*s, ws*s, G*Cg) -> (G, B, hs, ws, s*s*Cg), channels (ph, pw, c)
    x = jax.lax.pad(x, jnp.zeros((), x.dtype),
                    [(0, 0, 0), (0, hs * s - hp, 0), (0, ws * s - wp, 0),
                     (0, 0, 0)])
    xg = (x.reshape(b_, hs, s, ws, s, groups, cig)
          .transpose(5, 0, 1, 3, 2, 4, 6).reshape(groups, b_, hs, ws, cs))
    # (K,K,Cg,Cout) -> zero taps up to taps*s -> (G, taps², s*s*Cg, npg):
    # tap (kh', kw') channel (ph, pw, c) is original tap (kh'*s+ph, kw'*s+pw)
    e = taps * s - kernel
    wq = jnp.pad(w, ((0, e), (0, e), (0, 0), (0, 0)))
    wq = (wq.reshape(taps, s, taps, s, cig, groups, npg)
          .transpose(5, 0, 2, 1, 3, 4, 6)
          .reshape(groups, taps * taps, cs, npg))
    bvec = bias.reshape(groups, npg)
    if npg_pad != npg:
        wq = jnp.pad(wq, ((0, 0), (0, 0), (0, 0), (0, npg_pad - npg)))
        bvec = jnp.pad(bvec, ((0, 0), (0, npg_pad - npg)))
    bvec = bvec.reshape(1, groups * npg_pad)

    out = pl.pallas_call(
        functools.partial(_conv_fused_kernel, taps=taps, tr=tr, owp=owp,
                          relu=relu),
        grid=(groups, b_, tiles_pg, n_i),
        in_specs=[
            pl.BlockSpec((None, None, hs, ws, cs),
                         lambda g, b, j, i: (g, b, 0, 0, 0)),
            pl.BlockSpec((None, taps * taps, cs, bn),
                         lambda g, b, j, i: (g, 0, 0, j)),
            pl.BlockSpec((1, bn), lambda g, b, j, i: (0, g * tiles_pg + j)),
        ],
        out_specs=pl.BlockSpec(
            (None, tr * owp, bn),
            lambda g, b, j, i: (b, i, g * tiles_pg + j)),
        out_shape=jax.ShapeDtypeStruct((b_, ohp * owp, groups * npg_pad),
                                       x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 4),
        interpret=interpret,
    )(xg, wq, bvec)
    out = out.reshape(b_, ohp, owp, groups, npg_pad)
    if (ohp, owp, npg_pad) != (oh, ow, npg):
        out = out[:, :oh, :ow, :, :npg]
    return out.reshape(b_, oh, ow, cout)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _conv_fused_core(x, w, bias, kernel, stride, oh, ow, bm, bn, relu,
                     groups, interpret):
    return _conv_fused_impl(x, w, bias, kernel, stride, oh, ow, bm, bn,
                            relu, groups, interpret)


def _conv_fused_fwd(x, w, bias, kernel, stride, oh, ow, bm, bn, relu,
                    groups, interpret):
    y = _conv_fused_impl(x, w, bias, kernel, stride, oh, ow, bm, bn, relu,
                         groups, interpret)
    return y, (x, w, bias, y)


def _conv_fused_bwd(kernel, stride, oh, ow, bm, bn, relu, groups, interpret,
                    res, dy):
    x, w, bias, y = res
    if relu:
        dy = dy * (y > 0).astype(dy.dtype)
    db = dy.sum((0, 1, 2)).astype(bias.dtype)
    # conv is bilinear: each partial is the transpose of a linear map, so
    # XLA's conv-grad kernels fall out of linear_transpose with no
    # recomputed forward (x was padded by the caller; padding=VALID here).
    # feature_group_count keeps the backward block-diagonal too: dx and dw
    # for one group never read the other groups' cotangents.
    dyf = dy.astype(jnp.float32)

    def conv_x(x_):
        return jax.lax.conv_general_dilated(
            x_, w.astype(jnp.float32), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)

    def conv_w(w_):
        return jax.lax.conv_general_dilated(
            x.astype(jnp.float32), w_, (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)

    dx, = jax.linear_transpose(conv_x, x.astype(jnp.float32))(dyf)
    dw, = jax.linear_transpose(conv_w, w.astype(jnp.float32))(dyf)
    return dx.astype(x.dtype), dw.astype(w.dtype), db


_conv_fused_core.defvjp(_conv_fused_fwd, _conv_fused_bwd)


def conv2d_fused(x, w, *, stride: int, padding: int, bias=None,
                 relu: bool = False, groups: int = 1, bm: int = None,
                 bn: int = None, interpret: bool = None,
                 autotune: bool = None):
    """Implicit-GEMM conv: x (B,H,W,Cin), w (K,K,Cin/G,Cout) ->
    (B,OH,OW,Cout).

    The im2col patch tensor never materializes in HBM — each grid program
    gathers its windows from the (B,H,W,C) operand.  ``groups`` > 1 is
    the paper's intra-layer model parallelism (AlexNet conv2/4/5): the N
    grid axis walks block-diagonal tiles and each program stages only its
    group's input-channel slice.  Differentiable.
    """
    interpret = tune.resolve_interpret(interpret)
    k, _, wcin, cout = w.shape
    cin = x.shape[-1]
    if wcin * groups != cin:
        raise ValueError(f"w in-channels {wcin} x groups {groups} != "
                         f"x channels {cin}")
    if cout % groups:
        raise ValueError(f"cout {cout} not divisible by groups {groups}")
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)))
    b_, hp, wp, _ = x.shape
    oh = (hp - k) // stride + 1
    ow = (wp - k) // stride + 1
    if bm is None or bn is None:
        wd = None if w.dtype == x.dtype else w.dtype
        tbm, tbn = tune.conv_blocks(b_, oh, ow, k, cin, cout, stride,
                                    x.dtype, groups=groups,
                                    interpret=interpret, autotune=autotune,
                                    w_dtype=wd)
        bm, bn = bm or tbm, bn or tbn
    if bias is None:
        bias = jnp.zeros((cout,), x.dtype)
    return _conv_fused_core(x, w, bias, k, stride, oh, ow, bm, bn, relu,
                            groups, interpret)
