"""Pallas TPU kernel for the chunked RWKV6 WKV recurrence.

Grid: (B*H, T/C) with the chunk axis ``arbitrary`` (sequential) — the running
state S (K×K, fp32) lives in a VMEM scratch buffer and is carried across
chunk steps, so HBM traffic per chunk is just the (C,K) operand tiles plus
one (C,K) output tile.  All matmuls are (C,K)x(K,K) / (C,C)x(C,K) — MXU-
aligned for K=64/128 with fp32 accumulation.

The intra-chunk decay weights use exponents ``cum_{t-1} - cum_s ≤ 0`` (s<t),
so no term ever overflows — same scheme as the jnp oracle in ``ref.py``.

Differentiable via ``jax.custom_vjp`` with a *chunked-state backward*:
the WKV recurrence's cotangent needs the per-chunk states, so the
backward recomputes the chunked-parallel reference (``ref.wkv_chunked``,
pure XLA, same chunk size → identical state trajectory up to fp32
rounding) and pulls the cotangent through it.  This mirrors the conv2d
precedent — Pallas forward on the hot path, XLA transpose on the
backward — and keeps memory at O(T·K) residuals (the saved operands),
never an unchunked (T, K, K) state history.

Ragged T is padded with inert steps (k=v=r=0, w=1 — state unchanged) by
the public wrapper; the chunk size comes from the shared autotune cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pow2_clip, resolve_interpret
from repro.kernels.rwkv6 import ref


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref,
                s_scratch, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    r = r_ref[0].astype(jnp.float32)          # (C, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, K)
    s = s_scratch[...]                        # (K, K)

    lw = jnp.log(w)
    cum = jnp.cumsum(lw, axis=0)              # (C, K)
    cum_prev = cum - lw

    # intra-chunk scores A[t,s] = Σ_i r[t,i] k[s,i] e^{cum_prev[t,i]-cum[s,i]}, s<t
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)
    expo = cum_prev[:, None, :] - cum[None, :, :]          # (C, C, K)
    dec = jnp.exp(jnp.minimum(expo, 0.0)) * tri[:, :, None]
    a = jnp.einsum("tk,sk,tsk->ts", r, k, dec,
                   preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=-1)                     # (C,)
    a = a + jnp.eye(chunk, dtype=jnp.float32) * diag[:, None]

    y = jnp.dot(a, v, preferred_element_type=jnp.float32)
    y = y + jnp.dot(r * jnp.exp(cum_prev), s,
                    preferred_element_type=jnp.float32)

    cend = cum[-1:, :]                                     # (1, K)
    kscaled = k * jnp.exp(cend - cum)
    s_scratch[...] = jnp.exp(cend[0])[:, None] * s + jnp.dot(
        kscaled.T, v, preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        # the final state is already in VMEM — emitting it here is what
        # lets the prefill path skip a second full recurrence pass
        s_out_ref[0] = s_scratch[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv_impl(r, k, v, w, u, chunk, interpret):
    """r,k,v,w: (B,T,H,K) with T % chunk == 0; u: (H,K).
    -> (y (B,T,H,K), final state (B,H,K,K) fp32)."""
    b, t, h, kk = r.shape
    assert t % chunk == 0
    nc = t // chunk
    # (B*H, T, K) layout so each grid row owns one head's full sequence
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, kk)
    rf, kf, vf, wf = map(fold, (r, k, v, w))
    uf = jnp.broadcast_to(u[None], (b, h, kk)).reshape(b * h, 1, kk)

    spec = pl.BlockSpec((1, chunk, kk), lambda i, j: (i, j, 0))
    uspec = pl.BlockSpec((1, 1, kk), lambda i, j: (i, 0, 0))
    y, s_fin = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n_chunks=nc),
        grid=(b * h, nc),
        in_specs=[spec, spec, spec, spec, uspec],
        out_specs=[spec,
                   pl.BlockSpec((1, kk, kk), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, t, kk), r.dtype),
                   jax.ShapeDtypeStruct((b * h, kk, kk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((kk, kk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return (y.reshape(b, h, t, kk).transpose(0, 2, 1, 3),
            s_fin.reshape(b, h, kk, kk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _wkv_core(r, k, v, w, u, chunk, interpret):
    return _wkv_impl(r, k, v, w, u, chunk, interpret)


def _wkv_core_fwd(r, k, v, w, u, chunk, interpret):
    out = _wkv_impl(r, k, v, w, u, chunk, interpret)
    return out, (r, k, v, w, u)


def _wkv_core_bwd(chunk, interpret, res, dy):
    r, k, v, w, u = res
    dy_y, dy_s = dy
    # chunked-state backward: recompute the chunked-parallel XLA reference
    # (same chunk size → same per-chunk state trajectory) and pull both
    # cotangents (output AND final state) through it; scan residuals stay
    # O(T/C) chunk states
    _, pull = jax.vjp(
        lambda r_, k_, v_, w_, u_: ref.wkv_chunked(r_, k_, v_, w_, u_,
                                                   chunk=chunk),
        r, k, v, w, u)
    dr, dk, dv, dw, du = pull((dy_y.astype(jnp.float32),
                               dy_s.astype(jnp.float32)))
    return (dr.astype(r.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dw.astype(w.dtype), du.astype(u.dtype))


_wkv_core.defvjp(_wkv_core_fwd, _wkv_core_bwd)


def rwkv_blocks(t: int, kk: int, dtype, *, interpret: bool,
                autotune: bool = None):
    """(chunk,) for the WKV kernel, shared-autotuned on compiled backends."""
    from repro.kernels import common
    default = (pow2_clip(t, 64),)
    key = ("rwkv6", t, kk, str(dtype))
    if not common.autotune_enabled(interpret, autotune):
        return common.autotune(key, [default], None)
    cands = {default} | {(c,) for c in (32, 64, 128)
                         if c <= pow2_clip(t, 128)}
    import numpy as np
    rng = np.random.default_rng(0)
    r, k, v = (rng.normal(size=(1, t, 2, kk)).astype(dtype)
               for _ in range(3))
    w = np.exp(-np.exp(rng.normal(size=(1, t, 2, kk)) * 0.5)).astype(dtype)
    u = (rng.normal(size=(2, kk)) * 0.5).astype(dtype)

    def measure(c):
        return common.time_call(
            lambda: wkv_pallas(r, k, v, w, u, chunk=c[0], interpret=False))
    return common.autotune(key, sorted(cands), measure)


def wkv_pallas(r, k, v, w, u, *, chunk: int = None, interpret: bool = None,
               autotune: bool = None, return_state: bool = False):
    """r,k,v,w: (B,T,H,K); u: (H,K).  Returns y (B,T,H,K), or
    (y, final_state (B,H,K,K)) with ``return_state=True`` — the state
    comes straight from the kernel's VMEM scratch, so prefill needs no
    second recurrence pass.  Any T (padded internally with inert steps:
    k=v=0, w=1 leave the state unchanged).  Differentiable."""
    b, t, h, kk = r.shape
    interpret = resolve_interpret(interpret)
    if chunk is None:
        chunk = rwkv_blocks(t, kk, r.dtype, interpret=interpret,
                            autotune=autotune)[0]
    chunk = min(chunk, pow2_clip(t, chunk))
    t_pad = -(-t // chunk) * chunk
    if t_pad != t:
        widths = ((0, 0), (0, t_pad - t), (0, 0), (0, 0))
        r, k, v = (jnp.pad(x, widths) for x in (r, k, v))
        w = jnp.pad(w, widths, constant_values=1.0)
    y, s_fin = _wkv_core(r, k, v, w, u, chunk, interpret)
    y = y[:, :t] if t_pad != t else y
    return (y, s_fin) if return_state else y
