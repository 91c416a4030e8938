"""Pallas TPU flash attention — forward AND backward — with causal +
sliding-window masks.

Forward grid: (B*Hq, S/bq, S/bk) — the KV axis is ``arbitrary``
(sequential) and the online-softmax running stats (m, l, acc) live in
VMEM scratch carried across KV steps.  GQA is handled in the BlockSpec
index maps: the K/V block row for query head h is ``b*Hkv + h // group``
— no materialized head repetition.  Alongside the output the kernel
writes the per-row log-sum-exp ``lse = m + log(l)``, the residual the
backward pass needs to rebuild probabilities tile-by-tile.

Backward (``jax.custom_vjp`` — Pallas calls have no automatic AD) is the
standard recompute-style pass over (bq, bk) tiles:

  ``dq`` kernel: grid (B*Hq, S/bq, S/bk), KV sequential — per tile,
      rebuild ``p = exp(s - lse)``, form ``ds = p * (dp - delta)`` with
      ``dp = do @ vᵀ`` and ``delta = rowsum(do * o)`` (precomputed), and
      accumulate ``dq += ds @ k * scale`` in VMEM scratch.
  ``dk/dv`` kernel: grid (B*Hq, S/bk, S/bq), Q sequential — accumulate
      ``dv += pᵀ @ do`` and ``dk += dsᵀ @ q * scale`` per *query* head;
      the wrapper then sums the group axis onto the Hkv heads.

No (S, S) score matrix ever materializes in either direction (asserted
by jaxpr walk in tests/kernels/test_grad_parity.py).

Sequence lengths that don't divide the block sizes are zero-padded by
the public wrapper and masked in-kernel via the static ``s_valid`` bound
(so odd lengths work on both passes); block shapes (bq, hd) / (bk, hd)
are MXU-aligned for hd ∈ {64, 128, 256}.  Numerics: scores are computed
in fp32; masked lanes use -1e30.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pow2_clip, resolve_interpret

NEG = -1e30


def _tile_mask(rows, cols, *, causal, window, s_valid, with_rows: bool):
    """The (bq, bk) validity mask — single source of truth for fwd + bwd."""
    mask = cols < s_valid                      # zero-padded KV tail
    if with_rows:
        mask &= rows < s_valid                 # padded query rows (bwd only)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    return mask


def _tile_visible(qi, ki, *, bq, bk, causal, window, s_valid):
    """Whether the (qi, ki) tile has ANY unmasked entry.  Fully-masked
    tiles (above the causal diagonal, outside the sliding window, or in
    the padded KV tail) skip their MXU work entirely — for windowed
    attention that turns the O(S²) tile sweep into O(S·window) compute."""
    vis = ki * bk < s_valid
    if causal:
        vis &= ki * bk <= qi * bq + (bq - 1)       # first col <= last row
    if window is not None:
        vis &= ki * bk + (bk - 1) > qi * bq - window  # last col in window
    return vis


# --------------------------------------------------------------- forward ----

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, bq: int, bk: int, causal: bool, window, scale: float,
                  n_k: int, s_valid: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_tile_visible(qi, ki, bq=bq, bk=bk, causal=causal,
                           window=window, s_valid=s_valid))
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _tile_mask(rows, cols, causal=causal, window=window,
                          s_valid=s_valid, with_rows=False)
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # lse stays hugely negative (~NEG) for fully-masked padded rows,
        # so the backward's exp(s - lse) never sees an inf there
        lse_ref[0] = (m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]


@functools.partial(jax.jit, static_argnames=("n_q_heads", "n_kv_heads",
                                             "causal", "window", "scale",
                                             "bq", "bk", "interpret",
                                             "s_valid"))
def _flash_fwd_impl(q, k, v, n_q_heads, n_kv_heads, causal, window, scale,
                    bq, bk, interpret, s_valid):
    """Padded folded inputs -> (o (B*Hq,S,hd), lse (B*Hq,S) fp32)."""
    bhq, s, hd = q.shape
    group = n_q_heads // n_kv_heads
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_k = s // bk

    def q_map(i, j, kk):
        return (i, j, 0)

    def kv_map(i, j, kk):
        b, h = i // n_q_heads, i % n_q_heads
        return (b * n_kv_heads + h // group, kk, 0)

    def lse_map(i, j, kk):
        return (i, j)

    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, scale=scale, n_k=n_k,
                          s_valid=s_valid),
        grid=(bhq, s // bq, n_k),
        in_specs=[pl.BlockSpec((1, bq, hd), q_map),
                  pl.BlockSpec((1, bk, hd), kv_map),
                  pl.BlockSpec((1, bk, hd), kv_map)],
        out_specs=[pl.BlockSpec((1, bq, hd), q_map),
                   pl.BlockSpec((1, bq), lse_map)],
        out_shape=[jax.ShapeDtypeStruct((bhq, s, hd), q.dtype),
                   jax.ShapeDtypeStruct((bhq, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# -------------------------------------------------------------- backward ----

def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
                     dq_scr, *, bq: int, bk: int, causal: bool, window,
                     scale: float, n_k: int, s_valid: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_tile_visible(qi, ki, bq=bq, bk=bk, causal=causal,
                           window=window, s_valid=s_valid))
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)             # (bq, hd)
        lse = lse_ref[0][:, None]                      # (bq, 1)
        delta = dl_ref[0][:, None]                     # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _tile_mask(rows, cols, causal=causal, window=window,
                          s_valid=s_valid, with_rows=True)
        p = jnp.exp(jnp.where(mask, s - lse, NEG))     # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                      dv_ref, dk_scr, dv_scr, *, bq: int, bk: int,
                      causal: bool, window, scale: float, n_q: int,
                      s_valid: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_tile_visible(qi, ki, bq=bq, bk=bk, causal=causal,
                           window=window, s_valid=s_valid))
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, None]
        delta = dl_ref[0][:, None]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _tile_mask(rows, cols, causal=causal, window=window,
                          s_valid=s_valid, with_rows=True)
        p = jnp.exp(jnp.where(mask, s - lse, NEG))
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_q_heads", "n_kv_heads",
                                             "causal", "window", "scale",
                                             "bq", "bk", "interpret",
                                             "s_valid"))
def _flash_bwd_impl(q, k, v, do, lse, delta, n_q_heads, n_kv_heads, causal,
                    window, scale, bq, bk, interpret, s_valid):
    """Returns (dq (B*Hq,S,hd), dk_q, dv_q (B*Hq,S,hd) per *query* head —
    the caller group-sums onto the Hkv heads)."""
    bhq, s, hd = q.shape
    group = n_q_heads // n_kv_heads
    n_q, n_k = s // bq, s // bk

    def q_map(i, j, kk):
        return (i, j, 0)

    def kv_map(i, j, kk):
        b, h = i // n_q_heads, i % n_q_heads
        return (b * n_kv_heads + h // group, kk, 0)

    def lse_map(i, j, kk):
        return (i, j)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, scale=scale, n_k=n_k,
                          s_valid=s_valid),
        grid=(bhq, n_q, n_k),
        in_specs=[pl.BlockSpec((1, bq, hd), q_map),
                  pl.BlockSpec((1, bk, hd), kv_map),
                  pl.BlockSpec((1, bk, hd), kv_map),
                  pl.BlockSpec((1, bq, hd), q_map),
                  pl.BlockSpec((1, bq), lse_map),
                  pl.BlockSpec((1, bq), lse_map)],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((bhq, s, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: the KV tile is the parallel axis, queries are sequential
    def q_map2(i, j, qq):
        return (i, qq, 0)

    def kv_map2(i, j, qq):
        b, h = i // n_q_heads, i % n_q_heads
        return (b * n_kv_heads + h // group, j, 0)

    def lse_map2(i, j, qq):
        return (i, qq)

    def out_map2(i, j, qq):
        return (i, j, 0)

    dk_q, dv_q = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, scale=scale, n_q=n_q,
                          s_valid=s_valid),
        grid=(bhq, n_k, n_q),
        in_specs=[pl.BlockSpec((1, bq, hd), q_map2),
                  pl.BlockSpec((1, bk, hd), kv_map2),
                  pl.BlockSpec((1, bk, hd), kv_map2),
                  pl.BlockSpec((1, bq, hd), q_map2),
                  pl.BlockSpec((1, bq), lse_map2),
                  pl.BlockSpec((1, bq), lse_map2)],
        out_specs=[pl.BlockSpec((1, bk, hd), out_map2),
                   pl.BlockSpec((1, bk, hd), out_map2)],
        out_shape=[jax.ShapeDtypeStruct((bhq, s, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bhq, s, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk_q, dv_q


# ---------------------------------------------------------------- custom ----

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_core(q, k, v, n_q_heads, n_kv_heads, causal, window, scale, bq,
                bk, interpret, s_valid):
    o, _ = _flash_fwd_impl(q, k, v, n_q_heads, n_kv_heads, causal, window,
                           scale, bq, bk, interpret, s_valid)
    return o


def _flash_core_fwd(q, k, v, n_q_heads, n_kv_heads, causal, window, scale,
                    bq, bk, interpret, s_valid):
    o, lse = _flash_fwd_impl(q, k, v, n_q_heads, n_kv_heads, causal, window,
                             scale, bq, bk, interpret, s_valid)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(n_q_heads, n_kv_heads, causal, window, scale, bq, bk,
                    interpret, s_valid, res, dy):
    q, k, v, o, lse = res
    do = dy.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)   # (B*Hq, S)
    dq, dk_q, dv_q = _flash_bwd_impl(q, k, v, do, lse, delta, n_q_heads,
                                     n_kv_heads, causal, window, scale, bq,
                                     bk, interpret, s_valid)
    # fold the per-query-head dk/dv onto the shared Hkv heads (GQA)
    group = n_q_heads // n_kv_heads
    b = q.shape[0] // n_q_heads
    s, hd = q.shape[1], q.shape[2]
    dk = dk_q.reshape(b, n_kv_heads, group, s, hd).sum(2)
    dv = dv_q.reshape(b, n_kv_heads, group, s, hd).sum(2)
    return (dq.astype(q.dtype),
            dk.reshape(b * n_kv_heads, s, hd).astype(k.dtype),
            dv.reshape(b * n_kv_heads, s, hd).astype(v.dtype))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ----------------------------------------------------------------- entry ----

def flash_blocks(s: int, hd: int, dtype, *, interpret: bool,
                 autotune: bool = None, kv_dtype=None):
    """(bq, bk) tile sizes, shared-autotuned on compiled backends.
    ``kv_dtype`` widens the cache key to the (q, kv) dtype tuple when the
    operands differ (mixed-precision NumericsPolicy)."""
    from repro.kernels import common
    default = (pow2_clip(s, 128), pow2_clip(s, 128))
    dt_key = str(dtype) if kv_dtype is None else (str(dtype), str(kv_dtype))
    key = ("flash", s, hd, dt_key)
    if not common.autotune_enabled(interpret, autotune):
        return common.autotune(key, [default], None)
    cap = pow2_clip(s, 256)
    cands = {default} | {(bq, bk) for bq in (64, 128, 256)
                         for bk in (64, 128, 256)
                         if bq <= cap and bk <= cap}
    import numpy as np
    rng = np.random.default_rng(0)
    q = rng.normal(size=(4, s, hd)).astype(dtype)
    kv = rng.normal(size=(4, s, hd)).astype(kv_dtype or dtype)

    def measure(c):
        bq, bk = c
        return common.time_call(
            lambda: flash_attention_folded(
                q, kv, kv, n_q_heads=4, n_kv_heads=4, causal=True,
                scale=hd ** -0.5, bq=bq, bk=bk, interpret=False))
    return common.autotune(key, sorted(cands), measure)


def flash_attention_folded(q, k, v, *, n_q_heads: int, n_kv_heads: int,
                           causal=True, window=None, scale=1.0,
                           bq: int = None, bk: int = None,
                           interpret: bool = None, autotune: bool = None):
    """q (B*Hq, S, hd); k, v (B*Hkv, S, hd).  Differentiable in q, k, v.

    ``interpret=None`` auto-resolves (compiled on TPU); ``bq/bk=None``
    come from the shared autotune cache.  S may be any length — inputs
    are zero-padded to the block step and masked in-kernel.
    """
    bhq, s, hd = q.shape
    interpret = resolve_interpret(interpret)
    if bq is None or bk is None:
        kvd = None if k.dtype == q.dtype else k.dtype
        tbq, tbk = flash_blocks(s, hd, q.dtype, interpret=interpret,
                                autotune=autotune, kv_dtype=kvd)
        bq, bk = bq or tbq, bk or tbk
    bq = min(bq, pow2_clip(s, bq))
    bk = min(bk, pow2_clip(s, bk))
    step = math.lcm(bq, bk)
    s_pad = -(-s // step) * step
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    o = _flash_core(q, k, v, n_q_heads, n_kv_heads, causal, window, scale,
                    bq, bk, interpret, s)
    return o[:, :s] if s_pad != s else o
