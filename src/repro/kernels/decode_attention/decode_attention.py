"""Pallas TPU flash-decode: one query token per sequence against a ring
KV cache, with GQA group-sum and sliding-window masking.

The decode hot loop is memory-bound — one (G, hd) query tile sweeps the
(W, hd) cache — so unlike the training flash kernel there is no (S, S)
anything and no backward pass: this op is deliberately custom-vjp-free
(``jax.grad`` through it is a usage error; training uses
``kernels.flash_attention``).

Layout: q is folded to (B*Hkv, G, hd) with the G query heads of one KV
head as MXU rows (padded to the fp32 sublane count); the cache folds to
(B*Hkv, W, hd).  Grid is (B*Hkv, W/bk) with the KV axis ``arbitrary``
(sequential) carrying the online-softmax stats in VMEM scratch.

Per-row positions ride in as a scalar-prefetch argument
(``pltpu.PrefetchScalarGridSpec``): each grid row reads its own ``pos``
to build the ring-validity mask in-kernel — slot ``c`` holds absolute
position ``pos - ((pos - c) mod W)``, valid iff >= 0 and inside the
window.  Tiles that cannot contain a valid slot (the unwritten tail of a
not-yet-full ring, or the zero-padded cache tail) skip their MXU work
via ``pl.when``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SUBLANE, pow2_clip, resolve_interpret

NEG = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, *refs, bk: int, gp: int,
                   window, scale: float, n_k: int, n_kv_heads: int,
                   cap: int, quant: bool):
    if quant:
        # int8 cache: per-slot fp32 scales ride as two extra (1, bk) blocks
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(0)                   # b * Hkv + kv-head
    ki = pl.program_id(1)
    p = pos_ref[i // n_kv_heads]           # this row's absolute position

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # a tile is visible iff it can hold a valid slot: the ring has been
    # written up to slot p while p < cap (so tiles past p are untouched
    # zeros), and every slot holds a live position once the ring wrapped
    visible = (ki * bk < cap) & ((ki * bk <= p) | (p >= cap))

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)                   # (gp, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            # dequantize in the score domain: s[g,c] needs k[c]*ks[c], and
            # q.k_int scaled per COLUMN is a free (1, bk) lane broadcast —
            # no (bk, 1) relayout of the cache tile
            s = s * ks_ref[...]
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (gp, bk), 1)
        slot_pos = p - jnp.mod(p - cols, cap)
        mask = (cols < cap) & (slot_pos >= 0)
        if window is not None:
            mask &= slot_pos > p - window
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...]                                # (gp, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=1,
                                                  keepdims=True)
        if quant:
            # v dequant folds the same way: (pexp @ diag(vs)) @ v_int
            pexp = pexp * vs_ref[...]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            pexp.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_kv_heads", "window", "scale",
                                             "bk", "interpret", "cap"))
def _decode_impl(q, k, v, ks, vs, pos, n_kv_heads, window, scale, bk,
                 interpret, cap):
    """Folded padded inputs: q (B*Hkv, gp, hd), k/v (B*Hkv, Wp, hd),
    ks/vs (B*Hkv, Wp) fp32 dequant scales or None (unquantized cache),
    pos (B,) int32 -> o (B*Hkv, gp, hd)."""
    bh, gp, hd = q.shape
    wp = k.shape[1]
    assert wp % bk == 0, (wp, bk)
    n_k = wp // bk
    quant = ks is not None

    in_specs = [pl.BlockSpec((1, gp, hd), lambda i, ki, pos_ref: (i, 0, 0)),
                pl.BlockSpec((1, bk, hd), lambda i, ki, pos_ref: (i, ki, 0)),
                pl.BlockSpec((1, bk, hd), lambda i, ki, pos_ref: (i, ki, 0))]
    args = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, bk), lambda i, ki, pos_ref: (i, ki)),
                     pl.BlockSpec((1, bk), lambda i, ki, pos_ref: (i, ki))]
        args += [ks, vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gp, hd), lambda i, ki, pos_ref: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((gp, 1), jnp.float32),
                        pltpu.VMEM((gp, 1), jnp.float32),
                        pltpu.VMEM((gp, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, gp=gp, window=window,
                          scale=scale, n_k=n_k, n_kv_heads=n_kv_heads,
                          cap=cap, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, *args)


def _decode_kernel_table(pos_ref, tab_ref, *refs, **kw):
    # table mode: the block table is consumed by the BlockSpec index maps
    # (scalar prefetch); the in-kernel math is identical — logical column
    # ki*bk + j IS ring slot position, wherever the bytes physically live
    _decode_kernel(pos_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("n_kv_heads", "window", "scale",
                                             "interpret"))
def _decode_impl_table(q, k, v, ks, vs, pos, table, n_kv_heads, window,
                      scale, interpret):
    """Block-pool variant: q (B*Hkv, gp, hd); k/v (NB*Hkv, bs, hd) pools
    folded like the ring layout; table (B, cap/bs) int32 block ids;
    ks/vs (NB*Hkv, bs) fp32 or None.  The KV tile size IS the block size
    (one pool block per grid step), and the tile for grid row i, step ki
    is fetched from folded row ``table[i // Hkv, ki] * Hkv + i % Hkv`` —
    slot indirection via the second scalar-prefetch argument."""
    bh, gp, hd = q.shape
    bs = k.shape[1]
    n_k = table.shape[1]
    cap = n_k * bs
    quant = ks is not None

    def kvmap(i, ki, pos_ref, tab_ref):
        return (tab_ref[i // n_kv_heads, ki] * n_kv_heads
                + i % n_kv_heads, 0, 0)

    def scmap(i, ki, pos_ref, tab_ref):
        return (tab_ref[i // n_kv_heads, ki] * n_kv_heads
                + i % n_kv_heads, 0)

    in_specs = [pl.BlockSpec((1, gp, hd),
                             lambda i, ki, pos_ref, tab_ref: (i, 0, 0)),
                pl.BlockSpec((1, bs, hd), kvmap),
                pl.BlockSpec((1, bs, hd), kvmap)]
    args = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, bs), scmap),
                     pl.BlockSpec((1, bs), scmap)]
        args += [ks, vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gp, hd),
                               lambda i, ki, pos_ref, tab_ref: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((gp, 1), jnp.float32),
                        pltpu.VMEM((gp, 1), jnp.float32),
                        pltpu.VMEM((gp, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel_table, bk=bs, gp=gp, window=window,
                          scale=scale, n_k=n_k, n_kv_heads=n_kv_heads,
                          cap=cap, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, table, *args)


def decode_blocks(cap: int, hd: int, dtype, *, interpret: bool,
                  autotune: bool = None, kv_dtype=None):
    """(bk,) KV tile size, shared-autotuned on compiled backends.

    ``kv_dtype`` widens the cache key when the ring cache's storage dtype
    differs from the query's (bf16/int8 KV under a NumericsPolicy): tile
    timing depends on the bytes swept, so mixed-dtype calls must not
    share winners with same-dtype ones."""
    from repro.kernels import common
    default = (pow2_clip(cap, 128),)
    dt_key = str(dtype) if kv_dtype is None else (str(dtype), str(kv_dtype))
    key = ("decode_attn", cap, hd, dt_key)
    if not common.autotune_enabled(interpret, autotune):
        return common.autotune(key, [default], None)
    cands = {default} | {(bk,) for bk in (64, 128, 256)
                         if bk <= pow2_clip(cap, 256)}
    import numpy as np
    rng = np.random.default_rng(0)
    q = rng.normal(size=(4, 2, 4, hd)).astype(dtype)
    kwargs = {}
    if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
        kv = rng.integers(-127, 128, size=(4, cap, 2, hd)).astype(np.int8)
        sc = (np.abs(rng.normal(size=(4, cap, 2))) + 1e-3).astype(np.float32)
        kwargs = {"k_scale": sc, "v_scale": sc}
    else:
        kv = rng.normal(size=(4, cap, 2, hd)).astype(kv_dtype or dtype)
    pos = np.full((4,), cap - 1, np.int32)

    def measure(c):
        return common.time_call(
            lambda: decode_attention_pallas(
                q, kv, kv, pos, scale=hd ** -0.5, bk=c[0], interpret=False,
                **kwargs))
    return common.autotune(key, sorted(cands), measure)


def decode_attention_pallas(q, k, v, pos, *, window=None, scale=1.0,
                            bk: int = None, interpret: bool = None,
                            autotune: bool = None, k_scale=None,
                            v_scale=None, table=None):
    """q (B,Hkv,G,hd); k,v (B,W,Hkv,hd) ring cache; pos (B,) int32.
    ``k_scale``/``v_scale`` (B,W,Hkv) fp32 mark an int8-quantized cache —
    dequantized in-kernel (see ``_decode_kernel``).

    ``table`` (B, cap/bs) int32 switches k/v to BLOCK-POOL layout
    (NB, bs, Hkv, hd) — row b's ring slot s lives in
    ``pool[table[b, s//bs], s%bs]``; the KV tile size becomes the block
    size and the table rides as a second scalar-prefetch argument.

    Returns (B,Hkv,G,hd).  NOT differentiable (inference fast path).
    """
    b, hkv, g, hd = q.shape
    interpret = resolve_interpret(interpret)
    if table is not None:
        bs = k.shape[1]
        gp = -(-g // SUBLANE) * SUBLANE
        qf = q.reshape(b * hkv, g, hd)
        if gp != g:
            qf = jnp.pad(qf, ((0, 0), (0, gp - g), (0, 0)))
        kf = k.transpose(0, 2, 1, 3).reshape(-1, bs, hd)
        vf = v.transpose(0, 2, 1, 3).reshape(-1, bs, hd)
        ksf = vsf = None
        if k_scale is not None:
            ksf = jnp.asarray(k_scale, jnp.float32).transpose(0, 2, 1) \
                .reshape(-1, bs)
            vsf = jnp.asarray(v_scale, jnp.float32).transpose(0, 2, 1) \
                .reshape(-1, bs)
        o = _decode_impl_table(qf, kf, vf, ksf, vsf,
                               jnp.asarray(pos, jnp.int32),
                               jnp.asarray(table, jnp.int32),
                               hkv, window, scale, interpret)
        return o[:, :g].reshape(b, hkv, g, hd)
    cap = k.shape[1]
    if bk is None:
        kvd = None if k.dtype == q.dtype else k.dtype
        (bk,) = decode_blocks(cap, hd, q.dtype, interpret=interpret,
                              autotune=autotune, kv_dtype=kvd)
    bk = min(bk, pow2_clip(cap, bk))
    gp = -(-g // SUBLANE) * SUBLANE
    qf = q.reshape(b * hkv, g, hd)
    if gp != g:
        qf = jnp.pad(qf, ((0, 0), (0, gp - g), (0, 0)))
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, cap, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, cap, hd)
    wp = -(-cap // bk) * bk
    if wp != cap:
        pad = ((0, 0), (0, wp - cap), (0, 0))
        kf, vf = jnp.pad(kf, pad), jnp.pad(vf, pad)
    ksf = vsf = None
    if k_scale is not None:
        ksf = jnp.asarray(k_scale, jnp.float32).transpose(0, 2, 1) \
            .reshape(b * hkv, cap)
        vsf = jnp.asarray(v_scale, jnp.float32).transpose(0, 2, 1) \
            .reshape(b * hkv, cap)
        if wp != cap:
            ksf = jnp.pad(ksf, ((0, 0), (0, wp - cap)))
            vsf = jnp.pad(vsf, ((0, 0), (0, wp - cap)))
    o = _decode_impl(qf, kf, vf, ksf, vsf, jnp.asarray(pos, jnp.int32),
                     hkv, window, scale, bk, interpret, cap)
    return o[:, :g].reshape(b, hkv, g, hd)
