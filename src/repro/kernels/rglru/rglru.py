"""Pallas TPU kernel for the RG-LRU diagonal linear recurrence.

Grid: (B, D/bd, T/C) with the chunk axis sequential; the carried state
(1, bd) lives in VMEM scratch.  Within a chunk the prefix products run
on (C, C, bd) decay tiles with exponents ``logP_t - logP_s ≤ 0`` (s≤t),
so no term ever overflows — the naive ``P_t * cumsum(b_s / P_s)``
rescaling blows through fp32 range under the model's strong decay
(``a ≈ e^-10`` compounds to ``e^±640`` over a 64-chunk), which is why
chunks stay short and the same bounded-exponent scheme as the WKV6
kernel is used instead.  Channel tiles (bd=128) match the lane width.

Differentiable via ``jax.custom_vjp``: the cotangent of a linear
recurrence ``h_t = a_t h_{t-1} + b_t`` is itself a linear recurrence run
*backwards* (``g_t = dh_t + a_{t+1} g_{t+1}``), so the backward pass is
one more call of the SAME Pallas kernel on the time-reversed, one-step-
shifted coefficients (the transpose scan), plus elementwise products:

    db_t = g_t            da_t = g_t * h_{t-1}

Ragged T/D are zero-padded (a=1, b=0 — inert steps/channels) by the
public wrapper and sliced off; block sizes come from the shared autotune
cache (``repro.kernels.common``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pow2_clip, resolve_interpret


def _rglru_kernel(a_ref, b_ref, h_ref, state, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    a = a_ref[0].astype(jnp.float32)          # (C, bd)
    b = b_ref[0].astype(jnp.float32)
    loga = jnp.log(jnp.maximum(a, 1e-37))
    logp = jnp.cumsum(loga, axis=0)
    # A[t,s] = P_t / P_s = exp(logp_t - logp_s) for s <= t: with decaying
    # coefficients (a <= 1) every exponent is <= 0, so nothing overflows
    # regardless of decay strength; masked (s > t) entries are killed
    # INSIDE the exp (-1e30 -> 0), so a growing recurrence (a > 1) stays
    # exact too instead of being silently clamped
    expo = logp[:, None, :] - logp[None, :, :]           # (C, C, bd)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    dec = jnp.exp(jnp.where(tri[:, :, None], expo, -1e30))
    h_all = jnp.einsum("tsc,sc->tc", dec, b,
                       preferred_element_type=jnp.float32)
    h_all = h_all + jnp.exp(logp) * state[...]
    state[...] = h_all[-1:, :]
    h_ref[0] = h_all.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def _rglru_impl(a, b, chunk, bd, interpret):
    """a, b: (B, T, D) with T % chunk == 0 and D % bd == 0."""
    bsz, t, d = a.shape
    assert t % chunk == 0 and d % bd == 0, (t, d, chunk, bd)

    spec = pl.BlockSpec((1, chunk, bd), lambda i, j, k: (i, k, j))
    return pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=(bsz, d // bd, t // chunk),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, t, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rglru_core(a, b, chunk, bd, interpret):
    return _rglru_impl(a, b, chunk, bd, interpret)


def _rglru_core_fwd(a, b, chunk, bd, interpret):
    h = _rglru_impl(a, b, chunk, bd, interpret)
    # zero-size marker keeps b's dtype for the cotangent cast without
    # saving b itself (its value is not needed by the transpose scan)
    return h, (a, h, jnp.zeros((), b.dtype))


def _rglru_core_bwd(chunk, bd, interpret, res, dh):
    a, h, b_proto = res
    af = a.astype(jnp.float32)
    # g_t = dh_t + a_{t+1} g_{t+1}: the same recurrence on the reversed
    # sequence with coefficients shifted one step — run the kernel again
    a_rev = jnp.flip(af, axis=1)
    a_shift = jnp.concatenate([jnp.ones_like(a_rev[:, :1]), a_rev[:, :-1]],
                              axis=1)
    g = jnp.flip(_rglru_impl(a_shift, jnp.flip(dh.astype(jnp.float32), 1),
                             chunk, bd, interpret), axis=1)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1).astype(jnp.float32)
    return (g * h_prev).astype(a.dtype), g.astype(b_proto.dtype)


_rglru_core.defvjp(_rglru_core_fwd, _rglru_core_bwd)


def rglru_blocks(t: int, d: int, dtype, *, interpret: bool,
                 autotune: bool = None):
    """(chunk, bd), shared-autotuned on compiled backends."""
    from repro.kernels import common
    # chunk cap 64: the (C, C, bd) decay tile is the VMEM budget
    # (64·64·128 fp32 = 2 MB); larger chunks square it away
    default = (pow2_clip(t, 64), pow2_clip(d, 128))
    key = ("rglru", t, d, str(dtype))
    if not common.autotune_enabled(interpret, autotune):
        return common.autotune(key, [default], None)
    cands = {default} | {(c, default[1]) for c in (16, 32, 64)
                         if c <= pow2_clip(t, 64)}
    import numpy as np
    rng = np.random.default_rng(0)
    a = (1.0 / (1.0 + np.exp(-rng.normal(size=(2, t, d)) - 2.0))
         ).astype(dtype)
    b = rng.normal(size=(2, t, d)).astype(dtype)

    def measure(c):
        chunk, bd = c
        return common.time_call(
            lambda: rglru_pallas(a, b, chunk=chunk, bd=bd, interpret=False))
    return common.autotune(key, sorted(cands), measure)


def rglru_pallas(a, b, *, chunk: int = None, bd: int = None,
                 interpret: bool = None, autotune: bool = None):
    """a, b: (B, T, D), any T/D (padded internally).  Differentiable.

    Precondition: a > 0 (log-space prefix products).  Decaying
    coefficients (a <= 1, the RG-LRU's range) are unconditionally stable;
    a growing recurrence (a > 1) is computed exactly but inherits fp32
    range limits on the within-chunk product ``prod a``."""
    bsz, t, d = a.shape
    interpret = resolve_interpret(interpret)
    if chunk is None or bd is None:
        tc, tb = rglru_blocks(t, d, a.dtype, interpret=interpret,
                              autotune=autotune)
        chunk, bd = chunk or tc, bd or tb
    chunk = min(chunk, pow2_clip(t, chunk))
    bd = min(bd, pow2_clip(d, bd))
    t_pad = -(-t // chunk) * chunk
    d_pad = -(-d // bd) * bd
    if t_pad != t or d_pad != d:
        # inert padding: a=1, b=0 carries the state unchanged and keeps
        # padded channels at zero (sliced off below)
        widths = ((0, 0), (0, t_pad - t), (0, d_pad - d))
        a = jnp.pad(a, widths, constant_values=1.0)
        b = jnp.pad(b, widths)
    h = _rglru_core(a, b, chunk, bd, interpret)
    return h[:, :t, :d] if (t_pad != t or d_pad != d) else h
