"""The plain reference: AlexNet training in straightforward ``jax.numpy``,
float32 at "highest" precision, importing nothing of the program.

It follows the configuration file alone: conv (grouped where the file
says so) + bias + ReLU, LRN after the pool when ``faithful`` and before
it otherwise, 3x3/2 max-pool, two ReLU'd 4096-wide FC layers and the
classifier, mean softmax cross-entropy, and SGD with momentum and weight
decay (``v = m*v + g + wd*p``, ``p = p - lr*v``).  Several replicas that
average their parameters and momentum after every step train exactly as
one model on the mean of their gradients, so one reference serves every
replica count.

``operands`` puts a low-precision control in the program's place: every
operand of every convolution and matrix product, forward and backward
(the cotangent included), rounded with a per-tensor scale, accumulation
still float32.  ``"int8"``: symmetric int8, the tensor's largest
magnitude at 127 — the step a TPU v5e tempts, whose MXU multiplies int8
at twice its bf16 rate (393 against 197 TOP/s) and has no fp8.
``"e4m3"``: fp8 e4m3, the largest magnitude at 448 (3 mantissa bits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
E4M3_MIN_EXP = -6          # smallest normal exponent; below it, subnormal


def round_int8(x):
    """Round to symmetric int8 after scaling the tensor's largest
    magnitude to 127, then scale back: float32 in, float32 out."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def round_e4m3(x):
    """Round to fp8 e4m3 (3 mantissa bits) after scaling the tensor's
    largest magnitude to 448, then scale back: float32 in, float32 out."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    y = x / scale
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 1e-30)))
    ulp = jnp.exp2(jnp.maximum(e, E4M3_MIN_EXP) - 3)
    y = jnp.clip(jnp.round(y / ulp) * ulp, -E4M3_MAX, E4M3_MAX)
    return y * scale


ROUNDING = {"int8": round_int8, "e4m3": round_e4m3}


def _quantized(op, rnd):
    """``op(x, w)`` with every operand of its forward product and of the
    two backward products (the cotangent included) rounded by ``rnd``."""
    @jax.custom_vjp
    def f(x, w):
        return op(rnd(x), rnd(w))

    def fwd(x, w):
        xr, wr = rnd(x), rnd(w)
        return op(xr, wr), (xr, wr)

    def bwd(res, dy):
        _, vjp = jax.vjp(op, *res)
        return vjp(rnd(dy))

    f.defvjp(fwd, bwd)
    return f


def conv(x, w, stride, padding, groups, quant=None):
    def op(x_, w_):
        return jax.lax.conv_general_dilated(
            x_, w_, (stride, stride), [(padding, padding)] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=HIGHEST)
    return (_quantized(op, ROUNDING[quant]) if quant else op)(x, w)


def dense(x, w, quant=None):
    def op(x_, w_):
        return jnp.dot(x_, w_, precision=HIGHEST)
    return (_quantized(op, ROUNDING[quant]) if quant else op)(x, w)


def lrn(x, n, alpha, beta, k):
    c = x.shape[-1]
    sq = jnp.pad(x * x, [(0, 0)] * 3 + [(n // 2, n // 2)])
    win = sum(sq[..., i:i + c] for i in range(n))
    return x / (k + alpha * win) ** beta


def maxpool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "VALID")


def forward(params, cfg, x, quant=None):
    lrn_args = (cfg["lrn_n"], cfg["lrn_alpha"], cfg["lrn_beta"],
                cfg["lrn_k"])
    h = x
    for p, cs in zip(params["convs"], cfg["convs"]):
        h = jax.nn.relu(conv(h, p["w"], cs["stride"], cs["padding"],
                             cs["groups"], quant) + p["b"])
        if cs["lrn"] and not cfg["faithful"]:
            h = lrn(h, *lrn_args)
        if cs["pool"]:
            h = maxpool(h)
        if cs["lrn"] and cfg["faithful"]:
            h = lrn(h, *lrn_args)
    h = h.reshape(h.shape[0], -1)
    for i, p in enumerate(params["fcs"]):
        if i:
            h = jax.nn.relu(h)
        h = dense(h, p["w"], quant) + p["b"]
    return h


def loss_fn(params, cfg, images, labels, quant=None):
    logits = forward(params, cfg, images, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


@functools.partial(jax.jit, static_argnames=("cfg_items", "operands"))
def _step(params, vel, images, labels, cfg_items, operands):
    cfg = dict(cfg_items)
    cfg["convs"] = [dict(c) for c in cfg["convs"]]
    loss, g = jax.value_and_grad(loss_fn)(params, cfg, images, labels,
                                          operands)
    g_eff = jax.tree.map(lambda g_, p: g_ + cfg["weight_decay"] * p, g,
                         params)
    vel = jax.tree.map(lambda v, ge: cfg["momentum"] * v + ge, vel, g_eff)
    params = jax.tree.map(lambda p, v: p - cfg["lr"] * v, params, vel)
    return params, vel, loss, g


def _frozen(cfg: dict):
    items = {k: cfg[k] for k in ("faithful", "lrn_n", "lrn_alpha",
                                 "lrn_beta", "lrn_k", "lr", "momentum",
                                 "weight_decay")}
    items["convs"] = tuple(tuple(sorted(c.items())) for c in cfg["convs"])
    return tuple(sorted(items.items()))


def train(cfg: dict, params, batches, operands=None):
    """Run ``len(batches)`` steps from ``params`` (float32, no replica
    axis) on global batches ``{"images", "labels"}``, at full float32 or,
    with ``operands``, as a low-precision control.  Returns the loss of
    each step, the first step's gradient, and the final params."""
    if operands is not None and operands not in ROUNDING:
        raise ValueError(f"operands {operands!r} not in {sorted(ROUNDING)}")
    key = _frozen(cfg)
    vel = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for b in batches:
        params, vel, loss, g = _step(params, vel, jnp.asarray(b["images"]),
                                     jnp.asarray(b["labels"]), key,
                                     operands)
        losses.append(loss)
        if first_grad is None:
            first_grad = g
    return [float(x) for x in losses], first_grad, params
