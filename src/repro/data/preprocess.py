"""Image preprocessing — the paper's loader-side transforms (footnote 2):
subtract the mean image, random crop, random horizontal flip.

``make_image_preprocess`` is the transform the loaders run.  On the loader
thread it draws each batch's crops and mirrors from the host generator,
puts the unprocessed batch on the default device, and dispatches one
jitted program (``preprocess_on_device``) that subtracts the mean image
in float32, cuts each image's window and mirrors where drawn.  The batch
it hands on is float32 on the default device, so the loader thread is
left with the fetch, one put of the raw pixels (a third of the float32
crop's bytes for uint8 input) and two dispatches.  A caller that places
batches elsewhere (a replica sharding) moves it from there.

``subtract_mean`` and ``random_crop_flip`` are the same transforms in
numpy on the host: the plain reference the device program matches bit
for bit (subtraction commutes with crop and mirror), and what the legacy
CPU scripts in ``benchmarks/`` time.  Both paths draw through
``draw_crops``.  Deterministic given the seed.

Spans (``obs.span``), each entered once per batch by the device path,
time host work only:

    repro.data.crop_flip      the draws (host path: the numpy crop and
                              mirror, draws included)
    repro.data.raw_put        the host part of the raw batch's put
    repro.data.mean_subtract  the program's dispatch (host path: the numpy
                              subtraction)

The subtraction, crop and mirror themselves run on the device, where the
trace shows them as the program ``jit__crop_flip_subtract``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs


def draw_crops(rng: np.random.Generator, b: int, h: int, w: int,
               crop: int, flip: bool = True):
    """One batch's draws, in this order: crop rows, crop columns, then
    mirror flags (none drawn without ``flip``).  Resume's fast-forward
    and the benchmark's reference replay exactly this sequence."""
    if h < crop or w < crop:
        raise ValueError(f"crop {crop} larger than the {h}x{w} image")
    ys = rng.integers(0, h - crop + 1, size=b)
    xs = rng.integers(0, w - crop + 1, size=b)
    do_flip = rng.random(b) < 0.5 if flip else np.zeros(b, bool)
    return ys, xs, do_flip


def random_crop_flip(images: np.ndarray, crop: int, rng: np.random.Generator,
                     flip: bool = True, impl: str = "auto") -> np.ndarray:
    """images (B, H, W, C) -> (B, crop, crop, C).

    Two interchangeable kernels, bit-identical for a given ``rng`` state
    (all random draws happen here, before dispatch; parity-tested):

    ``loop``    per-image numpy block copies — each iteration is one
                C-level strided memcpy.
    ``gather``  one fancy-indexing gather for the whole batch (O(1)
                Python, the "vectorized" formulation).

    ``auto`` -> loop.  NOTE: vectorizing this was tried and REFUTED on CPU
    hosts: the loop's per-image cost is a block copy near the memory
    floor, while every gather formulation (multi-axis fancy indexing, flat
    int32 ``take``, two-stage row/col) pays elementwise index arithmetic —
    measured 2-4x SLOWER at every shape this repo trains (B=256 235->227:
    142ms vs 267-594ms; B=64 72->64: 0.8ms vs 3.1ms).  The interpreter
    overhead the loop was suspected of is ~microseconds/image.  Kept
    selectable for backends where gathers win; numbers in
    benchmarks/loading_overlap.py (``loading/crop_*`` rows).
    """
    b, h, w, c = images.shape
    ys, xs, do_flip = draw_crops(rng, b, h, w, crop, flip)
    if impl == "auto":
        impl = "loop"
    with obs.span("repro.data.crop_flip"):
        if impl == "loop":
            out = np.empty((b, crop, crop, c), images.dtype)
            for i in range(b):
                patch = images[i, ys[i]:ys[i] + crop, xs[i]:xs[i] + crop]
                out[i] = patch[:, ::-1] if do_flip[i] else patch
            return out
        if impl == "gather":
            rows = ys[:, None] + np.arange(crop)[None, :]       # (B, crop)
            cols = xs[:, None] + np.arange(crop)[None, :]       # (B, crop)
            cols = np.where(do_flip[:, None], cols[:, ::-1], cols)  # rev W
            return images[np.arange(b)[:, None, None],
                          rows[:, :, None], cols[:, None, :]]
    raise ValueError(f"unknown crop impl {impl!r} (loop|gather|auto)")


def subtract_mean(images: np.ndarray, mean_image: np.ndarray) -> np.ndarray:
    with obs.span("repro.data.mean_subtract"):
        return images.astype(np.float32) - mean_image.astype(np.float32)


def put_mean(mean_image: np.ndarray) -> jax.Array:
    """The (H, W, C) mean image on the default device as float32, in the
    (H, W*C) shape ``preprocess_on_device`` subtracts."""
    h, w, c = mean_image.shape
    return jax.device_put(
        np.asarray(mean_image, np.float32).reshape(h, w * c))


@functools.partial(jax.jit, static_argnames=("crop", "channels", "lead"))
def _crop_flip_subtract(raw, mean, draws, crop: int, channels: int,
                        lead: tuple):
    """raw (N, H, W*C), mean (H, W*C) float32, draws (3, N) int32 (crop
    rows, crop columns, mirror flags) -> (*lead, crop, crop, C) float32,
    N = prod(lead).

    Cast and subtract, then one dynamic slice per image and one select
    for the mirror: the same elements as the host path's subtraction,
    crop and mirror, so the same bits.  In the (H, W*C) view a window is
    one rectangle, and the wide minor dimension keeps the device's tiles
    full where a minor dimension of C would pad."""
    ys, xs, flips = draws
    x = raw.astype(jnp.float32) - mean
    win = jax.vmap(lambda img, y, x0: lax.dynamic_slice(
        img, (y, x0 * channels), (crop, crop * channels)))(x, ys, xs)
    win = win.reshape(-1, crop, crop, channels)
    out = jnp.where(flips[:, None, None, None] != 0, win[:, :, ::-1], win)
    return out.reshape(lead + out.shape[1:])


def preprocess_on_device(images: np.ndarray, mean: jax.Array, crop: int,
                         rng: np.random.Generator) -> jax.Array:
    """``random_crop_flip(subtract_mean(images, mean_image), crop, rng)``,
    bit for bit, computed on the default device: the draws on the host,
    the unprocessed batch put as an (N, H, W*C) view, then one program.
    ``images`` is (..., H, W, C): any leading dimensions (a replica split
    made on the host, say) come back as they went in, and the draws run
    over the images in their flat order.  ``mean`` is
    ``put_mean(mean_image)``."""
    *lead, h, w, c = images.shape
    n = int(np.prod(lead))
    if mean.shape != (h, w * c):
        raise ValueError(f"mean image {mean.shape} does not match images "
                         f"{images.shape} (want {(h, w * c)})")
    with obs.span("repro.data.crop_flip"):
        draws = np.stack(draw_crops(rng, n, h, w, crop)).astype(np.int32)
    with obs.span("repro.data.raw_put"):
        raw = jax.device_put(np.reshape(images, (n, h, w * c)))
    with obs.span("repro.data.mean_subtract"):
        return _crop_flip_subtract(raw, mean, draws, crop=crop, channels=c,
                                   lead=tuple(lead))


def make_image_preprocess(mean_image: np.ndarray, crop: int, seed: int = 0):
    """Returns a pytree-batch transform for the loaders: ``images`` becomes
    the device-resident float32 crop (``preprocess_on_device``), the other
    leaves pass through.  The mean image goes to the device once, here."""
    rng = np.random.default_rng(seed)
    mean = put_mean(mean_image)

    def f(batch):
        return {**batch, "images": preprocess_on_device(
            batch["images"], mean, crop, rng)}

    return f
