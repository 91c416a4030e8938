"""Image preprocessing — the paper's loader-side transforms (footnote 2):
subtract the mean image, random crop, random horizontal flip.

Pure numpy, run on the host inside the loader thread (mirroring the paper's
separate loading process).  Deterministic given the seed.  Each transform
runs inside an ``obs.span``: ``repro.data.mean_subtract`` and
``repro.data.crop_flip``.
"""
from __future__ import annotations

import numpy as np

from repro import obs


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
    assert h >= crop and w >= crop, (h, w, crop)
    ys = rng.integers(0, h - crop + 1, size=b)
    xs = rng.integers(0, w - crop + 1, size=b)
    do_flip = rng.random(b) < 0.5 if flip else np.zeros(b, bool)
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


def make_image_preprocess(mean_image: np.ndarray, crop: int, seed: int = 0):
    """Returns a pytree-batch transform for PrefetchLoader."""
    rng = np.random.default_rng(seed)

    def f(batch):
        imgs = subtract_mean(batch["images"], mean_image)
        return {**batch, "images": random_crop_flip(imgs, crop, rng)}

    return f
