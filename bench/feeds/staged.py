"""Staged feed: the paper's parallel loading (§2.1, Fig. 1) through the
program's own loader.

A host pool of ``pool_batches`` uint8 batches of ``pool_size``² images is
drawn from the seed (``seeding.image_pool``) and cycled through
``repro.data.make_loader`` with the trainer's stream layout: the
program's ``make_image_preprocess`` (mean subtraction, random crop to
the config's ``crop``, random mirror) runs in the loader thread, then the
replica reshape and the device put, with ``prefetch`` batches staged
ahead.  The loader's ``wait_ms_total`` is the data-staging counter.

The first consumed batches are kept; after the window they are compared,
exactly, with the benchmark's own crop, flip and mean subtraction of the
same pool batches under the same random draws (``reference_batches``),
which the plain reference then trains on.

Traffic parameters: ``pool_batches``, ``pool_size``, ``staging``,
``prefetch``, ``keep`` (consumed batches kept for the check).
"""
from __future__ import annotations

import itertools

import jax
import numpy as np

import seeding


class Feed:
    def __init__(self, cfg: dict, traffic: dict, seed: int, system):
        from repro.core import reshape_for_replicas
        from repro.data import make_loader
        from repro.data.preprocess import make_image_preprocess
        self._cfg, self._seed = cfg, seed
        self._pix, self._lab, self._mean = seeding.image_pool(
            cfg, seed, traffic["pool_batches"], cfg["global_batch"],
            traffic["pool_size"])
        prep = make_image_preprocess(self._mean, cfg["crop"], seed=seed)
        source = ({"images": p, "labels": y}
                  for p, y in itertools.cycle(zip(self._pix, self._lab)))
        stream = map(lambda b: reshape_for_replicas(b, system.n_rep),
                     map(prep, source))
        self._loader = make_loader(
            stream, prefetch=traffic["prefetch"],
            staging=traffic["staging"],
            device_put=lambda b: jax.device_put(b, system.layout(b)))
        self._keep = traffic["keep"]
        self._kept = []
        self._i = 0

    def next(self):
        b = next(self._loader)
        if len(self._kept) < self._keep:
            self._kept.append(b)
        self._i += 1
        return b

    def fence(self, token):
        self._loader.fence(token)

    def counters(self) -> dict:
        return {"batches": self._i,
                "stage_wait_ms_total": self._loader.wait_ms_total}

    def close(self):
        self._loader.close()

    def reference_batches(self, n: int) -> list:
        """The benchmark's own preprocessing of the first ``n`` pool
        batches: the draws ``make_image_preprocess`` documents (per batch:
        crop rows, crop columns, then mirror flags, from one generator
        seeded with the run's seed), the crop and flip by slicing, and
        the mean subtracted in float32."""
        rng = np.random.default_rng(self._seed)
        crop = self._cfg["crop"]
        out = []
        for i in range(n):
            pix = self._pix[i % len(self._pix)]
            b, h, w, _ = pix.shape
            ys = rng.integers(0, h - crop + 1, size=b)
            xs = rng.integers(0, w - crop + 1, size=b)
            flip = rng.random(b) < 0.5
            img = np.empty((b, crop, crop, pix.shape[-1]), np.float32)
            for j in range(b):
                win = pix[j, ys[j]:ys[j] + crop, xs[j]:xs[j] + crop]
                mean = self._mean[ys[j]:ys[j] + crop, xs[j]:xs[j] + crop]
                cut = win.astype(np.float32) - mean
                img[j] = cut[:, ::-1] if flip[j] else cut
            out.append({"images": img,
                        "labels": self._lab[i % len(self._lab)]})
        return out

    def consumed_gap(self, ref_batches) -> dict:
        """Largest absolute difference between each kept consumed batch
        and the benchmark's own preprocessing of it (exact: limit 0)."""
        gap = 0.0
        for got, ref in zip(self._kept, ref_batches):
            for key in ("images", "labels"):
                g = np.asarray(got[key]).reshape(ref[key].shape)
                gap = max(gap, float(np.max(np.abs(
                    g.astype(np.float64) - ref[key].astype(np.float64)))))
        self._kept = []
        return {"batch_gap": gap}
