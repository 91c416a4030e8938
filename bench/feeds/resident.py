"""Device-resident feed: ``n_batches`` distinct preprocessed image
batches and ``n_label_sets`` label vectors made on the device from the
seed (``seeding.resident_batches``), laid out for the step once, and
cycled: step i trains images ``i % n_batches`` on labels
``i % n_label_sets``.  No host work per step: the loader is bypassed.

Traffic parameters (``workloads/<cell>.json`` ``traffic``):
  ``n_batches``     distinct image batches to cycle;
  ``n_label_sets``  label vectors to cycle, prime to ``n_batches``, so
                    the net cannot memorise a few pairs: with 4 batches
                    and their own labels it does, and its loss then
                    diverges to NaN within a few hundred steps on some
                    seeds.
"""
from __future__ import annotations

import jax

import seeding


class Feed:
    def __init__(self, cfg: dict, traffic: dict, seed: int, system):
        self._cfg, self._seed = cfg, seed
        self._n = traffic["n_batches"]
        self._m = traffic["n_label_sets"]
        made = seeding.resident_batches(cfg, seed, self._n, self._m,
                                        cfg["global_batch"], system.n_rep)
        self._images, self._labels = jax.device_put(made,
                                                    system.layout(made))
        del made
        jax.block_until_ready((self._images, self._labels))
        self._i = 0

    def next(self):
        i = self._i
        self._i += 1
        return {"images": self._images[i % self._n],
                "labels": self._labels[i % self._m]}

    def fence(self, token):
        """Nothing to fence: the batches are never rewritten."""

    def counters(self) -> dict:
        return {"batches": self._i}

    def close(self):
        self._images = self._labels = ()

    def reference_batches(self, n: int) -> list:
        """The global batches of the first ``n`` steps, drawn again from
        the seed on the default device."""
        images, labels = seeding.resident_batches(
            self._cfg, self._seed, self._n, self._m,
            self._cfg["global_batch"])
        return [{"images": images[i % self._n],
                 "labels": labels[i % self._m]} for i in range(n)]

    def consumed_gap(self, ref_batches) -> dict:
        """No loader transforms these batches: nothing to compare."""
        return {}
