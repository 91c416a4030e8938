"""Data pipeline: prefetch ordering/overlap (paper §2.1) + preprocessing
properties (hypothesis)."""
import time

import jax
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import obs
from repro.data import PrefetchLoader, synthetic
from repro.data.preprocess import (make_image_preprocess, preprocess_on_device,
                                   put_mean, random_crop_flip, subtract_mean)


def counter_source(n, delay=0.0):
    for i in range(n):
        if delay:
            time.sleep(delay)
        yield {"x": np.full((2, 3), i, np.float32)}


def test_order_preserved():
    ld = PrefetchLoader(counter_source(10), prefetch=2)
    vals = [int(b["x"][0, 0]) for b in ld]
    assert vals == list(range(10))


def test_stop_iteration():
    ld = PrefetchLoader(counter_source(3), prefetch=2)
    assert len(list(ld)) == 3


def test_sync_mode_matches():
    a = [int(b["x"][0, 0]) for b in PrefetchLoader(counter_source(5),
                                                   prefetch=0)]
    assert a == list(range(5))


def test_overlap_hides_load_latency():
    """With prefetch, consumer wait ~ max(load, compute); without, the sum.
    (The paper's Fig. 1 claim, measured.)"""
    load, compute, n = 0.03, 0.03, 8

    def consume(prefetch):
        ld = PrefetchLoader(counter_source(n, delay=load), prefetch=prefetch)
        t0 = time.time()
        for _ in ld:
            time.sleep(compute)       # "training"
        return time.time() - t0

    t_overlap = consume(2)
    t_serial = consume(0)
    # serial ~ n*(load+compute); overlapped ~ n*max(load,compute) + load
    assert t_overlap < t_serial * 0.82, (t_overlap, t_serial)


def test_close_joins_worker_thread():
    """close() must JOIN the worker — the seed leaked one thread per
    loader (a real problem for benchmark sweeps building many loaders),
    and a worker blocked on a full queue must still exit."""
    ld = PrefetchLoader(counter_source(100, delay=0.001), prefetch=2)
    next(ld)
    worker = ld._thread
    assert worker is not None and worker.is_alive()
    ld.close()
    assert ld._thread is None
    worker.join(timeout=2.0)
    assert not worker.is_alive()


def test_close_idempotent_and_after_exhaustion():
    ld = PrefetchLoader(counter_source(2), prefetch=2)
    assert len(list(ld)) == 2
    ld.close()
    ld.close()


def test_next_after_close_raises_instead_of_hanging():
    """The seed blocked forever in q.get() here: queue drained by close(),
    worker dead, nothing ever arriving.  Must raise instead."""
    ld = PrefetchLoader(counter_source(50), prefetch=2)
    next(ld)
    ld.close()
    with pytest.raises(RuntimeError, match="closed"):
        next(ld)
    with pytest.raises(RuntimeError, match="closed"):   # and stays raised
        next(ld)


def test_next_after_close_sync_mode():
    ld = PrefetchLoader(counter_source(5), prefetch=0)
    next(ld)
    ld.close()
    with pytest.raises(RuntimeError, match="closed"):
        next(ld)


def test_close_unblocks_waiting_consumer():
    """close() from another thread must wake a consumer already blocked in
    __next__ on an empty queue (slow producer)."""
    import threading
    ld = PrefetchLoader(counter_source(3, delay=30.0), prefetch=2)
    err = []

    def consume():
        try:
            next(ld)
        except RuntimeError as e:
            err.append(e)

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.3)               # let it block on the empty queue
    ld.close()
    t.join(timeout=5.0)
    assert not t.is_alive() and err, "consumer stayed blocked past close()"


def test_exhausted_loader_keeps_raising_stopiteration():
    """Second next() after the sentinel used to hang (sentinel consumed
    once, queue then empty forever)."""
    ld = PrefetchLoader(counter_source(1), prefetch=2)
    next(ld)
    for _ in range(3):
        with pytest.raises(StopIteration):
            next(ld)
    ld.close()


def test_worker_exception_propagates():
    def bad():
        yield {"x": np.zeros(2)}
        raise ValueError("boom")

    ld = PrefetchLoader(bad(), prefetch=2)
    next(ld)
    with pytest.raises(ValueError, match="boom"):
        next(ld)
        next(ld)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_loader_spans_once_per_batch(prefetch):
    """Every stage of every batch lands in the obs registry once: the
    stream's fetch (preprocessing mapped into it, as the trainer and the
    benchmark map it), the draws, the raw batch's put and the program's
    dispatch inside it, the put and the trainer's wait; ``wait_ms_total``
    is the sum of the waits."""
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (5, 4, 12, 12, 3), dtype=np.uint8)
    prep = make_image_preprocess(np.full((12, 12, 3), 128.0), crop=8)
    stream = map(prep, ({"images": im} for im in images))
    before = obs.snapshot()
    ld = PrefetchLoader(stream, prefetch=prefetch)
    assert [b["images"].shape for b in ld] == [(4, 8, 8, 3)] * 5
    ld.close()
    after = obs.snapshot()

    def delta(name, key="n"):
        k = f"repro.data.{name}.{key}"
        return after.get(k, 0) - before.get(k, 0)

    for stage in ("fetch", "mean_subtract", "crop_flip", "raw_put",
                  "device_put", "wait"):
        assert delta(stage) == 5, stage
    assert delta("queue_put") == (5 if prefetch else 0)
    assert ld.wait_ms_total == pytest.approx(delta("wait", "ms"), rel=1e-9)


class _ForcedMirror:
    """A generator whose mirror draws are forced (``u`` 0.0: every image
    mirrored, 1.0: none); crop draws come from ``default_rng(seed)``."""

    def __init__(self, seed, u):
        self._g, self._u = np.random.default_rng(seed), u

    def integers(self, *args, **kw):
        return self._g.integers(*args, **kw)

    def random(self, n):
        return np.full(n, self._u)


def _staged_band(b, size, seed):
    """uint8 pixels in the staged cell's band (126..129) and their float32
    mean image, near 127.5."""
    pix = np.random.default_rng(seed).integers(
        126, 130, (b, size, size, 3), dtype=np.uint8)
    return pix, pix.mean(axis=0, dtype=np.float64).astype(np.float32)


def _trainer_blobs(b, crop, seed):
    """The trainer's input: float32 blob images with an 8-pixel margin
    round the crop, and their mean image (``launch/train.py``)."""
    size = crop + 8
    mean = synthetic.mean_image(synthetic.blob_images(4, b, size,
                                                      seed=seed + 1), 2)
    return next(synthetic.blob_images(4, b, size, seed=seed))["images"], mean


@pytest.mark.parametrize("mirror", ["drawn", "on", "off"])
@pytest.mark.parametrize("images,crop", [
    ("staged_band", 24), ("trainer_blobs", 24), ("staged_band", 32)],
    ids=["uint8-staged-band", "float32-trainer-margin", "crop-equals-size"])
def test_device_preprocess_bit_identical_to_host(images, crop, mirror):
    """The device program's batch equals the host reference
    ``random_crop_flip(subtract_mean(x, mean), crop, rng)`` bit for bit,
    from the same draws, mirroring drawn or forced either way."""
    x, mean = (_staged_band(6, 32, 3) if images == "staged_band"
               else _trainer_blobs(6, crop, 3))

    def rng():
        return (np.random.default_rng(11) if mirror == "drawn"
                else _ForcedMirror(11, 0.0 if mirror == "on" else 1.0))

    got = preprocess_on_device(x, put_mean(mean), crop, rng())
    want = random_crop_flip(subtract_mean(x, mean), crop, rng())
    assert isinstance(got, jax.Array) and got.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(got), want)
    if mirror != "drawn" and crop < x.shape[1]:
        # the forced mirror shows: unmirrored, the first pixel column of
        # the window is the left edge
        keep = random_crop_flip(subtract_mean(x, mean), crop,
                                _ForcedMirror(11, 1.0))
        assert np.array_equal(want, keep) == (mirror == "off")


def test_device_preprocess_leaves_the_generator_as_the_host_path():
    """After a batch the generator's state is the host path's, so resume's
    fast-forward replays the same crops; batch after batch the loader's
    transform gives the host reference's numbers."""
    x, mean = _staged_band(4, 32, 5)
    r_dev, r_host = np.random.default_rng(9), np.random.default_rng(9)
    preprocess_on_device(x, put_mean(mean), 24, r_dev)
    random_crop_flip(subtract_mean(x, mean), 24, r_host)
    assert r_dev.bit_generator.state == r_host.bit_generator.state
    prep, ref = make_image_preprocess(mean, 24, seed=9), \
        np.random.default_rng(9)
    for i in range(3):
        out = prep({"images": x[::-1] if i % 2 else x, "labels": i})
        np.testing.assert_array_equal(
            np.asarray(out["images"]), random_crop_flip(subtract_mean(
                x[::-1] if i % 2 else x, mean), 24, ref))
        assert out["labels"] == i


def test_device_preprocess_keeps_leading_replica_dims():
    """A batch split into replicas on the host, (R, B/R, H, W, C), comes
    back as (R, B/R, crop, crop, C): the flat batch's numbers, from the
    flat batch's draws."""
    x, mean = _staged_band(6, 32, 4)
    r_split, r_flat = np.random.default_rng(2), np.random.default_rng(2)
    got = preprocess_on_device(x.reshape((2, 3) + x.shape[1:]),
                               put_mean(mean), 24, r_split)
    want = random_crop_flip(subtract_mean(x, mean), 24, r_flat)
    assert got.shape == (2, 3, 24, 24, 3)
    np.testing.assert_array_equal(np.asarray(got).reshape(want.shape), want)
    assert r_split.bit_generator.state == r_flat.bit_generator.state


def test_device_preprocess_rejects_a_mismatched_mean():
    x, mean = _staged_band(2, 32, 0)
    with pytest.raises(ValueError, match="mean image"):
        preprocess_on_device(x, put_mean(mean[:16]), 24,
                             np.random.default_rng(0))
    with pytest.raises(ValueError, match="crop 40"):
        preprocess_on_device(x, put_mean(mean), 40, np.random.default_rng(0))


@settings(max_examples=20, deadline=None)
@given(h=st.integers(10, 40), crop=st.integers(4, 10),
       seed=st.integers(0, 1000))
def test_crop_within_bounds_and_shape(h, crop, seed):
    rng = np.random.default_rng(seed)
    imgs = rng.normal(size=(3, h, h, 2)).astype(np.float32)
    out = random_crop_flip(imgs, crop, np.random.default_rng(seed))
    assert out.shape == (3, crop, crop, 2)
    assert np.isfinite(out).all()


def test_crop_flip_impl_parity():
    """loop and gather kernels must be bit-identical (same RNG draws, same
    output) — the benchmark in loading_overlap.py compares their speed."""
    rng = np.random.default_rng(7)
    imgs = rng.normal(size=(16, 40, 40, 3)).astype(np.float32)
    for flip in (True, False):
        a = random_crop_flip(imgs, 32, np.random.default_rng(3), flip=flip,
                             impl="loop")
        b = random_crop_flip(imgs, 32, np.random.default_rng(3), flip=flip,
                             impl="gather")
        np.testing.assert_array_equal(a, b)
        assert a.dtype == b.dtype == np.float32


def test_crop_flip_impls_consume_rng_identically():
    """A stream switching impls mid-run must keep the same draw sequence
    (all draws happen before dispatch)."""
    r1, r2 = np.random.default_rng(5), np.random.default_rng(5)
    imgs = np.random.default_rng(0).normal(size=(4, 12, 12, 1))
    random_crop_flip(imgs, 8, r1, impl="loop")
    random_crop_flip(imgs, 8, r2, impl="gather")
    assert r1.integers(0, 1 << 30) == r2.integers(0, 1 << 30)


def test_crop_flip_unknown_impl():
    with pytest.raises(ValueError, match="unknown crop impl"):
        random_crop_flip(np.zeros((1, 8, 8, 1)), 4,
                         np.random.default_rng(0), impl="simd")


def test_flip_is_involution():
    rng = np.random.default_rng(0)
    imgs = rng.normal(size=(1, 8, 8, 1)).astype(np.float32)
    flipped = imgs[:, :, ::-1]
    np.testing.assert_array_equal(flipped[:, :, ::-1], imgs)


def test_mean_subtraction_centers():
    it = synthetic.blob_images(4, 16, 24, seed=3)
    mean = synthetic.mean_image(synthetic.blob_images(4, 16, 24, seed=3), 8)
    batch = next(it)
    out = subtract_mean(batch["images"], mean)
    assert abs(out.mean()) < abs(batch["images"].mean()) + 0.1


def test_preprocess_deterministic_given_seed():
    imgs = np.random.default_rng(1).normal(size=(4, 32, 32, 3)).astype(
        np.float32)
    mean = np.zeros((32, 32, 3), np.float32)
    f1 = make_image_preprocess(mean, 24, seed=5)
    f2 = make_image_preprocess(mean, 24, seed=5)
    o1 = f1({"images": imgs})["images"]
    o2 = f2({"images": imgs})["images"]
    np.testing.assert_array_equal(o1, o2)


def test_markov_lm_learnable_structure():
    """Sharp transition table => next token is predictable (low entropy)."""
    it = synthetic.markov_lm(32, 8, 256, seed=0)
    b = next(it)
    toks = b["tokens"]
    assert toks.shape == (8, 256)
    assert toks.min() >= 0 and toks.max() < 32
    # bigram predictability: most common successor frequency well above 1/V
    from collections import Counter, defaultdict
    succ = defaultdict(Counter)
    for row in toks:
        for a, bb in zip(row[:-1], row[1:]):
            succ[int(a)][int(bb)] += 1
    top_frac = np.mean([c.most_common(1)[0][1] / sum(c.values())
                        for c in succ.values() if sum(c.values()) >= 5])
    assert top_frac > 0.12, top_frac  # >> 1/V = 0.03
