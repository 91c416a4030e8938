"""Data pipeline: prefetch ordering/overlap (paper §2.1) + preprocessing
properties (hypothesis)."""
import time

import numpy as np
import pytest
from _hyp import given, settings, st

from repro import obs
from repro.data import PrefetchLoader, synthetic
from repro.data.preprocess import (make_image_preprocess, random_crop_flip,
                                   subtract_mean)


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
    benchmark map it), the mean subtraction and crop inside it, the put
    and the trainer's wait; ``wait_ms_total`` is the sum of the waits."""
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

    for stage in ("fetch", "mean_subtract", "crop_flip", "device_put",
                  "wait"):
        assert delta(stage) == 5, stage
    assert delta("queue_put") == (5 if prefetch else 0)
    assert ld.wait_ms_total == pytest.approx(delta("wait", "ms"), rel=1e-9)


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
