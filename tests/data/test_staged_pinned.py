"""StagedPinnedLoader: the fence-gated double-buffered staging path
(paper Fig. 1 taken literally — PR 7).

The invariant under test: a staged batch's host buffer is ALIASED by the
device array handed to the trainer (zero-copy on the CPU backend), so
the worker must not overwrite a slot until the step that consumed it has
fenced.  Every test here drives the loader exactly as the training loop
does — ``batch = next(loader); ...; loader.fence(token)``.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.data import make_loader
from repro.data.pipeline import PrefetchLoader, StagedPinnedLoader


def counter_source(n, delay=0.0):
    for i in range(n):
        if delay:
            time.sleep(delay)
        yield {"x": np.full((2, 3), i, np.float32)}


def test_order_and_values_preserved():
    ld = StagedPinnedLoader(counter_source(8))
    vals = []
    for b in ld:
        vals.append(int(np.asarray(b["x"])[0, 0]))
        ld.fence(b["x"])
    assert vals == list(range(8))
    ld.close()


def test_batch_content_stable_until_fence():
    """The handed-out batch must keep its values while the NEXT batches
    stream through the other slot — the whole point of the fence."""
    ld = StagedPinnedLoader(counter_source(6))
    first = next(ld)
    kept = np.asarray(first["x"]).copy()
    ld.fence(first["x"])
    second = next(ld)                    # other slot
    time.sleep(0.2)                      # worker wants to re-stage slot 0
    # slot 0's fence (first's token) is already released, so slot 0 may be
    # rewritten NOW — but second (slot 1) is un-fenced and must be intact
    np.testing.assert_array_equal(np.asarray(second["x"]),
                                  np.full((2, 3), 1, np.float32))
    np.testing.assert_array_equal(kept, np.full((2, 3), 0, np.float32))
    ld.fence(second["x"])
    ld.close()


def test_missing_fence_raises_instead_of_deadlocking():
    ld = StagedPinnedLoader(counter_source(10))
    next(ld)
    next(ld)                             # both slots handed out, no fences
    with pytest.raises(RuntimeError, match="await fences"):
        next(ld)
    ld.close()


def test_three_slots_allow_deeper_pipeline():
    ld = make_loader(counter_source(10), prefetch=3, staging="pinned")
    assert isinstance(ld, StagedPinnedLoader)
    a, b, c = next(ld), next(ld), next(ld)   # three in flight is fine
    with pytest.raises(RuntimeError, match="await fences"):
        next(ld)
    for t in (a, b, c):
        ld.fence(t["x"])
    assert int(np.asarray(next(ld)["x"])[0, 0]) == 3
    ld.close()


def test_worker_blocks_on_unready_fence_token(monkeypatch):
    """The worker must wait on the fence token before re-staging: with a
    slow token the re-stage of that slot cannot complete early."""
    import repro.data.pipeline as pl
    gate = threading.Event()
    orig = jax.block_until_ready

    def slow_ready(tok):
        if isinstance(tok, str) and tok == "slow":
            gate.wait(timeout=5.0)
            return tok
        return orig(tok)

    monkeypatch.setattr(pl.jax, "block_until_ready", slow_ready)
    before = obs.snapshot()
    ld = StagedPinnedLoader(counter_source(8))
    first = next(ld)
    ld.fence("slow")                     # slot 0 gated on the slow token
    next(ld)                             # slot 1 was pre-staged
    time.sleep(0.3)
    # batch 2 targets slot 0, whose fence is still blocked: queue stays
    # empty and first's buffer is untouched
    assert ld._q.empty()
    np.testing.assert_array_equal(np.asarray(first["x"]),
                                  np.full((2, 3), 0, np.float32))
    gate.set()
    ld.fence(None)
    assert int(np.asarray(next(ld)["x"])[0, 0]) == 2
    key = "repro.data.fence_wait.ms"
    fence_wait_ms = obs.snapshot()[key] - before.get(key, 0.0)
    assert fence_wait_ms > 200, fence_wait_ms
    ld.close()


def test_buffers_reused_after_first_lap():
    """After one lap the host buffers are warm: same id each revisit
    (the no-page-faults property the staging exists for)."""
    ld = StagedPinnedLoader(counter_source(9))
    seen = {}
    for i, b in enumerate(ld):
        ld.fence(b["x"])
        time.sleep(0.05)                 # let the worker re-stage
        slot = i % 2
        buf_id = id(ld._host[slot]["x"])
        if slot in seen and i >= 2:
            assert buf_id == seen[slot], f"slot {slot} reallocated lap {i}"
        seen[slot] = buf_id
    ld.close()


def test_device_leaves_pass_through_and_numpy_leaves_are_staged():
    """A ``jax.Array`` leaf (a batch preprocessed on the device) comes out
    as the same device buffer, with no host round trip and no slot
    buffer; the numpy leaves beside it are still copied into the slot's
    preallocated buffers, reused lap after lap."""
    import jax.numpy as jnp
    images = [jnp.full((2, 3), i, jnp.float32) for i in range(6)]
    ld = StagedPinnedLoader({"images": im, "labels": np.full(2, i, np.int32)}
                            for i, im in enumerate(images))
    label_bufs = {}
    for i, b in enumerate(ld):
        assert b["images"].unsafe_buffer_pointer() == \
            images[i].unsafe_buffer_pointer()
        np.testing.assert_array_equal(np.asarray(b["labels"]), [i, i])
        slot = ld._host[i % 2]
        assert not isinstance(slot["images"], np.ndarray)
        assert isinstance(slot["labels"], np.ndarray)
        label_bufs.setdefault(i % 2, set()).add(id(slot["labels"]))
        ld.fence(b["images"])
        time.sleep(0.05)                 # let the worker re-stage
    assert all(len(ids) == 1 for ids in label_bufs.values()), label_bufs
    ld.close()


def test_ragged_final_batch_reallocates():
    def ragged():
        yield {"x": np.zeros((4, 3), np.float32)}
        yield {"x": np.zeros((4, 3), np.float32)}
        yield {"x": np.ones((2, 3), np.float32)}     # smaller tail

    ld = StagedPinnedLoader(ragged())
    shapes = []
    for b in ld:
        shapes.append(np.asarray(b["x"]).shape)
        ld.fence(b["x"])
    assert shapes == [(4, 3), (4, 3), (2, 3)]
    ld.close()


def test_stop_iteration_and_stays_exhausted():
    ld = StagedPinnedLoader(counter_source(3))
    for b in ld:
        ld.fence(b["x"])
    for _ in range(2):
        with pytest.raises(StopIteration):
            next(ld)
    ld.close()


def test_close_joins_worker_and_next_raises():
    ld = StagedPinnedLoader(counter_source(100, delay=0.001))
    b = next(ld)
    ld.fence(b["x"])
    worker = ld._thread
    ld.close()
    assert ld._thread is None
    worker.join(timeout=2.0)
    assert not worker.is_alive()
    with pytest.raises(RuntimeError, match="closed"):
        next(ld)


def test_close_unblocks_worker_waiting_on_fence():
    """close() with both slots un-fenced must not hang the join."""
    ld = StagedPinnedLoader(counter_source(10))
    next(ld)
    next(ld)
    time.sleep(0.1)                      # worker parks in _take_fence
    t0 = time.time()
    ld.close()
    assert time.time() - t0 < 2.0


def test_worker_exception_propagates():
    def bad():
        yield {"x": np.zeros((2, 3), np.float32)}
        raise ValueError("boom")

    ld = StagedPinnedLoader(bad())
    b = next(ld)
    ld.fence(b["x"])
    with pytest.raises(ValueError, match="boom"):
        next(ld)
        next(ld)
    ld.close()


def test_wait_metrics_accumulate():
    ld = StagedPinnedLoader(counter_source(4, delay=0.05))
    total = 0.0
    for b in ld:
        ld.fence(b["x"])
        assert ld.last_wait_ms >= 0.0
        total = ld.wait_ms_total
    assert total > 0.0
    ld.close()


def test_prefetch_loader_fence_is_noop():
    ld = PrefetchLoader(counter_source(3), prefetch=2)
    for b in ld:
        ld.fence(b["x"])                 # uniform API, no effect
    ld.close()


def test_make_loader_factory_dispatch():
    assert isinstance(make_loader(counter_source(1), staging="queue"),
                      PrefetchLoader)
    pinned = make_loader(counter_source(1), prefetch=0, staging="pinned")
    assert isinstance(pinned, StagedPinnedLoader)
    assert pinned._slots == 2            # floor: always a double buffer
    with pytest.raises(ValueError, match="staging"):
        make_loader(counter_source(1), staging="dma")
