"""Parallel data loading — the JAX adaptation of the paper's §2.1 / Fig. 1.

The paper runs a separate *loading process* that copies the next minibatch
disk -> host -> GPU while the training process computes, handing off
device-resident buffers "instantly".  The CPython-GIL/multi-process motivation
does not apply here (the loader thread's host work is the fetch, puts and
dispatches, which release the GIL while they copy or wait, and the
preprocessing arithmetic runs on the device), so the same overlap is
achieved with a background thread and a depth-2 queue — a double buffer:

    loader thread:   fetch -> preprocess -> device_put (async) ->- queue
    trainer thread:  queue ->- step(current)            (overlapped)

Where each stage runs: the fetch pulls the next host batch; the image
preprocessing (``preprocess.make_image_preprocess``) draws its crops and
mirrors on the host, puts the raw batch on the device and dispatches the
program that crops, mirrors and subtracts the mean there, so the batch it
hands on is already device-resident; ``device_put`` then stages the rest
(labels) and places the batch on the step's layout.  ``device_put``
returns immediately; the transfer overlaps the in-flight step exactly like
the paper's staged copy.  Set ``prefetch=0`` to get the serial baseline
(the paper's "Parallel loading: No" rows in Table 1).

``StagedPinnedLoader`` is the paper's Fig. 1 taken literally: instead of
allocating fresh host+device arrays every batch (page faults on first touch
— the dominant cost of a cold ``device_put``), the loader thread stages into
a *rotating pair of preallocated host buffers* that the backend maps
zero-copy.  Only host (numpy) leaves are staged: a leaf that is already a
``jax.Array`` (preprocessed on the device) passes through untouched.
Reusing a buffer that the in-flight step may still be reading
would corrupt the batch, so reuse is gated on a **fence**: after dispatching
the step that consumed a batch, the trainer hands the loader any output
token of that step (``loader.fence(loss)``); the worker blocks on the
*previous* step's token before overwriting that slot — off the critical
path, exactly the "wait on the previous step's tokens" handshake the
overlap timeline in docs/architecture.md draws.

Both loaders expose stall observability: ``last_wait_ms`` (time the
trainer spent blocked in ``next()`` for the most recent batch) and
``wait_ms_total`` — the session logs these per step as ``stage_wait_ms``.

Each stage runs inside an ``obs.span`` (the profiler's trace and the
``obs`` registry), numbered by ``batch``, the batch's sequence number
from the moment the loader pulls it from its source:

    repro.data.fetch        ``next(source)``: everything the stream does,
                            preprocessing mapped into it included (its
                            own spans: ``crop_flip``, ``raw_put``,
                            ``mean_subtract``, see ``preprocess``)
    repro.data.device_put   the host part of the put
    repro.data.queue_put    the loader waiting on a full queue (near zero
                            when the loader sets the pace)
    repro.data.fence_wait   the pinned loader waiting on a slot's fence
    repro.data.wait         the trainer blocked in ``next()``; its reading
                            is ``last_wait_ms``
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading
from typing import Callable, Iterator, Optional

import jax
import numpy as np

from repro import obs


class PrefetchLoader:
    """Wraps a host-batch iterator with background staging onto device(s).

    Args:
      source: iterator yielding pytrees of numpy arrays.
      prefetch: queue depth (2 = classic double buffer; 0 = synchronous).
      preprocess: transform run in the loader thread (the paper's
        mean-subtract / crop / flip: ``make_image_preprocess`` dispatches
        them to the device and returns a device-resident batch).
      device_put: function staging a host pytree onto device(s); defaults to
        ``jax.device_put`` (pass a sharded variant for multi-device).
    """

    def __init__(self, source: Iterator, prefetch: int = 2,
                 preprocess: Optional[Callable] = None,
                 device_put: Optional[Callable] = None):
        self._source = iter(source)
        self._prefetch = prefetch
        self._preprocess = preprocess or (lambda x: x)
        self._device_put = device_put or jax.device_put
        self._q: queue.Queue = queue.Queue(maxsize=max(prefetch, 1))
        self._done = False           # sentinel seen: stay exhausted
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.last_wait_ms = 0.0      # trainer stall for the latest batch
        self.wait_ms_total = 0.0
        self._consumed = 0           # sequence number of the next batch out
        if prefetch > 0:
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()

    def _worker(self):
        try:
            for n in itertools.count():
                try:
                    batch = _fetch(self._source, n)
                except StopIteration:
                    break
                if self._stop.is_set():
                    return
                host = self._preprocess(batch)
                with obs.span("repro.data.device_put", batch=n):
                    staged = self._device_put(host)
                with obs.span("repro.data.queue_put", batch=n):
                    if not self._put(staged):
                        return
            self._put(_SENTINEL)
        except Exception as e:                      # surface in consumer
            self._put(_ExcBox(e))

    def _put(self, item) -> bool:
        """Enqueue unless stopped; never blocks past close() (a plain
        ``put`` on a full queue would deadlock the close/join)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            # after close() the queue is drained and the worker dead — a
            # bare q.get() would block forever (the seed's hang)
            raise RuntimeError("PrefetchLoader is closed")
        n = self._consumed
        with obs.span("repro.data.wait", batch=n) as wait:
            if self._prefetch == 0:
                # serial: the whole stage is a stall
                host = self._preprocess(_fetch(self._source, n))
                with obs.span("repro.data.device_put", batch=n):
                    out = self._device_put(host)
            else:
                out = self._get()
        self.last_wait_ms = wait.ms
        self.wait_ms_total += wait.ms
        self._consumed += 1
        return out

    def _get(self):
        """The next staged batch off the queue."""
        if self._done:
            raise StopIteration      # sentinel already consumed once
        while True:
            # timed get so a close() racing a blocked consumer unblocks it
            try:
                item = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._stop.is_set():
                    raise RuntimeError("PrefetchLoader is closed") from None
                t = self._thread       # snapshot: close() may null the field
                if t is not None and not t.is_alive() and self._q.empty():
                    # worker died without a sentinel (its exception was
                    # already re-raised once) — don't spin forever
                    raise RuntimeError(
                        "PrefetchLoader worker exited") from None
        if item is _SENTINEL:
            self._done = True
            raise StopIteration
        if isinstance(item, _ExcBox):
            raise item.exc
        return item

    def fence(self, token):
        """No-op: queue handoff never reuses buffers, so there is nothing
        to fence.  Present so the training loop can treat both loaders
        uniformly (StagedPinnedLoader needs the fence for correctness)."""

    def close(self):
        """Stop and JOIN the worker — a loader per benchmark config would
        otherwise leak a thread each (the old close never joined)."""
        self._stop.set()
        # drain so the worker can observe the stop flag
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


_SENTINEL = object()
_CLOSED = object()


def _fetch(source: Iterator, n: int):
    """``next(source)`` as batch ``n``, under its span (StopIteration
    passes through, and records no span)."""
    with obs.span("repro.data.fetch", batch=n):
        return next(source)


class _ExcBox:
    def __init__(self, exc):
        self.exc = exc


class StagedPinnedLoader:
    """Double-buffered staging into preallocated pinned host buffers.

    The worker rotates over ``slots`` (2 = the classic double buffer)
    preallocated host buffers: ``np.copyto`` into the warm buffer (no page
    faults after the first lap), then ``device_put`` — which the CPU
    backend maps zero-copy, so the "transfer" is free but the device array
    ALIASES the host buffer.  Overwriting a slot is therefore gated on the
    fence of the step that last consumed it:

        batch = next(loader)        # pre-staged, returns without copying
        state, loss = step(state, batch)     # async dispatch
        loader.fence(loss)          # any output token of that step

    ``fence`` associates the token with the oldest un-fenced handout; the
    worker calls ``block_until_ready`` on it *in the loader thread* before
    re-staging that slot.  One fence per consumed batch is mandatory —
    with both slots awaiting fences the pipeline is intentionally stalled
    (that is the correctness condition) and ``next()`` raises rather than
    deadlock.  Stall metrics match ``PrefetchLoader``.
    """

    def __init__(self, source: Iterator,
                 preprocess: Optional[Callable] = None,
                 device_put: Optional[Callable] = None, slots: int = 2):
        assert slots >= 2, "need at least a double buffer"
        self._source = iter(source)
        self._preprocess = preprocess or (lambda x: x)
        self._device_put = device_put or jax.device_put
        self._slots = slots
        self._host = [None] * slots          # preallocated numpy buffers
        # per-slot fence tokens; pre-seeded None = slot starts free
        self._free = [queue.Queue(maxsize=1) for _ in range(slots)]
        for fq in self._free:
            fq.put(None)
        self._handout: collections.deque = collections.deque()
        self._q: queue.Queue = queue.Queue(maxsize=slots)
        self._done = False
        self._stop = threading.Event()
        self.last_wait_ms = 0.0
        self.wait_ms_total = 0.0
        self._consumed = 0
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    # ------------------------------------------------------ worker side --
    def _take_fence(self, s: int):
        """Previous consumer's token for slot ``s`` (None on first lap)."""
        while not self._stop.is_set():
            try:
                return self._free[s].get(timeout=0.05)
            except queue.Empty:
                continue
        return _CLOSED

    def _worker(self):
        try:
            s = 0
            for n in itertools.count():
                try:
                    batch = _fetch(self._source, n)
                except StopIteration:
                    break
                if self._stop.is_set():
                    return
                host = self._preprocess(batch)
                tok = self._take_fence(s)
                if tok is _CLOSED:
                    return
                if tok is not None:
                    # worker-side, off the critical path
                    with obs.span("repro.data.fence_wait", batch=n):
                        jax.block_until_ready(tok)  # step done => reads done
                if not self._stage(s, n, host):
                    return
                s = (s + 1) % self._slots
            self._put(_SENTINEL)
        except Exception as e:                  # surface in consumer
            self._put(_ExcBox(e))

    def _stage(self, s: int, n: int, host) -> bool:
        """Copy ``host``'s numpy leaves into slot ``s``'s buffers, put the
        batch, and enqueue it as batch ``n``; False once closed.  A
        ``jax.Array`` leaf (preprocessed on the device) is already staged:
        it passes through untouched, with no host round trip."""
        with obs.span("repro.data.device_put", batch=n):
            dst = self._host[s]
            if dst is None or not _fits(dst, host):
                # first lap (or a ragged final batch): allocate fresh
                dst = jax.tree.map(
                    lambda x: _ON_DEVICE if isinstance(x, jax.Array)
                    else np.empty(x.shape, x.dtype), host)
                self._host[s] = dst
            staged = self._device_put(jax.tree.map(_into_slot, dst, host))
        with obs.span("repro.data.queue_put", batch=n):
            return self._put((s, staged))

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # ---------------------------------------------------- consumer side --
    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise RuntimeError("StagedPinnedLoader is closed")
        if self._done:
            raise StopIteration
        with obs.span("repro.data.wait", batch=self._consumed) as wait:
            slot, staged = self._get()
        self._handout.append(slot)
        self.last_wait_ms = wait.ms
        self.wait_ms_total += wait.ms
        self._consumed += 1
        return staged

    def _get(self):
        """The next ``(slot, staged batch)`` off the queue."""
        while True:
            try:
                item = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._stop.is_set():
                    raise RuntimeError(
                        "StagedPinnedLoader is closed") from None
                if len(self._handout) >= self._slots:
                    raise RuntimeError(
                        "all staging slots await fences — call "
                        "loader.fence(<step output>) after each consumed "
                        "batch") from None
                if not self._thread.is_alive() and self._q.empty():
                    raise RuntimeError(
                        "StagedPinnedLoader worker exited") from None
        if item is _SENTINEL:
            self._done = True
            raise StopIteration
        if isinstance(item, _ExcBox):
            raise item.exc
        return item

    def fence(self, token):
        """Mark the oldest un-fenced batch's slot reusable once ``token``
        (any device output of the step that consumed it) is ready."""
        if self._handout:
            self._free[self._handout.popleft()].put(token)

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


_ON_DEVICE = object()    # a slot's place for a leaf that needs no buffer


def _fits(dst, host) -> bool:
    """Whether slot buffers ``dst`` can take ``host`` as they are."""
    if jax.tree.structure(dst) != jax.tree.structure(host):
        return False
    return all(d is _ON_DEVICE if isinstance(x, jax.Array)
               else isinstance(d, np.ndarray) and d.shape == x.shape
               and d.dtype == x.dtype
               for d, x in zip(jax.tree.leaves(dst), jax.tree.leaves(host)))


def _into_slot(d, x):
    """A device-resident leaf as it is; a host leaf copied into its
    slot's buffer ``d``."""
    if isinstance(x, jax.Array):
        return x
    np.copyto(d, x)
    return d


def make_loader(source: Iterator, *, prefetch: int = 2,
                staging: str = "queue",
                preprocess: Optional[Callable] = None,
                device_put: Optional[Callable] = None):
    """Loader factory the launchers use.  ``staging="queue"`` is the
    depth-``prefetch`` handoff queue (``prefetch=0`` = serial baseline);
    ``staging="pinned"`` is the double-buffered pinned path (needs the
    ``fence`` handshake — see StagedPinnedLoader)."""
    if staging == "pinned":
        return StagedPinnedLoader(source, preprocess=preprocess,
                                  device_put=device_put,
                                  slots=max(prefetch, 2))
    if staging != "queue":
        raise ValueError(f"staging must be 'queue' or 'pinned', "
                         f"got {staging!r}")
    return PrefetchLoader(source, prefetch=prefetch, preprocess=preprocess,
                          device_put=device_put)
