"""Program spans: named host intervals, in the profiler's trace and in a
process-wide registry.

    with obs.span("repro.data.fetch", batch=n) as s:
        ...
    s.ms                     # the span's own duration, once it closed

A span opens a ``jax.profiler.TraceAnnotation(name, **ids)``, so a
profiler trace shows it on the thread that ran it, on the clock the
device trace shares, with ``ids`` as its arguments.  It takes one
``time.perf_counter()`` reading on each side and, when its block exits
without an exception, adds the milliseconds and a count of one to the
registry under its name.  ``snapshot()`` returns the registry as a flat
dict of numbers (``"<name>.ms"``, ``"<name>.n"``); subtract two snapshots
for a window, or hand them to ``mean_ms``.

Always on: with no profiler running a span costs about a microsecond.
Every name starts with ``repro.``; PERF.md lists each span with what
reads it.
"""
from __future__ import annotations

import threading
import time

import jax

_lock = threading.Lock()
_totals: dict = {}          # name -> [ms, n]


class span:
    """Context manager; see the module docstring."""

    __slots__ = ("name", "ms", "_ann", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ms = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None:
            with _lock:
                total = _totals.setdefault(self.name, [0.0, 0])
                total[0] += self.ms
                total[1] += 1
        return False


def snapshot() -> dict:
    """Every span's total milliseconds and count since the process began."""
    with _lock:
        return {f"{name}.{key}": v for name, (ms, n) in _totals.items()
                for key, v in (("ms", ms), ("n", n))}


def mean_ms(before: dict, after: dict, prefix: str) -> dict:
    """Mean milliseconds per span between two snapshots, for each span
    whose name starts with ``prefix`` and that closed in between, keyed by
    the rest of its name."""
    out = {}
    for key, n in after.items():
        name = key[:-2]
        if key.endswith(".n") and name.startswith(prefix):
            dn = n - before.get(key, 0)
            if dn:
                out[name[len(prefix):]] = (
                    after[name + ".ms"] - before.get(name + ".ms", 0.0)) / dn
    return out
