"""Program spans (``repro.obs``): the registry adds each span's time and
count exactly, from several threads at once, and records nothing for a
block that raised."""
import os
import sys
import threading
import time

import pytest

from repro import obs


def test_span_adds_time_and_count_from_threads():
    """More threads than cores, switching as often as the interpreter
    allows: a lost update would show in the count or the total."""
    name = "repro.test.threads"
    n_threads, per_thread = (os.cpu_count() or 1) + 4, 200
    before = obs.snapshot()
    readings = []

    def work():
        mine = []
        for i in range(per_thread):
            with obs.span(name, batch=i) as s:
                pass
            mine.append(s.ms)
        readings.extend(mine)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    after = obs.snapshot()
    assert after[f"{name}.n"] - before.get(f"{name}.n", 0) == \
        n_threads * per_thread
    assert after[f"{name}.ms"] - before.get(f"{name}.ms", 0.0) == \
        pytest.approx(sum(readings), rel=1e-9)


def test_span_reading_is_available_after_close():
    with obs.span("repro.test.sleep") as s:
        time.sleep(0.02)
    assert s.ms >= 20.0


def test_span_that_raises_records_nothing():
    name = "repro.test.raises"
    before = obs.snapshot()
    with pytest.raises(KeyError):
        with obs.span(name):
            raise KeyError("x")
    assert obs.snapshot().get(f"{name}.n", 0) == before.get(f"{name}.n", 0)


def test_mean_ms_between_snapshots():
    with obs.span("repro.test.mean.a"):
        pass
    before = obs.snapshot()
    spans = []
    for _ in range(3):
        with obs.span("repro.test.mean.a") as s:
            time.sleep(0.001)
        spans.append(s.ms)
    with obs.span("repro.other.b"):
        pass
    means = obs.mean_ms(before, obs.snapshot(), "repro.test.mean.")
    assert means == {"a": pytest.approx(sum(spans) / 3, rel=1e-12)}
