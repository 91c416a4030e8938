"""Metrics JSONL: percentiles, torn-tail tolerance on resume, truncation."""
import json

import pytest

from repro.train_loop import MetricsWriter, percentile, read_jsonl


def test_percentile_nearest_rank():
    vals = sorted([5.0, 1.0, 3.0, 2.0, 4.0])
    assert percentile(vals, 50) == 3.0
    assert percentile(vals, 0) == 1.0
    assert percentile(vals, 100) == 5.0
    # even n: nearest-rank p50 of [1,2,3,4] is the 2nd value, not the 3rd
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert percentile([1.0, 2.0], 50) == 1.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 90) == 4.0
    assert percentile([], 50) != percentile([], 50)      # nan


def test_resume_truncates_tail_and_tolerates_torn_line(tmp_path):
    """A SIGKILL mid-write leaves a torn final line; resuming must drop it
    (it's part of the un-checkpointed tail) instead of crashing."""
    path = tmp_path / "m.jsonl"
    w = MetricsWriter(str(path), images_per_step=8)
    for s in (1, 2, 3, 4):
        w.train(s, loss=float(s), lr=0.1, step_time_s=0.01)
    w.close()
    with open(path, "a") as f:
        f.write('{"kind": "train", "step": 5, "lo')      # torn write
    # resume from the step-3 checkpoint
    w2 = MetricsWriter(str(path), images_per_step=8, resume_step=3)
    w2.train(4, loss=40.0, lr=0.1, step_time_s=0.01)
    w2.close()
    recs = read_jsonl(str(path), "train")
    assert [r["step"] for r in recs] == [1, 2, 3, 4]
    assert recs[-1]["loss"] == 40.0                      # replayed, not stale


def test_read_jsonl_strict_raises_on_garbage(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "train", "step": 1}\n{oops\n')
    with pytest.raises(ValueError):
        read_jsonl(str(p))
    assert [r["step"] for r in read_jsonl(str(p), tolerant=True)] == [1]


def test_stage_wait_ms_logged_and_summarized(tmp_path):
    """Per-step loader stall lands in the train records and the summary
    aggregates it (mean + p90) over TIMED steps only."""
    p = tmp_path / "m.jsonl"
    w = MetricsWriter(str(p), images_per_step=4)
    w.train(1, 1.0, 0.1, 5.0, timed=False, stage_wait_ms=900.0)  # compile
    w.train(2, 1.0, 0.1, 0.01, stage_wait_ms=2.0)
    w.train(3, 1.0, 0.1, 0.01, stage_wait_ms=4.0)
    w.train(4, 1.0, 0.1, 0.01)                 # loader without the metric
    s = w.summary(4)
    w.close()
    recs = read_jsonl(str(p), "train")
    assert recs[0]["stage_wait_ms"] == 900.0   # logged even on compile...
    assert [r.get("stage_wait_ms") for r in recs[1:]] == [2.0, 4.0, None]
    assert s["stage_wait_ms_mean"] == 3.0      # ...but excluded here
    assert s["stage_wait_ms_p90"] == 4.0


def test_summary_omits_stage_wait_when_never_reported(tmp_path):
    p = tmp_path / "m.jsonl"
    w = MetricsWriter(str(p), images_per_step=4)
    w.train(1, 1.0, 0.1, 0.01)
    s = w.summary(1)
    w.close()
    assert "stage_wait_ms_mean" not in s


def test_summary_excludes_compile_steps(tmp_path):
    p = tmp_path / "m.jsonl"
    w = MetricsWriter(str(p), images_per_step=4)
    w.train(1, 1.0, 0.1, 5.0, timed=False)               # compile step
    w.train(2, 1.0, 0.1, 0.01)
    w.train(3, 1.0, 0.1, 0.03)
    s = w.summary(3)
    w.close()
    assert s["timed_steps"] == 2
    assert s["step_ms_p99"] <= 30.001                    # 5s compile excluded
    recs = read_jsonl(str(p), "train")
    assert recs[0].get("compile") is True
    assert "images_per_sec" not in recs[0]

def test_summary_carries_loader_stage_means(tmp_path):
    p = tmp_path / "m.jsonl"
    w = MetricsWriter(str(p), images_per_step=4)
    w.train(1, 1.0, 0.1, 0.01)
    s = w.summary(1, loader={"fetch": 12.34567, "wait": 0.5})
    assert s["loader"] == {"fetch": 12.346, "wait": 0.5}
    assert read_jsonl(str(p), "summary")[0]["loader"] == s["loader"]
    assert "loader" not in MetricsWriter(None).summary(1, loader={})
    w.close()
