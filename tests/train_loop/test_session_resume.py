"""Resumable sessions: an interrupted-and-resumed run must reproduce the
uninterrupted loss trace BIT-exactly, on both exchange engines, across
host-device counts (the ISSUE-3 acceptance matrix; CI job resume-smoke).

Subprocess-driven through the real CLI so the whole stack is exercised:
arg wiring, engine setup, sharding-aware restore, stream fast-forward,
metrics JSONL.  Device counts are forced per-subprocess so nothing leaks
into the main test process (the dry-run isolation rule)."""
import json

import pytest
from _subproc import run_isolated

# tiny but real: smoke AlexNet at 48px keeps per-config compile ~seconds
BASE = ["--arch", "alexnet", "--smoke", "--image-size", "48", "--batch", "8",
        "--log-every", "100"]


def run_cli(args, devices: int, timeout=560):
    return run_isolated(["-m", "repro.launch.train"] + args, devices,
                        timeout).stdout


def train_trace(path):
    """step -> (loss, lr) from a metrics JSONL, full float precision."""
    out = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r.get("kind") == "train":
                out[r["step"]] = (r["loss"], r["lr"])
    return out


@pytest.mark.parametrize("engine,devices", [
    ("reference", 1), ("reference", 2), ("reference", 4),
    ("mesh", 1), ("mesh", 2), ("mesh", 4),
])
def test_resume_reproduces_uninterrupted_trace(tmp_path, engine, devices):
    common = BASE + ["--engine", engine, "--ckpt-every", "3"]
    # uninterrupted: 6 steps straight through
    m_full = str(tmp_path / "full.jsonl")
    run_cli(common + ["--steps", "6", "--ckpt-dir", str(tmp_path / "ck_a"),
                      "--metrics-out", m_full], devices)
    # killed at step 4 (one step PAST the step-3 checkpoint: the resumed
    # run must discard that un-checkpointed tail and replay it)...
    m_part = str(tmp_path / "part.jsonl")
    run_cli(common + ["--steps", "4", "--ckpt-dir", str(tmp_path / "ck_b"),
                      "--metrics-out", m_part], devices)
    # ...then resumed from step 3 up to 6, appending to the same trace
    out = run_cli(common + ["--steps", "6",
                            "--ckpt-dir", str(tmp_path / "ck_b"),
                            "--metrics-out", m_part, "--resume"], devices)
    assert "steps 3 -> 6" in out
    full, part = train_trace(m_full), train_trace(m_part)
    assert set(full) == set(part) == {1, 2, 3, 4, 5, 6}
    for step in sorted(full):
        assert full[step] == part[step], (
            f"step {step}: uninterrupted {full[step]} != resumed "
            f"{part[step]} ({engine}, {devices} devices)")


def test_resume_with_eval_and_plateau_state(tmp_path):
    """The plateau controller's decision state rides in the manifest: a
    resume mid-patience must drop the LR at the same step the
    uninterrupted run does, and eval metrics must match bit-exactly."""
    common = BASE + ["--engine", "mesh", "--ckpt-every", "3",
                     "--schedule", "plateau", "--eval-every", "2",
                     "--plateau-patience", "1",
                     "--plateau-threshold", "0.5"]
    m_full = str(tmp_path / "full.jsonl")
    run_cli(common + ["--steps", "6", "--ckpt-dir", str(tmp_path / "a"),
                      "--metrics-out", m_full], devices=2)
    m_part = str(tmp_path / "part.jsonl")
    run_cli(common + ["--steps", "4", "--ckpt-dir", str(tmp_path / "b"),
                      "--metrics-out", m_part], devices=2)
    run_cli(common + ["--steps", "6", "--ckpt-dir", str(tmp_path / "b"),
                      "--metrics-out", m_part, "--resume"], devices=2)

    def recs(path, kind):
        with open(path) as f:
            return [r for line in f if (r := json.loads(line)).get("kind")
                    == kind]

    full_t, part_t = train_trace(m_full), train_trace(m_part)
    assert full_t == part_t
    fe = [(r["step"], r["loss"], r["top1_err"], r["lr_dropped"])
          for r in recs(m_full, "eval")]
    pe = [(r["step"], r["loss"], r["top1_err"], r["lr_dropped"])
          for r in recs(m_part, "eval")]
    assert fe == pe
    # the rigged threshold guarantees at least one LR drop in 3 evals
    assert any(r[3] for r in fe)
    lrs = sorted({lr for _, lr in full_t.values()}, reverse=True)
    assert len(lrs) >= 2 and abs(lrs[1] / lrs[0] - 0.1) < 1e-6


def test_resume_nothing_to_do(tmp_path):
    args = BASE + ["--steps", "3", "--ckpt-dir", str(tmp_path / "ck"),
                   "--ckpt-every", "3"]
    run_cli(args, devices=1)
    out = run_cli(args + ["--resume"], devices=1)
    assert "nothing to do" in out


def test_invalid_image_size_errors():
    r = run_isolated(
        ["-m", "repro.launch.train", "--arch", "alexnet", "--steps", "1",
         "--image-size", "48"],         # full net: pool window underflows
        devices=1, timeout=120, check=False)
    assert r.returncode != 0
    assert "invalid" in r.stderr and "48" in r.stderr


def test_plateau_without_eval_errors():
    r = run_isolated(
        ["-m", "repro.launch.train", "--arch", "alexnet", "--smoke",
         "--steps", "1", "--schedule", "plateau"],
        devices=1, timeout=120, check=False)
    assert r.returncode != 0 and "--eval-every" in r.stderr


def test_resume_warns_on_numerics_switch(tmp_path):
    """The NumericsPolicy describe() rides in run_meta: resuming under a
    different policy must print the loud drift warning (the continued
    trace will not be bit-exact), while a same-policy resume stays
    quiet."""
    common = BASE + ["--ckpt-every", "2", "--ckpt-dir", str(tmp_path / "ck")]
    run_cli(common + ["--steps", "2"], devices=1)
    quiet = run_cli(common + ["--steps", "4", "--resume"], devices=1)
    assert "WARNING: resuming under a different configuration" not in quiet
    out = run_cli(common + ["--steps", "6", "--resume",
                            "--kv-cache-dtype", "int8"], devices=1)
    assert "WARNING: resuming under a different configuration" in out
    assert "numerics" in out and "kv=int8" in out


def test_bf16_session_logs_loss_scale(tmp_path):
    """--numerics bf16 end to end through the session: metrics JSONL
    train records carry the live loss scale and skipped-step count, and
    the summary repeats the final values."""
    m = str(tmp_path / "m.jsonl")
    out = run_cli(BASE + ["--steps", "3", "--numerics", "bf16",
                          "--metrics-out", m], devices=1)
    assert "numerics=param=bfloat16,master_fp32,loss_scale=dynamic" in out
    recs = [json.loads(line) for line in open(m)]
    trains = [r for r in recs if r.get("kind") == "train"]
    assert trains and all(r["loss_scale"] == 2.0 ** 15 for r in trains)
    assert all(r["skipped_steps"] == 0 for r in trains)
    summ = [r for r in recs if r.get("kind") == "summary"]
    assert summ and summ[-1]["loss_scale"] == 2.0 ** 15
    assert summ[-1]["skipped_steps"] == 0


def test_summary_reports_loader_stages(tmp_path):
    """The summary line's ``loader`` field: mean ms per batch of each
    loader stage over the session, from the ``repro.data.*`` spans."""
    m = str(tmp_path / "m.jsonl")
    run_cli(BASE + ["--steps", "3", "--metrics-out", m], devices=1)
    summ = [json.loads(line) for line in open(m)][-1]
    assert summ["kind"] == "summary"
    loader = summ["loader"]
    assert {"fetch", "mean_subtract", "crop_flip", "raw_put", "device_put",
            "queue_put", "wait"} <= set(loader)
    assert all(v >= 0 for v in loader.values())
    # the preprocessing runs inside the stream's fetch
    assert loader["fetch"] >= loader["mean_subtract"]
