"""Rehearsal of ``bench/run.py`` without a chip: it refuses to run, and
its loop, driven on the faithful net at test size with the Pallas
kernels in interpret mode, prints a last line with exactly the contract's
keys."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_smoke
from bench_smoke import BENCH

RUN = str(BENCH / "run.py")
CELL = "alexnet-faithful.b256-resident"


def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    r = _cli([RUN, "--workload", CELL, "--seed", str(2**33 + 1),
              "--seconds", "1", "--trace", "0"], cwd=BENCH.parent)
    assert r.returncode != 0, r.stderr
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_exits_nonzero_for_an_unknown_cell():
    r = _cli([RUN, "--workload", "no-such-cell", "--seed", "1",
              "--seconds", "1"], cwd=BENCH.parent)
    assert r.returncode == 2 and r.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    r = _cli(["bench/run.py", "--workload", CELL, "--seed", "5",
              "--seconds", "1", "--trace", "0"], cwd=tmp_path,
             env_extra={"JAX_PLATFORMS": ""})
    assert r.returncode != 0 and r.stdout.strip() == ""


CONTRACT = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("name,trace", [
    (CELL, 0), (CELL, 1), ("alexnet-faithful.b256-staged", 0)])
def test_last_line_has_the_contract_keys(name, trace):
    import run
    cell = bench_smoke.smoke_cell(name)
    result, parts = run.run_cell(cell, 2**33 + 17, 0.5, bool(trace),
                                 require_tpu=False,
                                 trainer_argv=("--kernel-backend", "pallas"))
    line = json.loads(json.dumps(result))
    keys = CONTRACT + (["breakdown"] if trace else []) + ["check"]
    assert list(line) == keys
    assert parts["kernels"] == {"conv2d": "pallas", "lrn": "pallas"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert line["device"]["busy_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        allowed = {m["name"] for m in cell["per_layer"]}
        assert set(line["metrics"]) <= allowed and line["metrics"]
    else:
        assert set(line["metrics"]) == {m["name"]
                                        for m in cell["end_to_end"]}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["check"]) == set(cell["workload"]["limits"])
    for c in line["check"].values():
        assert set(c) == {"value", "limit"}
