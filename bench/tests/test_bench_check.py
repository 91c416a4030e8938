"""The check that decides ``correct``, at test size on the CPU: sound runs
pass the cells' limits; the low-precision control and every fault the
cells can have, planted underneath the timed path, fail them."""
import json
import os
import subprocess
import sys

import pytest

import bench_smoke
from bench_smoke import BENCH, TESTS

RESIDENT = "alexnet-faithful.b256-resident"
SEED = 2**32 + 99


@pytest.fixture(scope="module")
def cell():
    return bench_smoke.smoke_cell(RESIDENT)


def _numbers(cell, seed, control=None, fault=None):
    import calibrate
    import seeding
    import spec
    import sut
    system = sut.build(cell["config"], cell["entry"]["chips"],
                       seeding.init_params(cell["config"], seed),
                       fault=fault)
    return calibrate.readings(system, cell, seed, spec, control)


def test_program_passes_and_int8_control_fails(cell):
    import compare
    limits = cell["workload"]["limits"]
    ok, check = compare.verdict(_numbers(cell, SEED), limits)
    assert ok, check
    ok, check = compare.verdict(_numbers(cell, SEED, control="int8"),
                                limits)
    assert not ok, check


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "loss_altered"])
def test_fault_under_the_timed_path_reads_incorrect(cell, fault):
    import run
    result, _ = run.run_cell(cell, SEED + 1, 0.3, False, require_tpu=False,
                             fault=fault)
    assert result["correct"] is False, result["check"]


def test_staged_feed_catches_a_wrong_crop():
    """The staged cell's exact batch comparison: a crop one pixel off
    (the benchmark's own preprocessing shifted) reads a nonzero gap."""
    import run
    import spec
    import sut
    import seeding
    cell = bench_smoke.smoke_cell("alexnet-faithful.b256-staged")
    cfg = cell["config"]
    system = sut.build(cfg, 1, seeding.init_params(cfg, SEED))
    feed = spec.load_module("feeds", "staged").Feed(
        cfg, cell["workload"]["traffic"], SEED, system)
    try:
        for _ in range(run.WARM_STEPS):
            feed.next()
    finally:
        feed.close()
    ref = feed.reference_batches(run.WARM_STEPS)
    shifted = [dict(b, images=b["images"][:, 1:]) for b in ref]
    for b, s in zip(ref, shifted):
        b["images"][:, :-1] = s["images"]
    assert feed.consumed_gap(ref)["batch_gap"] > 0


def test_exchange_left_out_reads_incorrect_on_four_devices():
    """The four-chip cell on four virtual CPU devices: the mesh engine's
    run is correct, and with the exchange left out it is not."""
    code = f"""
import json, sys
sys.path.insert(0, {str(TESTS)!r})
import bench_smoke, run
cell = bench_smoke.smoke_cell("alexnet-faithful.dp4-b256-resident")
out = {{}}
for fault in (None, "no_exchange"):
    res, parts = run.run_cell(cell, {SEED + 2}, 0.3, False,
                              require_tpu=False, fault=fault)
    out[str(fault)] = res["correct"]
print(json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=BENCH,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == \
        {"None": True, "no_exchange": False}
