"""Shared benchmark utilities: timing + CSV emission + trajectory record.

Benchmarks print ``name,us_per_call,derived`` rows (harness contract) and
run on host devices.  Multi-device benchmarks spawn a subprocess with
XLA_FLAGS set, keeping the main process at 1 device.

Every emitted row is also recorded in-process; ``write_bench_json``
persists the run as ``BENCH_<date>.json`` so future PRs have a baseline
trajectory to regress against (set ``REPRO_BENCH_OUT`` to override the
path).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import jax

REPO = os.path.join(os.path.dirname(__file__), "..")

_ROWS: list = []


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-time per call in microseconds (blocks on device)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def emit(name: str, us: float, derived: str = "", **meta):
    """Emit one CSV row; ``meta`` kwargs (arch=, slots=, backend=,
    groups=, model_parallel=, ...) are persisted on the JSON row so
    trajectories stay comparable across PRs even when row names drift —
    grouped-conv rows carry ``groups``/``model_parallel`` so a faithful
    AlexNet row never gets regressed against a legacy ungrouped one."""
    print(f"{name},{us:.1f},{derived}", flush=True)
    row = {"name": name, "us_per_call": round(us, 1), "derived": derived}
    if meta:
        row["meta"] = meta
    _ROWS.append(row)


def recorded_rows() -> list:
    return list(_ROWS)


def write_bench_json(path: str = None, extra: dict = None,
                     partial: bool = False) -> str:
    """Persist this run's rows as a BENCH_<date>.json trajectory file.

    ``partial`` runs (``--only`` / fast mode) land in the system tempdir
    — NOT in benchmarks/ — so a scratch run can never leave a stray
    ``.partial.json`` in the working tree next to the committed
    full-suite baseline (``*.partial.json`` is also gitignored as a
    belt-and-braces guard for REPRO_BENCH_OUT overrides).
    """
    date = time.strftime("%Y-%m-%d")
    if path is None:
        path = os.environ.get("REPRO_BENCH_OUT")
    if path is None:
        path = (os.path.join(tempfile.gettempdir(),
                             f"BENCH_{date}.partial.json") if partial
                else os.path.normpath(
                    os.path.join(REPO, "benchmarks", f"BENCH_{date}.json")))
    payload = {"date": date, "jax": jax.__version__,
               "backend": jax.default_backend(),
               "device_count": jax.device_count(), "rows": _ROWS}
    payload.update(extra or {})
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def run_subprocess_bench(code: str, devices: int, timeout=560) -> str:
    from repro.launch.host import forbid_jax_children
    forbid_jax_children("run_subprocess_bench")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{r.stderr[-2000:]}")
    return r.stdout
