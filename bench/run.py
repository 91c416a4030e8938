#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The run:

1. finds the cell's workload, configuration, feed and metric readers by
   name (``spec.py``); exits 2 when one is missing;
2. exits 3, printing no result, unless JAX sees a TPU with at least the
   cell's chips; the cell uses exactly its own number of them;
3. set-up: makes the weights from ``--seed`` on the device, builds,
   traces (with the kernels' autotune) and compiles the trainer's step
   (``sut.py``), makes the feed, and drives the step through its first
   three steps, reading it after steps 1 and 3 for the check;
4. measures for ``--seconds`` with two steps dispatched ahead: dispatch
   step i+2, then block on step i's loss and stamp its completion.  With
   ``--trace 1`` the window runs under the profiler and the per-layer
   metrics are read from the trace instead of the end-to-end ones;
5. reads the device's peak memory, frees the program's state, runs the
   plain reference over the first three steps' batches and compares
   (``compare.py``);
6. prints its set-up parts on stderr, then each compared number beside
   its limit, and as the last line of stdout one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
   ``breakdown`` (traced runs) and ``check``.

JAX's persistent compilation cache and the autotune winners live in
``bench/.cache/`` (fixed paths inside the checkout), so only a cell's
first run in a checkout compiles and measures tiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

# ruff: noqa: E402
import argparse
import contextlib
import gc
import json
import math
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
AUTOTUNE = CACHE / "autotune.json"      # the kernels' autotune winners
WARM_STEPS = 3
# steps dispatched ahead of the one the host blocks on.  With one, the
# chip idles whenever the shared host stalls for longer than a step
# (idle gaps of 50-80 ms under ``bench.block`` in one-chip traces), and
# runs read up to 10% slow; two ride out stalls of two steps.
IN_FLIGHT = 2


class NoChip(Exception):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def _paths():
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def _jax_setup():
    """On the chip: the compilation cache at a fixed path inside the
    checkout, every program cached; set before JAX compiles anything."""
    import jax
    if jax.devices()[0].platform != "tpu":
        return jax
    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def _span(name: str, on: bool):
    import jax
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


def _load_autotune():
    from repro.kernels import common
    try:
        common.load_cache_state(json.loads(AUTOTUNE.read_text()))
    except FileNotFoundError:
        pass


def _save_autotune():
    from repro.kernels import common
    state = common.cache_state()
    try:
        old = json.loads(AUTOTUNE.read_text())
    except FileNotFoundError:
        old = {}
    if any(k not in old for k in state):
        AUTOTUNE.parent.mkdir(parents=True, exist_ok=True)
        tmp = AUTOTUNE.with_suffix(".tmp")
        tmp.write_text(json.dumps({**old, **state}, indent=0))
        tmp.replace(AUTOTUNE)


def timed_window(system, feed, seconds: float, traced: bool) -> dict:
    """Measure for ``seconds``: keep ``IN_FLIGHT`` steps dispatched ahead
    of the one the host waits for — dispatch step i+IN_FLIGHT, then block
    on step i's loss and stamp its completion — until the deadline, then
    drain.  Returns the start, the stamps and the losses; the state stays
    with ``system``."""
    import collections
    state, stamps, losses = system.state, [], []
    pending = collections.deque()

    def complete():
        with _span("bench.block", traced):
            losses.append(float(pending.popleft()))
        stamps.append(time.perf_counter())

    t_start = time.perf_counter()
    deadline = t_start + seconds
    with _span("bench.window", traced):
        while time.perf_counter() < deadline:
            with _span("bench.next", traced):
                batch = feed.next()
            with _span("bench.dispatch", traced):
                state, loss = system.step(state, batch)
            feed.fence(loss)
            pending.append(loss)
            if len(pending) > IN_FLIGHT:
                complete()
        while pending:
            complete()
    system.state = state
    return {"t_start": t_start, "stamps": stamps, "losses": losses}


def per_step_ms(t_start: float, stamps: list, span_s: float = 0.25):
    """Time per step over consecutive spans of at least ``span_s`` of
    step completions (the host clock is good to about half a
    millisecond, so a time read from it spans 250 ms or more)."""
    times = [t_start] + list(stamps)
    out, i0 = [], 0
    for i in range(1, len(times)):
        if times[i] - times[i0] >= span_s:
            out.append((times[i] - times[i0]) / (i - i0) * 1e3)
            i0 = i
    return out


def _span_summary(win: dict) -> dict:
    """Per-step ms over >=250 ms spans of the window: where a run's
    host-clock noise sits (the median, the tail, the slowest spans)."""
    import statistics
    spans = per_step_ms(win["t_start"], win["stamps"])
    if not spans:
        return {}
    med = statistics.median(spans)
    return {"n": len(spans), "median": med, "max": max(spans),
            "over_5pct": sum(1 for s in spans if s > 1.05 * med)}


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def checked_steps(system, feed, p0, cfg: dict) -> dict:
    """Drive the step from the seed through its first ``WARM_STEPS``
    steps, through the window's own call and feed, and read it for the
    check: each step's loss, the first gradient's norms (after step 1),
    the parameters' change norms (after the last).  ``p0`` are the
    weights, laid out on the cell's chips.  Nothing compiles here that
    the window will not use."""
    import compare
    losses = []
    for i in range(WARM_STEPS):
        batch = feed.next()
        system.state, loss = system.step(system.state, batch)
        feed.fence(loss)
        losses.append(float(loss))
        if i == 0:
            grad_norms = compare.program_grad_norms(
                system.state, p0, cfg["weight_decay"])
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": compare.program_change_norms(system.state, p0),
            "names": compare.leaf_names(p0)}


def check_numbers(cfg: dict, seed: int, feed, prog: dict, control=None):
    """The plain reference from the seed's weights over the checked
    steps' batches (``feed.reference_batches``), and the compared numbers
    -> (numbers, reference readings).  ``control`` (``"int8"``,
    ``"e4m3"``) puts the reference's low-precision control in the
    program's place instead."""
    import compare
    import seeding
    ref_batches = feed.reference_batches(WARM_STEPS)
    nums = feed.consumed_gap(ref_batches)
    p0 = seeding.init_params(cfg, seed)
    ref = compare.reference_readings(cfg, p0, ref_batches)
    if control is not None:
        prog = dict(compare.reference_readings(cfg, p0, ref_batches,
                                               control),
                    names=prog["names"])
    nums.update(compare.numbers(prog, ref, prog["names"]))
    return nums, ref


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, fault=None, trainer_argv=()):
    """One run of ``cell`` (as ``spec.load_cell`` gives it) -> (result
    line dict, set-up parts dict).  ``require_tpu=False``, ``fault`` and
    ``trainer_argv`` exist for the benchmark's own tests."""
    import compare
    import counts
    import seeding
    import spec
    import sut
    import xplane

    cfg, work, chips = cell["config"], cell["workload"], cell["entry"]["chips"]
    device = device_info(chips, require_tpu)
    jax = _jax_setup()
    t_init = time.perf_counter()
    parts = {"imports_device_init_s": t_init - T_PROCESS}

    _load_autotune()
    params = seeding.init_params(cfg, seed)
    system = sut.build(cfg, chips, params, trainer_argv=trainer_argv,
                       fault=fault)
    _save_autotune()
    parts.update(system.parts)
    t_built = time.perf_counter()

    feed = spec.load_module("feeds", work["feed"]).Feed(
        cfg, work["traffic"], seed, system)
    t_feed = time.perf_counter()
    parts["feed_s"] = t_feed - t_built

    p0 = system.put_replicated(params)
    del params
    prog = checked_steps(system, feed, p0, cfg)
    del p0
    t_warm = time.perf_counter()
    parts["warm_steps_s"] = t_warm - t_feed
    setup_s = t_warm - T_PROCESS
    parts["setup_s"] = setup_s

    trace_dir = CACHE / "trace" / cell["name"]
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=xplane.profile_options())
    feed_before = feed.counters()
    # set-up's objects stay put: the collector does not rescan them in
    # the window, so its pauses are the window's own
    gc.collect()
    gc.freeze()
    try:
        win = timed_window(system, feed, seconds, trace)
    finally:
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
    counters = {k: v - feed_before.get(k, 0)
                for k, v in feed.counters().items()}
    mem_peak = memory_peak(chips)
    hlo = system.step.as_text() if trace else ""

    # free the program's state before the reference runs
    system.state = system.step = None
    feed.close()
    steps = len(win["stamps"])
    ctx = {"cfg": cfg, "chips": chips, "batch": cfg["global_batch"],
           "steps": steps, "t_start": win["t_start"],
           "stamps": win["stamps"], "window_s": win["stamps"][-1]
           - win["t_start"], "setup_s": setup_s, "counters": counters,
           "device_kind": device["kind"], "trace": None}
    if trace:
        ctx["trace"] = xplane.load(trace_dir, chips, hlo)
    try:
        ctx["peaks"] = counts.peaks(device["kind"])
    except KeyError:
        if require_tpu:
            raise
        ctx["peaks"] = None       # a rehearsal off the chip has no peaks
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = spec.load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    nums, ref = check_numbers(cfg, seed, feed, prog)
    worst = nums.pop("_worst")
    correct, check = compare.verdict(nums, work["limits"])
    failed = sum(1 for v in win["losses"] if not math.isfinite(v))
    device["memory_peak_bytes"] = mem_peak
    if trace:
        device["busy_s"] = ctx["trace"].busy_s()
        device["window_s"] = ctx["trace"].window_s()
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = ctx["trace"].breakdown()
    result["check"] = {k: {"value": v["value"] if math.isfinite(
        v["value"]) else None, "limit": v["limit"]}
        for k, v in check.items()}
    parts.update({"kernels": system.kernels, "worst_leaf": worst,
                  "steps": steps, "window_s": ctx["window_s"],
                  "images": steps * cfg["global_batch"],
                  "loss_first_last": [win["losses"][0], win["losses"][-1]],
                  "span_step_ms": _span_summary(win),
                  "reference_losses": ref["losses"],
                  "program_losses": prog["losses"]})
    return result, parts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    import spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        result, parts = run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    print("setup " + json.dumps(parts, default=str), file=sys.stderr)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
