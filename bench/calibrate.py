#!/usr/bin/env python3
"""Readings that the check's limits are set from, for one cell, in one
process on the chips the cell asks for.  The benchmark's runs never call
this.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 \\
        --faults 3 [--first-seed N] [--out FILE]

For each seed: the program's step (built and compiled once) from that
seed's weights through the three checked steps, and the plain reference;
the compared numbers.  Then the control — the reference with every
product's operands rounded to int8, put in the program's place — on
the first ``--control`` seeds (``--controls``: int8, the cells' own,
and e4m3 for information), and each fault the cell can have
(``sut.FAULTS``: half the batch dropped, the loss altered where it is
produced, and on several chips the exchange left out), planted in the
program's step, on the first ``--faults`` seeds.  A step that returns
its state unchanged reads ``change_gap`` 1 by the measure's definition
and is not run.  One JSON line per reading; a summary last.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import run  # noqa: E402


def readings(system, cell, seed, spec_mod, control=None):
    import seeding
    cfg, work = cell["config"], cell["workload"]
    params = seeding.init_params(cfg, seed)
    system.state = system.make_state(params)
    feed = spec_mod.load_module("feeds", work["feed"]).Feed(
        cfg, work["traffic"], seed, system)
    try:
        p0 = system.put_replicated(params)
        prog = run.checked_steps(system, feed, p0, cfg)
        del p0
    finally:
        feed.close()
    nums, _ = run.check_numbers(cfg, seed, feed, prog, control)
    nums.pop("_worst", None)
    return nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--controls", default="int8",
                    help="comma-separated controls: int8 (the cells' own), "
                    "e4m3")
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import seeding
    import spec
    import sut
    cell = spec.load_cell(args.workload)
    run._jax_setup()
    chips = cell["entry"]["chips"]
    run.device_info(chips, require_tpu=True)
    run._load_autotune()
    cfg = cell["config"]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = open(args.out, "w") if args.out else sys.stdout

    def emit(rec):
        print(json.dumps(rec), file=out, flush=True)

    def built(fault=None):
        return sut.build(cfg, chips, seeding.init_params(cfg, seeds[0]),
                         fault=fault)

    summary = {}
    system = built()
    for s in seeds:
        t = time.perf_counter()
        nums = readings(system, cell, s, spec)
        emit({"kind": "program", "seed": s, **nums,
              "s": time.perf_counter() - t})
        summary.setdefault("program", []).append(nums)
    for control in args.controls.split(","):
        for s in seeds[:args.control]:
            nums = readings(system, cell, s, spec, control)
            emit({"kind": f"control_{control}", "seed": s, **nums})
            summary.setdefault(f"control_{control}", []).append(nums)
    system = None
    faults = ["half_batch", "loss_altered"] + (
        ["no_exchange"] if chips > 1 else [])
    for fault in faults if args.faults else []:
        fsys = built(fault)
        for s in seeds[:args.faults]:
            nums = readings(fsys, cell, s, spec)
            emit({"kind": fault, "seed": s, **nums})
            summary.setdefault(fault, []).append(nums)
        fsys = None
    agg = {kind: {k: [min(r[k] for r in rows), max(r[k] for r in rows)]
                  for k in rows[0]}
           for kind, rows in summary.items()}
    emit({"kind": "summary", "workload": args.workload,
          "min_max": agg, "limits": cell["workload"]["limits"]})
    return 0


if __name__ == "__main__":
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
