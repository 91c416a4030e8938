"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
workload file, its configuration file, its feed and its metric readers.

Nothing here imports JAX, so a run that lacks a piece fails before it
touches the chip.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(Exception):
    """A cell, configuration, feed or metric the harness cannot find."""


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path.relative_to(ROOT)}") from None


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold '-' and '.')."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """Everything one run of cell ``name`` needs, as plain data:
    ``entry`` (its line in BENCHMARK.json), ``workload`` (its file under
    ``workloads/``), ``config`` (its file under ``configs/``), and the
    benchmark's ``end_to_end`` and ``per_layer`` metrics that apply to it.
    """
    bench = _load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"] if c["name"] == entry["config"]),
                None)
    if conf is None:
        raise SpecError(f"no config {entry['config']!r} in BENCHMARK.json")
    workload = _load_json(BENCH / "workloads" / f"{name}.json")
    for key in ("config", "chips"):
        if workload[key] != entry[key]:
            raise SpecError(f"workloads/{name}.json says {key}="
                            f"{workload[key]!r}, BENCHMARK.json "
                            f"{entry[key]!r}")
    config = _load_json(ROOT / conf["file"])

    def applies(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and applies(m)]
    return {"name": name, "entry": entry, "workload": workload,
            "config": config, "end_to_end": e2e, "per_layer": per_layer,
            "run_seconds": bench["run_seconds"]}
