"""``BENCHMARK.json`` and the files it names: every piece is found by
name, and each configuration file builds the program's own net."""
import dataclasses
import json
import re

import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on sys.path)
import spec
from bench_smoke import BENCH

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_names():
    assert list(BENCHMARK) == ["command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCHMARK[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCHMARK["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_piece_of_a_cell_is_found_by_name(cell):
    c = spec.load_cell(cell)
    assert (BENCH / "feeds" / f"{c['workload']['feed']}.py").is_file()
    for m in c["end_to_end"] + c["per_layer"]:
        assert hasattr(spec.load_module("metrics", m["name"]), "read")
    assert c["per_layer"], "every cell reports a per-layer metric"


@pytest.mark.parametrize("name,program", [
    ("alexnet-faithful", "FAITHFUL"), ("alexnet-ungrouped", "CONFIG")])
def test_config_file_builds_the_programs_net(name, program):
    from repro.configs import alexnet
    import sut
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    built = sut.program_config(cfg)
    want = getattr(alexnet, program)
    # the trainer never applies dropout; the file states it as run
    assert dataclasses.replace(built, name=want.name,
                               dropout=want.dropout) == want
    assert built.n_params() == cfg["n_params"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == name)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
