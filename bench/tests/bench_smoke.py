"""Shared by the benchmark's tests, which run on the CPU: importing this
puts the harness's modules and the program on ``sys.path``, and a
smoke-sized cell stands in for the full-size ones."""
import copy
import json
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def smoke_cell(name: str, chips: int = None) -> dict:
    """Cell ``name`` of BENCHMARK.json with the faithful net at test size
    (``alexnet-faithful-smoke.json``) in place of its configuration."""
    import spec
    cell = copy.deepcopy(spec.load_cell(name))
    cell["config"] = json.loads(
        (TESTS / "alexnet-faithful-smoke.json").read_text())
    if chips is not None:
        cell["entry"]["chips"] = cell["workload"]["chips"] = chips
    traffic = cell["workload"]["traffic"]
    if "pool_size" in traffic:
        traffic["pool_size"] = cell["config"]["crop"] + 8
    return cell
