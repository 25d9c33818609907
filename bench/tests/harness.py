"""Shared set-up of the benchmark's tests: the benchmark's own modules on
``sys.path`` and the program's ``src``, and cells cut to CPU sizes."""

from __future__ import annotations

import copy
import importlib.util
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _load(name: str, path: pathlib.Path):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


bench_run = _load("bench_run", BENCH / "run.py")


def spec() -> dict:
    return bench_run.load_json(ROOT / "BENCHMARK.json")


def small_grid_cell(planner: str = "fused", name="a2a128_4p.sweep128"):
    """A sweep cell cut to 32 nodes and 4 x 4 cells, its plans timed on
    the jax backend as the chip's grids are."""
    cell = bench_run.Cell(spec(), name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["n_nodes"] = 32
    cell.config["planner"].update(planner=planner, backend="jax")
    cell.traffic.update(n_sizes=4, n_delays=4)
    return cell


def replay_spec() -> dict:
    """BENCHMARK.json with the fleet replay cell's entries added back
    (``data/replay_cell.json``).  The cell is out of the benchmark while
    the arbiter carries a step twice past 2**15 simulated seconds; its
    harness is kept and driven here at short windows."""
    full = spec()
    extra = bench_run.load_json(BENCH / "tests" / "data" / "replay_cell.json")
    for key, entries in extra.items():
        full[key] = full[key] + entries
    return full


def small_replay_cell():
    """The fleet cell with its lease re-scoring on numpy, so that set-up
    compiles nothing on the CPU."""
    cell = bench_run.Cell(replay_spec(), "fleet_8n4p.replay")
    cell.config = copy.deepcopy(cell.config)
    cell.config["arbiter"]["backend"] = "numpy"
    return cell


def args(workload: str, seconds: float = 0.5, trace: int = 0, seed=2**31 + 7):
    return bench_run.parse([
        "--workload", workload, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", str(trace),
    ])


def run_cell(cell, seconds: float = 0.5, trace: int = 0) -> dict:
    """A whole run of ``cell`` on the CPU, the chip check skipped."""
    return bench_run.run(args(cell.name, seconds, trace), device_check=False,
                         cell=cell)
