#!/usr/bin/env python3
"""The control of a cell's `correct`: the timed path one precision lower.

    python bench/control.py --workload <cell> --seed <n> [--seed <n> ...]
        [--seconds <s>] [--precision float32|float64]

The program states float64 for every device program it runs (each entry
enables 64-bit types at call time through ``repro.core.ir.backends.x64``).
With ``--precision float32`` this script switches that off, so the same
programs run in float32 on the same requests, and prints, for each seed,
the numbers the cell's check compares with their limits.  A sound limit
lets the float64 runs pass and makes every float32 run fail.  With
``--precision float64`` it prints the program's own readings the same way,
so both sides can be read in one process, warm-up shared.

The benchmark's own runs never run this.  It exits non-zero, printing no
result, where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import run as bench


@contextlib.contextmanager
def precision(name: str):
    """Run the program's device entries in ``name`` (float32 or float64)."""
    import jax

    from repro.core.ir import backends, fused

    if name == "float64":
        yield
        return
    saved = backends.x64, fused.x64
    lower = lambda: jax.enable_x64(False)  # noqa: E731
    backends.x64 = fused.x64 = lower
    try:
        yield
    finally:
        backends.x64, fused.x64 = saved


def readings(cell: bench.Cell, seed: int, seconds: float) -> dict:
    driver = cell.driver_module.Driver(cell.config, cell.traffic, seed)
    driver.setup()
    driver.window(seconds)
    return {name: value for name, value, _ in driver.check()}


def main(argv=None, device_check: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, action="append", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--precision", choices=("float32", "float64"),
                   default="float32")
    args = p.parse_args(argv)
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    try:
        cell = bench.Cell(spec, args.workload)
        sys.path.insert(0, str(bench.ROOT / "src"))
        if device_check:
            bench.find_devices(int(cell.entry["chips"]))
    except bench.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    bench.compile_cache()
    limits = {}
    for seed in args.seed:
        t0 = time.perf_counter()
        with precision(args.precision):
            got = readings(cell, seed, args.seconds)
        print(json.dumps({
            "workload": args.workload,
            "precision": args.precision,
            "seed": seed,
            "seconds": time.perf_counter() - t0,
            "readings": got,
        }), flush=True)
        limits = cell.traffic["limits"]
    print(json.dumps({"limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(bench.BENCH))
    sys.exit(main())
