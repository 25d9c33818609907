#!/usr/bin/env python3
"""Where the fused CHAIN grid planner overtakes the per-step numpy loop.

For each grid size, plans grids of the ``a2a128_4p`` deployment's cells
(`bench/configs/a2a128_4p.json`: 128-rank pairwise all-to-all, 127 steps,
4 planes of 200 Gbps links, ``max_enumerated_planes=4``, rollout horizon
24) through `repro.core.api.plan`, once with ``planner="step"`` and once
with ``planner="fused"``.  Each cell draws its message size (0.8-409.6 MB)
and reconfiguration delay (12.5-800 us) log-uniformly from ``--seed``;
both planners plan the same requests.  One JSON line per size:

* ``step_ms``: median request time with the per-step numpy loop;
* ``fused_ms``: median request time with the fused scan, its program
  already loaded;
* ``fused_first_ms``: the first fused request of the size, which traces
  the scan and compiles it or reads it from the persistent cache;
* ``compile_s``, ``cache_hits``: XLA compile seconds and persistent-cache
  reads during that first request;
* ``cells_differ``: cells whose decisions differ in any bit between the
  planners (none where the device's float64 is IEEE; on a chip that
  emulates float64, the split volumes differ in their last bits);
* ``choices_differ``: cells whose planes or relays at some step differ,
  not only the split volumes (a near-tie reserve set flipped).

The first step request of each size is not timed: it compiles the timing
scan that both planners' requests share.  Every line names the device.

Usage:  python benchmarks/planner_crossover.py
            [--sizes 1,2,4,8,16,32,64,128,256] [--requests 5] [--seed 1]
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import time

import numpy as np

_NODES, _PLANES, _GBPS = 128, 4, 200
_BYTES = (0.8e6, 409.6e6)
_DELAYS = (12.5e-6, 800e-6)


def _request(n_cells: int, seed: int, k: int, planner: str):
    from repro.core import OpticalFabric
    from repro.core.api import PlannerOptions, PlanRequest
    from repro.core.patterns import get_pattern

    rng = np.random.default_rng([seed, n_cells, k])

    def log_uniform(lo, hi):
        return np.exp(rng.uniform(math.log(lo), math.log(hi), n_cells))

    cells = [
        (
            OpticalFabric(
                _NODES, _PLANES, bandwidth=_GBPS * 1e9 / 8,
                t_recfg=float(delay),
            ),
            get_pattern("pairwise_alltoall", _NODES, float(size)),
        )
        for size, delay in zip(log_uniform(*_BYTES), log_uniform(*_DELAYS))
    ]
    options = PlannerOptions(
        max_enumerated_planes=4, rollout_horizon=24, planner=planner
    )
    return PlanRequest.grid(cells, options=options)


class _Compiles:
    """XLA compile seconds and persistent-cache reads while ``active``."""

    def __init__(self) -> None:
        import jax

        self.active = False
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event: str, duration: float, **_kw) -> None:
            if self.active and "backend_compile" in event:
                self.compile_s += duration

        def on_event(event: str, **_kw) -> None:
            if self.active and event.endswith("/cache_hits"):
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _choices(decisions) -> tuple:
    return tuple(tuple(sorted(s)) for s in decisions.splits), decisions.bypass


def _timed(request):
    from repro.core.api import plan

    t0 = time.perf_counter()
    result = plan(request)
    return (time.perf_counter() - t0) * 1e3, result


def measure(n_cells: int, requests: int, seed: int, compiles) -> dict:
    step = [
        _request(n_cells, seed, k, "step") for k in range(requests + 1)
    ]
    fused = [
        _request(n_cells, seed, k, "fused") for k in range(requests + 1)
    ]
    _timed(step[0])  # compiles the timing scan both planners use
    step_runs = [_timed(r) for r in step[1:]]
    compiles.active, compiles.compile_s, compiles.cache_hits = True, 0.0, 0
    first_ms, _ = _timed(fused[0])
    compiles.active = False
    fused_runs = [_timed(r) for r in fused[1:]]
    pairs = [
        (a.plan.decisions, b.plan.decisions)
        for (_, rs), (_, rf) in zip(step_runs, fused_runs)
        for a, b in zip(rs.grid, rf.grid)
    ]
    return {
        "cells": n_cells,
        "step_ms": statistics.median(t for t, _ in step_runs),
        "fused_ms": statistics.median(t for t, _ in fused_runs),
        "fused_first_ms": first_ms,
        "compile_s": compiles.compile_s,
        "cache_hits": compiles.cache_hits,
        "cells_differ": sum(a != b for a, b in pairs),
        "choices_differ": sum(_choices(a) != _choices(b) for a, b in pairs),
        "step_runs_ms": [t for t, _ in step_runs],
        "fused_runs_ms": [t for t, _ in fused_runs],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--sizes", default="1,2,4,8,16,32,64,128,256")
    p.add_argument("--requests", type=int, default=5)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)

    import jax

    from repro import use_compile_cache

    cache = use_compile_cache()
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d)}
    print(json.dumps({"device": device, "compile_cache": cache}), flush=True)
    compiles = _Compiles()
    for n in (int(s) for s in args.sizes.split(",")):
        row = measure(n, args.requests, args.seed, compiles)
        print(json.dumps({**row, "device": device["kind"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
