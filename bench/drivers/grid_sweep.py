"""Grid sweeps: a closed loop of one client planning whole grids.

Each request is ``plan(PlanRequest.grid(cells))`` over a grid of message
sizes x reconfiguration delays on the configuration's fabric, drawn
log-uniformly from the traffic's ranges and from ``--seed``: every request
draws its own values, every request has the same shapes.  Requests are
built outside the timed calls; the window is the sum of the timed calls,
and it closes after the call that reaches ``--seconds``.

The check, after the window: a sample of the window's cells, drawn from
the seed, against the plain reference (`reference.chain`):

* ``volume_gap``: a plan's worst step volume not carried, over the bytes
  the water-fill's idle threshold lets the planes leave (`chain.volume_gap`);
* ``cct_excess_pos_mean``: how much longer each plan's CCT is than the
  reference greedy's own plan for the same cell, both timed by the
  reference executor (relative, counted only where longer: a cell whose
  plan is shorter offsets nothing), averaged over the sample.  A single
  cell's choice can flip between candidates that tie to rounding, so the
  sample's mean and not its widest cell is compared;
* ``timing_gap``: the program's CCT of its own plan against the reference
  executor's (relative);
* ``cells_missing``: cells a request asked for and did not get back.
"""

from __future__ import annotations

import math
import time

import numpy as np

from reference import chain
from reference.patterns import steps_of


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.results: list = []  # (request index, cells asked, PlanResult)
        self.call_s: list[float] = []

    # -- requests -----------------------------------------------------------
    def _values(self, k: int):
        """Message sizes and delays of request ``k`` (k < 0: warm-up)."""
        t = self.traffic
        rng = np.random.default_rng([self.seed, k + 1, 0x5EED])

        def log_uniform(lo, hi, n):
            return np.exp(rng.uniform(math.log(lo), math.log(hi), n))

        sizes = log_uniform(*t["message_bytes"], t["n_sizes"])
        delays = log_uniform(*t["t_recfg_s"], t["n_delays"])
        return sizes, delays

    def _request(self, k: int):
        from repro.core import OpticalFabric
        from repro.core.api import PlannerOptions, PlanRequest
        from repro.core.patterns import get_pattern

        c = self.config
        sizes, delays = self._values(k)
        cells = []
        for size in sizes:
            pattern = get_pattern(c["pattern"], c["n_nodes"], float(size))
            for t_recfg in delays:
                fabric = OpticalFabric(
                    c["n_nodes"],
                    c["n_planes"],
                    bandwidth=c["link_gbps"] * 1e9 / 8,
                    t_recfg=float(t_recfg),
                )
                cells.append((fabric, pattern))
        options = PlannerOptions(**c["planner"])
        return PlanRequest.grid(cells, options=options)

    # -- phases -------------------------------------------------------------
    def setup(self) -> dict:
        from repro.core.api import plan

        # One request of the timed shape compiles (or reads from the
        # persistent cache) every program the window runs.
        t0 = time.perf_counter()
        plan(self._request(-1))
        return {"warmup_request_s": time.perf_counter() - t0}

    def window(self, seconds: float) -> None:
        import jax

        from repro.core.api import plan

        elapsed = 0.0
        k = 0
        with jax.profiler.TraceAnnotation("bench.window"):
            while elapsed < seconds:
                request = self._request(k)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.request"):
                    result = plan(request)
                dt = time.perf_counter() - t0
                self.call_s.append(dt)
                elapsed += dt
                self.results.append((k, len(request.cells), result))
                k += 1

    def _cells(self) -> int:
        return self.traffic["n_sizes"] * self.traffic["n_delays"]

    def summary(self) -> dict:
        return {
            "requests": len(self.results),
            "cells": len(self.results) * self._cells(),
            "window_s": sum(self.call_s),
            "request_s": self.call_s,
        }

    def end_to_end(self) -> dict:
        cells_done = sum(len(r.ccts) for _, _, r in self.results)
        return {"grid_cells_per_s": cells_done / sum(self.call_s)}

    def context(self, trace: dict, peaks: dict):
        return Context(self, trace, peaks)

    def attempted_failed(self) -> tuple[int, int]:
        asked = sum(n for _, n, _ in self.results)
        got = sum(len(r.ccts) for _, _, r in self.results)
        return asked, asked - got

    # -- the check ----------------------------------------------------------
    def _reference_cell(self, k: int, i: int) -> chain.Cell:
        c = self.config
        sizes, delays = self._values(k)
        n_d = len(delays)
        step_cfg, step_vol = steps_of(
            c["pattern"], c["n_nodes"], float(sizes[i // n_d])
        )
        return chain.Cell(
            n_planes=c["n_planes"],
            bandwidth=c["link_gbps"] * 1e9 / 8,
            t_recfg=float(delays[i % n_d]),
            step_cfg=step_cfg,
            step_vol=step_vol,
        )

    def check(self) -> list[tuple[str, float, float]]:
        limits = self.traffic["limits"]
        _, missing = self.attempted_failed()
        pairs = [
            (j, i)
            for j, (_, n, result) in enumerate(self.results)
            for i in range(min(n, len(result.ccts)))
        ]
        rng = np.random.default_rng([self.seed, 0xC4EC])
        n_check = min(len(pairs), self.traffic["check_cells"])
        picks = rng.choice(len(pairs), size=n_check, replace=False)
        cells, splits, ccts = [], [], []
        for p in sorted(picks):
            j, i = pairs[p]
            k, _, result = self.results[j]
            cells.append(self._reference_cell(k, i))
            splits.append(list(result.grid[i].plan.decisions.splits))
            ccts.append(float(result.ccts[i]))
        volume = max(
            (chain.volume_gap(c, s) for c, s in zip(cells, splits)),
            default=float("inf"),
        )
        timing = 0.0
        for c, s, cct in zip(cells, splits, ccts):
            ref = chain.execute(c, s)
            timing = max(timing, abs(cct - ref) / ref)
        opts = self.config["planner"]
        excess = chain.plan_excess(
            chain.Batch.of(cells), cells, splits,
            opts["rollout_horizon"], opts["max_enumerated_planes"],
        )
        return [
            ("cells_missing", float(missing), limits["cells_missing"]),
            ("volume_gap", volume, limits["volume_gap"]),
            ("cct_excess_pos_mean", float(np.maximum(excess, 0.0).mean()),
             limits["cct_excess_pos_mean"]),
            ("timing_gap", timing, limits["timing_gap"]),
        ]


class Context:
    """What a per-layer metric reader may read of a traced grid run."""

    def __init__(self, driver: Driver, trace: dict, peaks: dict) -> None:
        self.trace = trace
        self.peaks = peaks
        self.config = driver.config
        self.requests = len(driver.results)
        self.cells_per_request = driver._cells()
