"""Grid sweeps whose plans relay: `grid_sweep`'s closed loop, checked with relays.

Requests and the window are `grid_sweep.Driver`'s: one client planning
whole grids of message sizes x reconfiguration delays, drawn from the
seed, under the configuration's planner options (here with
``bypass_depth >= 2``, so the planner's Topology-Bypassing twin pass runs
and a cell's plan may carry relays in ``decisions.bypass``).

The window also records the growth of the planner's relay tally
(``repro.core.greedy.RELAY_TALLY``: cells planned with a twin, cells that
kept relays, relay routes kept) as ``counters``; a program without the
tally records none.

The check, after the window: a sample of the window's cells, drawn from
the seed, against the plain reference of relays (`reference.relay`), which
reads each plan's direct splits (``decisions.splits``) and its relays
(``decisions.bypass``):

* ``volume_gap``: a plan's worst step volume not carried by its splits
  and relays together, over the water-fill's idle allowance; infinite for
  a relay that rides no installed circuit composing to its step;
* ``cct_excess_pos_mean``: how much longer each plan's CCT is than the
  reference's own pick (the base greedy or its relay twin) for the cell,
  both timed by the reference executor, counted where longer, averaged;
* ``timing_gap``: the program's CCT of its own plan against the reference
  executor's (relative);
* ``cells_missing``: cells a request asked for and did not get back.
"""

from __future__ import annotations

import importlib.util
import pathlib
import sys

import numpy as np

from reference import relay


def _grid_sweep():
    """`grid_sweep.py` beside this file, loaded once by path."""
    name = "bench_driver_grid_sweep"
    if name not in sys.modules:
        path = pathlib.Path(__file__).resolve().parent / "grid_sweep.py"
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


grid_sweep = _grid_sweep()


def relay_tally() -> dict | None:
    """The planner's relay tally now, or None where the program has none."""
    from repro.core import greedy

    tally = getattr(greedy, "RELAY_TALLY", None)
    return None if tally is None else dict(tally.snapshot())


class Driver(grid_sweep.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        super().__init__(config, traffic, seed)
        self.counters: dict = {}

    def window(self, seconds: float) -> None:
        before = relay_tally()
        super().window(seconds)
        after = relay_tally()
        if before is not None and after is not None:
            self.counters = {k: after[k] - before[k] for k in after}

    def summary(self) -> dict:
        return {**super().summary(), "counters": dict(self.counters)}

    def context(self, trace: dict, peaks: dict):
        return Context(self, trace, peaks)

    # -- the check ----------------------------------------------------------
    def _reference_cell(self, k: int, i: int) -> relay.Cell:
        c = self.config
        sizes, delays = self._values(k)
        n_d = len(delays)
        step_cfg, step_vol, cfg_rot = relay.steps(
            c["pattern"], c["n_nodes"], float(sizes[i // n_d])
        )
        return relay.Cell(
            n_nodes=c["n_nodes"],
            n_planes=c["n_planes"],
            bandwidth=c["link_gbps"] * 1e9 / 8,
            t_recfg=float(delays[i % n_d]),
            step_cfg=step_cfg,
            step_vol=step_vol,
            cfg_rot=cfg_rot,
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
        cells, splits, relays, ccts = [], [], [], []
        for p in sorted(picks):
            j, i = pairs[p]
            k, _, result = self.results[j]
            decisions = result.grid[i].plan.decisions
            cells.append(self._reference_cell(k, i))
            splits.append(list(decisions.splits))
            relays.append(
                None if decisions.bypass is None else [
                    [(tuple(r.planes), float(r.volume)) for r in routes]
                    for routes in decisions.bypass
                ]
            )
            ccts.append(float(result.ccts[i]))
        volume = max(
            (relay.volume_gap(c, s, r)
             for c, s, r in zip(cells, splits, relays)),
            default=float("inf"),
        )
        timing = 0.0
        for c, s, r, cct in zip(cells, splits, relays, ccts):
            ref = relay.execute(c, s, r)
            timing = max(timing, abs(cct - ref) / ref)
        opts = self.config["planner"]
        excess = relay.plan_excess(
            cells, splits, relays, opts["rollout_horizon"],
            opts["max_enumerated_planes"], opts["bypass_depth"],
        ) if cells else np.zeros(0)
        return [
            ("cells_missing", float(missing), limits["cells_missing"]),
            ("volume_gap", volume, limits["volume_gap"]),
            ("cct_excess_pos_mean",
             float(np.maximum(excess, 0.0).mean()) if len(excess) else
             float("inf"), limits["cct_excess_pos_mean"]),
            ("timing_gap", timing, limits["timing_gap"]),
        ]


class Context(grid_sweep.Context):
    """What a per-layer metric reader may read of a traced relay sweep:
    `grid_sweep.Context` and the relay tally's growth over the window."""

    def __init__(self, driver: Driver, trace: dict, peaks: dict) -> None:
        super().__init__(driver, trace, peaks)
        self.counters = dict(driver.counters)
