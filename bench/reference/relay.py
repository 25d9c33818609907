"""Plain reference of Topology Bypassing on rotation collectives, in float64 numpy.

A relay carries a step's traffic over a circuit already installed on a
plane, without reconfiguring it: node ``x`` sends its chunk over the
plane's installed pairing, the receiver forwards it over the same pairing,
and after ``h`` store-and-forward hops the chunk has travelled the
pairing's ``h``-th power.  On rotations the powers are arithmetic:
``rot(a)^h = rot(h * a mod n)``.  Bruck's all-to-all sends phase ``k``
over ``rot(2^k)``, so each phase after the first is the square of the one
before it.

The pieces, each restated from the semantics the planner documents
(SWOT's Topology Bypassing; DESIGN.md section 15; the module docstring of
the planner's greedy):

* `steps`: Bruck's step list, from its definition;
* `relay_depth`: the least ``h`` in ``[2, depth]`` with
  ``h * a = c (mod n)``, the self-relay of ``rot(a)`` onto ``rot(c)``;
* `execute`: earliest-start timing of direct splits and relays.  A step's
  relays run before its direct traffic; a relay's first hop starts at the
  later of the step barrier and its plane's free time, hop ``k + 1`` at
  the later of hop ``k``'s end and its plane's free time, and each hop
  lasts ``volume / bw``.  A relay leaves its plane's config as it was;
* `volume_gap`: `chain.volume_gap` with relayed volume counted once per
  relay, and a relay that rides no installed circuit composing to its
  step infinitely far;
* `greedy`: the planner's twin pass.  Every reserve-set candidate of
  `chain.greedy` gains a twin row in which each plane that is neither
  reserved nor set to the step's config, and whose installed circuit
  self-relays onto the step in ``h`` hops, serves over that circuit at
  ``bw / h`` with no reconfiguration.  A twin with no such plane is not a
  candidate.  The installed circuits are the configs the chosen direct
  serves installed (the executor's lazy state), which reserve retargets
  never advance.  Scoring and the pick among rows are `chain.greedy`'s;
* `pick`: per cell, the twin plan where it carries a relay and its CCT
  (`execute`) is strictly below the base plan's (`chain.greedy`), else
  the base plan; `plan_excess` times a given plan against it.

Departures from those texts: the planner times both plans of the pick on
its own numpy timing backend, this file with `execute`; the two agree to
rounding.  Relays are given as ``(planes, volume)`` pairs, one per route.

Nothing here imports the program under test.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from reference import chain

NO_CONFIG = chain.NO_CONFIG
EPS = chain.EPS
EPS_VOLUME = chain.EPS_VOLUME


def steps(name: str, n: int, size: float):
    """(config id per step, bytes per step, rotation per config id) of
    collective ``name`` on ``n`` ranks with ``size`` bytes a rank.

    Bruck's all-to-all: ``ceil(log2 n)`` phases; phase ``k`` sends, over
    ``rot(2^k mod n)``, every block whose offset ``o`` in ``[1, n)`` has
    bit ``k`` set: that many blocks of ``size / n`` bytes."""
    if name != "bruck_alltoall":
        raise ValueError(f"no relay reference step list for {name!r}")
    phases = max(1, math.ceil(math.log2(n)))
    block = size / n
    cfg, vol = [], []
    for k in range(phases):
        blocks = sum(1 for o in range(1, n) if (o >> k) & 1)
        if blocks:
            cfg.append(k)
            vol.append(blocks * block)
    rot = np.array([(1 << k) % n for k in range(phases)], dtype=np.int64)
    return np.array(cfg, np.int64), np.array(vol, np.float64), rot


def relay_depth(a: int, c: int, n: int, depth: int) -> int:
    """Least ``h`` in ``[2, depth]`` with ``rot(a)^h = rot(c)`` on ``n``
    ranks, that is ``h * a = c (mod n)``; 0 where none is."""
    for h in range(2, depth + 1):
        if (h * a - c) % n == 0:
            return h
    return 0


@dataclasses.dataclass(frozen=True)
class Cell:
    """One planning problem: a uniform fabric, a chain of rotation steps."""

    n_nodes: int
    n_planes: int
    bandwidth: float  # bytes/s per plane
    t_recfg: float  # seconds
    step_cfg: np.ndarray  # (S,) int config id per step
    step_vol: np.ndarray  # (S,) bytes per step
    cfg_rot: np.ndarray  # rotation of each config id


def execute(cell: Cell, splits, relays=None) -> float:
    """CCT of the earliest-start schedule of direct ``splits`` (one dict
    per step) and ``relays`` (per step, ``(planes, volume)`` pairs)."""
    config = [NO_CONFIG] * cell.n_planes
    free = [0.0] * cell.n_planes
    barrier = 0.0
    for i, (cfg, split) in enumerate(zip(cell.step_cfg, splits)):
        step_end = barrier
        for planes, v in relays[i] if relays is not None else ():
            if v <= EPS_VOLUME:
                continue
            end = barrier
            for j in planes:
                start = max(end, free[j])
                end = start + v / cell.bandwidth
                free[j] = end
            step_end = max(step_end, end)
        for j in sorted(split):
            v = split[j]
            if v <= EPS_VOLUME:
                continue
            if config[j] != cfg:
                free[j] += cell.t_recfg
                config[j] = int(cfg)
            start = max(barrier, free[j])
            free[j] = start + v / cell.bandwidth
            step_end = max(step_end, free[j])
        barrier = step_end
    return barrier


def volume_gap(cell: Cell, splits, relays=None) -> float:
    """`chain.volume_gap` with relays: a step's carried volume is its
    direct splits plus its relays' volumes, against the same allowance.

    A relay is legal where it has two hops or more on planes that exist,
    a volume that is not negative, and, if it carries more than the idle
    threshold, hops whose installed circuits (the configs the direct
    serves before it installed) compose to the step's rotation.  An
    illegal relay, or relays for a different number of steps, is
    infinitely far."""
    n_steps = len(cell.step_vol)
    if len(splits) != n_steps:
        return float("inf")
    if relays is None:
        relays = [()] * n_steps
    if len(relays) != n_steps:
        return float("inf")
    allowance = cell.n_planes * cell.bandwidth * EPS
    held = [NO_CONFIG] * cell.n_planes
    worst = 0.0
    for cfg, vol, split, routes in zip(
        cell.step_cfg, cell.step_vol, splits, relays
    ):
        if any(not 0 <= j < cell.n_planes for j in split):
            return float("inf")
        if any(v < 0.0 for v in split.values()):
            return float("inf")
        carried = sum(split.values())
        for planes, v in routes:
            if len(planes) < 2 or v < 0.0:
                return float("inf")
            if any(not 0 <= j < cell.n_planes for j in planes):
                return float("inf")
            if v > EPS_VOLUME:
                if any(held[j] == NO_CONFIG for j in planes):
                    return float("inf")
                reach = sum(int(cell.cfg_rot[held[j]]) for j in planes)
                if (reach - int(cell.cfg_rot[cfg])) % cell.n_nodes:
                    return float("inf")
            carried += v
        worst = max(worst, abs(carried - vol) / allowance)
        for j, v in split.items():
            if v > EPS_VOLUME:
                held[j] = int(cfg)
    return worst


def greedy(batch: chain.Batch, cells: list[Cell], horizon: int,
           max_enum: int, depth: int):
    """The twin pass's own plans: ``(splits, hops)``, each (N, S, P); a
    plane with ``hops >= 2`` relays its split over its installed circuit.

    `chain.greedy`'s step with a twin row after every candidate (module
    docstring); the rows of a cell are its base candidates in
    `chain.greedy`'s order, then their twins in the same order, and the
    least (score, level, row) wins."""
    n, p = batch.bw.shape
    s_max = batch.step_cfg.shape[1]
    rot = [np.asarray(c.cfg_rot) for c in cells]
    config = np.full((n, p), NO_CONFIG, dtype=np.int64)
    installed = np.full((n, p), NO_CONFIG, dtype=np.int64)
    free = np.zeros((n, p))
    barrier = np.zeros(n)
    cells_ix = np.arange(n)
    plans = np.zeros((n, s_max, p))
    plan_hops = np.zeros((n, s_max, p), dtype=np.int64)
    for i in range(s_max):
        cfg_i = batch.step_cfg[:, i]
        vol = batch.step_vol[:, i]
        reserved = chain._reserve_masks(free, max_enum)  # (N, C, P)
        c = reserved.shape[1]
        tc = np.repeat(config[:, None], c, axis=1)
        tf = np.repeat(free[:, None], c, axis=1)
        n_res = reserved.sum(axis=2)
        targets = np.full((n, p), NO_CONFIG, dtype=np.int64)
        for cell in range(n):
            held = {int(x) for x in config[cell] if x != NO_CONFIG}
            held.add(int(cfg_i[cell]))
            up = chain._upcoming(batch.step_cfg[cell], i, held,
                                 int(n_res[cell].max()))
            targets[cell, : len(up)] = up
        by_free = np.argsort(free, axis=1, kind="stable")
        res_sorted = np.take_along_axis(reserved, by_free[:, None, :], axis=2)
        rank = np.cumsum(res_sorted, axis=2) - 1
        target = np.take_along_axis(
            np.broadcast_to(targets[:, None, :], rank.shape),
            np.maximum(rank, 0), axis=2,
        )
        moves = res_sorted & (target != NO_CONFIG)
        unsort = np.argsort(by_free, axis=1)[:, None, :]
        moves = np.take_along_axis(moves, unsort, axis=2)
        target = np.take_along_axis(target, unsort, axis=2)
        tf = tf + np.where(moves, batch.t_recfg[:, None, None], 0.0)
        tc = np.where(moves, target, tc)
        # Relay depth of each plane's installed circuit onto this step.
        plane_h = np.array([
            [0 if installed[k, j] == NO_CONFIG else relay_depth(
                int(rot[k][installed[k, j]]), int(rot[k][cfg_i[k]]),
                cells[k].n_nodes, depth)
             for j in range(p)]
            for k in range(n)
        ], dtype=np.int64).reshape(n, p)
        hops = np.where(
            reserved | (tc == cfg_i[:, None, None]), 0, plane_h[:, None, :]
        )
        valid = n_res < p
        valid = np.concatenate([valid, valid & hops.any(axis=2)], axis=1)
        hops = np.concatenate([np.zeros_like(hops), hops], axis=1)
        reserved = np.concatenate([reserved, reserved], axis=1)
        tc = np.concatenate([tc, tc], axis=1)
        tf = np.concatenate([tf, tf], axis=1)
        c2 = 2 * c
        rows = np.repeat(cells_ix, c2)
        tc, tf = tc.reshape(n * c2, p), tf.reshape(n * c2, p)
        hops = hops.reshape(n * c2, p)
        relaying = hops >= 2
        extra = np.where(
            (tc == cfg_i[rows, None]) | relaying, 0.0, batch.t_recfg[rows, None]
        )
        ready = np.maximum(barrier[rows, None], tf + extra)
        ready = np.where(reserved.reshape(n * c2, p), chain.BIG, ready)
        bw = batch.bw[rows]
        bw_eff = np.where(relaying, bw / np.maximum(hops, 1), bw)
        level, split = chain.waterfill(ready, bw_eff, vol[rows])
        valid = valid.reshape(-1) & (
            (vol[rows] <= EPS) | (split > 0.0).any(axis=1)
        )
        active = split > 0.0
        new_free = np.where(active, level[:, None], tf)
        new_cfg = np.where(active & ~relaying, cfg_i[rows, None], tc)
        score = chain.rollout(batch, rows, new_cfg, new_free, level, i + 1,
                              horizon)
        score = np.where(valid, score, np.inf).reshape(n, c2)
        level = np.where(valid, level, np.inf).reshape(n, c2)
        order = np.arange(c2)
        chosen = np.array([
            np.lexsort((order, level[k], score[k]))[0] for k in cells_ix
        ])
        flat = cells_ix * c2 + chosen
        plans[:, i] = split[flat]
        plan_hops[:, i] = hops[flat]
        config, free = new_cfg[flat], new_free[flat]
        barrier = level[cells_ix, chosen]
        installed = np.where(
            (split[flat] > EPS_VOLUME) & ~relaying[flat],
            cfg_i[:, None], installed,
        )
    return plans, plan_hops


def _as_plan(splits: np.ndarray, hops: np.ndarray | None):
    """(direct split dicts, relays) of one cell's (S, P) arrays."""
    direct, relays = [], []
    for i, row in enumerate(splits):
        h = np.zeros(len(row), np.int64) if hops is None else hops[i]
        direct.append({j: v for j, v in enumerate(row) if v > 0.0 and h[j] < 2})
        relays.append(tuple(
            ((j,) * int(h[j]), v) for j, v in enumerate(row)
            if v > 0.0 and h[j] >= 2
        ))
    return direct, relays


def pick(cells: list[Cell], horizon: int, max_enum: int, depth: int):
    """The reference's plan per cell: ``(splits, relays, cct)``, the twin's
    where it carries a relay and its CCT is strictly below the base's."""
    batch = chain.Batch.of(cells)
    base = chain.greedy(batch, horizon, max_enum)
    twin, hops = greedy(batch, cells, horizon, max_enum, depth)
    out = []
    for k, cell in enumerate(cells):
        b_split, b_relay = _as_plan(base[k], None)
        t_split, t_relay = _as_plan(twin[k], hops[k])
        b_cct = execute(cell, b_split, b_relay)
        t_cct = execute(cell, t_split, t_relay)
        if any(t_relay) and t_cct < b_cct:
            out.append((t_split, t_relay, t_cct))
        else:
            out.append((b_split, b_relay, b_cct))
    return out


def plan_excess(cells: list[Cell], splits: list, relays: list, horizon: int,
                max_enum: int, depth: int) -> np.ndarray:
    """Per cell, how much longer the given plan's CCT is than `pick`'s,
    both timed by `execute` (relative; negative where the given plan is
    the shorter)."""
    ref = pick(cells, horizon, max_enum, depth)
    return np.array([
        (execute(cell, s, r) - base) / base
        for cell, s, r, (_, _, base) in zip(cells, splits, relays, ref)
    ])
