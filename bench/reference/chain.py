"""Plain reference of CHAIN-mode SWOT planning and timing, in float64 numpy.

Three pieces, each a direct restatement of the semantics the planner
documents (paper §3; DESIGN.md):

* `execute`: earliest-start timing of discrete per-step splits.  A plane
  whose installed config differs from the step's reconfigures right after
  its previous activity (``t_recfg``); a transmission starts at the later of
  the step barrier and the plane's ready time and lasts ``volume / bw``.
  The CCT is the last step's end.
* `volume_gap`: the legality of a plan's splits (planes exist, volumes
  are not negative, every step's volume is carried).
* `greedy`: the planner's decision rule run on its own.  At each step it
  builds the candidate reserve sets, water-fills the step's volume over
  each candidate's planes, scores each candidate by the no-reserve rollout
  over the next ``horizon`` steps plus the aggregate-bandwidth tail bound,
  and keeps the best; `plan_excess` times a given plan against it.

All arrays are float64; nothing here depends on the program's dtypes.
"""

from __future__ import annotations

import dataclasses

import numpy as np

NO_CONFIG = -1
BIG = 1e30  # ready time of a plane a candidate reserves (never fills)
EPS = 1e-12  # water-fill: a plane below the level by more than this fills
EPS_VOLUME = 1e-6  # bytes below which a split counts as idle


@dataclasses.dataclass(frozen=True)
class Cell:
    """One planning problem: a uniform fabric and a chain of steps."""

    n_planes: int
    bandwidth: float  # bytes/s per plane
    t_recfg: float  # seconds
    step_cfg: np.ndarray  # (S,) int config id per step
    step_vol: np.ndarray  # (S,) bytes per step


def execute(cell: Cell, splits, ready=None, init=None) -> float:
    """CCT of the earliest-start schedule of ``splits`` (one dict per step).

    ``ready`` gives per-plane earliest activity times (default zero) and
    ``init`` the config each plane starts with (default none)."""
    config = [NO_CONFIG] * cell.n_planes if init is None else list(init)
    free = [0.0] * cell.n_planes if ready is None else [float(r) for r in ready]
    barrier = 0.0
    for cfg, split in zip(cell.step_cfg, splits):
        step_end = barrier
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


def volume_gap(cell: Cell, splits) -> float:
    """How far a plan's steps fall short of (or exceed) their volume.

    A water-filled step leaves a plane within `EPS` seconds of the level
    idle, so each plane may carry up to ``bandwidth * EPS`` bytes less than
    the level implies: the plan is legal while every step's
    ``|carried - volume|`` stays within ``planes * bandwidth * EPS``.
    Returns the worst step's ``|carried - volume|`` over that allowance
    (legal at or under 1); a missing or extra step, a plane that does not
    exist or a negative volume is infinitely far."""
    if len(splits) != len(cell.step_vol):
        return float("inf")
    allowance = cell.n_planes * cell.bandwidth * EPS
    worst = 0.0
    for vol, split in zip(cell.step_vol, splits):
        if any(not 0 <= j < cell.n_planes for j in split):
            return float("inf")
        if any(v < 0.0 for v in split.values()):
            return float("inf")
        worst = max(worst, abs(sum(split.values()) - vol) / allowance)
    return worst


def waterfill(ready: np.ndarray, bw: np.ndarray, volume: np.ndarray):
    """Equal-finish water level over each row's planes.

    ``ready`` and ``bw`` are (R, P), ``volume`` is (R,).  The level L solves
    ``sum_j bw_j max(0, L - r_j) = volume`` over the planes below it;
    planes below L by more than `EPS` carry ``bw_j (L - r_j)``.  A row of
    no volume keeps its earliest ready time and carries nothing.  Returns
    ``(level (R,), split (R, P))``."""
    order = np.argsort(ready, axis=1, kind="stable")
    r = np.take_along_axis(ready, order, axis=1)
    b = np.take_along_axis(bw, order, axis=1)
    cb = np.cumsum(b, axis=1)
    cbr = np.cumsum(b * r, axis=1)
    # Volume the planes before k absorb when the level reaches r[:, k];
    # it grows with k, and the last k it does not exceed sets the level.
    absorbed = np.zeros_like(r)
    absorbed[:, 1:] = r[:, 1:] * cb[:, :-1] - cbr[:, :-1]
    k = (absorbed <= volume[:, None]).sum(axis=1) - 1
    rows = np.arange(ready.shape[0])
    level = (volume + cbr[rows, k]) / cb[rows, k]
    empty = volume <= EPS
    level = np.where(empty, ready.min(axis=1), level)
    gap = level[:, None] - ready
    split = np.where((gap > EPS) & ~empty[:, None], bw * gap, 0.0)
    return level, split


@dataclasses.dataclass(frozen=True)
class Batch:
    """Cells of one shape (planes, steps) stacked for the greedy check."""

    bw: np.ndarray  # (N, P)
    t_recfg: np.ndarray  # (N,)
    step_cfg: np.ndarray  # (N, S) int
    step_vol: np.ndarray  # (N, S)

    @classmethod
    def of(cls, cells: list[Cell]) -> "Batch":
        return cls(
            bw=np.array([[c.bandwidth] * c.n_planes for c in cells]),
            t_recfg=np.array([c.t_recfg for c in cells]),
            step_cfg=np.stack([np.asarray(c.step_cfg) for c in cells]),
            step_vol=np.stack([np.asarray(c.step_vol, float) for c in cells]),
        )


def rollout(batch: Batch, rows, config, free, barrier, start: int, horizon: int):
    """No-reserve rollout estimate of the CCT from each row's state.

    ``rows`` (R,) maps each state row to its cell.  Runs the next
    ``horizon`` steps with water-filled splits and no reserve, then adds
    the tail bound: the remaining volume at the planes' summed bandwidth,
    plus one ``t_recfg / planes`` per config change within the tail."""
    bw = batch.bw[rows]
    t_recfg = batch.t_recfg[rows]
    n_steps = batch.step_cfg.shape[1]
    end = min(n_steps, start + horizon)
    for i in range(start, end):
        cfg = batch.step_cfg[rows, i][:, None]
        extra = np.where(config == cfg, 0.0, t_recfg[:, None])
        ready = np.maximum(barrier[:, None], free + extra)
        level, split = waterfill(ready, bw, batch.step_vol[rows, i])
        active = split > 0.0
        free = np.where(active, level[:, None], free)
        config = np.where(active, cfg, config)
        barrier = level
    if end < n_steps:
        tail = batch.step_cfg[rows, end:]
        changes = np.count_nonzero(tail[:, 1:] != tail[:, :-1], axis=1)
        barrier = barrier + batch.step_vol[rows, end:].sum(axis=1) / bw.sum(1)
        barrier = barrier + changes * t_recfg / bw.shape[1]
    return barrier


def _reserve_masks(free: np.ndarray, max_enum: int) -> np.ndarray:
    """(N, C, P) reserve sets: every proper subset of the planes in order
    of size where there are at most ``max_enum`` planes, else the 0..3
    soonest-free planes (ties by plane index)."""
    n, p = free.shape
    if p <= max_enum:
        import itertools

        sets = [
            c for size in range(p) for c in itertools.combinations(range(p), size)
        ]
        masks = np.zeros((len(sets), p), dtype=bool)
        for m, c in enumerate(sets):
            masks[m, list(c)] = True
        return np.broadcast_to(masks, (n, len(sets), p)).copy()
    by_free = np.argsort(free, axis=1, kind="stable")
    masks = np.zeros((n, 4, p), dtype=bool)
    for size in range(1, 4):
        np.put_along_axis(masks[:, size], by_free[:, :size], True, axis=1)
    return masks


def _upcoming(step_cfg: np.ndarray, i: int, held: set, n: int) -> list[int]:
    """The next ``n`` distinct configs after step ``i`` not in ``held``."""
    out: list[int] = []
    for t in step_cfg[i + 1 :]:
        t = int(t)
        if t not in held and t not in out:
            out.append(t)
            if len(out) == n:
                break
    return out


def greedy(batch: Batch, horizon: int, max_enum: int) -> np.ndarray:
    """The greedy's own plans: (N, S, P) split volumes per cell and step.

    At each step: the candidate reserve sets (reserved planes retarget,
    soonest free first, toward the next configs not yet held, and carry
    nothing), each water-filled over the planes it keeps and scored by
    `rollout`; the least score wins, then the lower level, then the
    candidate's order.  The winner's state carries on."""
    n, p = batch.bw.shape
    s_max = batch.step_cfg.shape[1]
    config = np.full((n, p), NO_CONFIG, dtype=np.int64)
    free = np.zeros((n, p))
    barrier = np.zeros(n)
    cells = np.arange(n)
    plans = np.zeros((n, s_max, p))
    for i in range(s_max):
        cfg_i = batch.step_cfg[:, i]
        vol = batch.step_vol[:, i]
        reserved = _reserve_masks(free, max_enum)  # (N, C, P)
        c = reserved.shape[1]
        tc = np.repeat(config[:, None], c, axis=1)
        tf = np.repeat(free[:, None], c, axis=1)
        n_res = reserved.sum(axis=2)
        # Reserved planes retarget, soonest free first (ties by plane
        # index), toward the next configs not yet held, in order.
        targets = np.full((n, p), NO_CONFIG, dtype=np.int64)
        for cell in range(n):
            held = {int(x) for x in config[cell] if x != NO_CONFIG}
            held.add(int(cfg_i[cell]))
            up = _upcoming(batch.step_cfg[cell], i, held, int(n_res[cell].max()))
            targets[cell, : len(up)] = up
        by_free = np.argsort(free, axis=1, kind="stable")  # (N, P)
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
        rows = np.repeat(cells, c)
        tc, tf = tc.reshape(n * c, p), tf.reshape(n * c, p)
        extra = np.where(tc == cfg_i[rows, None], 0.0, batch.t_recfg[rows, None])
        ready = np.maximum(barrier[rows, None], tf + extra)
        ready = np.where(reserved.reshape(n * c, p), BIG, ready)
        level, split = waterfill(ready, batch.bw[rows], vol[rows])
        valid = (n_res.reshape(-1) < p) & (
            (vol[rows] <= EPS) | (split > 0.0).any(axis=1)
        )
        active = split > 0.0
        new_free = np.where(active, level[:, None], tf)
        new_cfg = np.where(active, cfg_i[rows, None], tc)
        score = rollout(batch, rows, new_cfg, new_free, level, i + 1, horizon)
        score = np.where(valid, score, np.inf).reshape(n, c)
        level = np.where(valid, level, np.inf).reshape(n, c)
        order = np.arange(c)
        chosen = np.array([
            np.lexsort((order, level[cell], score[cell]))[0] for cell in cells
        ])
        flat = cells * c + chosen
        plans[:, i] = split[flat]
        config, free = new_cfg[flat], new_free[flat]
        barrier = level[cells, chosen]
    return plans


def plan_excess(batch: Batch, cells: list[Cell], splits: list, horizon: int,
                max_enum: int) -> np.ndarray:
    """Per cell, how much longer the given plan's CCT is than the greedy's
    own plan's, both timed by `execute` (relative; negative where the
    given plan is the shorter)."""
    ref = greedy(batch, horizon, max_enum)
    out = np.empty(len(cells))
    for k, (cell, plan) in enumerate(zip(cells, splits)):
        own = [{j: v for j, v in enumerate(row) if v > 0.0} for row in ref[k]]
        base = execute(cell, own)
        out[k] = (execute(cell, plan) - base) / base
    return out
