"""Fused on-device grid planner: the whole per-step greedy loop as one
jitted ``lax.scan``.

``swot_greedy_grid``'s per-step loop (`repro.core.greedy`) is pure array
code already, but it dispatches a fresh batch of numpy ops from Python at
every step -- at 1024 cells that host round-trip is the planning
bottleneck, not the arithmetic.  This module lowers the SAME loop --
candidate reserve-set construction from the precomputed table, upcoming-
target retargeting, water-fill splits, horizon rollouts, bypass twins,
and the per-instance lexicographic selection -- into one device program:
a ``jax.lax.scan`` over steps whose carry is the planner state
``(config, free, barrier, installed)`` and whose stacked outputs are the
chosen per-step splits.

The contract is *bitwise* parity with the per-step numpy planner (which
is itself bitwise-pinned to the per-instance reference): every float op
below mirrors its numpy twin operation for operation.  It holds where the
device's float64 is IEEE (the CPU).  A TPU emulates float64 with pairs
of float32s, so there the ops round differently and near-tie decisions
differ (ROADMAP speed item 3).  The places where
a naive lowering would break the bit pattern (or the performance):

* XLA:CPU contracts ``a * b + c`` into a single-rounding FMA, a 1-ULP
  divergence from numpy's separately-rounded product; every product
  feeding an add/subtract in the water-fill goes through the `_no_fma`
  guard (see its docstring for why ``abs`` and nothing weaker works).
* ``jnp.cumsum`` lowers to an associative scan whose float reduction
  order differs from numpy's sequential accumulation, so the water-fill
  prefix sums are unrolled over the (static, small) plane axis as
  per-column adds inside `_waterfill_j`.
* XLA's generic sort is both ~5x slower than numpy's and not pinned to
  ``np.argsort(kind="stable")`` tie order.  The plane axis is tiny and
  static, so sorting is an odd-even transposition network over plane
  columns (`_network_sort_cols`, stable by strict-``>`` construction)
  and dynamic-row refresh uses O(P^2) pairwise stable ranks
  (`_stable_ranks_j`).
* ``np.lexsort``'s per-instance first-row selection becomes a cascade of
  ``segment_min`` reductions with exact float-equality eligibility masks
  (min score -> min level among score-ties -> min row id), which is the
  same (score, level, candidate order) lexicographic minimum.
* numpy's early ``break``s and live-row filtering become fixed-trip
  loops with live masking; every masked iteration is arithmetically
  inert, so the carried state stays identical.

Everything runs in float64 under the scoped `x64` helper (the same
policy as the jax timing backend).  Entry points return the per-step
``chosen`` tuples the numpy loop accumulates, so `repro.core.greedy`
materializes Decisions through one shared epilogue for both planners.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import numpy as np

from repro.core.ir.backends import x64
from repro.core.ir.engine import _BIG, NO_CONFIG
from repro.core.tolerances import EPS as _EPS
from repro.core.tolerances import EPS_VOLUME as _EPS_VOLUME

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (types only)
    from repro.core.greedy import _GridState


def _require_jax():
    try:
        import jax  # noqa: F401
    except Exception as exc:  # pragma: no cover - env without jax
        from repro.core.ir.backends import BackendUnavailable

        raise BackendUnavailable(
            "the fused grid planner needs jax installed (pip install jax)"
        ) from exc
    return jax


def _no_fma(product):
    """Force a float product to round before it feeds an add/subtract.

    XLA:CPU compiles with LLVM fp contraction enabled, so a fused
    elementwise ``a * b + c`` becomes a single-rounding FMA -- a 1-ULP
    divergence from numpy's separately-rounded product that breaks the
    bitwise-parity contract.  ``optimization_barrier`` and bitcast
    round-trips are both simplified away before instruction selection;
    ``abs`` is not (the simplifier cannot prove a product non-negative),
    it survives to LLVM as an intrinsic no FMA pattern can match
    through, and it is an exact identity here: every guarded product is
    of non-negative operands (bandwidths, ready times, prefix sums).
    """
    import jax.numpy as jnp

    return jnp.abs(product)


@functools.lru_cache(maxsize=None)
def _oddeven_comparators(n: int) -> tuple[tuple[int, int], ...]:
    """Odd-even transposition network: ``n`` rounds of adjacent swaps.

    Adjacent compare-exchange with a *strict* ``>`` test never reorders
    equal keys, so the network is a stable sort by construction -- the
    same permutation as ``np.argsort(kind="stable")`` -- and ``n``
    rounds are sufficient for any input (the classic brick-sort bound).
    """
    comps = []
    for rnd in range(n):
        comps.extend((i, i + 1) for i in range(rnd % 2, n - 1, 2))
    return tuple(comps)


def _network_sort_cols(key_cols, extra_col_lists=()):
    """Stable ascending lane sort over column lists, unrolled in place.

    XLA lowers ``jnp.argsort`` to a generic comparator sort that is ~5x
    slower than numpy's on the (R, P) shapes the water-fill hits in
    every rollout iteration -- the fused planner's hot loop.  The plane
    axis is static and tiny, so a compare-exchange network of ``P``
    unrolled rounds turns the sort into a handful of fusible ``where``
    ops instead.  Mutates ``key_cols`` (and every column list in
    ``extra_col_lists``, carried through the same swaps); the
    permutation is exact (values only move, never recompute), so
    bitwise parity with the numpy reference is preserved.
    """
    import jax.numpy as jnp

    for i, j in _oddeven_comparators(len(key_cols)):
        a, b = key_cols[i], key_cols[j]
        swap = a > b
        key_cols[i] = jnp.where(swap, b, a)
        key_cols[j] = jnp.where(swap, a, b)
        for ec in extra_col_lists:
            ea, eb = ec[i], ec[j]
            ec[i] = jnp.where(swap, eb, ea)
            ec[j] = jnp.where(swap, ea, eb)


def _stable_ranks_j(key):
    """Device twin of ``greedy._stable_ranks`` (rank under stable sort).

    No sort at all: a lane's stable rank is the count of lanes that beat
    it -- strictly smaller key, or equal key at a smaller index.  All
    ``P^2`` pairwise comparisons are exact (float equality, integer
    adds), so this is bitwise-identical to ranking through
    ``np.argsort(kind="stable")`` at a fraction of XLA's sort cost.
    """
    import jax.numpy as jnp

    n = key.shape[-1]
    if n == 1:
        return jnp.zeros(key.shape, jnp.int64)
    cols = [key[..., j] for j in range(n)]
    ranks = []
    for o in range(n):
        acc = None
        for j in range(n):
            if j == o:
                continue
            beats = (cols[j] < cols[o]) if j > o else (
                cols[j] <= cols[o]
            )
            acc = beats.astype(jnp.int64) if acc is None else (
                acc + beats
            )
        ranks.append(acc)
    return jnp.stack(ranks, axis=-1)


def _waterfill_j(ready, bw, vol):
    """Bitwise device twin of ``engine.waterfill_batch``.

    Same closed-form: stable sort by ready time, sequential prefix sums,
    largest feasible knee, one division.  The numpy reference's all-zero
    early return is subsumed by the ``zero`` select (the general path is
    finite for zero-volume rows, so the ``where`` is exact).

    The two multiply-into-add chains are guarded by `_no_fma`: under jit
    XLA:CPU contracts ``a * b + c`` into an FMA (one rounding instead of
    two), which numpy never does -- a 1-ULP water level is enough to
    flip a downstream argmin tie, so the products must round separately
    exactly like the reference.
    """
    import jax.numpy as jnp

    n = ready.shape[-1]
    zero = vol <= _EPS
    r0 = [ready[..., j] for j in range(n)]
    b0 = [bw[..., j] for j in range(n)]
    r_s = list(r0)
    b_s = list(b0)
    _network_sort_cols(r_s, (b_s,))
    # Sequential prefix sums and knee test, unrolled per lane (the numpy
    # cumsum order, column at a time -- no gathers, no transposes).
    cb = [b_s[0]]
    cbr = [_no_fma(b_s[0] * r_s[0])]
    for j in range(1, n):
        cb.append(cb[-1] + b_s[j])
        cbr.append(cbr[-1] + _no_fma(b_s[j] * r_s[j]))
    # absorbed_j = r_s[j] * cb[j-1] - cbr[j-1]; lane 0 is the explicit
    # r*0 - 0 the reference computes (exactly +0, but kept literal).
    k = (r_s[0] * 0.0 - 0.0 <= vol).astype(jnp.int64)
    for j in range(1, n):
        k = k + (_no_fma(r_s[j] * cb[j - 1]) - cbr[j - 1] <= vol)
    k = k - 1
    cb_k, cbr_k = cb[0], cbr[0]
    for j in range(1, n):
        at_j = k == j
        cb_k = jnp.where(at_j, cb[j], cb_k)
        cbr_k = jnp.where(at_j, cbr[j], cbr_k)
    level = (vol + cbr_k) / cb_k
    level = jnp.where(zero, ready.min(axis=-1), level)
    split_cols = []
    for j in range(n):
        gap = level - r0[j]
        split_cols.append(
            jnp.where((gap > _EPS) & ~zero, b0[j] * gap, 0.0)
        )
    return level, jnp.stack(split_cols, axis=-1)


def _segment_first_lexmin(scores, level_key, inst, n_inst):
    """Per-instance argmin by ``(score, level, row order)``.

    The device twin of the numpy loop's instance-keyed
    ``np.lexsort((arange, level_key, scores, inst))`` + first-of-segment
    pick: cascade segment minima with exact float-equality eligibility
    masks.  ``inf == inf`` compares True, so fully-dead instances (all
    rows invalid) still resolve to their first row, exactly like the
    lexsort does.
    """
    import jax
    import jax.numpy as jnp

    n_rows = scores.shape[0]
    min_score = jax.ops.segment_min(scores, inst, num_segments=n_inst)
    elig = scores == jnp.take(min_score, inst)
    min_level = jax.ops.segment_min(
        jnp.where(elig, level_key, jnp.inf), inst, num_segments=n_inst
    )
    elig = elig & (level_key == jnp.take(min_level, inst))
    row_id = jnp.arange(n_rows)
    best = jax.ops.segment_min(
        jnp.where(elig, row_id, n_rows), inst, num_segments=n_inst
    )
    return best


def _upcoming_targets_j(step_cfg, prev_same, n_s, config, scfg, i, p_max):
    """Device twin of ``_GridState.upcoming_targets_table`` at step ``i``.

    The numpy version slices the step window ``[i+1:]``; here the window
    start is a traced scalar, so the full-width masks carry the window
    condition instead.  Columns before the window contribute nothing to
    the integer slot cumsum (int addition is exact in any order), and the
    scatter becomes a one-hot max over a ``NO_CONFIG`` floor (slots are
    unique per instance: first occurrences of distinct configs).
    """
    import jax.numpy as jnp

    s_max = step_cfg.shape[1]
    s = i + 1
    kk = jnp.arange(s_max)[None, :]
    in_win = (kk >= s) & (kk < n_s[:, None])
    first_occ = prev_same < s
    held = (step_cfg[:, :, None] == config[:, None, :]).any(axis=2)
    held = held | (step_cfg == scfg[:, None])
    avail = first_occ & ~held & in_win
    slot = jnp.cumsum(avail.astype(jnp.int64), axis=1) - 1
    take = avail & (slot < p_max)
    onehot = take[:, :, None] & (
        slot[:, :, None] == jnp.arange(p_max)[None, None, :]
    )
    targets = jnp.max(
        jnp.where(onehot, step_cfg[:, :, None], NO_CONFIG), axis=1
    )
    return targets, avail.sum(axis=1)


def _rollout_j(
    tab, inst, cfg, free, barrier, start_step, horizon: int
):
    """Device twin of ``greedy._rollout_rows`` (fixed-trip, live-masked).

    ``start_step`` is traced; the loop runs exactly ``horizon``
    iterations with per-iteration live masks (numpy's early ``break`` and
    past-end iterations are arithmetically inert), then adds the
    aggregate-bandwidth tail as two separate additions, matching the
    reference's float evaluation order.
    """
    import jax
    import jax.numpy as jnp

    bw_rows = jnp.take(tab["bw"], inst, axis=0)
    real_rows = jnp.take(tab["real"], inst, axis=0)
    t_rows = jnp.take(tab["t_recfg"], inst)[:, None]
    n_s_rows = jnp.take(tab["n_s"], inst)
    cfg_tab = jnp.take(tab["step_cfg"], inst, axis=0)
    vol_tab = jnp.take(tab["step_vol"], inst, axis=0)
    s_max = cfg_tab.shape[1]

    def body(t, carry):
        cfg, free, barrier = carry
        k = start_step + t
        kc = jnp.minimum(k, s_max - 1)
        live = k < n_s_rows
        cfg_k = jax.lax.dynamic_slice_in_dim(cfg_tab, kc, 1, axis=1)
        vol_k = jnp.where(
            live,
            jax.lax.dynamic_slice_in_dim(vol_tab, kc, 1, axis=1)[:, 0],
            0.0,
        )
        extra = jnp.where(cfg == cfg_k, 0.0, t_rows)
        ready = jnp.maximum(barrier[:, None], free + extra)
        ready = jnp.where(real_rows, ready, _BIG)
        level, split = _waterfill_j(ready, bw_rows, vol_k)
        active = (split > 0.0) & live[:, None]
        free = jnp.where(active, level[:, None], free)
        cfg = jnp.where(active, cfg_k, cfg)
        barrier = jnp.where(live, level, barrier)
        return cfg, free, barrier

    cfg, free, barrier = jax.lax.fori_loop(
        0, horizon, body, (cfg, free, barrier)
    )
    end_step = jnp.minimum(n_s_rows, start_step + horizon)
    has_tail = end_step < n_s_rows
    suffix_vol = jnp.take(tab["suffix_vol"], inst, axis=0)
    suffix_changes = jnp.take(tab["suffix_changes"], inst, axis=0)
    tail_vol = (
        jnp.take_along_axis(suffix_vol, end_step[:, None], axis=1)[:, 0]
        / jnp.take(tab["bw_sum"], inst)
    )
    barrier = jnp.where(has_tail, barrier + tail_vol, barrier)
    tail_rec = (
        jnp.take_along_axis(suffix_changes, end_step[:, None], axis=1)[:, 0]
        * jnp.take(tab["t_recfg"], inst)
        / jnp.take(tab["n_p"], inst)
    )
    return jnp.where(has_tail, barrier + tail_rec, barrier)


def _chain_step(horizon: int, with_bypass: bool, tab, carry, xs):
    """One fused CHAIN planning step (the ``lax.scan`` body).

    Refresh dynamic candidate masks from the carried ``free``, construct
    every candidate row's trial state (reserve retargets toward upcoming
    configs), optionally append bypass-twin rows, water-fill, roll out,
    select the per-instance lexicographic winner, and advance the
    carried planner state for live instances only.  Module-level (not a
    closure) so parity tests can replay single steps eagerly.
    """
    jax = _require_jax()
    import jax.numpy as jnp

    config, free, barrier, installed = carry
    i, scfg_b, svol_b = xs
    cand_inst = tab["cand_inst"]
    live_b = i < tab["n_s"]

    # Dynamic soonest-free prefix rows, recomputed from the carried
    # free times (the numpy loop refreshes live instances in place;
    # dead instances' free is frozen, so recomputation is identical).
    ranks_inst = _stable_ranks_j(
        jnp.where(tab["real"], free, jnp.inf)
    )
    dyn_mask = (
        jnp.take(ranks_inst, cand_inst, axis=0)
        < tab["dyn_size"][:, None]
    ) & jnp.take(tab["real"], cand_inst, axis=0)
    mask = jnp.where(
        tab["dyn_row"][:, None], dyn_mask, tab["cand_mask"]
    )
    size = mask.sum(axis=1)
    valid = size != jnp.take(tab["n_p"], cand_inst)

    free_rows = jnp.take(free, cand_inst, axis=0)
    cfg_rows = jnp.take(config, cand_inst, axis=0)
    ranks = _stable_ranks_j(jnp.where(mask, free_rows, jnp.inf))
    targets, n_avail = _upcoming_targets_j(
        tab["step_cfg"], tab["prev_same"], tab["n_s"], config,
        scfg_b, i, tab["real"].shape[1],
    )
    n_tgt = jnp.minimum(size, jnp.take(n_avail, cand_inst))
    assigned = mask & (ranks < n_tgt[:, None])
    tgt = jnp.take_along_axis(
        jnp.take(targets, cand_inst, axis=0), ranks, axis=1
    )
    t_recfg_rows = jnp.take(tab["t_recfg"], cand_inst)[:, None]
    trial_free = jnp.where(
        assigned, free_rows + t_recfg_rows, free_rows
    )
    trial_cfg = jnp.where(assigned, tgt, cfg_rows)

    inst = cand_inst
    reserved_mask = mask
    byp_h = jnp.zeros_like(trial_cfg)
    if with_bypass:
        # Bypass twin rows appended after ALL base rows: the global
        # candidate (= row) order matches the numpy loop, so the
        # row-id tie-break selects identically.
        depth_tab = tab["depth_tab"]
        c_max = depth_tab.shape[1]
        scfg_r = jnp.take(scfg_b, cand_inst)
        inst_rows = jnp.take(installed, cand_inst, axis=0)
        known = (inst_rows >= 0) & (inst_rows < c_max)
        plane_hops = jnp.where(
            known,
            depth_tab[
                cand_inst[:, None],
                jnp.clip(inst_rows, 0, c_max - 1),
                jnp.clip(scfg_r, 0, c_max - 1)[:, None],
            ],
            0,
        )
        hops = jnp.where(
            reserved_mask | (trial_cfg == scfg_r[:, None]),
            0,
            plane_hops,
        )
        inst = jnp.concatenate([inst, inst])
        trial_cfg = jnp.concatenate([trial_cfg, trial_cfg], axis=0)
        trial_free = jnp.concatenate([trial_free, trial_free], axis=0)
        reserved_mask = jnp.concatenate(
            [reserved_mask, reserved_mask], axis=0
        )
        valid = jnp.concatenate([valid, valid & hops.any(axis=1)])
        byp_h = jnp.concatenate(
            [jnp.zeros_like(hops), hops], axis=0
        )
    bypassing = byp_h >= 2
    cfg_i = jnp.take(scfg_b, inst)[:, None]
    vol_i = jnp.take(svol_b, inst)
    t_rows = jnp.take(tab["t_recfg"], inst)[:, None]
    extra = jnp.where(
        (trial_cfg == cfg_i) | bypassing, 0.0, t_rows
    )
    ready = jnp.maximum(
        jnp.take(barrier, inst)[:, None], trial_free + extra
    )
    ready = jnp.where(
        reserved_mask | ~jnp.take(tab["real"], inst, axis=0),
        _BIG,
        ready,
    )
    bw_rows = jnp.take(tab["bw"], inst, axis=0)
    bw_eff = jnp.where(
        bypassing, bw_rows / jnp.maximum(byp_h, 1), bw_rows
    )
    level, split = _waterfill_j(ready, bw_eff, vol_i)
    valid = valid & (
        (vol_i <= _EPS) | (split > 0.0).any(axis=1)
    )
    n_inst = tab["n_s"].shape[0]
    feasible = (
        jax.ops.segment_max(
            valid.astype(jnp.int32), inst, num_segments=n_inst
        )
        > 0
    )
    active = split > 0.0
    new_free = jnp.where(active, level[:, None], trial_free)
    new_cfg = jnp.where(active & ~bypassing, cfg_i, trial_cfg)
    scores = _rollout_j(
        tab, inst, new_cfg, new_free, level, i + 1, horizon
    )
    scores = jnp.where(valid, scores, jnp.inf)
    level_key = jnp.where(valid, level, jnp.inf)
    best = _segment_first_lexmin(scores, level_key, inst, n_inst)

    split_b = jnp.take(split, best, axis=0)
    byph_b = jnp.take(byp_h, best, axis=0)
    config = jnp.where(
        live_b[:, None], jnp.take(new_cfg, best, axis=0), config
    )
    free = jnp.where(
        live_b[:, None], jnp.take(new_free, best, axis=0), free
    )
    barrier = jnp.where(live_b, jnp.take(level, best), barrier)
    installed = jnp.where(
        live_b[:, None]
        & (split_b > _EPS_VOLUME)
        & ~(byph_b >= 2),
        scfg_b[:, None],
        installed,
    )
    return (config, free, barrier, installed), (
        split_b, byph_b, feasible,
    )


def _build_chain_scan(horizon: int, with_bypass: bool):
    """jit-wrap `_chain_step` as a ``lax.scan`` over planning steps."""
    jax = _require_jax()
    import jax.numpy as jnp

    body = functools.partial(_chain_step, horizon, with_bypass)

    def run(tab):
        s_max = tab["step_cfg"].shape[1]
        carry = (
            tab["config"], tab["free"],
            jnp.zeros_like(tab["t_recfg"]), tab["installed"],
        )
        xs = (
            jnp.arange(s_max),
            tab["step_cfg"].T,
            tab["step_vol"].T,
        )
        _, ys = jax.lax.scan(functools.partial(body, tab), carry, xs)
        return ys

    # The XLA program takes its name from the function: the base scan
    # compiles as ``jit_run`` (the benchmark's ``fused_scan_ms``), its
    # Topology-Bypassing twin with relay rows as ``jit_relay_run``
    # (``relay_scan_ms``); pinned by tests/test_trace_spans.py.
    if with_bypass:
        run.__name__ = run.__qualname__ = "relay_run"
    return jax.jit(run)


def _build_independent_scan(split_mode: bool):
    """Fused INDEPENDENT-mode packing: argmin or per-row water-fill."""
    jax = _require_jax()
    import jax.numpy as jnp

    def step(tab, carry, xs):
        config, free = carry
        i, scfg_b, svol_b = xs
        live = i < tab["n_s"]
        extra = jnp.where(
            config == scfg_b[:, None], 0.0, tab["t_recfg"][:, None]
        )
        if split_mode:
            ready = jnp.where(tab["real"], free + extra, _BIG)
            vol_i = jnp.where(live, svol_b, 0.0)
            level, split = _waterfill_j(ready, tab["bw"], vol_i)
            active = (split > 0.0) & live[:, None]
            free = jnp.where(active, level[:, None], free)
            config = jnp.where(active, scfg_b[:, None], config)
            return (config, free), split
        finish = free + extra + svol_b[:, None] / tab["bw"]
        finish = jnp.where(tab["real"], finish, jnp.inf)
        j = jnp.argmin(finish, axis=1)
        fin_j = jnp.take_along_axis(finish, j[:, None], axis=1)[:, 0]
        onehot = (
            jnp.arange(free.shape[1])[None, :] == j[:, None]
        ) & live[:, None]
        free = jnp.where(onehot, fin_j[:, None], free)
        config = jnp.where(onehot, scfg_b[:, None], config)
        return (config, free), j

    def run(tab):
        s_max = tab["step_cfg"].shape[1]
        carry = (tab["config"], tab["free"])
        xs = (
            jnp.arange(s_max),
            tab["step_cfg"].T,
            tab["step_vol"].T,
        )
        _, ys = jax.lax.scan(functools.partial(step, tab), carry, xs)
        return ys

    # The XLA program takes its name from the function: each scan gets a
    # name of its own, apart from the CHAIN scans' ``jit_run`` and
    # ``jit_relay_run`` that the benchmark's ``fused_scan_ms`` and
    # ``relay_scan_ms`` read.
    run.__name__ = run.__qualname__ = (
        "independent_split_scan" if split_mode else "independent_pack_scan"
    )
    return jax.jit(run)


# jit-wrapped scan programs keyed by (kind, horizon, with_bypass); jax's
# own jit cache handles the per-shape specialization underneath, once a
# power-of-two bucket of cells where the CHAIN scan is bucketed
# (`_pad_cells`).
_SCAN_CACHE: dict[tuple, object] = {}


def _chain_scan(horizon: int, with_bypass: bool):
    key = ("chain", horizon, with_bypass)
    if key not in _SCAN_CACHE:
        _SCAN_CACHE[key] = _build_chain_scan(horizon, with_bypass)
    return _SCAN_CACHE[key]


def _independent_scan(split_mode: bool):
    key = ("independent", split_mode)
    if key not in _SCAN_CACHE:
        _SCAN_CACHE[key] = _build_independent_scan(split_mode)
    return _SCAN_CACHE[key]


# The planner tables every fused scan reads, with their device dtypes
# (`_GridState` attributes; cell axis first) ...
_BASE_DTYPES = {
    "n_p": np.int64, "n_s": np.int64, "bw": np.float64, "real": bool,
    "config": np.int64, "free": np.float64, "installed": np.int64,
    "step_cfg": np.int64, "step_vol": np.float64, "t_recfg": np.float64,
}
# ... and those only the CHAIN scan reads (the last two: one row a
# candidate reserve set, not a cell).
_CHAIN_DTYPES = {
    **_BASE_DTYPES,
    "bw_sum": np.float64, "suffix_vol": np.float64,
    "suffix_changes": np.int64, "prev_same": np.int64,
    "cand_mask": bool, "cand_inst": np.int64,
}


def _host_tables(st: "_GridState", dtypes: dict) -> dict:
    return {key: np.asarray(getattr(st, key), t) for key, t in dtypes.items()}


def _on_device(tab: dict) -> dict:
    import jax.numpy as jnp

    return {key: jnp.asarray(a) for key, a in tab.items()}


def _base_tables(st: "_GridState") -> dict:
    """The shape-static planner tables, as device arrays (float64/int64)."""
    return _on_device(_host_tables(st, _BASE_DTYPES))


# Tables indexed by candidate row, not by cell.
_ROW_KEYS = ("cand_mask", "cand_inst", "dyn_row", "dyn_size")
# A padded cell's entries where zero is not inert: no config installed
# or asked for, no earlier step of the same config, every plane present
# (n_p) with finite nonzero bandwidth (the rollout tail divides by
# ``bw_sum`` and ``n_p``).
_PAD_FILL = {
    "config": NO_CONFIG, "installed": NO_CONFIG, "step_cfg": NO_CONFIG,
    "prev_same": -1, "bw": 1.0, "bw_sum": 1.0,
}


def _pad_cells(tab: dict, cand_start: np.ndarray, b_pad: int) -> dict:
    """Append inert cells to the CHAIN tables' cell axis, up to ``b_pad``.

    A padded cell is never live (``n_s = 0``) and has no real plane
    (`_PAD_FILL` for the rest).  It gets as many candidate rows as the
    grid's largest block, each reserving every plane, so ``valid``
    (``size != n_p``) rejects them all.  The real rows keep their order,
    so the row-id tie-break selects as before, and every reduction over
    rows is per cell, so a padded cell touches no real one.
    """
    b = tab["n_s"].shape[0]
    if b_pad == b:
        return tab
    pad = b_pad - b
    p_max = tab["bw"].shape[1]
    fill = {**_PAD_FILL, "n_p": p_max}
    out = {
        key: np.concatenate(
            [a, np.full((pad,) + a.shape[1:], fill.get(key, 0), a.dtype)]
        )
        for key, a in tab.items()
        if key not in _ROW_KEYS
    }
    n_rows = tab["cand_inst"].shape[0]
    block = int(np.diff(np.r_[cand_start, n_rows]).max())
    rows = pad * block
    out.update(
        cand_mask=np.concatenate(
            [tab["cand_mask"], np.ones((rows, p_max), bool)]
        ),
        cand_inst=np.concatenate(
            [tab["cand_inst"], np.repeat(np.arange(b, b_pad), block)]
        ),
        dyn_row=np.concatenate([tab["dyn_row"], np.zeros(rows, bool)]),
        dyn_size=np.concatenate(
            [tab["dyn_size"], np.full(rows, -1, np.int64)]
        ),
    )
    return out


def _chain_tables(
    st: "_GridState", with_bypass: bool, b_pad: int | None = None
) -> dict:
    """Every table the fused CHAIN scan reads (call under `x64`), the cell
    axis padded to ``b_pad`` cells where given (`_pad_cells`)."""
    tab = _host_tables(st, _CHAIN_DTYPES)
    # Dynamic rows: soonest-free prefixes of sizes 0..3, refreshed per
    # step on device.  `dyn_size` holds the prefix size per dynamic row
    # (-1 for static rows, which never match a rank).
    dyn_row = np.zeros(st.cand_inst.shape[0], dtype=bool)
    dyn_size = np.full(st.cand_inst.shape[0], -1, dtype=np.int64)
    for bi in st.dyn_insts:
        start = int(st.cand_start[bi])
        dyn_row[start:start + 4] = True
        dyn_size[start:start + 4] = np.arange(4)
    tab.update(dyn_row=dyn_row, dyn_size=dyn_size)
    if with_bypass:
        tab["depth_tab"] = np.asarray(st.depth_tab, np.int64)
    if b_pad is not None:
        tab = _pad_cells(tab, st.cand_start, b_pad)
    return _on_device(tab)


def fused_chain_grid_chosen(
    st: "_GridState", rollout_horizon: int, b_pad: int | None = None
) -> list[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Plan every CHAIN step of the grid in one device program.

    Returns the same per-step ``(live_insts, split, byp_h)`` tuples the
    numpy loop (`greedy._chain_grid_decisions`) accumulates -- bitwise
    identical -- for the shared Decisions materialization epilogue.
    ``b_pad`` pads the scan's cell axis (`repro.core.ir.backends.route`
    gives it); the padded cells' outputs are cut off first.  Raises the same
    "no feasible reserve set" assertion on infeasible steps.
    """
    _require_jax()
    b = len(st.cells)
    with_bypass = st.bypass_depth >= 2 and st.depth_tab.shape[1] > 0
    with x64():
        ys = _chain_scan(rollout_horizon, with_bypass)(
            _chain_tables(st, with_bypass, b_pad)
        )
        split_s = np.asarray(ys[0], dtype=np.float64)[:, :b]
        byph_s = np.asarray(ys[1], dtype=np.int64)[:, :b]
        feas_s = np.asarray(ys[2], dtype=bool)[:, :b]
    chosen = []
    for i in range(st.s_max):
        live = i < st.n_s
        if not live.any():
            break
        assert feas_s[i][live].all(), "no feasible reserve set"
        rows = np.nonzero(live)[0]
        chosen.append((rows, split_s[i][rows], byph_s[i][rows]))
    return chosen


def fused_independent_grid_chosen(
    st: "_GridState",
) -> list[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Fused least-finish-time packing; per-step tuples as the numpy loop."""
    _require_jax()
    with x64():
        ys = _independent_scan(split_mode=False)(_base_tables(st))
        j_s = np.asarray(ys, dtype=np.int64)
    chosen = []
    for i in range(st.s_max):
        live = i < st.n_s
        if not live.any():
            break
        rows = np.nonzero(live)[0]
        chosen.append((rows, j_s[i][rows], st.step_vol[rows, i]))
    return chosen


def fused_independent_split_grid_chosen(
    st: "_GridState",
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Fused per-row-volume water-fill packing (INDEPENDENT split mode)."""
    _require_jax()
    with x64():
        ys = _independent_scan(split_mode=True)(_base_tables(st))
        split_s = np.asarray(ys, dtype=np.float64)
    chosen = []
    for i in range(st.s_max):
        live = i < st.n_s
        if not live.any():
            break
        chosen.append((np.nonzero(live)[0], split_s[i]))
    return chosen
