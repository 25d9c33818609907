"""Fused on-device grid planner: parity, selection, and kernel suite.

Contract under test (`repro.core.ir.fused` + the ``planner=`` plumbing):

* the fused ``lax.scan`` planner produces BITWISE-identical decisions to
  the per-step numpy loop in every mode x bypass x split combination
  (property-tested over random grids);
* ``attribution=True`` composes with the fused planner;
* where the CHAIN scan's cell axis is padded to a power of two (CHAIN
  grids under the default threshold on a TPU), decisions stay
  bitwise-equal to the step loop's, padded instances never reach the
  per-step tuples, and grids of one bucket share one compiled program;
  elsewhere each grid keeps its own shape;
* ``route`` honors threshold and explicit choice, gives the planner,
  backend and padded cells of the table below on both platforms, only
  CHAIN sweep grids under the default threshold on a TPU take the
  chip's threshold, and a grid planned on numpy never imports jax;
* the fused planner's numeric primitives (`_no_fma` FMA guard, the
  odd-even sorting network, pairwise stable ranks, the column-wise
  water-fill) match their numpy references bitwise -- eager AND jitted,
  which is where XLA:CPU FMA contraction would otherwise bite.

Run with ``JAX_PLATFORMS=cpu`` in CI so these legs exercise the exact
code path a CPU-only host gets.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import OpticalFabric
from repro.core import greedy
from repro.core.greedy import _GridState, swot_greedy_grid
from repro.core.ir.backends import (
    FUSED_FROM,
    TPU_CHAIN_FUSED_FROM,
    _bucket,
    route,
    x64,
)
from repro.core.ir.engine import _BIG, waterfill_batch
from repro.core.patterns import pairwise_alltoall, rabenseifner_allreduce
from repro.core.schedule import DependencyMode
from repro.core.scheduler import plan_grid

jax = pytest.importorskip("jax")

from repro.core.ir import fused  # noqa: E402  (needs jax)


def _assert_same_plans(a, b):
    assert len(a) == len(b)
    for pa, pb in zip(a, b):
        assert pa.decisions == pb.decisions
        assert pa.cct == pb.cct  # bitwise: same decisions, same scorer
        assert pa.n_reconfigurations == pb.n_reconfigurations


# ---------------------------------------------------------------------------
# Fused-vs-per-step planner parity (the tentpole invariant)
# ---------------------------------------------------------------------------
@st.composite
def _grids(draw):
    """Small random grids; fixed node/plane counts bound jit recompiles."""
    n_nodes = 8
    n_cells = draw(st.integers(min_value=1, max_value=3))
    cells = []
    for _ in range(n_cells):
        maker = draw(
            st.sampled_from([pairwise_alltoall, rabenseifner_allreduce])
        )
        size = draw(st.floats(min_value=1e5, max_value=2e8))
        t_recfg = draw(st.sampled_from([0.0, 50e-6, 3.2e-3]))
        pattern = maker(n_nodes, size)
        fabric = OpticalFabric(n_nodes, 4, t_recfg=t_recfg)
        if draw(st.booleans()):
            fabric = fabric.prestaged(pattern.steps[0].config)
        cells.append((fabric, pattern))
    return cells


class TestFusedChainParity:
    @settings(max_examples=15, deadline=None)
    @given(cells=_grids(), enum_planes=st.sampled_from([2, 8]))
    def test_chain(self, cells, enum_planes):
        # enum_planes=2 forces the dynamic soonest-free reserve rows
        # (the at-scale path); 8 keeps full subset enumeration.
        step = swot_greedy_grid(
            cells, max_enumerated_planes=enum_planes, planner="step"
        )
        fus = swot_greedy_grid(
            cells, max_enumerated_planes=enum_planes, planner="fused"
        )
        _assert_same_plans(step, fus)

    @settings(max_examples=10, deadline=None)
    @given(cells=_grids())
    def test_chain_bypass(self, cells):
        step = swot_greedy_grid(cells, bypass_depth=2, planner="step")
        fus = swot_greedy_grid(cells, bypass_depth=2, planner="fused")
        _assert_same_plans(step, fus)

    @settings(max_examples=10, deadline=None)
    @given(cells=_grids(), split=st.booleans())
    def test_independent(self, cells, split):
        step = swot_greedy_grid(
            cells,
            mode=DependencyMode.INDEPENDENT,
            independent_split=split,
            planner="step",
        )
        fus = swot_greedy_grid(
            cells,
            mode=DependencyMode.INDEPENDENT,
            independent_split=split,
            planner="fused",
        )
        _assert_same_plans(step, fus)

    def test_padded_cell_isolation(self):
        """Heterogeneous shapes: padding must not perturb real cells.

        Each cell planned inside the padded batch (different n_steps
        AND different n_planes per cell) must match the same cell
        planned alone, bitwise, under both planners.
        """
        p_a = pairwise_alltoall(8, 4e6)  # 7 steps
        p_b = rabenseifner_allreduce(8, 1e6)  # 6 steps
        cells = [
            (OpticalFabric(8, 4, t_recfg=200e-6), p_a),
            (OpticalFabric(8, 2, t_recfg=50e-6), p_b),
            (OpticalFabric(8, 3, t_recfg=3.2e-3), p_a),
        ]
        for planner in ("step", "fused"):
            batched = swot_greedy_grid(cells, planner=planner)
            for cell, plan in zip(cells, batched):
                solo = swot_greedy_grid([cell], planner=planner)[0]
                assert plan.decisions == solo.decisions
                assert plan.cct == solo.cct


# ---------------------------------------------------------------------------
# The CHAIN scan's cell axis is padded to a power of two
# ---------------------------------------------------------------------------
def _sweep(n_cells: int, planes=(4,)) -> list:
    """``n_cells`` 8-node cells over sizes, delays and ``planes``."""
    rng = np.random.default_rng(n_cells)
    cells = []
    for i in range(n_cells):
        maker = (pairwise_alltoall, rabenseifner_allreduce)[i % 2]
        pattern = maker(8, float(rng.uniform(1e5, 2e8)))
        fabric = OpticalFabric(
            8, planes[i % len(planes)],
            t_recfg=float(rng.choice([0.0, 50e-6, 200e-6, 3.2e-3])),
        )
        if i % 5 == 0:
            fabric = fabric.prestaged(pattern.steps[0].config)
        cells.append((fabric, pattern))
    return cells


class TestBucketedChain:
    @pytest.mark.parametrize(
        "n_cells, planes, bypass, enum_planes",
        [
            (3, (4, 2, 3), 0, 8),
            (70, (4,), 0, 2),
            (100, (2, 4, 3), 2, 8),
        ],
    )
    def test_parity_with_step_loop(self, n_cells, planes, bypass,
                                   enum_planes, monkeypatch):
        # A TPU as the chip rule sees it: the fused scan is bucketed.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cells = _sweep(n_cells, planes)
        kw = dict(bypass_depth=bypass, max_enumerated_planes=enum_planes)
        step = swot_greedy_grid(cells, planner="step", **kw)
        fus = swot_greedy_grid(cells, planner="fused", **kw)
        _assert_same_plans(step, fus)

    @pytest.mark.parametrize("n_cells", [3, 70])
    def test_padded_instances_never_reach_the_tuples(self, n_cells):
        cells = _sweep(n_cells, (4, 3))
        b_pad = _bucket(n_cells)
        assert b_pad > n_cells
        with x64():
            tab = fused._chain_tables(_GridState(cells), False, b_pad)
        assert tab["n_s"].shape == (b_pad,)
        fus = fused.fused_chain_grid_chosen(_GridState(cells), 24, b_pad)
        ref = greedy._chain_grid_chosen(_GridState(cells), 24)
        assert len(fus) == len(ref)
        for (rows, split, byph), (r_rows, r_split, r_byph) in zip(fus, ref):
            assert rows.max() < n_cells
            assert np.array_equal(rows, r_rows)
            assert np.array_equal(split, r_split)
            assert np.array_equal(byph, r_byph)

    def test_power_of_two_grid_keeps_its_shapes(self):
        st = _GridState(_sweep(64, (4, 2)))
        assert _bucket(64) == 64
        with x64():
            tab = fused._chain_tables(st, False, 64)
        assert tab["n_s"].shape == (64,)
        assert np.array_equal(np.asarray(tab["cand_inst"]), st.cand_inst)
        assert np.array_equal(np.asarray(tab["cand_mask"]), st.cand_mask)

    def test_one_program_per_bucket(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fused, "_SCAN_CACHE", {})
        for n_cells in (70, 100):
            swot_greedy_grid(_sweep(n_cells), planner="fused")
        assert fused._SCAN_CACHE[("chain", 24, False)]._cache_size() == 1

    def test_off_the_chip_each_grid_keeps_its_shape(self, monkeypatch):
        assert route(3, chain=True, planner="fused").cells == 3
        monkeypatch.setattr(fused, "_SCAN_CACHE", {})
        for n_cells in (3, 5):
            swot_greedy_grid(_sweep(n_cells), planner="fused")
        assert fused._SCAN_CACHE[("chain", 24, False)]._cache_size() == 2


# ---------------------------------------------------------------------------
# Attribution composes with the fused planner
# ---------------------------------------------------------------------------
class TestFusedAttribution:
    def test_plan_grid_attribution_fused(self):
        pattern = pairwise_alltoall(8, 8e6)
        cells = [
            (OpticalFabric(8, 4, t_recfg=t), pattern)
            for t in (50e-6, 3.2e-3)
        ]
        step = plan_grid(cells, planner="step", attribution=True)
        fus = plan_grid(cells, planner="fused", attribution=True)
        for s, f in zip(step, fus):
            att = f.plan.attribution
            assert att is not None
            total = np.where(att.plane_mask, att.plane_total, 0.0)
            want = np.where(att.plane_mask, f.plan.cct, 0.0)
            assert np.array_equal(total, want)
            s_att = s.plan.attribution
            for field in ("t_xmit", "t_bypass", "t_recfg_wait",
                          "t_recfg_hidden", "t_idle"):
                assert np.array_equal(
                    getattr(att, field), getattr(s_att, field)
                )


# ---------------------------------------------------------------------------
# The route: planner, timing backend and padded cells
# ---------------------------------------------------------------------------
# planner/backend/cells a grid takes, by platform and mode, at 1, 63, 64,
# 255, 256 and 1024 cells (the table the route replaced three selectors
# with, read from them).
_ROUTE_TABLE = {
    ("cpu", "CHAIN"): ("step/numpy/1", "step/numpy/63", "step/jax/64",
                       "step/jax/255", "fused/jax/256", "fused/jax/1024"),
    ("cpu", "INDEPENDENT"): ("step/numpy/1", "step/numpy/63", "step/jax/64",
                             "step/jax/255", "fused/jax/256",
                             "fused/jax/1024"),
    ("tpu", "CHAIN"): ("fused/numpy/1", "fused/numpy/64", "fused/jax/64",
                       "fused/jax/256", "fused/jax/256", "fused/jax/1024"),
    ("tpu", "INDEPENDENT"): ("step/numpy/1", "step/numpy/63", "step/jax/64",
                             "step/jax/255", "fused/jax/256",
                             "fused/jax/1024"),
}
_ROUTE_CELLS = (1, 63, 64, 255, 256, 1024)


@pytest.mark.parametrize(
    "platform, mode, n_cells, want",
    [
        (platform, mode, n, want)
        for (platform, mode), row in _ROUTE_TABLE.items()
        for n, want in zip(_ROUTE_CELLS, row)
    ],
)
def test_route_table(monkeypatch, platform, mode, n_cells, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    taken = route(n_cells, chain=mode == "CHAIN")
    assert f"{taken.planner}/{taken.backend}/{taken.cells}" == want


class TestSelectPlanner:
    def test_threshold_default(self):
        at = FUSED_FROM
        assert route(at - 1, chain=True).planner == "step"
        assert route(at, chain=True).planner == "fused"

    def test_explicit_wins(self):
        assert route(9999, chain=True, planner="step").planner == "step"
        assert route(1, chain=True, planner="fused").planner == "fused"

    def test_rejects_unknown(self):
        with pytest.raises(ValueError, match="planner"):
            route(4, chain=True, planner="magic")

    def test_chain_grid_off_the_tpu_keeps_the_default(self, monkeypatch):
        at = FUSED_FROM
        for n_cells, want in ((at - 1, "step"), (at, "fused")):
            taken = _planner_taken(
                monkeypatch, "_chain_grid_decisions",
                lambda: swot_greedy_grid(_sweep(n_cells)),
            )
            assert taken == want

    def test_small_chain_plan_leaves_jax_unimported(self):
        code = (
            "import sys\n"
            "from repro.core import OpticalFabric\n"
            "from repro.core.greedy import swot_greedy_grid\n"
            "from repro.core.patterns import pairwise_alltoall\n"
            "cells = [(OpticalFabric(8, 4, t_recfg=t), "
            "pairwise_alltoall(8, 1e6)) for t in (0.0, 5e-5, 2e-4)]\n"
            "assert len(swot_greedy_grid(cells)) == 3\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
        )
        src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
        env = {**os.environ, "PYTHONPATH": os.path.abspath(src)}
        done = subprocess.run(
            [sys.executable, "-c", code], env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert done.returncode == 0, done.stderr


class _Chosen(Exception):
    """Raised by a spy once the planner a grid took is known."""


def _planner_taken(monkeypatch, target: str, call) -> str:
    """The planner ``call()`` hands ``greedy.<target>``: its last
    argument, a `Route` (CHAIN) or the planner's name (INDEPENDENT)."""

    def spy(st, *args):
        raise _Chosen(getattr(args[-1], "planner", args[-1]))

    monkeypatch.setattr(greedy, target, spy)
    with pytest.raises(_Chosen) as got:
        call()
    return got.value.args[0]


class TestSelectPlannerOnTpu:
    """The chip's rule for CHAIN sweep grids, and where it does not reach."""

    @pytest.fixture(autouse=True)
    def _tpu(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def _chain(self, monkeypatch, n_cells, **kw):
        return _planner_taken(
            monkeypatch, "_chain_grid_decisions",
            lambda: swot_greedy_grid(_sweep(n_cells), **kw),
        )

    def test_chain_grid_switches_at_the_chip_threshold(self, monkeypatch):
        at = TPU_CHAIN_FUSED_FROM
        assert 1 <= at < FUSED_FROM
        assert self._chain(monkeypatch, at) == "fused"
        assert self._chain(monkeypatch, 3) == "fused"
        assert self._chain(monkeypatch, FUSED_FROM - 1) == "fused"
        # One cell below it (no grid at all where it is 1) keeps the loop.
        assert route(at - 1, chain=True).planner == "step"

    def test_only_the_chip_rule_buckets(self, monkeypatch):
        at = FUSED_FROM
        assert route(1, chain=True).cells == 1
        assert route(3, chain=True).cells == 4
        assert route(70, chain=True).cells == 128
        assert route(at - 1, chain=True).cells == at
        assert route(at + 44, chain=True).cells == at + 44
        b_pad = {}

        def spy(st, horizon, pad=None):
            b_pad[len(st.cells)] = pad
            raise _Chosen(pad)

        monkeypatch.setattr(fused, "fused_chain_grid_chosen", spy)
        for n_cells in (70, at - 1, at + 44):
            with pytest.raises(_Chosen):
                swot_greedy_grid(_sweep(n_cells))
        with pytest.raises(_Chosen):
            greedy.swot_greedy_chain_batch(_sweep(at + 44))
        assert b_pad == {70: 128, at - 1: at, at + 44: at + 44}

    def test_independent_grid_keeps_the_default(self, monkeypatch):
        at = FUSED_FROM
        for n_cells, want in ((at - 1, "step"), (at, "fused")):
            taken = _planner_taken(
                monkeypatch, "_independent_grid_decisions",
                lambda: swot_greedy_grid(
                    _sweep(n_cells), mode=DependencyMode.INDEPENDENT
                ),
            )
            assert taken == want

    def test_chain_batch_keeps_the_default(self, monkeypatch):
        at = FUSED_FROM
        for n_cells, want in ((at - 1, "step"), (at, "fused")):
            taken = _planner_taken(
                monkeypatch, "_chain_grid_decisions",
                lambda: greedy.swot_greedy_chain_batch(_sweep(n_cells)),
            )
            assert taken == want

    def test_env_and_explicit_still_win(self, monkeypatch):
        at = TPU_CHAIN_FUSED_FROM
        assert self._chain(monkeypatch, at, planner="step") == "step"
        assert self._chain(monkeypatch, 70, planner="step") == "step"
        assert self._chain(monkeypatch, at, planner="fused") == "fused"
        taken = route(70, chain=True, planner="fused", backend="numpy")
        assert taken == ("fused", "numpy", 128)


# ---------------------------------------------------------------------------
# Numeric primitives: bitwise parity eager AND under jit
# ---------------------------------------------------------------------------
class TestFusedPrimitives:
    @pytest.fixture(autouse=True, scope="class")
    def _x64(self):
        # The fused planner always runs under the x64 helper (bitwise
        # parity with the float64 numpy loop is the whole contract);
        # mirror it.  Class-scoped, so hypothesis examples share it.
        with x64():
            yield

    def _rand(self, seed, shape, lo=0.0, hi=1.0):
        rng = np.random.default_rng(seed)
        return rng.uniform(lo, hi, size=shape)

    def test_no_fma_is_identity_on_nonnegative(self):
        x = jax.numpy.asarray(self._rand(0, (64,)))
        assert np.array_equal(np.asarray(fused._no_fma(x)), np.asarray(x))

    @pytest.mark.parametrize("p", [1, 2, 3, 4, 8])
    def test_network_sort_matches_stable_argsort(self, p):
        key = self._rand(1, (32, p))
        # Duplicate keys in half the rows exercise stability.
        key[::2, : p // 2 + 1] = 0.5
        carry = self._rand(2, (32, p))
        k_cols = [jax.numpy.asarray(key[:, j]) for j in range(p)]
        c_cols = [jax.numpy.asarray(carry[:, j]) for j in range(p)]
        fused._network_sort_cols(k_cols, (c_cols,))
        order = np.argsort(key, axis=-1, kind="stable")
        want_k = np.take_along_axis(key, order, axis=-1)
        want_c = np.take_along_axis(carry, order, axis=-1)
        got_k = np.stack([np.asarray(c) for c in k_cols], axis=-1)
        got_c = np.stack([np.asarray(c) for c in c_cols], axis=-1)
        assert np.array_equal(got_k, want_k)
        assert np.array_equal(got_c, want_c)

    @pytest.mark.parametrize("p", [1, 2, 4, 8])
    def test_stable_ranks(self, p):
        key = self._rand(3, (32, p))
        key[1::2, : p // 2 + 1] = 0.25  # ties
        got = np.asarray(fused._stable_ranks_j(jax.numpy.asarray(key)))
        order = np.argsort(key, axis=-1, kind="stable")
        want = np.argsort(order, axis=-1, kind="stable")
        assert np.array_equal(got, want)

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(min_value=0, max_value=2**31),
        rows=st.integers(min_value=1, max_value=17),
        p=st.sampled_from([1, 2, 3, 4, 8]),
        jit=st.booleans(),
    )
    def test_waterfill_bitwise(self, seed, rows, p, jit):
        """The jit leg is the FMA-contraction regression test."""
        rng = np.random.default_rng(seed)
        ready = rng.uniform(0.0, 1e-2, size=(rows, p))
        # Mask a random subset of lanes the way _chain_step does
        # (excluded planes carry ready=_BIG), keeping >= 1 lane live.
        mask = rng.random((rows, p)) < 0.3
        mask[mask.all(axis=1), 0] = False
        ready = np.where(mask, _BIG, ready)
        bw = rng.uniform(0.5, 2.0, size=(rows, p))
        vol = rng.uniform(0.0, 1e7, size=rows)
        vol[rng.random(rows) < 0.2] = 0.0
        want_level, want_split = waterfill_batch(ready, bw, vol)
        fn = fused._waterfill_j
        if jit:
            fn = jax.jit(fn)
        got_level, got_split = fn(
            jax.numpy.asarray(ready),
            jax.numpy.asarray(bw),
            jax.numpy.asarray(vol),
        )
        assert np.array_equal(np.asarray(got_level), want_level)
        assert np.array_equal(np.asarray(got_split), want_split)
