"""Fused on-device grid planner: parity, selection, and kernel suite.

Contract under test (`repro.core.ir.fused` + the ``planner=`` plumbing):

* the fused ``lax.scan`` planner produces BITWISE-identical decisions to
  the per-step numpy loop in every mode x bypass x split combination
  (property-tested over random grids);
* the pallas timing kernel handles Topology-Bypassing batches natively
  (no numpy delegation) with bitwise CCT/attribution parity across
  padding shapes, and padded cells never leak into real cells;
* ``attribution=True`` composes with the fused planner;
* ``select_planner_by_size`` honors threshold / env / explicit choice;
* the fused planner's numeric primitives (`_no_fma` FMA guard, the
  odd-even sorting network, pairwise stable ranks, the column-wise
  water-fill) match their numpy references bitwise -- eager AND jitted,
  which is where XLA:CPU FMA contraction would otherwise bite.

Run with ``JAX_PLATFORMS=cpu`` in CI so these legs exercise the exact
code path a CPU-only host gets.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import BatchInstance, OpticalFabric, batch_evaluate
from repro.core.greedy import _GridState, swot_greedy_grid
from repro.core.ir.backends import (
    BackendUnavailable,
    DEFAULT_FUSED_PLANNER_THRESHOLD,
    ENV_FUSED_PLANNER_THRESHOLD,
    get_backend,
    select_planner_by_size,
    x64,
)
from repro.core.ir.engine import _BIG, pack_instances, waterfill_batch
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
# Planner auto-selection policy
# ---------------------------------------------------------------------------
class TestSelectPlanner:
    def test_threshold_default(self, monkeypatch):
        monkeypatch.delenv(ENV_FUSED_PLANNER_THRESHOLD, raising=False)
        at = DEFAULT_FUSED_PLANNER_THRESHOLD
        assert select_planner_by_size(at - 1) == "step"
        assert select_planner_by_size(at) == "fused"

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv(ENV_FUSED_PLANNER_THRESHOLD, "1")
        assert select_planner_by_size(1) == "fused"
        monkeypatch.setenv(ENV_FUSED_PLANNER_THRESHOLD, "100000")
        assert select_planner_by_size(1024) == "step"

    def test_explicit_wins(self, monkeypatch):
        monkeypatch.setenv(ENV_FUSED_PLANNER_THRESHOLD, "1")
        assert select_planner_by_size(9999, explicit="step") == "step"
        assert select_planner_by_size(1, explicit="fused") == "fused"

    def test_rejects_unknown(self):
        with pytest.raises(ValueError, match="planner"):
            select_planner_by_size(4, explicit="magic")

    def test_bad_env(self, monkeypatch):
        monkeypatch.setenv(ENV_FUSED_PLANNER_THRESHOLD, "soon")
        with pytest.raises(ValueError):
            select_planner_by_size(4)


# ---------------------------------------------------------------------------
# Pallas kernel: native bypass batches, padding parity, no delegation
# ---------------------------------------------------------------------------
def _bypass_instances(n: int) -> list[BatchInstance]:
    """n bypass-winning cells (pre-staged rotations, high t_recfg)."""
    pattern = pairwise_alltoall(8, 8e6)
    cells = [
        (
            OpticalFabric(
                8, 4, t_recfg=3.2e-3 * (1 + 0.1 * i)
            ).prestaged(pattern.steps[0].config),
            pattern,
        )
        for i in range(n)
    ]
    plans = swot_greedy_grid(cells, backend="numpy", bypass_depth=2)
    assert any(
        plan.decisions.bypass is not None and any(plan.decisions.bypass)
        for plan in plans
    ), "fixture produced no relays; bypass leg would be vacuous"
    return [
        BatchInstance(fabric, pattern, plan.decisions)
        for (fabric, pattern), plan in zip(cells, plans)
    ]


class TestPallasBypass:
    @pytest.fixture()
    def pallas(self):
        try:
            return get_backend("pallas")
        except BackendUnavailable as exc:
            pytest.skip(f"pallas unavailable: {exc}")

    # Batch sizes straddling the padding buckets (1 -> 1, 3 -> 4,
    # 5 -> 8): padded rows must not perturb the real bypass cells.
    @pytest.mark.parametrize("n", [1, 3, 5])
    def test_bypass_parity_across_padding(self, pallas, n):
        instances = _bypass_instances(n)
        ref = batch_evaluate(instances, backend="numpy", attribution=True)
        got = batch_evaluate(instances, backend="pallas", attribution=True)
        assert np.array_equal(got.cct, ref.cct)
        assert np.array_equal(
            got.n_reconfigurations, ref.n_reconfigurations
        )
        for field in ("t_xmit", "t_bypass", "t_recfg_wait",
                      "t_recfg_hidden", "t_idle"):
            assert np.array_equal(
                getattr(got.attribution, field),
                getattr(ref.attribution, field),
            ), f"pallas attribution field {field} diverges on bypass"

    def test_no_numpy_delegation(self, pallas, monkeypatch):
        """The kernel itself must evaluate bypass batches.

        Pre-PR the pallas backend silently handed any batch containing
        relay routes to ``_timing_numpy``; sabotaging that fallback
        proves the kernel path is the one running.
        """
        import repro.core.ir.backends as B

        instances = _bypass_instances(2)  # planned before the sabotage

        def boom(*args, **kwargs):
            raise AssertionError(
                "pallas delegated a bypass batch to numpy"
            )

        monkeypatch.setattr(B, "_timing_numpy", boom)
        packed = pack_instances(instances, None)
        result = pallas.derive_timing(packed)
        assert np.all(result.feasible)


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
