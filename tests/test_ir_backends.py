"""Backend parity suite for the pluggable IR timing engine.

Contract: every timing backend (numpy reference, jax jit+scan) must
produce CCTs equal to the object-path oracle
(`repro.core.simulator.execute`) within the shared
tolerances on ``validate_ir``/``execute_ir``/``batch_evaluate``-covered
paths, padded cells must never leak into real-cell results, and the
instance-batched greedy must match the per-instance greedy bitwise.

Run with ``JAX_PLATFORMS=cpu`` in CI so the jax legs exercise the
exact code path a CPU-only host gets.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BatchInstance,
    OpticalFabric,
    batch_evaluate,
    evaluate_decisions,
    execute_ir,
    get_pattern,
    prestage_for,
    strawman_decisions,
    strawman_instance,
    to_ir,
    validate_ir,
)
from repro.core.greedy import (
    independent_decisions,
    swot_greedy_chain,
    swot_greedy_grid,
    swot_greedy_independent,
)
from repro.core.schedule import DependencyMode
from repro.core.ir.backends import (
    BackendUnavailable,
    JaxBackend,
    _bucket,
    get_backend,
    pad_packed,
    relay_buckets,
    resolve_backend,
)
from repro.core.ir.engine import pack_instances
from repro.core.milp import solve_milp
from repro.core.scheduler import plan_grid, swot_schedule
from repro.core.simulator import execute
from repro.core.tolerances import TOL

BACKEND_NAMES = ("numpy", "jax")


def _backend_or_skip(name: str):
    try:
        return get_backend(name)
    except BackendUnavailable as exc:
        pytest.skip(f"backend {name} unavailable: {exc}")


# Module-scoped: the backends are process-wide singletons, and the
# property tests below take the fixture under @given.
@pytest.fixture(scope="module", params=BACKEND_NAMES)
def backend(request):
    return _backend_or_skip(request.param)


@st.composite
def _instances(draw):
    alg = draw(
        st.sampled_from(
            ["rabenseifner_allreduce", "pairwise_alltoall", "bruck_alltoall"]
        )
    )
    if alg == "rabenseifner_allreduce":
        n = draw(st.sampled_from([2, 4, 8]))
    else:
        n = draw(st.integers(min_value=2, max_value=10))
    size = draw(st.floats(min_value=1e5, max_value=2e8))
    planes = draw(st.integers(min_value=1, max_value=4))
    t_recfg = draw(st.sampled_from([0.0, 50e-6, 200e-6]))
    prestaged = draw(st.booleans())
    return alg, n, size, planes, t_recfg, prestaged


def _cell(inst):
    alg, n, size, planes, t_recfg, prestaged = inst
    pattern = get_pattern(alg, n, size)
    fabric = OpticalFabric(n, planes, t_recfg=t_recfg)
    if prestaged:
        fabric = prestage_for(fabric, pattern)
    return fabric, pattern


class TestBackendOracleParity:
    @settings(max_examples=25, deadline=None)
    @given(inst=_instances())
    def test_batch_evaluate_matches_object_oracle(self, backend, inst):
        fabric, pattern = _cell(inst)
        decisions = strawman_decisions(fabric, pattern)
        obj = execute(fabric, pattern, decisions)
        res = batch_evaluate(
            [BatchInstance(fabric, pattern, decisions)], backend=backend
        )
        assert res.cct[0] == pytest.approx(obj.cct, abs=TOL)
        assert (
            int(res.n_reconfigurations[0]) == obj.total_reconfigurations
        )
        assert bool(res.feasible[0]) and bool(res.volume_ok[0])

    @settings(max_examples=25, deadline=None)
    @given(inst=_instances())
    def test_validate_execute_and_backend_agree(self, backend, inst):
        """validate_ir accepts the oracle schedule and every backend's
        evaluate_decisions reproduces execute_ir's CCT reduction."""
        fabric, pattern = _cell(inst)
        decisions = strawman_decisions(fabric, pattern)
        schedule = execute(fabric, pattern, decisions)
        ir = to_ir(schedule)
        validate_ir(ir)  # backend-independent legality
        metrics = execute_ir(ir)
        via_backend = evaluate_decisions(
            fabric, pattern, decisions, backend=backend
        )
        assert via_backend.cct == pytest.approx(metrics.cct, abs=TOL)
        assert (
            via_backend.n_reconfigurations == metrics.n_reconfigurations
        )
        np.testing.assert_allclose(
            via_backend.plane_busy, metrics.plane_busy, atol=TOL
        )

    @settings(max_examples=15, deadline=None)
    @given(inst=_instances(), offset=st.floats(min_value=0.0, max_value=1e-3))
    def test_plane_ready_offsets_match_object_path(
        self, backend, inst, offset
    ):
        fabric, pattern = _cell(inst)
        decisions = strawman_decisions(fabric, pattern)
        ready = tuple(
            offset * (j + 1) for j in range(fabric.n_planes)
        )
        obj = execute(fabric, pattern, decisions, plane_ready=ready)
        via = evaluate_decisions(
            fabric, pattern, decisions, plane_ready=ready, backend=backend
        )
        assert via.cct == pytest.approx(obj.cct, abs=TOL)


class TestPaddingIsolation:
    def _mixed_batch(self):
        """Heterogeneous (steps, planes) instances: padding differs per
        row, so any cross-row leak shows up as a CCT shift."""
        specs = [
            ("ring_allreduce", 8, 10e6, 1, 50e-6),
            ("pairwise_alltoall", 10, 3e6, 4, 200e-6),
            ("rabenseifner_allreduce", 8, 40e6, 2, 0.0),
            ("bruck_alltoall", 5, 7e6, 3, 100e-6),
            ("rabenseifner_allreduce", 4, 1e6, 4, 400e-6),
        ]
        out = []
        for alg, n, size, planes, t_recfg in specs:
            pattern = get_pattern(alg, n, size)
            fabric = prestage_for(
                OpticalFabric(n, planes, t_recfg=t_recfg), pattern
            )
            out.append(
                BatchInstance(
                    fabric, pattern, strawman_decisions(fabric, pattern)
                )
            )
        return out

    def test_padded_cells_never_leak_into_real_ccts(self, backend):
        """Regression: a row's result must be independent of its batch
        companions (i.e. of how much padding the batch forces on it)."""
        instances = self._mixed_batch()
        together = batch_evaluate(instances, backend=backend)
        for k, inst in enumerate(instances):
            alone = batch_evaluate([inst], backend=backend)
            assert together.cct[k] == alone.cct[0], (
                f"instance {k} CCT changed when batched: "
                f"{together.cct[k]} vs {alone.cct[0]}"
            )
            assert (
                together.n_reconfigurations[k]
                == alone.n_reconfigurations[0]
            )
            n_p = inst.fabric.n_planes
            np.testing.assert_array_equal(
                together.plane_busy[k, :n_p], alone.plane_busy[0, :n_p]
            )
            # Padded plane columns stay exactly zero.
            assert not together.plane_busy[k, n_p:].any()

    def test_backends_agree_on_mixed_batch(self):
        instances = self._mixed_batch()
        results = {}
        for name in BACKEND_NAMES:
            try:
                results[name] = batch_evaluate(instances, backend=name)
            except BackendUnavailable:
                continue
        ref = results["numpy"]
        for name, res in results.items():
            np.testing.assert_allclose(
                res.cct, ref.cct, atol=TOL, err_msg=name
            )
            np.testing.assert_array_equal(
                res.n_reconfigurations, ref.n_reconfigurations
            )
            np.testing.assert_array_equal(res.feasible, ref.feasible)
            np.testing.assert_array_equal(res.volume_ok, ref.volume_ok)


class TestBucketing:
    def test_bucket_rounds_to_next_power_of_two(self):
        assert [_bucket(n) for n in (1, 2, 3, 5, 8, 9, 64, 65)] == [
            1, 2, 4, 8, 8, 16, 64, 128,
        ]

    def test_pad_packed_marks_padding_inert(self):
        instances = [
            strawman_instance(
                OpticalFabric(8, 2, t_recfg=1e-4),
                get_pattern("ring_allreduce", 8, 1e6),
                prestage=True,
            )
        ]
        packed = pack_instances(instances, None)
        b, s, p = packed["vol"].shape
        padded = pad_packed(packed, b + 3, s + 2, p + 1)
        assert padded["vol"].shape == (b + 3, s + 2, p + 1)
        assert not padded["step_mask"][b:].any()
        assert not padded["plane_mask"][:, p:].any()
        assert (padded["bw"][b:] == 1.0).all()  # NaN-free divisor
        np.testing.assert_array_equal(
            padded["vol"][:b, :s, :p], packed["vol"]
        )

    def test_jax_buckets_bound_compile_shapes(self):
        try:
            jb = JaxBackend()
        except BackendUnavailable as exc:
            pytest.skip(str(exc))
        pattern = get_pattern("ring_allreduce", 8, 1e6)
        fabric = prestage_for(OpticalFabric(8, 3), pattern)
        inst = strawman_instance(fabric, pattern)
        for n in (3, 4):  # both bucket to batch=4
            padded, _ = jb._padded(pack_instances([inst] * n, None))
            assert padded["vol"].shape[0] == 4

    def test_jax_buckets_relay_routes_and_hops(self):
        """Grids whose plans relay differently share one timing shape
        (routes padded to the plane bucket, hops to a power of two), a
        relay-free batch keeps none, and the padded routes are inert."""
        try:
            jb = JaxBackend()
        except BackendUnavailable as exc:
            pytest.skip(str(exc))
        assert relay_buckets(0, 0, 4) == (0, 0)
        assert relay_buckets(1, 2, 4) == relay_buckets(3, 2, 4) == (4, 2)
        assert relay_buckets(5, 3, 4) == (8, 4)
        cells = [
            (
                OpticalFabric(16, 4, t_recfg=t),
                get_pattern("bruck_alltoall", 16, size),
            )
            for size in (1e6, 3e7)
            for t in (1e-3, 3e-3)
        ]
        plans = swot_greedy_grid(cells, backend="numpy", bypass_depth=2)
        instances = [
            BatchInstance(g.fabric, g.pattern, g.decisions) for g in plans
        ]
        assert any(g.decisions.bypass for g in plans)
        shapes = set()
        for batch in (instances, instances[:1], instances[1:]):
            packed = pack_instances(batch, None)
            if packed["byp_plane"].shape[2]:
                padded, _ = jb._padded(packed)
                shapes.add(padded["byp_plane"].shape[2:])
            np.testing.assert_array_equal(
                jb.derive_timing(packed).cct,
                get_backend("numpy").derive_timing(packed).cct,
            )
        assert shapes == {(4, 2)}
        free = pack_instances([strawman_instance(*cells[0])], None)
        assert jb._padded(free)[0]["byp_plane"].shape[2:] == (0, 0)


class TestBackendSelection:
    def test_env_default(self):
        assert resolve_backend(None).name == "numpy"

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown IR backend"):
            resolve_backend("cuda")

    def test_instance_passthrough(self):
        be = get_backend("numpy")
        assert resolve_backend(be) is be


class TestGreedyGrid:
    def test_matches_per_instance_greedy_bitwise(self):
        cells = []
        for alg, n in (
            ("rabenseifner_allreduce", 8),
            ("pairwise_alltoall", 6),
            ("bruck_alltoall", 5),
        ):
            for planes in (1, 2, 4):
                for t_recfg in (0.0, 2e-4):
                    pattern = get_pattern(alg, n, 8e6)
                    fabric = OpticalFabric(n, planes, t_recfg=t_recfg)
                    cells.append((fabric, pattern))
                    cells.append((prestage_for(fabric, pattern), pattern))
        plans = swot_greedy_grid(cells)
        for (fabric, pattern), plan in zip(cells, plans):
            ref = swot_greedy_chain(fabric, pattern, polish=False)
            assert plan.cct == ref.cct, (pattern.name, fabric.n_planes)
            sched = plan.schedule()
            sched.validate()
            assert sched.cct == ref.cct

    def test_grid_backends_agree(self):
        pattern = get_pattern("rabenseifner_allreduce", 8, 16e6)
        cells = [
            (OpticalFabric(8, p, t_recfg=t), pattern)
            for p in (2, 4)
            for t in (5e-5, 2e-4)
        ]
        ref = swot_greedy_grid(cells, backend="numpy")
        got = swot_greedy_grid(cells, backend=_backend_or_skip("jax"))
        for a, b in zip(ref, got):
            assert a.decisions == b.decisions
            assert b.cct == pytest.approx(a.cct, abs=TOL)

    def test_plan_grid_beats_or_ties_strawman(self):
        pattern = get_pattern("rabenseifner_allreduce", 8, 32e6)
        cells = [
            (
                prestage_for(
                    OpticalFabric(8, p, t_recfg=2e-4), pattern
                ),
                pattern,
            )
            for p in (2, 4, 8)
        ]
        for cell_plan in plan_grid(cells):
            assert cell_plan.vs_strawman is not None
            assert cell_plan.vs_strawman >= -1e-9

    def test_empty_grid(self):
        assert swot_greedy_grid([]) == []

    def test_fallback_planes_match_per_instance_greedy_bitwise(self):
        """Plane counts above ``max_enumerated_planes`` take the dynamic
        soonest-free-prefix rows; they must stay bitwise-equal to the
        per-instance reference too (incl. saturated prefixes when
        ``max_enumerated_planes`` is tiny)."""
        pattern = get_pattern("rabenseifner_allreduce", 8, 16e6)
        cells = []
        for planes in (3, 9, 12):
            fabric = OpticalFabric(8, planes, t_recfg=2e-4)
            cells.append((fabric, pattern))
            cells.append((prestage_for(fabric, pattern), pattern))
        for max_enum in (8, 2):
            plans = swot_greedy_grid(
                cells, max_enumerated_planes=max_enum
            )
            for (fabric, pattern_), plan in zip(cells, plans):
                ref = swot_greedy_chain(
                    fabric, pattern_, polish=False,
                    max_enumerated_planes=max_enum,
                )
                assert plan.cct == ref.cct, (fabric.n_planes, max_enum)


class TestGreedyGridIndependent:
    """INDEPENDENT-mode grid parity: the batched argmin packing must make
    bitwise-identical decisions to per-instance ``independent_decisions``
    (and therefore to ``swot_greedy_independent(polish=False)``)."""

    @settings(max_examples=12, deadline=None)
    @given(insts=st.lists(_instances(), min_size=1, max_size=6))
    def test_plan_grid_independent_matches_per_instance_bitwise(
        self, insts
    ):
        cells = [_cell(inst) for inst in insts]
        plans = plan_grid(cells, mode=DependencyMode.INDEPENDENT)
        for (fabric, pattern), cell_plan in zip(cells, plans):
            ref = independent_decisions(fabric, pattern)
            assert cell_plan.plan.decisions == ref
            sched = swot_greedy_independent(
                fabric, pattern, polish=False
            )
            assert cell_plan.plan.cct == sched.cct

    def test_grid_plans_validate_as_independent(self):
        pattern = get_pattern("pairwise_alltoall", 8, 16e6)
        cells = [
            (OpticalFabric(8, p, t_recfg=2e-4), pattern) for p in (2, 4)
        ]
        for plan in swot_greedy_grid(
            cells, mode=DependencyMode.INDEPENDENT
        ):
            assert plan.decisions.mode is DependencyMode.INDEPENDENT
            plan.schedule().validate()


class TestCandidatePaddingIsolation:
    """Regression: the precomputed padded reserve-set tensor must not let
    one cell's candidates (or padding rows) bleed into another cell's
    decisions -- every cell's plan must be independent of its batch
    companions."""

    def _mixed_cells(self):
        specs = [
            ("rabenseifner_allreduce", 8, 40e6, 1, 0.0),
            ("pairwise_alltoall", 10, 3e6, 4, 2e-4),
            ("bruck_alltoall", 5, 7e6, 3, 1e-4),
            ("rabenseifner_allreduce", 4, 1e6, 8, 4e-4),
            ("ring_allreduce", 6, 12e6, 10, 5e-5),  # dynamic fallback row
        ]
        cells = []
        for alg, n, size, planes, t_recfg in specs:
            pattern = get_pattern(alg, n, size)
            fabric = OpticalFabric(n, planes, t_recfg=t_recfg)
            cells.append((fabric, pattern))
        return cells

    @pytest.mark.parametrize(
        "mode", [DependencyMode.CHAIN, DependencyMode.INDEPENDENT]
    )
    def test_decisions_independent_of_batch_companions(self, mode):
        cells = self._mixed_cells()
        together = swot_greedy_grid(cells, mode=mode)
        for k, cell in enumerate(cells):
            alone = swot_greedy_grid([cell], mode=mode)[0]
            assert together[k].decisions == alone.decisions, (
                f"cell {k} decisions changed when batched ({mode})"
            )
            assert together[k].cct == alone.cct


class TestMilpPlaneReady:
    def _setup(self):
        pattern = get_pattern("rabenseifner_allreduce", 4, 10e6)
        fabric = prestage_for(
            OpticalFabric(4, 2, t_recfg=2e-4), pattern
        )
        return fabric, pattern

    def test_respects_offsets_and_beats_greedy(self):
        fabric, pattern = self._setup()
        ready = (0.0, 3e-4)
        res = solve_milp(fabric, pattern, plane_ready=ready, time_limit=20)
        res.schedule.validate()
        for a in res.schedule.activities:
            assert a.start >= ready[a.plane] - TOL
        greedy = swot_greedy_chain(fabric, pattern, plane_ready=ready)
        assert res.schedule.cct <= greedy.cct * (1 + 1e-9)

    def test_zero_offsets_identical_to_fresh_solve(self):
        fabric, pattern = self._setup()
        fresh = solve_milp(fabric, pattern, time_limit=20).schedule
        zeros = solve_milp(
            fabric, pattern, plane_ready=(0.0, 0.0), time_limit=20
        ).schedule
        assert zeros.cct == pytest.approx(fresh.cct, abs=TOL)

    def test_small_replans_stay_exact_in_auto_mode(self):
        """The satellite contract: swot_schedule no longer falls back to
        the greedy just because ready offsets are present."""
        fabric, pattern = self._setup()
        schedule, method = swot_schedule(
            fabric, pattern, plane_ready=(0.0, 3e-4)
        )
        assert method == "milp"
        schedule.validate()
        greedy = swot_greedy_chain(
            fabric, pattern, plane_ready=(0.0, 3e-4)
        )
        assert schedule.cct <= greedy.cct * (1 + 1e-9)

    def test_negative_offsets_rejected(self):
        fabric, pattern = self._setup()
        with pytest.raises(ValueError):
            solve_milp(fabric, pattern, plane_ready=(-1e-3, 0.0))
