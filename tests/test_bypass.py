"""Topology Bypassing tests: relay algebra, P4 legality, greedy + grid.

The object-path validator is the oracle: bypass schedules must be
accepted by BOTH validators, corrupted relays rejected identically, and
the IR timing recurrence must reproduce the object executor's CCT
bitwise.  The bypass-enabled greedy must never lose to the no-bypass
greedy (the guarded pick), the instance-batched grid must match the
per-instance greedy bitwise with bypassing on, and padded bypass arrays
must never leak across batch companions.
"""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BatchInstance,
    BypassRoute,
    Decisions,
    OpticalFabric,
    batch_evaluate,
    enumerate_relay_routes,
    from_ir,
    get_pattern,
    prestage_for,
    to_ir,
    validate_ir,
)
from repro.core import bypass
from repro.core.bypass import (
    compose,
    config_perms,
    relay_depth_table,
    relay_depth_tables,
    self_relay_depth,
)
from repro.core.greedy import (
    _GridState,
    _chain_decisions,
    independent_decisions,
    independent_split_decisions,
    swot_greedy_chain,
    swot_greedy_grid,
)
from repro.core.ir import BackendUnavailable
from repro.core.schedule import (
    DependencyMode,
    Kind,
    validate_object,
)
from repro.core.simulator import cct_of, execute
from repro.core.tolerances import TOL


@st.composite
def _bypass_instances(draw):
    """Instances whose rotation algebra gives self-relay opportunities."""
    alg = draw(st.sampled_from(["pairwise_alltoall", "ring_allreduce",
                                "bruck_alltoall"]))
    n = draw(st.integers(min_value=3, max_value=10))
    size = draw(st.floats(min_value=1e5, max_value=2e8))
    planes = draw(st.integers(min_value=1, max_value=4))
    t_recfg = draw(st.sampled_from([0.0, 2e-4, 8e-4, 3.2e-3]))
    depth = draw(st.integers(min_value=2, max_value=5))
    prestaged = draw(st.booleans())
    return alg, n, size, planes, t_recfg, depth, prestaged


def _cell(inst):
    alg, n, size, planes, t_recfg, depth, prestaged = inst
    pattern = get_pattern(alg, n, size)
    fabric = OpticalFabric(n, planes, t_recfg=t_recfg)
    if prestaged:
        fabric = prestage_for(fabric, pattern)
    return fabric, pattern, depth


def _bypass_decisions(fabric, pattern, depth):
    """The bypass-pass decisions (no guarded pick), for legality tests."""
    return _chain_decisions(
        fabric, pattern, 24, 8, None, relay_depth_table(pattern, depth)
    )


class TestRelayAlgebra:
    def test_rotation_self_relay_depths(self):
        """rot(a)^h = rot(h*a mod n): the table must find minimal h."""
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        tab = relay_depth_table(pattern, 7)
        perms = config_perms(pattern)
        # Config k is rotation by k+1; from rot(1) any rot(c+1) is
        # reachable in exactly c+1 hops (>= 2).
        for c in range(1, 7):
            assert tab[0, c] == c + 1
        # Minimality and correctness against brute force.
        for a, pa in perms.items():
            for c, pc in perms.items():
                h = tab[a, c]
                if h:
                    cur = pa
                    for _ in range(h - 1):
                        cur = compose(cur, pa)
                    assert cur == pc
                    assert self_relay_depth(pa, pc, h - 1) == 0 or h == 2

    def test_depth_below_two_disables(self):
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        assert not relay_depth_table(pattern, 1).any()
        assert not relay_depth_table(pattern, 0).any()

    def test_xor_pairings_have_no_self_relay(self):
        """xor masks are involutions: xor^2 = id != any step pairing."""
        pattern = get_pattern("rabenseifner_allreduce", 8, 40e6)
        tab = relay_depth_table(pattern, 2)
        assert not tab.any()
        # Odd depths re-reach the pairing itself, but h=1 is direct and
        # the minimal bypass depth 3 only ties a,a pairs.
        tab3 = relay_depth_table(pattern, 3)
        for a in config_perms(pattern):
            for c in config_perms(pattern):
                assert tab3[a, c] == (3 if a == c else 0)

    def test_cross_plane_route_enumeration(self):
        """rot(1) then rot(2) composes to rot(3) across two planes."""
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        routes = enumerate_relay_routes(
            pattern, step_config=2, installed=[0, 1], max_hops=2
        )
        perms = config_perms(pattern)
        assert routes, "no 2-hop route found"
        for route in routes:
            composed = None
            for j in route:
                p = perms[[0, 1][j]]
                composed = p if composed is None else compose(composed, p)
            assert composed == perms[2]
        assert (0, 1) in routes and (1, 0) in routes

    def test_unknown_step_config_rejected(self):
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        with pytest.raises(ValueError, match="no known pairing"):
            enumerate_relay_routes(pattern, 99, [0, 1])


class TestBypassLegality:
    @settings(max_examples=30, deadline=None)
    @given(inst=_bypass_instances())
    def test_bypass_schedules_pass_both_validators(self, inst):
        fabric, pattern, depth = _cell(inst)
        decisions = _bypass_decisions(fabric, pattern, depth)
        schedule = execute(fabric, pattern, decisions, validate=False)
        validate_object(schedule)
        validate_ir(to_ir(schedule))

    @settings(max_examples=30, deadline=None)
    @given(inst=_bypass_instances())
    def test_ir_object_cct_bitwise_parity(self, inst):
        fabric, pattern, depth = _cell(inst)
        decisions = _bypass_decisions(fabric, pattern, depth)
        obj = execute(fabric, pattern, decisions, validate=False)
        assert cct_of(fabric, pattern, decisions) == obj.cct

    @settings(max_examples=20, deadline=None)
    @given(inst=_bypass_instances())
    def test_round_trip_preserves_route_fields(self, inst):
        fabric, pattern, depth = _cell(inst)
        decisions = _bypass_decisions(fabric, pattern, depth)
        schedule = execute(fabric, pattern, decisions, validate=False)
        assert from_ir(to_ir(schedule)) == schedule

    @settings(max_examples=40, deadline=None)
    @given(
        inst=_bypass_instances(),
        pick=st.integers(min_value=0, max_value=1 << 30),
        mutation=st.sampled_from(
            ["wrong_hop_config", "hop_volume", "drop_hop", "reorder_hop",
             "early_hop"]
        ),
    )
    def test_corrupted_relays_judged_identically(self, inst, pick, mutation):
        fabric, pattern, depth = _cell(inst)
        decisions = _bypass_decisions(fabric, pattern, depth)
        schedule = execute(fabric, pattern, decisions, validate=False)
        acts = list(schedule.activities)
        hops = [k for k, a in enumerate(acts)
                if a.kind is Kind.XMIT and a.route >= 0]
        if not hops:
            return
        k = hops[pick % len(hops)]
        a = acts[k]
        if mutation == "wrong_hop_config":
            acts[k] = dataclasses.replace(a, config=a.config + 1)
        elif mutation == "hop_volume":
            acts[k] = dataclasses.replace(
                a, volume=a.volume * 2 + 1.0,
                end=a.start + (a.volume * 2 + 1.0)
                / fabric.plane_bandwidth(a.plane),
            )
        elif mutation == "drop_hop":
            del acts[k]
        elif mutation == "reorder_hop":
            acts[k] = dataclasses.replace(a, hop=a.hop + 1)
        elif mutation == "early_hop":
            if a.hop == 0:
                return
            acts[k] = dataclasses.replace(
                a, start=0.0, end=a.duration
            )
        mutated = dataclasses.replace(schedule, activities=tuple(acts))
        try:
            validate_object(mutated)
            oracle = True
        except ValueError:
            oracle = False
        try:
            validate_ir(to_ir(mutated))
            ir_ok = True
        except ValueError:
            ir_ok = False
        assert oracle == ir_ok, f"oracle={oracle} ir={ir_ok} ({mutation})"

    def test_cross_plane_route_executes_and_validates(self):
        """A hand-built 2-plane relay (rot1 then rot2 = rot3) is legal.

        Plane 0 serves every direct step (its installed config advances
        lazily); planes 1 and 2 never serve directly, so they keep their
        pre-staged rot1 / rot2 circuits for the relay.
        """
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        fabric = OpticalFabric(8, 3, t_recfg=1e-3).with_initial_configs(
            (0, 0, 1)
        )
        step_vol = pattern.steps[0].volume
        splits = []
        bypass = []
        for step in pattern.steps:
            if step.config == 2:
                splits.append({})
                bypass.append(
                    (BypassRoute(planes=(1, 2), volume=step_vol),)
                )
            else:
                splits.append({0: step_vol})
                bypass.append(())
        decisions = Decisions(tuple(splits), bypass=tuple(bypass))
        schedule = execute(fabric, pattern, decisions)
        assert any(a.route >= 0 for a in schedule.activities)
        assert cct_of(fabric, pattern, decisions) == schedule.cct

    def test_bypass_on_unconfigured_plane_rejected(self):
        pattern = get_pattern("pairwise_alltoall", 4, 4e6)
        fabric = OpticalFabric(4, 2, t_recfg=1e-3)  # nothing installed
        vol = pattern.steps[0].volume
        decisions = Decisions(
            splits=({}, {0: vol}, {0: vol}),
            bypass=((BypassRoute(planes=(1, 1), volume=vol),), (), ()),
        )
        with pytest.raises(ValueError, match="unconfigured"):
            execute(fabric, pattern, decisions)

    def test_single_hop_route_rejected(self):
        pattern = get_pattern("pairwise_alltoall", 4, 4e6)
        fabric = prestage_for(OpticalFabric(4, 2, t_recfg=1e-3), pattern)
        vol = pattern.steps[0].volume
        decisions = Decisions(
            splits=({}, {0: vol}, {0: vol}),
            bypass=((BypassRoute(planes=(1,), volume=vol),), (), ()),
        )
        with pytest.raises(ValueError, match=">= 2 hops"):
            execute(fabric, pattern, decisions)


class TestBypassGreedy:
    @settings(max_examples=25, deadline=None)
    @given(inst=_bypass_instances())
    def test_bypass_never_loses_to_no_bypass(self, inst):
        """The guarded pick: enabling bypassing cannot regress CCT."""
        fabric, pattern, depth = _cell(inst)
        base = swot_greedy_chain(fabric, pattern, polish=False)
        byp = swot_greedy_chain(
            fabric, pattern, polish=False, bypass_depth=depth
        )
        byp.validate()
        assert byp.cct <= base.cct

    def test_documented_high_t_recfg_win(self):
        """The acceptance point: prestaged pairwise all-to-all, 8 nodes x
        4 planes, t_recfg = 3.2 ms, depth 2 -- bypassing must strictly
        reduce CCT (the benchmark asserts the same point)."""
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        fabric = prestage_for(
            OpticalFabric(8, 4, t_recfg=3.2e-3), pattern
        )
        base = swot_greedy_chain(fabric, pattern, polish=False)
        byp = swot_greedy_chain(
            fabric, pattern, polish=False, bypass_depth=2
        )
        byp.validate()
        assert byp.cct < base.cct * (1 - 0.25), (
            f"bypass {byp.cct} vs base {base.cct}"
        )
        assert any(a.route >= 0 for a in byp.activities)

    def test_polished_chain_also_never_loses(self):
        pattern = get_pattern("pairwise_alltoall", 8, 8e6)
        fabric = prestage_for(
            OpticalFabric(8, 4, t_recfg=3.2e-3), pattern
        )
        base = swot_greedy_chain(fabric, pattern)
        byp = swot_greedy_chain(fabric, pattern, bypass_depth=2)
        assert byp.cct <= base.cct


class TestBypassGrid:
    def _cells(self):
        cells = []
        for alg, n in (
            ("pairwise_alltoall", 8),
            ("pairwise_alltoall", 5),
            ("ring_allreduce", 6),
            ("bruck_alltoall", 8),
        ):
            for planes in (1, 2, 4):
                for t_recfg in (2e-4, 3.2e-3):
                    pattern = get_pattern(alg, n, 8e6)
                    fabric = OpticalFabric(n, planes, t_recfg=t_recfg)
                    cells.append((fabric, pattern))
                    cells.append((prestage_for(fabric, pattern), pattern))
        return cells

    @pytest.mark.parametrize("depth", [2, 3])
    def test_grid_matches_per_instance_bitwise(self, depth):
        cells = self._cells()
        plans = swot_greedy_grid(cells, bypass_depth=depth)
        for (fabric, pattern), plan in zip(cells, plans):
            ref = swot_greedy_chain(
                fabric, pattern, polish=False, bypass_depth=depth
            )
            assert plan.cct == ref.cct, (pattern.name, fabric.n_planes)
            sched = plan.schedule()
            sched.validate()
            assert sched.cct == ref.cct

    def test_grid_decisions_independent_of_companions(self):
        cells = self._cells()[:8]
        together = swot_greedy_grid(cells, bypass_depth=2)
        for k, cell in enumerate(cells):
            alone = swot_greedy_grid([cell], bypass_depth=2)[0]
            assert together[k].decisions == alone.decisions, k
            assert together[k].cct == alone.cct


class TestGridDepthTables:
    """A grid builds one self-relay depth table per distinct config ->
    pairing mapping, and pads exactly what per-cell tables would give."""

    def _cells(self):
        bruck16 = get_pattern("bruck_alltoall", 16, 1e6)
        patterns = [
            bruck16,
            get_pattern("bruck_alltoall", 16, 4e7),
            get_pattern("bruck_alltoall", 16, 1e6),
            get_pattern("pairwise_alltoall", 16, 8e6),
            get_pattern("bruck_alltoall", 32, 8e6),
            get_pattern("ring_allreduce", 16, 8e6),
        ]
        return [
            (OpticalFabric(p.n_nodes, 4, t_recfg=t_recfg), p)
            for p in patterns
            for t_recfg in (2e-4, 3.2e-3)
        ]

    @pytest.mark.parametrize("depth", [2, 3])
    def test_padded_tables_match_per_cell_tables(self, depth):
        cells = self._cells()
        st = _GridState(cells, bypass_depth=depth)
        tabs = [relay_depth_table(p, depth) for _, p in cells]
        c_max = max(t.shape[0] for t in tabs)
        want = np.zeros((len(cells), c_max, c_max), dtype=np.int64)
        for bi, t in enumerate(tabs):
            want[bi, : t.shape[0], : t.shape[1]] = t
        assert st.depth_tab.dtype == want.dtype
        np.testing.assert_array_equal(st.depth_tab, want)
        assert st.n_depth_tables == 4

    def test_one_table_per_distinct_mapping(self, monkeypatch):
        built = []
        table = bypass.relay_depth_table

        def counted(pattern, max_depth):
            built.append(pattern)
            return table(pattern, max_depth)

        monkeypatch.setattr(bypass, "relay_depth_table", counted)
        patterns = [p for _, p in self._cells()]
        tabs = relay_depth_tables(patterns, 2)
        assert len(built) == 4
        assert len({id(t) for t in tabs}) == 4
        # Bruck-16 at two sizes, and built twice, shares one table.
        assert tabs[0] is tabs[2] is tabs[4]
        assert tabs[5] is not tabs[6]

    @pytest.mark.parametrize("depth", [0, 1])
    def test_depth_below_two_builds_no_table(self, depth):
        cells = self._cells()
        st = _GridState(cells, bypass_depth=depth)
        assert st.depth_tab.shape == (len(cells), 0, 0)
        assert st.n_depth_tables == 0


class TestBypassBatchPadding:
    def _mixed_instances(self):
        """Bypass and non-bypass instances of different route/hop/plane
        shapes in ONE batch: padded byp rows must stay inert."""
        out = []
        for alg, n, planes, t, depth in (
            ("pairwise_alltoall", 8, 4, 3.2e-3, 2),
            ("pairwise_alltoall", 5, 2, 8e-4, 4),
            ("ring_allreduce", 6, 3, 2e-4, 0),
            ("bruck_alltoall", 8, 2, 8e-4, 3),
        ):
            pattern = get_pattern(alg, n, 8e6)
            fabric = prestage_for(
                OpticalFabric(n, planes, t_recfg=t), pattern
            )
            if depth >= 2:
                dec = _bypass_decisions(fabric, pattern, depth)
            else:
                dec = _chain_decisions(fabric, pattern, 24, 8, None)
            out.append(BatchInstance(fabric, pattern, dec))
        return out

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_padded_bypass_cells_never_leak(self, backend):
        instances = self._mixed_instances()
        try:
            together = batch_evaluate(instances, backend=backend)
        except BackendUnavailable as exc:
            pytest.skip(str(exc))
        for k, inst in enumerate(instances):
            alone = batch_evaluate([inst], backend=backend)
            assert together.cct[k] == alone.cct[0], k
            assert (
                together.n_reconfigurations[k]
                == alone.n_reconfigurations[0]
            )
            n_p = inst.fabric.n_planes
            np.testing.assert_array_equal(
                together.plane_busy[k, :n_p], alone.plane_busy[0, :n_p]
            )
            assert not together.plane_busy[k, n_p:].any()

    def test_backends_agree_on_bypass_batch(self):
        instances = self._mixed_instances()
        ref = batch_evaluate(instances, backend="numpy")
        objs = [
            execute(i.fabric, i.pattern, i.decisions, validate=False).cct
            for i in instances
        ]
        np.testing.assert_array_equal(ref.cct, objs)
        try:
            res = batch_evaluate(instances, backend="jax")
        except BackendUnavailable as exc:
            pytest.skip(str(exc))
        np.testing.assert_allclose(res.cct, ref.cct, atol=TOL)
        np.testing.assert_array_equal(res.feasible, ref.feasible)
        np.testing.assert_array_equal(res.volume_ok, ref.volume_ok)


class TestIndependentSplit:
    def _cells(self):
        cells = []
        for alg, n, planes, scale in (
            ("ring_allreduce", 8, 4, (1.0, 1.0, 0.25, 0.1)),
            ("ring_allreduce", 6, 3, None),
            ("pairwise_alltoall", 8, 4, (1.0, 0.5, 1.0, 0.5)),
            ("rabenseifner_allreduce", 8, 2, (1.0, 0.2)),
        ):
            pattern = get_pattern(alg, n, 16e6)
            fabric = OpticalFabric(
                n, planes, t_recfg=2e-4, plane_bandwidth_scale=scale
            )
            cells.append((prestage_for(fabric, pattern), pattern))
        return cells

    def test_grid_matches_per_instance_bitwise(self):
        cells = self._cells()
        plans = swot_greedy_grid(
            cells,
            mode=DependencyMode.INDEPENDENT,
            independent_split=True,
        )
        for (fabric, pattern), plan in zip(cells, plans):
            ref = independent_split_decisions(fabric, pattern)
            assert plan.decisions == ref, pattern.name
            plan.schedule().validate()

    def test_split_beats_packing_on_heterogeneous_shared_config(self):
        """Ring (one config) + straggler planes: splitting every step
        across planes must beat whole-step argmin packing."""
        pattern = get_pattern("ring_allreduce", 8, 32e6)
        fabric = prestage_for(
            OpticalFabric(
                8, 4, t_recfg=2e-4,
                plane_bandwidth_scale=(1.0, 1.0, 0.25, 0.1),
            ),
            pattern,
        )
        pack = cct_of(fabric, pattern, independent_decisions(fabric, pattern))
        split = cct_of(
            fabric, pattern, independent_split_decisions(fabric, pattern)
        )
        assert split < pack


class TestGridBackendSelection:
    def test_threshold_env_and_explicit(self):
        from repro.core.ir.backends import (
            GRID_JAX_FROM,
            BackendUnavailable,
            get_backend,
            route,
        )

        assert route(1, chain=True).backend == "numpy"
        assert route(GRID_JAX_FROM - 1, chain=True).backend == "numpy"
        try:
            get_backend("jax")
            expected = "jax"
        except BackendUnavailable:
            expected = "numpy"
        assert route(GRID_JAX_FROM, chain=True).backend == expected
        # An explicit backend always wins, either way.
        assert route(1 << 20, chain=True, backend="numpy").backend == "numpy"
        assert route(1, chain=True, backend="jax").backend == "jax"

    def test_small_grid_results_unchanged_by_threshold(self):
        """The scoring backend changes only the scores' last bits, never
        the decisions."""
        pattern = get_pattern("pairwise_alltoall", 6, 8e6)
        cells = [
            (OpticalFabric(6, p, t_recfg=2e-4), pattern) for p in (2, 3)
        ]
        try:
            forced = swot_greedy_grid(cells, backend="jax")
        except BackendUnavailable:
            pytest.skip("jax unavailable")
        plain = swot_greedy_grid(cells)
        for a, b in zip(forced, plain):
            assert a.decisions == b.decisions
            assert a.cct == pytest.approx(b.cct, abs=TOL)
