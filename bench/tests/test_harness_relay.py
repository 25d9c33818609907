"""Topology Bypassing against its plain reference (`reference.relay`) on the CPU.

At a small size (Bruck's all-to-all at 16 and 32 ranks, 4 planes,
millisecond delays, seeded sizes) the program's relay plans, re-timed by
the reference, match its CCTs; they carry every step's volume; they are
the reference greedy's own pick; and most cells keep relays.  Through the
relay sweep's driver, a sound run is correct, and the control (the timed
path in float32) and each fault planted in the twin pass's output make it
incorrect.  The relay readers read a traced run, and read nothing where
the program has no twin program, span or tally (the parent's)."""

import dataclasses
import math
import types

import harness
import numpy as np
import pytest
from reference import relay

CELL = "bruck512_4p.sweep1024_ms"
RELAY_READERS = {
    "relay_scan_ms", "relay_scan_roofline", "relay_pass_ms", "relay_cell_pct",
}


def _grid(n: int, seed: int, side: int = 4):
    """(program cells, reference cells) of a seeded sizes x delays grid."""
    from repro.core import OpticalFabric
    from repro.core.patterns import get_pattern

    rng = np.random.default_rng(seed)
    sizes = np.exp(rng.uniform(math.log(8e5), math.log(4.096e8), side))
    delays = np.exp(rng.uniform(math.log(8e-4), math.log(3.2e-3), side))
    bw = 200e9 / 8
    cells, refs = [], []
    for size in sizes:
        pattern = get_pattern("bruck_alltoall", n, float(size))
        cfg, vol, rot = relay.steps("bruck_alltoall", n, float(size))
        for t_recfg in delays:
            cells.append((OpticalFabric(n, 4, bandwidth=bw,
                                        t_recfg=float(t_recfg)), pattern))
            refs.append(relay.Cell(n, 4, bw, float(t_recfg), cfg, vol, rot))
    return cells, refs


def _plans(cells, planner: str):
    from repro.core.api import PlannerOptions, PlanRequest, plan

    options = PlannerOptions(
        planner=planner, backend="jax", max_enumerated_planes=4,
        rollout_horizon=24, bypass_depth=2,
    )
    result = plan(PlanRequest.grid(cells, options=options))
    out = []
    for g, cct in zip(result.grid, result.ccts):
        d = g.plan.decisions
        relays = None if d.bypass is None else [
            [(r.planes, r.volume) for r in routes] for routes in d.bypass
        ]
        out.append((list(d.splits), relays, float(cct)))
    return out


@pytest.mark.parametrize("n", [16, 32])
def test_reference_steps_and_depths_are_the_programs(n):
    from repro.core.bypass import relay_depth_table
    from repro.core.patterns import get_pattern

    pattern = get_pattern("bruck_alltoall", n, 3.3e7)
    cfg, vol, rot = relay.steps("bruck_alltoall", n, 3.3e7)
    assert list(cfg) == list(pattern.configs)
    assert list(vol) == list(pattern.volumes)  # bitwise
    assert all(s.perm[0] == rot[s.config] for s in pattern.steps)
    table = relay_depth_table(pattern, 2)
    ref = [[relay.relay_depth(int(rot[a]), int(rot[c]), n, 2)
            for c in cfg] for a in cfg]
    assert (table == np.array(ref)).all()
    assert table.any()


@pytest.mark.parametrize("planner", ["fused", "step"])
@pytest.mark.parametrize("n", [16, 32])
def test_program_relay_plans_match_the_reference(n, planner):
    cells, refs = _grid(n, seed=1500 + n)
    plans = _plans(cells, planner)
    kept = [p for p in plans if p[1] is not None and any(p[1])]
    assert len(kept) > len(plans) // 2  # most cells keep relays
    for ref, (splits, relays, cct) in zip(refs, plans):
        assert abs(relay.execute(ref, splits, relays) - cct) <= 1e-12 * cct
        assert relay.volume_gap(ref, splits, relays) <= 1.0
    excess = relay.plan_excess(
        refs, [p[0] for p in plans], [p[1] for p in plans], 24, 4, 2
    )
    assert excess.max() <= 1e-12


def test_the_reference_refuses_an_illegal_relay():
    _, refs = _grid(16, seed=7, side=1)
    ref = refs[0]
    vol = ref.step_vol
    # Step 0 installs rot(1) on plane 0; step 1 relays rot(2) over it.
    direct = [{0: vol[0]}, {}] + [{1: v} for v in vol[2:]]
    sound = [(), (((0, 0), vol[1]),)] + [()] * (len(vol) - 2)
    assert relay.volume_gap(ref, direct, sound) <= 1.0
    assert relay.execute(ref, direct, sound) > 0.0
    for bad in (((1, 1), vol[1]), ((0, 0, 0), vol[1]), ((0,), vol[1])):
        routes = [(), (bad,)] + [()] * (len(vol) - 2)
        assert relay.volume_gap(ref, direct, routes) == float("inf")
    half = [(), (((0, 0), vol[1] / 2),)] + [()] * (len(vol) - 2)
    assert relay.volume_gap(ref, direct, half) > 1.0


# -- through the driver ------------------------------------------------------
def _cell():
    return harness.small_grid_cell("fused", name=CELL)


def test_sound_relay_run_is_correct():
    r = harness.run_cell(_cell(), seconds=0.3)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 16
    assert r["compared"]["cct_excess_pos_mean"]["value"] == 0.0
    assert r["metrics"]["grid_cells_per_s"]["value"] > 0


def test_float32_control_fails_the_relay_check():
    import control

    with control.precision("float32"):
        r = harness.run_cell(_cell(), seconds=0.3)
    assert not r["correct"]
    failed = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert failed & {"volume_gap", "cct_excess_pos_mean", "timing_gap"}


def _moved(cell, dec):
    """Every relay moved to a plane whose installed config differs from
    its own plane's, so its circuit cannot compose to the step."""
    from repro.core.schedule import BypassRoute

    fabric, pattern = cell
    held = [None] * fabric.n_planes
    bypass = []
    for step, split, routes in zip(pattern.steps, dec.splits, dec.bypass):
        new = []
        for r in routes:
            j = r.planes[0]
            other = [k for k in range(fabric.n_planes) if held[k] != held[j]]
            new.append(
                BypassRoute(planes=(other[0],) * len(r.planes),
                            volume=r.volume) if other else r
            )
        bypass.append(tuple(new))
        for j, v in split.items():
            if v > relay.EPS_VOLUME:
                held[j] = step.config
    return dataclasses.replace(dec, bypass=tuple(bypass))


def _each_relay(change):
    def alter(cell, dec):
        return dataclasses.replace(dec, bypass=tuple(
            tuple(change(r) for r in routes) for routes in dec.bypass
        ))
    return alter


FAULTS = {
    "twin_skipped": None,
    "hop_count_changed": _each_relay(
        lambda r: dataclasses.replace(r, planes=r.planes + r.planes[:1])
    ),
    "relayed_volume_halved": _each_relay(
        lambda r: dataclasses.replace(r, volume=r.volume / 2)
    ),
    "relay_moved_to_another_plane": _moved,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_makes_the_run_incorrect(monkeypatch, fault):
    from repro.core import greedy

    twin_pass = greedy._relay_twin_pass
    alter = FAULTS[fault]

    def planted(cells, decisions, *args):
        if alter is None:
            return decisions
        out = twin_pass(cells, decisions, *args)
        return [
            d if not d.bypass else alter(c, d) for c, d in zip(cells, out)
        ]

    monkeypatch.setattr(greedy, "_relay_twin_pass", planted)
    r = harness.run_cell(_cell(), seconds=0.3)
    assert not r["correct"], r["compared"]
    failed = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert failed & {"volume_gap", "cct_excess_pos_mean"}, r["compared"]


# -- the relay readers -------------------------------------------------------
@pytest.fixture(scope="module")
def traced():
    cell = _cell()
    result = harness.run_cell(cell, seconds=0.5, trace=1)
    return cell, result


def _readers():
    cell = harness.bench_run.Cell(harness.spec(), CELL)
    return cell.readers()


def test_the_relay_readers_read_a_traced_run(traced):
    cell, result = traced
    import trace_reduce

    assert result["correct"], result["compared"]
    listed = {m["name"] for m in cell.per_layer}
    assert RELAY_READERS <= listed
    assert "fused_scan_roofline" not in listed
    assert "timing_scan_roofline" not in listed
    metrics = result["metrics"]
    assert metrics["relay_pass_ms"]["value"] > 0
    assert metrics["relay_cell_pct"]["value"] >= 50.0
    assert metrics["plan_untraced_ms"]["value"] >= 0.0
    events = trace_reduce.load_events(harness.BENCH / ".traces" / CELL)
    program = sorted(
        (e for e in events if e[0] == "host" and e[1].startswith("repro.")),
        key=lambda e: e[2],
    )
    assert {e[1] for e in program} >= {
        "repro.relay.state", "repro.relay.call", "repro.relay.materialize",
        "repro.relay.pick",
    }
    for a, b in zip(program, program[1:]):
        assert a[3] <= b[2], (a, b)


def test_the_twin_scan_readers_read_its_program():
    import workcount

    spec = harness.spec()
    config = harness.bench_run.Cell(spec, CELL).config
    ctx = types.SimpleNamespace(
        trace={"programs": {
            "jit_run": {"runs": 2, "seconds": 9.0},
            "jit_relay_run": {"runs": 2, "seconds": 0.5},
        }},
        requests=2, config=config, cells_per_request=1024,
        peaks={"hbm_bytes_per_s": 819e9},
    )
    readers = _readers()
    assert readers["relay_scan_ms"].read(ctx) == pytest.approx(250.0)
    nbytes = workcount.fused_chain_scan_bytes(
        1024, 9, 4, workcount.candidate_rows(4, 4, True), True
    )
    share = readers["relay_scan_roofline"].read(ctx)
    assert share == pytest.approx(100.0 * nbytes / 819e9 / 0.25)
    assert 0.0 < share < 100.0


def test_nothing_to_read_on_a_program_without_the_twins_names(monkeypatch):
    """The parent's program compiles the twin as ``jit_run``, writes no
    ``repro.relay.*`` span and keeps no tally: every relay reader returns
    nothing and none raises."""
    import host_spans

    monkeypatch.setattr(host_spans, "of", lambda _ctx: {
        "bench.request": {"count": 2, "seconds": 1.0},
        "repro.grid.state": {"count": 4, "seconds": 0.2},
    })
    ctx = types.SimpleNamespace(
        trace={"programs": {"jit_run": {"runs": 4, "seconds": 1.0}},
               "window_s": 1.0},
        requests=2, config=harness.bench_run.Cell(harness.spec(), CELL).config,
        cells_per_request=1024, peaks={"hbm_bytes_per_s": 819e9}, counters={},
    )
    readers = _readers()
    assert all(readers[n].read(ctx) is None for n in RELAY_READERS)
