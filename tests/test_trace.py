"""Closed-loop trace extraction + unified planning facade.

Covers the `repro.trace` package (records, static / HLO / runtime
extraction, arbiter replay) and the `repro.core.api` facade:

* static-vs-HLO consistency: the two extractors agree on the TP
  activation sync (same algorithm, same group, byte-exact payload) for
  two real configs, compiled on an 8-device host mesh in a subprocess;
* MoE dispatch parity: static-trace payloads reproduce the capacity
  semantics of `repro.models.moe` (padded experts, capacity floor);
* dependency order survives ``trace_to_jobs`` (arrivals respect deps,
  expansion preserves bytes, cadence paces steps);
* facade parity: ``plan()`` is bitwise-identical to the primitive
  schedulers and to the legacy ``swot_schedule`` / ``plan_grid``
  wrappers across method x mode x bypass x planner.
"""

import dataclasses
import math
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import ShapeCell
from repro.configs.registry import get_config
from repro.core.api import (
    PlannerOptions,
    PlanRequest,
    PlanResult,
    plan,
)
from repro.core.baselines import strawman_cct
from repro.core.fabric import OpticalFabric
from repro.core.greedy import swot_greedy_chain, swot_greedy_independent
from repro.core.patterns import get_pattern
from repro.core.scheduler import DependencyMode, plan_grid, swot_schedule
from repro.core.shim import CollectiveRequest
from repro.trace import (
    CollectiveTrace,
    TraceEvent,
    TraceRecorder,
    event_from_hlo_op,
    hlo_trace,
    replay_trace,
    request_to_event,
    static_trace,
    trace_to_jobs,
)
from repro.trace.static import _mesh_context

BW = 25e9


def _fabric(n_nodes=4, n_planes=3, t_recfg=200e-6):
    return OpticalFabric(n_nodes, n_planes, t_recfg=t_recfg)


# ---------------------------------------------------------------- records


def test_request_to_event_count_roundtrip():
    req = CollectiveRequest(
        "rabenseifner_allreduce", 4, 1e6, "tp_act_allreduce_x96"
    )
    ev = request_to_event(req, phase="train")
    assert ev.count == 96
    assert ev.tag == "tp_act_allreduce"
    assert ev.phase == "train"
    trace = CollectiveTrace("m", "static", (ev,))
    (back,) = trace.requests()
    assert back.tag == "tp_act_allreduce_x96"
    assert back.signature == req.signature


def test_request_to_event_no_suffix():
    ev = request_to_event(CollectiveRequest("ring_allreduce", 2, 5.0, "dp"))
    assert (ev.count, ev.tag) == (1, "dp")
    # A bare _x with no digits is part of the name, not a count.
    ev = request_to_event(CollectiveRequest("ring_allreduce", 2, 5.0, "a_xb"))
    assert (ev.count, ev.tag) == (1, "a_xb")


def test_trace_validation():
    ok = TraceEvent("ring_allreduce", 1.0, 2)
    with pytest.raises(ValueError, match="unknown collective"):
        CollectiveTrace("m", "s", (TraceEvent("nope", 1.0, 2),))
    with pytest.raises(ValueError, match="participants"):
        CollectiveTrace("m", "s", (TraceEvent("ring_allreduce", 1.0, 1),))
    with pytest.raises(ValueError, match="topologically"):
        CollectiveTrace(
            "m", "s", (ok, dataclasses.replace(ok, deps=(1,)))
        )
    with pytest.raises(ValueError, match="topologically"):
        CollectiveTrace("m", "s", (dataclasses.replace(ok, deps=(0,)),))
    with pytest.raises(ValueError, match="n_steps"):
        CollectiveTrace("m", "s", (ok,), n_steps=0)
    with pytest.raises(ValueError, match="count"):
        CollectiveTrace("m", "s", (TraceEvent("ring_allreduce", 1.0, 2, count=0),))


def test_step_bytes_count_weighted():
    trace = CollectiveTrace(
        "m",
        "s",
        (
            TraceEvent("ring_allreduce", 10.0, 2, count=3),
            TraceEvent("all_gather", 5.0, 4),
        ),
    )
    assert trace.step_bytes == 35.0
    assert trace.by_kind() == {"ring_allreduce": 30.0, "all_gather": 5.0}
    assert trace.n_events == 2


# ----------------------------------------------------------------- static


def test_static_trace_matches_phase1_profile():
    """The static extractor is byte-exact vs the live shim's profile."""
    from repro.core.planner import profile_train_step
    from repro.trace.static import _model_specs

    cfg = get_config("gemma_2b")
    ctx = _mesh_context(dp=2, tp=4, pod=1)
    cell = ShapeCell("t", "train", 4096, 256)
    specs = _model_specs(cfg, ctx)
    trace = static_trace(cfg, kind="train", cell=cell, specs=specs)
    want = {
        (r.algorithm, r.n_nodes, r.size, r.tag)
        for r in profile_train_step(cfg, ctx, cell, specs)
    }
    got = {
        (r.algorithm, r.n_nodes, r.size, r.tag) for r in trace.requests()
    }
    assert got == want
    assert trace.source == "static"
    assert trace.model == cfg.name


def test_static_trace_train_dependency_order():
    trace = static_trace("qwen2_moe_a2_7b", kind="train", dp=2, tp=4)
    tags = [e.tag for e in trace.events]
    # Compute collectives chain linearly; gradient sync anchors on the
    # last of them; the FSDP param all-gather waits on the gradient RS.
    i_moe = tags.index("moe_ep_alltoall")
    i_rs = tags.index("dp_grad_rs")
    i_ag = tags.index("dp_param_ag")
    assert trace.events[i_moe].deps == (i_moe - 1,)
    assert trace.events[i_rs].deps == (i_moe,)
    assert trace.events[i_ag].deps == (i_rs,)
    assert all(e.phase == "train" for e in trace.events)


def test_moe_capacity_parity_prefill_vs_decode():
    """Static-trace MoE payloads reproduce models/moe.py's capacity
    semantics: experts padded to a multiple of EP, capacity floored at 8."""
    cfg = get_config("qwen2_moe_a2_7b")
    dp, ep = 2, 4
    e_pad = math.ceil(cfg.n_experts / ep) * ep

    def expected(cell):
        tokens = (
            cell.global_batch // dp * cell.seq_len
            if cell.kind != "decode"
            else max(cell.global_batch // dp, 1)
        )
        if cfg.moe_token_slice and tokens % ep == 0:
            tokens //= ep
        cap = max(
            8, math.ceil(tokens * cfg.top_k * cfg.capacity_factor / e_pad)
        )
        return float(e_pad * cap * cfg.d_model * 2)

    prefill = ShapeCell("p", "prefill", 2048, 8)
    decode = ShapeCell("d", "decode", 2048, 8)
    for cell, per_layer in ((prefill, 2), (decode, 2)):
        trace = static_trace(cfg, kind=cell.kind, cell=cell, dp=dp, tp=ep)
        (moe,) = [e for e in trace.events if e.tag == "moe_ep_alltoall"]
        assert moe.payload_bytes == expected(cell)
        assert moe.count == per_layer * cfg.n_layers
        assert moe.participants == ep
    # Decode routes 4 tokens -> capacity floor dominates: exactly the
    # 8-slot buffer, and far smaller than the prefill dispatch.
    dec = expected(decode)
    assert dec == e_pad * 8 * cfg.d_model * 2
    assert dec < expected(prefill)
    # Training doubles the per-layer count (fwd + bwd pairs).
    train = static_trace(cfg, kind="train", dp=dp, tp=ep)
    (moe_t,) = [e for e in train.events if e.tag == "moe_ep_alltoall"]
    assert moe_t.count == 4 * cfg.n_layers


def test_static_trace_pipeline_events():
    trace = static_trace(
        "gemma_2b",
        kind="prefill",
        dp=2,
        tp=4,
        pipeline_stages=4,
        pipeline_microbatches=2,
    )
    pp = [e for e in trace.events if e.tag == "pp_stage_handoff"]
    assert len(pp) == 2 + 4 - 1  # microbatches + stages - 1 ticks
    assert all(e.op == "neighbor_exchange" for e in pp)
    # Each tick serializes on its predecessor.
    first = trace.events.index(pp[0])
    for k, ev in enumerate(pp[1:], start=1):
        assert ev.deps == (first + k - 1,)


def test_static_trace_rejects_mismatched_cell():
    with pytest.raises(ValueError, match="kind"):
        static_trace(
            "gemma_2b", kind="train", cell=ShapeCell("x", "decode", 8, 2)
        )
    with pytest.raises(ValueError, match="train/prefill/decode"):
        static_trace("gemma_2b", kind="backprop")


def test_neighbor_exchange_pattern():
    pat = get_pattern("neighbor_exchange", 4, 1e6)
    pat.validate()
    assert len(pat.steps) == 1
    assert pat.steps[0].volume == 1e6


# ------------------------------------------------------------- hlo bridge


def _hlo_op(kind, group_size, nbytes=1024.0, count=1, name="op"):
    from repro.analysis.hlo import HloCollectiveOp

    return HloCollectiveOp(
        kind=kind,
        op_name=name,
        computation="main",
        bytes_per_call=nbytes,
        count=count,
        group_size=group_size,
    )


def test_event_from_hlo_op_kind_mapping():
    cases = {
        ("all-reduce", 4): "rabenseifner_allreduce",
        ("all-reduce", 3): "ring_allreduce",
        ("all-gather", 8): "all_gather",
        ("all-gather", 6): "ring_allreduce",
        ("reduce-scatter", 2): "reduce_scatter",
        ("all-to-all", 4): "pairwise_alltoall",
        ("collective-permute", 4): "neighbor_exchange",
    }
    for (kind, group), algo in cases.items():
        ev = event_from_hlo_op(_hlo_op(kind, group))
        assert ev.op == algo, (kind, group)
        assert ev.participants == group
    # Degenerate / unknown groups: skipped unless a default is supplied.
    assert event_from_hlo_op(_hlo_op("all-reduce", 1)) is None
    assert event_from_hlo_op(_hlo_op("all-reduce", 0)) is None
    ev = event_from_hlo_op(
        _hlo_op("all-reduce", 0), default_participants=8
    )
    assert (ev.op, ev.participants) == ("rabenseifner_allreduce", 8)


def test_hlo_trace_chains_program_order():
    from repro.analysis.hlo import HloCostSummary

    summary = HloCostSummary(
        flops=0.0,
        bytes_accessed=0.0,
        collective_bytes=0.0,
        collective_by_kind={},
        collective_counts={},
        while_trip_counts={},
        collective_ops=[
            _hlo_op("all-reduce", 4, 100.0, count=12, name="ar.1"),
            _hlo_op("all-reduce", 1, 1.0, name="skipme"),
            _hlo_op("reduce-scatter", 2, 50.0, name="rs.1"),
        ],
    )
    trace = hlo_trace(summary, model="toy", phase="train")
    assert trace.source == "hlo"
    assert [e.tag for e in trace.events] == ["hlo:ar.1", "hlo:rs.1"]
    assert trace.events[0].deps == ()
    assert trace.events[1].deps == (0,)  # chained past the skipped op
    assert trace.events[0].count == 12


_CONSISTENCY_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs.base import ShapeCell
    from repro.configs.registry import smoke_config
    from repro.sharding.rules import make_auto_mesh
    from repro.trace import hlo_trace, static_trace

    mesh = make_auto_mesh((2, 4), ("data", "model"))
    DP, TP = 2, 4

    for arch in ("gemma_2b", "qwen2_1_5b"):
        cfg = smoke_config(arch)
        cell = ShapeCell("t", "prefill", 64, 4)
        tokens_local = cell.global_batch // DP * cell.seq_len

        # A Megatron MLP block in bf16: the row-sharded second matmul
        # leaves partial sums that XLA must all-reduce over "model" --
        # the same (tokens_local, d_model) bf16 slab the static
        # extractor books as tp_act_allreduce.
        def block(x, w1, w2):
            return x @ w1 @ w2

        x = jax.ShapeDtypeStruct(
            (tokens_local, cfg.d_model), jnp.bfloat16
        )
        w1 = jax.ShapeDtypeStruct((cfg.d_model, cfg.d_ff), jnp.bfloat16)
        w2 = jax.ShapeDtypeStruct((cfg.d_ff, cfg.d_model), jnp.bfloat16)
        with jax.set_mesh(mesh):
            compiled = (
                jax.jit(
                    block,
                    in_shardings=(
                        NamedSharding(mesh, P(None, None)),
                        NamedSharding(mesh, P(None, "model")),
                        NamedSharding(mesh, P("model", None)),
                    ),
                    out_shardings=NamedSharding(mesh, P(None, None)),
                )
                .lower(x, w1, w2)
                .compile()
            )
        hlo = hlo_trace(
            compiled.as_text(), model=arch, default_participants=TP
        )
        assert hlo.n_events, f"{arch}: no collectives recovered from HLO"
        static = static_trace(cfg, kind="prefill", cell=cell, dp=DP, tp=TP)
        (tp_ev,) = [
            e for e in static.events if e.tag == "tp_act_allreduce"
        ]
        # Same algorithm, same group, same element count.  XLA may
        # all-reduce the partial sums in f32 where the static profile
        # books bf16, so compare elements, not raw bytes.
        n_elems = tp_ev.payload_bytes / 2
        match = [
            e
            for e in hlo.events
            if e.op == tp_ev.op
            and e.participants == tp_ev.participants
            and e.payload_bytes in (n_elems * 2, n_elems * 4)
        ]
        assert match, (
            arch,
            tp_ev,
            [(e.op, e.participants, e.payload_bytes) for e in hlo.events],
        )
        print("CONSISTENT", arch)
    print("TRACE_CONSISTENCY_OK")
    """
)


def test_static_vs_hlo_consistency_two_configs():
    """Both extractors book the identical TP sync for two real configs."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    result = subprocess.run(
        [sys.executable, "-c", _CONSISTENCY_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-3000:]
    assert "TRACE_CONSISTENCY_OK" in result.stdout
    assert result.stdout.count("CONSISTENT") == 2


# -------------------------------------------------------- runtime recorder


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_trace_recorder_steps_cadence_and_strict():
    clock = _FakeClock()
    rec = TraceRecorder(model="fake", clock=clock)
    reqs = [
        CollectiveRequest("rabenseifner_allreduce", 4, 1e6, "tp_x3"),
        CollectiveRequest("reduce_scatter", 2, 2e6, "rs"),
    ]
    for _ in range(2):
        for r in reqs:
            rec.record(r, phase="train")
        clock.t += 0.5
        rec.step_boundary()
    assert rec.n_steps == 2
    trace = rec.to_trace(strict=True)
    assert trace.n_steps == 2
    assert trace.cadence == pytest.approx(0.5)
    assert [e.tag for e in trace.events] == ["tp", "rs"]
    assert trace.events[0].count == 3  # _x3 folded
    assert trace.events[1].deps == (0,)  # issue order chained


def test_trace_recorder_strict_mismatch_and_empty():
    rec = TraceRecorder()
    with pytest.raises(ValueError, match="no collectives"):
        rec.to_trace()
    rec.record(CollectiveRequest("ring_allreduce", 2, 1.0, "a"))
    rec.step_boundary()
    rec.record(CollectiveRequest("ring_allreduce", 2, 2.0, "a"))
    rec.step_boundary()
    with pytest.raises(ValueError):
        rec.to_trace(strict=True)
    assert rec.to_trace().n_steps == 2  # non-strict keeps the template


def test_serve_engine_record_step_hook():
    """ServeEngine._record_step feeds the recorder the Phase-1 serving
    profile without touching devices."""
    from types import SimpleNamespace

    from repro.serve.engine import ServeEngine

    cfg = get_config("gemma_2b")
    ctx = _mesh_context(dp=2, tp=4, pod=1)
    model = SimpleNamespace(
        cfg=cfg, ctx=ctx, prefill=lambda *a: None, decode_step=lambda *a: None
    )
    rec = TraceRecorder(model="serve")
    engine = ServeEngine(model, params=None, recorder=rec)
    engine._record_step("prefill", batch_size=4, seq_len=128)
    assert rec.n_steps == 1
    trace = rec.to_trace()
    assert trace.n_events >= 1
    assert all(e.phase == "prefill" for e in trace.events)
    # No recorder attached: the hook is a no-op.
    ServeEngine(model, params=None)._record_step("prefill", 4, 128)


# ------------------------------------------------------------------ replay


def _toy_trace(n_steps=1, cadence=0.0):
    return CollectiveTrace(
        model="toy",
        source="static",
        events=(
            TraceEvent("rabenseifner_allreduce", 4e6, 4, "a", count=3),
            TraceEvent("reduce_scatter", 2e6, 4, "b", deps=(0,)),
            TraceEvent("all_gather", 2e6, 4, "c", deps=(1,)),
        ),
        n_steps=n_steps,
        cadence=cadence,
    )


def test_trace_to_jobs_preserves_dep_order():
    jobs = trace_to_jobs(_toy_trace(), _fabric(), max_expand=2)
    by_tag = {}
    for j in jobs:
        by_tag.setdefault(j.request.tag, []).append(j)
    assert len(by_tag["a_x3"]) == 2  # count=3 capped at max_expand
    # Bytes preserved through expansion: 2 jobs carry 3 issues' payload.
    assert sum(j.request.size for j in by_tag["a_x3"]) == 3 * 4e6
    # b waits for every expanded repeat of a; c waits for b.
    last_a = max(j.arrival for j in by_tag["a_x3"])
    assert by_tag["b"][0].arrival > last_a
    assert by_tag["c"][0].arrival > by_tag["b"][0].arrival
    assert all(j.tenant == "toy" for j in jobs)
    # Sorted stream (the arbiter replays in arrival order).
    arrivals = [j.arrival for j in jobs]
    assert arrivals == sorted(arrivals)


def test_trace_to_jobs_steps_and_cadence():
    # Back-to-back: step 2's root starts after step 1 fully drains.
    jobs = trace_to_jobs(_toy_trace(n_steps=2), _fabric(), max_expand=1)
    roots = [j.arrival for j in jobs if j.request.tag == "a_x3"]
    step1_max = max(
        j.arrival for j in jobs if j.arrival < max(roots)
    )
    assert max(roots) >= step1_max
    # Fixed cadence: roots land exactly on the cadence grid.
    jobs = trace_to_jobs(
        _toy_trace(n_steps=3, cadence=0.25), _fabric(), max_expand=1
    )
    roots = sorted(j.arrival for j in jobs if j.request.tag == "a_x3")
    assert roots == pytest.approx([0.0, 0.25, 0.5])
    with pytest.raises(ValueError, match="max_expand"):
        trace_to_jobs(_toy_trace(), _fabric(), max_expand=0)


def test_replay_trace_closed_loop_and_overlap():
    fabric = OpticalFabric(8, 4, t_recfg=200e-6)
    trace = static_trace("gemma_2b", kind="train", dp=2, tp=4)
    report, times = replay_trace(
        trace, fabric, size_scale=1 / 4096
    )
    st = times["gemma_2b"]
    assert st.n_completed == st.n_jobs == len(report.records)
    assert st.step_time > 0
    _, off_times = replay_trace(
        trace, fabric, overlap=False, size_scale=1 / 4096
    )
    # Strawman-ICR (no reconfiguration-communication overlap) can only
    # be slower: the paper's headline ordering, from a real model trace.
    assert off_times["gemma_2b"].step_time >= st.step_time


def test_replay_report_per_tenant_and_nan():
    from repro.runtime.workload import replay

    empty = replay([], OpticalFabric(4, 2), solo_refs=False)
    assert math.isnan(empty.mean_cct)
    assert math.isnan(empty.mean_queueing_delay)
    assert math.isnan(empty.p95_queueing_delay)
    assert empty.per_tenant() == {}

    fabric = OpticalFabric(8, 4, t_recfg=200e-6)
    traces = [
        static_trace("gemma_2b", kind="train", dp=2, tp=4),
        static_trace("qwen2_1_5b", kind="prefill", dp=2, tp=4),
    ]
    report, _ = replay_trace(traces, fabric, size_scale=1 / 4096)
    tenants = report.per_tenant()
    assert set(tenants) == {"gemma_2b", "qwen2_1_5b"}
    assert sum(t.n_jobs for t in tenants.values()) == len(report.records)
    for t in tenants.values():
        assert t.n_completed == t.n_jobs
        assert t.mean_cct > 0


# ------------------------------------------------------------------ facade


def _pattern(algo="pairwise_alltoall", n=4, size=8e6):
    return get_pattern(algo, n, size)


def _schedule_key(schedule):
    return [
        (a.kind, a.plane, a.start, a.end, getattr(a, "config", None))
        for a in schedule.activities
    ]


def test_plan_matches_greedy_primitives():
    fabric = _fabric()
    pat = _pattern()
    for bypass in (0, 2):
        direct = swot_greedy_chain(fabric, pat, bypass_depth=bypass)
        res = plan(
            PlanRequest.single(
                fabric,
                pat,
                options=PlannerOptions(method="greedy", bypass_depth=bypass),
            )
        )
        assert res.cct == direct.cct
        assert _schedule_key(res.schedule()) == _schedule_key(direct)
        assert res.method == "greedy"


def test_plan_independent_is_best_of():
    fabric = _fabric()
    pat = _pattern()
    chain = swot_greedy_chain(fabric, pat)
    indep = swot_greedy_independent(fabric, pat)
    best = chain if chain.cct < indep.cct else indep
    res = plan(
        PlanRequest.single(
            fabric,
            pat,
            options=PlannerOptions(
                method="greedy", mode=DependencyMode.INDEPENDENT
            ),
        )
    )
    assert res.cct == best.cct


def test_plan_strawman_method():
    fabric = _fabric()
    pat = _pattern()
    res = plan(
        PlanRequest.single(
            fabric, pat, options=PlannerOptions(method="strawman")
        )
    )
    assert res.method == "strawman"
    assert res.cct == pytest.approx(strawman_cct(fabric, pat))
    greedy = plan(
        PlanRequest.single(
            fabric, pat, options=PlannerOptions(method="greedy")
        )
    )
    assert greedy.cct <= res.cct


def test_legacy_swot_schedule_delegates_bitwise():
    fabric = _fabric()
    pat = _pattern()
    for method in ("auto", "greedy", "milp"):
        for mode in (DependencyMode.CHAIN, DependencyMode.INDEPENDENT):
            for bypass in (0, 2):
                legacy, lm = swot_schedule(
                    fabric, pat, method=method, mode=mode, bypass_depth=bypass
                )
                res = plan(
                    PlanRequest.single(
                        fabric,
                        pat,
                        options=PlannerOptions(
                            method=method, mode=mode, bypass_depth=bypass
                        ),
                    )
                )
                assert res.method == lm
                assert res.cct == legacy.cct
                assert _schedule_key(res.schedule()) == _schedule_key(legacy)


def test_plan_grid_parity_and_single_cell():
    fabric = _fabric()
    cells = [
        (fabric, _pattern(size=4e6)),
        (fabric, _pattern("rabenseifner_allreduce", 4, 16e6)),
        (_fabric(n_planes=2), _pattern(size=1e6)),
    ]
    for planner in (None, "step", "fused"):
        legacy = plan_grid(cells, planner=planner)
        res = plan(
            PlanRequest.grid(
                cells, options=PlannerOptions(planner=planner)
            )
        )
        assert [c.cct for c in res.grid] == [c.cct for c in legacy]
        assert [c.strawman_cct for c in res.grid] == [
            c.strawman_cct for c in legacy
        ]
        assert res.ccts == tuple(c.cct for c in legacy)
    # One cell still takes the batched path when asked for a grid.
    res1 = plan(PlanRequest.grid(cells[:1]))
    assert res1.grid is not None and len(res1.grid) == 1
    # Materialized schedule realizes the planned CCT.
    sched = res1.schedule(0)
    assert sched.cct == pytest.approx(res1.grid[0].cct, rel=1e-9)


def test_planner_options_validation():
    with pytest.raises(ValueError, match="method"):
        PlannerOptions(method="annealing")
    with pytest.raises(ValueError, match="bypass_depth"):
        PlannerOptions(bypass_depth=1)
    with pytest.raises(ValueError, match="independent_split"):
        PlannerOptions(independent_split=True)
    with pytest.raises(ValueError, match="planner"):
        PlannerOptions(planner="warp")
    with pytest.raises(ValueError, match="rollout_horizon"):
        PlannerOptions(rollout_horizon=0)
    with pytest.raises(ValueError, match="DependencyMode"):
        PlannerOptions(mode="chain")
    # Frozen: the facade can memoize on options safely.
    opts = PlannerOptions()
    with pytest.raises(dataclasses.FrozenInstanceError):
        opts.method = "milp"


def test_plan_request_validation():
    fabric = _fabric()
    pat = _pattern()
    with pytest.raises(ValueError, match="at least one"):
        PlanRequest(cells=())
    with pytest.raises(ValueError, match="exactly one"):
        PlanRequest(cells=((fabric, pat), (fabric, pat)), batched=False)
    with pytest.raises(ValueError, match="plane_ready"):
        PlanRequest(
            cells=((fabric, pat),),
            plane_ready=(0.0,) * fabric.n_planes,
            batched=True,
        )
    with pytest.raises(ValueError, match="milp"):
        plan(
            PlanRequest.grid(
                [(fabric, pat)], options=PlannerOptions(method="milp")
            )
        )
    single = PlanRequest.single(fabric, pat)
    assert not single.is_batched
    res = plan(single)
    assert isinstance(res, PlanResult)
    with pytest.raises(ValueError):
        _ = plan(PlanRequest.grid([(fabric, pat), (fabric, pat)])).cct


# ------------------------------------------------------------------- knobs


def test_knobs_read_env_per_call(monkeypatch):
    from repro.core import knobs

    monkeypatch.delenv(knobs.ENV_IR_BACKEND, raising=False)
    assert knobs.ir_backend() == "numpy"
    monkeypatch.setenv(knobs.ENV_IR_BACKEND, "jax")
    assert knobs.ir_backend() == "jax"  # no import-time caching
    monkeypatch.setenv(knobs.ENV_GRID_BACKEND_THRESHOLD, "123")
    assert knobs.grid_backend_threshold() == 123
    desc = knobs.describe()
    assert knobs.ENV_IR_BACKEND in desc
    assert desc[knobs.ENV_IR_BACKEND]["effective"] == "jax"
