"""Per-iteration optical plan for a production MoE training step.

Profiles the collectives one optimizer step of qwen2-moe-a2.7b will issue
on the 16x16 production mesh (DP gradient sync, TP activation
all-reduces, EP all-to-alls), schedules each on the optical fabric with
SWOT, and prints the timelines + per-iteration optical report --
the paper's Phase 1/Phase 2 flow end to end.  Closes with a batched
what-if sweep over reconfiguration latencies through the array IR
(`repro.core.batch_evaluate`) on a selectable timing backend, attributed
per cell: ``attribution=True`` splits each CCT into transmit / exposed
vs. hidden reconfiguration / idle, and the printed *overlap efficiency*
is the fraction of reconfiguration time hidden behind transmission.

``--bypass`` appends a Topology-Bypassing section: the EP all-to-all is
re-planned with relay candidates up to ``--bypass-depth`` hops
(`repro.core.bypass`), printing the relay timeline and the CCT
reduction vs the no-bypass greedy across the ``t_recfg`` axis.

``--trace out.json`` exports the planned timelines as Chrome
trace-event JSON (one thread row per plane; plans laid out
back-to-back), loadable at https://ui.perfetto.dev.

    PYTHONPATH=src python examples/optical_schedule_demo.py \
        [--backend numpy|jax] [--bypass] [--bypass-depth H] \
        [--trace out.json]
"""

import argparse

from jax.sharding import AbstractMesh

from repro import use_compile_cache
from repro.configs.base import shape_cell
from repro.configs.registry import get_config
from repro.core import (
    OpticalFabric,
    SwotShim,
    TPU_V5E_LINK_BANDWIDTH,
    batch_evaluate,
    pairwise_alltoall,
    strawman_instance,
)
from repro.core.greedy import swot_greedy_chain
from repro.core.planner import profile_train_step
from repro.models.lm import _decoder_specs  # spec-only; no allocation
from repro.obs import ChromeTracer, get_logger, trace_schedule
from repro.sharding.rules import MeshContext

log = get_logger("optical_schedule_demo")


def main() -> None:
    use_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--backend",
        choices=("numpy", "jax"),
        default=None,
        help="IR timing backend for the what-if sweep (default: numpy)",
    )
    parser.add_argument(
        "--bypass",
        action="store_true",
        help="add the Topology-Bypassing section (relay-routing the EP "
        "all-to-all over installed circuits)",
    )
    parser.add_argument(
        "--bypass-depth",
        type=int,
        default=2,
        metavar="H",
        help="maximum relay hops for bypass candidates (default 2)",
    )
    parser.add_argument(
        "--trace",
        metavar="OUT.json",
        default=None,
        help="write the planned timelines as Chrome trace-event JSON",
    )
    args = parser.parse_args()
    cfg = get_config("qwen2_moe_a2_7b")
    # AbstractMesh: the planner only needs mesh *shapes*; no devices.
    mesh = AbstractMesh((16, 16), ("data", "model"))
    ctx = MeshContext(mesh=mesh, dp_axes=("data",))
    specs = _decoder_specs(cfg, ctx)
    cell = shape_cell("train_4k")

    requests = profile_train_step(cfg, ctx, cell, specs)
    log.info(f"profiled {len(requests)} collective signatures for one "
             f"{cfg.name} train step on 16x16:")
    for r in requests:
        log.info(f"  {r.tag:28s} {r.algorithm:24s} n={r.n_nodes:3d} "
                 f"{r.size / 1e6:10.2f} MB/node")

    # TPU-calibrated optical fabric: 16 endpoints x 4 OCS planes.
    fabric = OpticalFabric(
        n_nodes=16,
        n_planes=4,
        bandwidth=TPU_V5E_LINK_BANDWIDTH,
        t_recfg=200e-6,
    )
    shim = SwotShim(fabric)
    shim.install(requests)  # Phase 1
    for r in requests:  # Phase 2: one training iteration
        shim.intercept(r)
    log.info("")
    log.info(shim.iteration_report())
    log.info("")
    for plan in shim.plans:
        log.info(f"--- {plan.pattern.name} "
                 f"{plan.pattern.total_volume / 1e6:.1f}MB/node ---")
        log.info(plan.schedule.timeline())
        log.info("")

    if args.trace:
        tracer = ChromeTracer(process_name="demo plans")
        t0 = 0.0
        for plan in shim.plans:
            trace_schedule(plan.schedule, tracer, t0=t0)
            t0 += plan.schedule.cct
        tracer.write(args.trace)
        log.info(
            f"wrote {len(tracer.events)} trace events to {args.trace} "
            "(open at https://ui.perfetto.dev)"
        )
        log.info("")

    # What-if sweep: how does lockstep-ICR CCT move with OCS reconfig
    # latency?  One batched array-IR pass evaluates every (collective,
    # t_recfg) cell -- with attribution=True splitting each CCT into
    # components, no per-instance schedule objects.
    recfgs = (25e-6, 100e-6, 200e-6, 800e-6)
    cells = [
        strawman_instance(
            OpticalFabric(
                n_nodes=plan.fabric.n_nodes,
                n_planes=plan.fabric.n_planes,
                bandwidth=plan.fabric.bandwidth,
                t_recfg=t_recfg,
            ),
            plan.pattern,
            prestage=True,
        )
        for plan in shim.plans
        for t_recfg in recfgs
    ]
    result = batch_evaluate(cells, backend=args.backend, attribution=True)
    ccts = result.cct
    eff = result.attribution.overlap_efficiency
    log.info(
        f"strawman CCT vs t_recfg ({len(cells)} cells, one IR pass, "
        f"backend={args.backend or 'default'}; "
        "eff = fraction of reconfig time hidden):"
    )
    k = 0
    for plan in shim.plans:
        points = "  ".join(
            f"{recfgs[r] * 1e6:.0f}us->{ccts[k + r] * 1e6:.0f}us"
            f"(eff {max(float(eff[k + r]), 0.0):.0%})"
            for r in range(len(recfgs))
        )
        log.info(f"  {plan.pattern.name:24s} {points}")
        k += len(recfgs)

    if args.bypass:
        # Topology Bypassing: re-plan the EP all-to-all with relay
        # candidates -- traffic for an uninstalled pairing rides
        # already-installed circuits at bandwidth/h instead of waiting
        # out a reconfiguration.
        ep_sizes = [
            plan.pattern.total_volume
            for plan in shim.plans
            if plan.pattern.name == "pairwise_alltoall"
        ]
        size = ep_sizes[0] if ep_sizes else 32e6
        pattern = pairwise_alltoall(fabric.n_nodes, size)
        log.info("")
        log.info(
            f"--- topology bypassing (depth {args.bypass_depth}): "
            f"pairwise all-to-all {size / 1e6:.1f}MB/node on "
            f"{fabric.n_nodes}x{fabric.n_planes} ---"
        )
        for t_recfg in recfgs:
            what_if = OpticalFabric(
                n_nodes=fabric.n_nodes,
                n_planes=fabric.n_planes,
                bandwidth=fabric.bandwidth,
                t_recfg=t_recfg,
            ).prestaged(pattern.steps[0].config)
            base = swot_greedy_chain(what_if, pattern, polish=False)
            byp = swot_greedy_chain(
                what_if, pattern, polish=False,
                bypass_depth=args.bypass_depth,
            )
            relays = sum(1 for a in byp.activities if a.route >= 0)
            log.info(
                f"  t_recfg={t_recfg * 1e6:5.0f}us: no-bypass "
                f"{base.cct * 1e6:8.1f}us  bypass {byp.cct * 1e6:8.1f}us "
                f"({1 - byp.cct / base.cct:+.1%}, {relays} relay hops)"
            )
            if t_recfg == recfgs[-1] and relays:
                log.info(byp.timeline())


if __name__ == "__main__":
    main()
