"""Two tenants sharing one optical fabric, end to end.

A dense (qwen3-4b) and an MoE (qwen2-moe-a2.7b) training job issue their
collectives concurrently on the same 8-node x 4-plane fabric.  The
``repro.runtime`` arbiter leases planes between them, shrinking and
growing leases at step boundaries; the replay prints per-job realized
CCT, queueing delay, and fabric utilization -- then contrasts the same
trace on a serial (one-collective-at-a-time) fabric.

``--trace out.json`` records the replay with ``repro.obs.ChromeTracer``
and writes Chrome trace-event JSON: load it at https://ui.perfetto.dev
to see per-plane transmit/reconfigure spans, lease churn, and queue
depth over simulated time.  Narrative output goes through the
``repro.obs`` logger (``REPRO_LOG=quiet`` silences it, ``=json``
renders JSON lines).

    PYTHONPATH=src python examples/multi_tenant_demo.py [--trace out.json]
"""

import argparse
import contextlib

from repro import use_compile_cache
from repro.configs.registry import get_config
from repro.core import OpticalFabric, get_pattern, swot_schedule
from repro.obs import ChromeTracer, get_logger
from repro.runtime import arch_request_mix, poisson_trace, replay

N_NODES = 8
N_PLANES = 4
SIZE_SCALE = 1 / 256  # demo-scale messages (full DP syncs are GBs)

log = get_logger("multi_tenant_demo")


def scaled_mix(name: str):
    mix = arch_request_mix(
        get_config(name), n_nodes=N_NODES, tokens_per_step=16_384
    )
    return [
        type(r)(r.algorithm, r.n_nodes, r.size * SIZE_SCALE, r.tag)
        for r in mix
    ]


def main() -> None:
    use_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--trace",
        metavar="OUT.json",
        default=None,
        help="write the replay as Chrome trace-event JSON (Perfetto)",
    )
    args = parser.parse_args()
    fabric = OpticalFabric(N_NODES, N_PLANES)
    tenants = [
        ("qwen3_4b", scaled_mix("qwen3_4b")),
        ("qwen2_moe_a2_7b", scaled_mix("qwen2_moe_a2_7b")),
    ]
    trace = poisson_trace(
        tenants,
        rate=600.0,  # heavy enough that collectives genuinely overlap
        horizon=0.05,
        seed=7,
        priorities={"qwen3_4b": 1},  # dense job preempts queue order
    )
    log.info(
        f"{len(trace)} collectives from {len(tenants)} tenants on "
        f"{N_NODES} nodes x {N_PLANES} planes\n"
    )

    # Context-managed tracer: the trace file is written when the block
    # exits, including on a mid-replay crash (partial traces still load
    # in Perfetto).
    with contextlib.ExitStack() as stack:
        tracer = None
        if args.trace:
            tracer = stack.enter_context(ChromeTracer(path=args.trace))
        report = replay(trace, fabric, method="greedy", tracer=tracer)
    log.info("== shared fabric (arbitrated) ==")
    log.info(report.summary())

    log.info("\nper-job timeline (first 10):")
    for r in report.records[:10]:
        log.info(
            f"  t={r.arrival * 1e3:7.2f}ms {r.tag:32s} "
            f"wait={r.queueing_delay * 1e6:8.1f}us "
            f"cct={r.cct * 1e6:8.1f}us "
            f"planes={r.planes_min}..{r.planes_max}"
        )

    # Serial baseline: same jobs, one at a time, whole fabric each.
    serial_busy = 0.0
    for spec in trace:
        pattern = get_pattern(
            spec.request.algorithm, spec.request.n_nodes, spec.request.size
        )
        schedule, _ = swot_schedule(
            fabric.prestaged(pattern.steps[0].config),
            pattern,
            method="greedy",
        )
        serial_busy += schedule.cct
    last_arrival = max(s.arrival for s in trace)
    serial_makespan = max(last_arrival, serial_busy)
    log.info(
        f"\n== serial fabric (one collective at a time) ==\n"
        f"sum of solo CCTs {serial_busy * 1e3:.2f} ms "
        f"(makespan >= {serial_makespan * 1e3:.2f} ms vs arbitrated "
        f"{report.makespan * 1e3:.2f} ms)"
    )

    if tracer is not None:
        log.info(
            f"\nwrote {len(tracer.events)} trace events to {args.trace} "
            "(open at https://ui.perfetto.dev)"
        )


if __name__ == "__main__":
    main()
