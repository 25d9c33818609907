"""Device time of the fused planner's Topology-Bypassing twin scan per
request, in ms.

The twin scan, with its relay rows, is the XLA program ``jit_relay_run``
(`repro.core.ir.fused`); its time is summed over the traced window's runs
and divided by the requests the window served.  Nothing to read where the
program did not run, or where the twin compiles under the base scan's
name."""

PROGRAM = "jit_relay_run"


def read(ctx):
    p = ctx.trace["programs"].get(PROGRAM)
    if not p or not ctx.requests:
        return None
    return 1e3 * p["seconds"] / ctx.requests
