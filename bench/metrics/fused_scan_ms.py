"""Device time of the fused CHAIN planning scan per request, in ms.

The scan is the XLA program ``jit_run`` (`repro.core.ir.fused`); its
time is summed over the traced window's runs and divided by the requests
the window served.  Nothing to read where the program did not run."""

PROGRAM = "jit_run"


def read(ctx):
    p = ctx.trace["programs"].get(PROGRAM)
    if not p or not ctx.requests:
        return None
    return 1e3 * p["seconds"] / ctx.requests
