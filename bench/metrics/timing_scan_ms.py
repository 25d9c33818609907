"""Device time of the jax timing scan per request, in ms.

The scan is the XLA program ``jit_fn`` (`repro.core.ir.backends`); a grid
request runs it for its plans and for the strawman baseline.  Summed over
the traced window and divided by the requests served."""

PROGRAM = "jit_fn"


def read(ctx):
    p = ctx.trace["programs"].get(PROGRAM)
    if not p or not ctx.requests:
        return None
    return 1e3 * p["seconds"] / ctx.requests
