"""Share of its HBM roofline the jax timing scan reaches, in %.

A grid request times its cells' plans and their strawman baselines: the
least bytes of that work (`workcount`) over the chip's HBM bandwidth,
over the scan's measured device time per request."""

import workcount
from reference.patterns import steps_of


def read(ctx):
    p = ctx.trace["programs"].get("jit_fn")
    if not p or not ctx.requests or not p["seconds"]:
        return None
    c = ctx.config
    steps = len(steps_of(c["pattern"], c["n_nodes"], 1.0)[0])
    nbytes = workcount.timing_scan_bytes(
        2 * ctx.cells_per_request, steps, c["n_planes"]
    )
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (p["seconds"] / ctx.requests)
