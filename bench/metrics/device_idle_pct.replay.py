"""Share of the traced replay window in which no operation ran on the
device, in %: one minus the union of the device's operation intervals
over the window."""


def read(ctx):
    return ctx.trace["idle_pct"]
