"""Share of the cells planned with a relay twin whose plan kept relays, in %.

From the planner's relay tally (``repro.core.greedy.RELAY_TALLY``), its
growth over the window: cells whose pick kept the twin's relays over cells
planned with a twin.  Nothing to read where the program keeps no tally or
planned no twin."""


def read(ctx):
    counters = getattr(ctx, "counters", None) or {}
    twin = counters.get("twin_cells")
    if not twin:
        return None
    return 100.0 * counters["relay_cells"] / twin
