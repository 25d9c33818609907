"""The host spans of a traced run: count and seconds of each name in the window.

`trace_reduce.reduce_trace` gives the device's numbers; this module gives
the host's.  Every span on the harness's host thread that starts inside
the timed window (the harness's ``bench.*`` spans and the program's
``repro.*`` spans, `repro.obs.trace.host_span`) is summed by name:
``{name: {"count": n, "seconds": s}}``.

A per-layer reader gets the reduced trace only, so `of` finds the traced
run's ``.xplane.pb`` under ``bench/.traces`` (the newest whose window is
the reduced trace's) and reads its host lines alone, once a process; the
device lines, which hold nearly all the trace's records, are skipped.
"""

from __future__ import annotations

import pathlib

from trace_reduce import WINDOW_SPAN

TRACES = pathlib.Path(__file__).resolve().parent / ".traces"

_loaded: dict[pathlib.Path, tuple[int, list[list]]] = {}


def load_host(xplane: pathlib.Path) -> list[list]:
    """The ``host`` records of one ``.xplane.pb``, as
    `trace_reduce.load_events` reads them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    events = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            rows = [
                ["host", e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns)]
                for e in line.events
            ]
            if any(r[1].startswith("bench.") for r in rows):
                events += rows
    return events


def window(host: list[list]) -> tuple[int, int] | None:
    """The timed window, by `trace_reduce.reduce_trace`'s rule: the
    ``bench.window`` span, else the extent of the ``bench.*`` spans."""
    windows = [e for e in host if e[1] == WINDOW_SPAN]
    if windows:
        return windows[0][2], windows[0][3]
    spans = [e for e in host if e[1].startswith("bench.")]
    if spans:
        return min(e[2] for e in spans), max(e[3] for e in spans)
    return None


def summarize(host: list[list]) -> dict[str, dict]:
    """Count and seconds of each host span that starts inside the window."""
    bounds = window(host)
    if bounds is None:
        return {}
    lo, hi = bounds
    out: dict[str, dict] = {}
    for e in host:
        if lo <= e[2] < hi:
            s = out.setdefault(e[1], {"count": 0, "seconds": 0.0})
            s["count"] += 1
            s["seconds"] += (e[3] - e[2]) / 1e9
    return out


def _host(xplane: pathlib.Path) -> list[list]:
    mtime = xplane.stat().st_mtime_ns
    if xplane not in _loaded or _loaded[xplane][0] != mtime:
        _loaded[xplane] = (mtime, load_host(xplane))
    return _loaded[xplane][1]


def of(ctx) -> dict[str, dict]:
    """The host spans of the traced run whose reduced trace ``ctx`` holds;
    empty where no trace under `TRACES` has its window."""
    files = sorted(TRACES.rglob("*.xplane.pb"), key=lambda f: f.stat().st_mtime_ns,
                   reverse=True)
    for xplane in files:
        host = _host(xplane)
        bounds = window(host)
        if bounds and (bounds[1] - bounds[0]) / 1e9 == ctx.trace["window_s"]:
            return summarize(host)
    return {}


def per_request_ms(ctx, name: str) -> float | None:
    """Milliseconds a request in span ``name``; None where it did not run."""
    s = of(ctx).get(name)
    if not s or not ctx.requests:
        return None
    return 1e3 * s["seconds"] / ctx.requests
