"""From a `jax.profiler` trace of the timed window to the benchmark's numbers.

Two stages, so that the reduction can be checked on a small recorded trace:

* `load_events` reads the ``.xplane.pb`` of a traced run into plain records
  ``[kind, name, start_ns, end_ns]``: ``module`` for an XLA program's run on
  a device (line "XLA Modules"), ``op`` for one device operation (line
  "XLA Ops"), ``host`` for a span on the host thread that ran the harness
  (its own ``bench.*`` annotations and the runtime's named calls there).
* `reduce_trace` turns those records into the device's busy time (the
  union of its operations' intervals inside the ``bench.window`` span, or
  where the profiler lost that span, the extent of the window's requests
  or events; averaged over the devices traced), the idle share, the device time and
  run count of each XLA program, the operations that took most time, and
  the longest idle gaps, each labelled by what the host was doing in it.
"""

from __future__ import annotations

import pathlib
import shutil

WINDOW_SPAN = "bench.window"
TOP = 10


def start_trace(trace_dir: pathlib.Path) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the harness's spans suffice
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def load_events(trace_dir: pathlib.Path) -> list[list]:
    """Records of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in ("XLA Modules", "XLA Ops"):
                kind = "module" if line.name == "XLA Modules" else "op"
                kind = f"{kind}@{plane.name}"
            elif device:
                continue
            else:
                # The host thread that ran the harness: the line holding
                # its ``bench.*`` spans, with the runtime calls beside them.
                kind = "host"
            rows = [
                [kind, e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns)]
                for e in line.events
            ]
            if kind != "host" or any(r[1].startswith("bench.") for r in rows):
                events += rows
    return events


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _program(name: str) -> str:
    """``jit_run(1085...)`` -> ``jit_run``: the program's stable name."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.945 = u32[...] fusion(...)`` -> ``fusion.945``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _leaves(events: list[list]) -> list[list]:
    """Operations that contain no other operation (a loop's body ops are
    recorded inside the loop's own event)."""
    events = sorted(events, key=lambda e: (e[2], -e[3]))
    out = []
    for a, b in zip(events, events[1:] + [None]):
        if b is None or b[2] >= a[3]:
            out.append(a)
    return out


def _host_label(spans: list[list], lo: int, hi: int) -> str:
    """What the host was doing in ``[lo, hi)``: the innermost ``bench.*``
    span around it, and the runtime call that covers most of it, where one
    covers half or more (else the host ran the program's own Python)."""
    mid = (lo + hi) // 2
    bench = [s for s in spans if s[1].startswith("bench.") and s[2] <= mid < s[3]]
    label = min(bench, key=lambda s: s[3] - s[2])[1] if bench else "no span"
    best, best_overlap = None, 0
    for s in spans:
        if s[1].startswith("bench."):
            continue
        overlap = min(s[3], hi) - max(s[2], lo)
        if overlap > best_overlap:
            best, best_overlap = s[1], overlap
    if best_overlap * 2 < hi - lo:
        best = "python"
    return f"{label} > {best}"


def reduce_trace(events: list[list]) -> dict:
    """The traced window's device numbers (see the module docstring)."""
    host = [e for e in events if e[0] == "host"]
    windows = [e for e in host if e[1] == WINDOW_SPAN]
    spans = [e for e in host if e[1].startswith("bench.")]
    if windows:
        lo, hi = windows[0][2], windows[0][3]
    elif spans:  # the window's own span lost: the extent of its calls
        lo, hi = min(e[2] for e in spans), max(e[3] for e in spans)
    else:
        raise ValueError("the trace holds no bench.* span")
    window_s = (hi - lo) / 1e9
    devices = sorted({e[0].split("@", 1)[1] for e in events if "@" in e[0]})
    busy_ns = 0
    gaps: list[tuple[int, int]] = []
    ops: dict[str, float] = {}
    programs: dict[str, dict] = {}
    for dev in devices:
        dev_ops = [e for e in events if e[0] == f"op@{dev}"]
        merged = _union(_clip([(e[2], e[3]) for e in dev_ops], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
        for e in _leaves([e for e in dev_ops if lo <= e[2] < hi]):
            ops[_op(e[1])] = ops.get(_op(e[1]), 0.0) + (e[3] - e[2]) / 1e9
        for e in events:
            if e[0] == f"module@{dev}" and lo <= e[2] < hi:
                p = programs.setdefault(_program(e[1]), {"runs": 0, "seconds": 0.0})
                p["runs"] += 1
                p["seconds"] += (e[3] - e[2]) / 1e9
    n_dev = max(1, len(devices))
    busy_s = busy_ns / 1e9 / n_dev
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "programs": programs,
        "breakdown": {
            "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:TOP],
            "idle_gaps": [
                [_host_label(host, s, e), (e - s) / 1e9] for s, e in gaps[:TOP]
            ],
        },
    }
