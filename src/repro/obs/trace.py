"""Structured fabric tracing: spans + counters -> Chrome trace-event JSON.

The multi-tenant runtime is an event-driven simulation; debugging lease
churn or backpressure from aggregate statistics alone is guesswork.  This
module gives the runtime (and the single-collective demos) a tracer with
three primitives:

* ``span(name, t0, t1, tid)``    -- a complete duration event ("X");
* ``instant(name, t, tid)``      -- a point event ("i");
* ``counter(name, t, value)``    -- a time series sample ("C").

The default is ``NULL_TRACER``, whose ``enabled`` flag is False:
instrumentation sites guard with ``if tracer.enabled`` so the disabled
cost is one attribute load per site -- the quick-bench regression band
(25%) gates this staying negligible.

``ChromeTracer`` records events in memory and exports the Chrome
trace-event JSON format (https://ui.perfetto.dev loads it directly): one
process row named ``fabric`` (pid 1), one thread row per optical plane
(tid = plane index) plus a ``jobs`` lane for admission-level events.
Simulated seconds become microsecond timestamps.

``validate_trace`` is the schema checker the tests and the CI smoke job
share: it verifies the exported payload is structurally a trace-event
file (required keys per phase type, numeric timestamps, known lanes)
without depending on Perfetto.

Two clocks.  The ``Tracer`` family records *simulated* fabric seconds:
when a plane reconfigures or transmits in the plan, whatever wall time
computing the plan took.  ``host_span(name, **args)`` records *wall-clock*
host time of the planner's own code: it writes a
``jax.profiler.TraceAnnotation`` into the JAX profiler's trace, where it
lands on the profiler's clock beside the device's XLA ops, so an idle
gap on the device is named by the host phase that covers it.  Use a
``Tracer`` to see what a plan does on the fabric; use ``host_span``
(captured with ``jax.profiler.trace(dir)`` around ``plan()``) to see
where planning spends its time.  ``host_span`` never imports ``jax``:
where nothing has imported it, no profiler can be running and the span
is a shared null context.  Spans opened under ``request_scope()`` (every
``repro.core.api.plan`` call) carry that call's ``request=<n>`` argument.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import sys
from typing import Any, Iterator

# Lane (Chrome "thread") ids that are not plane indices.
JOBS_LANE = 1000
_PID = 1


class Tracer:
    """No-op tracer base; also the disabled-path implementation."""

    enabled: bool = False

    def span(
        self,
        name: str,
        t0: float,
        t1: float,
        tid: int = JOBS_LANE,
        **args: Any,
    ) -> None:
        pass

    def instant(
        self, name: str, t: float, tid: int = JOBS_LANE, **args: Any
    ) -> None:
        pass

    def counter(self, name: str, t: float, value: float) -> None:
        pass


class NullTracer(Tracer):
    """Explicit name for the default no-op tracer."""


NULL_TRACER = NullTracer()


# -- wall-clock host spans (JAX profiler trace) ----------------------------
_NULL_SPAN = contextlib.nullcontext()
_REQUESTS = itertools.count(1)
_REQUEST: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_request", default=None
)


@contextlib.contextmanager
def request_scope() -> Iterator[int]:
    """Number one planning call: every ``host_span`` opened inside the
    block carries ``request=<n>``, ``n`` unique in the process."""
    token = _REQUEST.set(next(_REQUESTS))
    try:
        yield _REQUEST.get()
    finally:
        _REQUEST.reset(token)


def host_span(name: str, **args: Any):
    """A wall-clock span of host code in the JAX profiler's trace.

    ``jax.profiler.TraceAnnotation(name, **args)`` where ``jax`` is
    already imported, with the enclosing ``request_scope``'s number added
    as ``request``; else a shared null context.  With the profiler off
    the annotation costs about a microsecond.
    """
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL_SPAN
    request = _REQUEST.get()
    if request is not None:
        args["request"] = request
    return jax.profiler.TraceAnnotation(name, **args)


class ChromeTracer(Tracer):
    """In-memory recorder exporting Chrome trace-event JSON.

    Usable as a context manager: ``with ChromeTracer(path="out.json") as
    tracer: ...`` writes the trace on exit *even when the body raises*,
    so a demo that crashes mid-replay still leaves a valid, validatable
    trace of everything recorded up to the failure.
    """

    enabled = True

    def __init__(
        self, process_name: str = "fabric", path: str | None = None
    ) -> None:
        self.process_name = process_name
        self.path = path
        self.events: list[dict[str, Any]] = []
        self._named_lanes: dict[int, str] = {}

    def __enter__(self) -> "ChromeTracer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # Flush on both clean exit and exception; never swallow the
        # in-flight exception (returning None propagates it).
        if self.path is not None:
            self.write(self.path)

    # -- recording ----------------------------------------------------------
    def span(
        self,
        name: str,
        t0: float,
        t1: float,
        tid: int = JOBS_LANE,
        **args: Any,
    ) -> None:
        self.events.append(
            {
                "name": name,
                "ph": "X",
                "ts": t0 * 1e6,
                "dur": max(t1 - t0, 0.0) * 1e6,
                "pid": _PID,
                "tid": tid,
                "args": args,
            }
        )

    def instant(
        self, name: str, t: float, tid: int = JOBS_LANE, **args: Any
    ) -> None:
        self.events.append(
            {
                "name": name,
                "ph": "i",
                "ts": t * 1e6,
                "pid": _PID,
                "tid": tid,
                "s": "t",  # thread-scoped instant
                "args": args,
            }
        )

    def counter(self, name: str, t: float, value: float) -> None:
        self.events.append(
            {
                "name": name,
                "ph": "C",
                "ts": t * 1e6,
                "pid": _PID,
                "args": {"value": value},
            }
        )

    def name_lane(self, tid: int, name: str) -> None:
        """Label a thread row (``plane 3``, ``jobs``) in the viewer."""
        self._named_lanes[tid] = name

    # -- export -------------------------------------------------------------
    def to_json(self) -> dict[str, Any]:
        """The trace-event payload (metadata + recorded events)."""
        lanes = dict(self._named_lanes)
        lanes.setdefault(JOBS_LANE, "jobs")
        for ev in self.events:
            tid = ev.get("tid")
            if tid is not None and tid not in lanes:
                lanes[tid] = f"plane {tid}"
        meta: list[dict[str, Any]] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": _PID,
                "args": {"name": self.process_name},
            }
        ]
        for tid in sorted(lanes):
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": _PID,
                    "tid": tid,
                    "args": {"name": lanes[tid]},
                }
            )
        return {
            "traceEvents": meta + self.events,
            "displayTimeUnit": "ms",
        }

    def write(self, path: str) -> None:
        payload = self.to_json()
        validate_trace(payload)
        with open(path, "w") as fh:
            json.dump(payload, fh)


_REQUIRED = {
    "X": ("name", "ts", "dur", "pid", "tid"),
    "i": ("name", "ts", "pid", "tid"),
    "C": ("name", "ts", "pid", "args"),
    "M": ("name", "pid", "args"),
}


def validate_trace(payload: dict[str, Any]) -> None:
    """Raise ``ValueError`` unless ``payload`` is a well-formed trace.

    Checks the structural contract Perfetto's legacy-JSON importer
    relies on: a ``traceEvents`` list, a known phase per event, the
    phase's required keys, numeric non-negative timestamps/durations,
    and exactly one ``process_name`` metadata record.
    """
    if not isinstance(payload, dict) or "traceEvents" not in payload:
        raise ValueError("trace payload must be a dict with 'traceEvents'")
    events = payload["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    n_process = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        ph = ev.get("ph")
        if ph not in _REQUIRED:
            raise ValueError(f"event {i} has unknown phase {ph!r}")
        for key in _REQUIRED[ph]:
            if key not in ev:
                raise ValueError(f"event {i} (ph={ph}) missing {key!r}")
        for key in ("ts", "dur"):
            if key in ev:
                val = ev[key]
                if not isinstance(val, (int, float)) or val < 0:
                    raise ValueError(
                        f"event {i} has non-numeric/negative {key!r}: {val!r}"
                    )
        if ph == "M" and ev["name"] == "process_name":
            n_process += 1
        if ph == "C" and "value" not in ev["args"]:
            raise ValueError(f"counter event {i} missing args.value")
    if n_process != 1:
        raise ValueError(
            f"expected exactly one process_name record, found {n_process}"
        )


def validate_trace_file(path: str) -> None:
    """``validate_trace`` for a file on disk (the CI smoke entry point)."""
    with open(path) as fh:
        validate_trace(json.load(fh))


def trace_schedule(schedule, tracer: ChromeTracer, t0: float = 0.0) -> None:
    """Emit one timed ``Schedule``'s activities as spans (demo traces).

    Planes map to thread rows exactly like the runtime tracer, so a
    single-collective plan and a multi-tenant replay render the same way
    in Perfetto.
    """
    from repro.core.schedule import Kind

    for a in schedule.activities:
        if a.kind is Kind.RECFG:
            tracer.span(
                f"reconfig->c{a.config}",
                t0 + a.start,
                t0 + a.end,
                tid=a.plane,
                step=a.step,
            )
        elif a.route >= 0:
            tracer.span(
                f"bypass r{a.route}h{a.hop}",
                t0 + a.start,
                t0 + a.end,
                tid=a.plane,
                step=a.step,
                volume=a.volume,
            )
        else:
            tracer.span(
                f"xmit s{a.step}",
                t0 + a.start,
                t0 + a.end,
                tid=a.plane,
                step=a.step,
                volume=a.volume,
            )


def main(argv: list[str] | None = None) -> int:
    """CLI validator: ``python -m repro.obs.trace trace.json [...]``."""
    import sys

    paths = argv if argv is not None else sys.argv[1:]
    if not paths:
        print("usage: python -m repro.obs.trace TRACE.json [...]")
        return 2
    for path in paths:
        validate_trace_file(path)
        print(f"{path}: valid trace-event JSON")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
