#!/usr/bin/env python3
"""Chip benchmark of the SWOT planner: runs one cell of BENCHMARK.json.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name:

* the cell's entry in ``BENCHMARK.json`` names a configuration and a
  traffic mix;
* ``bench/configs/<config>.json`` holds the deployment's sizes;
* ``bench/traffic/<mix>.json`` holds the traffic's parameters, and its
  ``generator`` key names the driver, ``bench/drivers/<generator>.py``,
  that generates the requests, warms up, runs the timed window and checks
  the answers against the plain reference (``bench/reference``);
* each per-layer metric of the cell is read by ``bench/metrics/<name>.py``.

The run warms up the cell's own shapes (set-up), measures for ``--seconds``
with nothing compiling, reads the peak device memory, then checks what the
window produced.  Earlier lines of standard output are JSON records of the
phases; the compared numbers and their limits are the last lines of
standard error; the last line of standard output is the result.  With
``--trace 1`` the window runs under the JAX profiler and the result carries
the per-layer metrics, the device's busy time and a breakdown.

The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program under test is missing.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
PLATFORM = "tpu"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, no program, bad data)."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load_module(path: pathlib.Path, name: str):
    """Import one file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


class Cell:
    """One workload of BENCHMARK.json with its files, found by name."""

    def __init__(self, spec: dict, name: str) -> None:
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in spec["configs"]}
        self.config = load_json(ROOT / configs[self.entry["config"]]["file"])
        self.traffic = load_json(
            BENCH / "traffic" / f"{self.entry['traffic']}.json"
        )
        self.driver_module = load_module(
            BENCH / "drivers" / f"{self.traffic['generator']}.py",
            f"bench_driver_{self.traffic['generator']}",
        )
        self.end_to_end = [
            m for m in spec["end_to_end"] if name in m.get("workloads", [name])
        ]
        self.per_layer = [
            m for m in spec["per_layer"] if name in m.get("workloads", [name])
        ]

    def readers(self) -> dict:
        return {
            m["name"]: load_module(
                BENCH / "metrics" / f"{m['name']}.py",
                "bench_metric_" + m["name"].replace(".", "_"),
            )
            for m in self.per_layer
        }


def find_devices(chips: int) -> dict:
    """The accelerator JAX sees; raises where it is no TPU or too few."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != PLATFORM:
        raise BenchError(
            f"JAX found no TPU (platform {platform!r}); nothing was run"
        )
    if len(devices) < chips:
        raise BenchError(
            f"the cell asks for {chips} chips, JAX found {len(devices)}"
        )
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peaks_for(kind: str) -> dict:
    """Published peaks of ``kind``; an unknown device is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no published peaks for device kind {kind!r}")
    return table[kind]


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA compilations and compile-cache reads while ``active``."""

    def __init__(self) -> None:
        import jax

        self.active = False
        self.events: dict[str, int] = {}

        def listener(event: str, duration: float, **_kw) -> None:
            if self.active and "compil" in event:
                self.events[event] = self.events.get(event, 0) + 1

        jax.monitoring.register_event_duration_secs_listener(listener)

    @property
    def count(self) -> int:
        return sum(
            n for e, n in self.events.items() if "backend_compile" in e
        )


def compile_cache() -> str:
    """The program's persistent compilation cache, keeping every program.

    JAX writes only programs that took a second or more to compile; the
    re-scoring's many small programs would then compile in every run."""
    import jax

    from repro import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def run(args, device_check: bool = True, cell: Cell | None = None) -> dict:
    """One run of one cell; returns the result line's fields.

    ``device_check=False`` and a ``cell`` whose sizes a test has cut let
    the tests drive a whole run on the CPU; the benchmark never does."""
    if cell is None:
        cell = Cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError("the program under test (src/repro) is missing")
    sys.path.insert(0, str(ROOT / "src"))
    if device_check:
        try:
            device = find_devices(int(cell.entry["chips"]))
        except RuntimeError as e:  # JAX could not start any backend
            raise BenchError(str(e)) from e
    else:
        import jax

        d = jax.devices()
        device = {"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d)}
    peaks = peaks_for(device["kind"]) if device_check else None
    emit(phase="device", **device, compile_cache=compile_cache())
    readers = cell.readers() if args.trace else {}
    driver = cell.driver_module.Driver(
        cell.config, cell.traffic, int(args.seed)
    )
    compiles = CompileCounter()
    compiles.active = True

    t0 = time.perf_counter()
    setup_info = driver.setup()
    setup_s = time.perf_counter() - _T_START
    emit(
        phase="setup",
        setup_s=setup_s,
        warmup_s=time.perf_counter() - t0,
        compile_events=dict(compiles.events),
        **setup_info,
    )

    trace_dir = BENCH / ".traces" / args.workload
    compiles.events.clear()
    if args.trace:
        from trace_reduce import start_trace, stop_trace

        start_trace(trace_dir)
    driver.window(float(args.seconds))
    if args.trace:
        stop_trace()
    compiles.active = False
    window = driver.summary()
    emit(
        phase="window",
        compiles_in_window=compiles.count,
        compile_events=dict(compiles.events),
        **window,
    )
    memory = memory_peak_bytes()
    device["memory_peak_bytes"] = memory

    result: dict = {}
    if args.trace:
        from trace_reduce import load_events, reduce_trace

        trace = reduce_trace(load_events(trace_dir))
        emit(phase="trace", **{k: v for k, v in trace.items() if k != "breakdown"})
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        ctx = driver.context(trace=trace, peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            value = readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = trace["breakdown"]
    else:
        e2e = driver.end_to_end()
        e2e["setup_s"] = setup_s
        metrics = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }

    t0 = time.perf_counter()
    checks = [(n, float(v), float(lim)) for n, v, lim in driver.check()]
    emit(phase="check", check_s=time.perf_counter() - t0)
    if compiles.count:
        checks.append(("compiles_in_window", float(compiles.count), 0.0))
    correct = all(value <= limit for _, value, limit in checks)
    compared = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    for name, v, lim in checks:
        print(f"compared {name} = {v!r} (limit {lim!r})", file=sys.stderr)
    attempted, failed = driver.attempted_failed()
    result.update(
        correct=correct,
        attempted=attempted,
        failed=failed,
        metrics=metrics,
        device=device,
        compared=compared,
    )
    return result


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(BENCH))
    try:
        result = run(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    ordered = {k: result[k] for k in result if k != "compared"}
    ordered["compared"] = result["compared"]
    print(json.dumps(ordered), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
