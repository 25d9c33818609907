"""Structured logging for examples and benchmarks: one knob, ``REPRO_LOG``.

The demos and benchmark drivers used ad-hoc ``print()`` calls -- fine
until output needs to be quieted in CI, grepped by tooling, or rendered
as JSON lines.  This logger replaces them with two channels:

* ``info`` / ``debug`` / ``warning`` -- *narrative* output (progress,
  summaries, timelines).  Rendering follows ``REPRO_LOG``:

  - unset or ``plain``  -- the message followed by ``key=value`` fields;
  - ``json``            -- one JSON object per line
    (``{"level", "logger", "msg", ...fields}``);
  - ``debug``           -- plain, plus ``debug``-level records;
  - ``quiet`` or ``0``  -- ``info``/``debug`` suppressed (warnings kept).

* ``data`` -- *program output* (the benchmark CSV rows).  Always printed
  verbatim to stdout regardless of ``REPRO_LOG``: machine-readable
  output is the program's contract, not a log.

Stateless by design: the knob is re-read per record, so tests can
monkeypatch the environment without reloading modules.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, TextIO

ENV_LOG = "REPRO_LOG"

_LEVELS = {"debug": 10, "info": 20, "warning": 30}


def _mode() -> str:
    """The normalized ``REPRO_LOG`` mode (lowercased, stripped)."""
    return os.environ.get(ENV_LOG, "").strip().lower()


def _threshold(mode: str) -> int:
    if mode in ("quiet", "0", "off"):
        return _LEVELS["warning"]
    if mode == "debug":
        return _LEVELS["debug"]
    return _LEVELS["info"]


class ObsLogger:
    """A named logger writing narrative records per the ``REPRO_LOG`` knob."""

    def __init__(self, name: str, stream: TextIO | None = None) -> None:
        self.name = name
        self._stream = stream

    # -- narrative channel --------------------------------------------------
    def _emit(self, level: str, msg: str, fields: dict[str, Any]) -> None:
        mode = _mode()
        if _LEVELS[level] < _threshold(mode):
            return
        stream = self._stream or (
            sys.stderr if level == "warning" else sys.stdout
        )
        if mode == "json":
            record = {"level": level, "logger": self.name, "msg": msg}
            record.update(fields)
            print(json.dumps(record, default=str), file=stream)
            return
        parts = [msg] if msg else []
        parts.extend(f"{k}={v}" for k, v in fields.items())
        print(" ".join(parts), file=stream)

    def debug(self, msg: str = "", **fields: Any) -> None:
        self._emit("debug", msg, fields)

    def info(self, msg: str = "", **fields: Any) -> None:
        self._emit("info", msg, fields)

    def warning(self, msg: str = "", **fields: Any) -> None:
        self._emit("warning", msg, fields)

    # -- data channel -------------------------------------------------------
    def data(self, line: str) -> None:
        """Machine-readable program output (CSV rows): never filtered,
        never reformatted, always stdout (flushed: CI tails the rows
        while slow sweeps run)."""
        print(line, file=self._stream or sys.stdout, flush=True)


_loggers: dict[str, ObsLogger] = {}


def get_logger(name: str) -> ObsLogger:
    """The process-wide logger for ``name`` (benchmark/demo module)."""
    if name not in _loggers:
        _loggers[name] = ObsLogger(name)
    return _loggers[name]
