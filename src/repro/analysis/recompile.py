"""Recompilation tracker: assert the compile counts the docs promise.

``api.run``'s executable cache and ``run_stream``'s fixed-shape segments
exist so that a multi-gigabyte stream costs *two* compilations (steady
segment + tail) and a resumed run costs *zero*.  Nothing enforced that —
a carry-dtype drift or a shape-keying bug silently turns every segment
into a recompile and the perf claims into fiction.

:func:`track_compiles` watches two signals at once:

* **trace compiles** — jax's own ``log_compiles`` stream ("Compiling
  jit(<name>) with global shapes...", emitted once per new (function, avals)
  trace, AOT or not), captured with a logging handler;
* **executable compiles** — misses of ``api._EXEC_CACHE``, reported by
  the hook :func:`repro.cachesim.api.add_compile_listener`; this is the
  precise "one compile per stream shape" counter.

Usage::

    with track_compiles() as log:
        run_stream(pd, chunks, ...)
    log.assert_executables(2)          # steady segment + tail
    assert log.trace_count("run_fn") <= 2

No device computation is performed by the tracker itself; it only
observes.
"""

from __future__ import annotations

import contextlib
import logging
import re
from dataclasses import dataclass, field
from typing import List, Optional

import jax

__all__ = ["CompileEvent", "CompileLog", "track_compiles"]

_COMPILING_RE = re.compile(r"^Compiling jit\(([^\s()]+)\) ")

#: the logger that carries the log_compiles "Compiling jit(<name>) ..."
#: records
_JAX_LOGGERS = ("jax._src.interpreters.pxla",)


@dataclass(frozen=True)
class CompileEvent:
    """One observed compilation."""

    source: str  # "trace" (jax log) | "executable" (api cache miss)
    name: str  # traced function name, or the api cache-key summary


@dataclass
class CompileLog:
    events: List[CompileEvent] = field(default_factory=list)

    # -- queries -----------------------------------------------------------
    @property
    def traces(self) -> List[CompileEvent]:
        return [e for e in self.events if e.source == "trace"]

    @property
    def executables(self) -> List[CompileEvent]:
        return [e for e in self.events if e.source == "executable"]

    def trace_count(self, name: Optional[str] = None) -> int:
        """Trace compiles, optionally restricted to one function name
        (tiny op compiles like ``convert_element_type`` otherwise count)."""
        return sum(1 for e in self.traces if name is None or e.name == name)

    @property
    def executable_count(self) -> int:
        return len(self.executables)

    # -- assertions --------------------------------------------------------
    def assert_executables(self, expected: int) -> None:
        got = self.executable_count
        if got != expected:
            raise AssertionError(
                f"expected exactly {expected} executable compiles, "
                f"observed {got}: {[e.name for e in self.executables]}"
            )

    def assert_no_recompilation(self) -> None:
        self.assert_executables(0)


class _Handler(logging.Handler):
    def __init__(self, log: CompileLog):
        super().__init__(level=logging.DEBUG)
        self._log = log

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILING_RE.match(record.getMessage())
        except Exception:  # reprolint: allow(broad-except) a log record must never break the run
            return
        if m:
            self._log.events.append(CompileEvent("trace", m.group(1)))


@contextlib.contextmanager
def track_compiles():
    """Context manager yielding a live :class:`CompileLog`.

    Temporarily enables ``jax.log_compiles`` and attaches a counting
    handler; also subscribes to the api executable-cache-miss hook.  Both
    are detached on exit — nesting is safe (each tracker sees the events
    fired within its own extent)."""
    from repro.cachesim import api

    log = CompileLog()
    handler = _Handler(log)

    def _on_executable(info: dict) -> None:
        log.events.append(
            CompileEvent("executable", info.get("name", "<unknown>"))
        )

    loggers = [logging.getLogger(name) for name in _JAX_LOGGERS]
    prior_levels = [lg.level for lg in loggers]
    api.add_compile_listener(_on_executable)
    for lg in loggers:
        lg.addHandler(handler)
        if lg.level > logging.DEBUG or lg.level == logging.NOTSET:
            lg.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles():
            yield log
    finally:
        for lg, lvl in zip(loggers, prior_levels):
            lg.removeHandler(handler)
            lg.setLevel(lvl)
        api.remove_compile_listener(_on_executable)
