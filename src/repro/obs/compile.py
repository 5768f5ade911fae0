"""Compile-event tracking: count jit retraces as a first-class metric.

The repo's zero-retrace contracts (``Scene.refit`` animation frames,
chunked dispatch re-entering one compiled function, the serving ladder's
O(log) program count) are asserted through :class:`CompileTracker`
(DESIGN.md §11), and the same signal is *served* — exported in
``obs.snapshot()``, attached to benchmark rows, and asserted by the
serving example against a live run.

Mechanism: one process-wide listener on jax's public monitoring event
``/jax/core/compile/jaxpr_trace_duration``, which jax records exactly
once per (fun, abstract-args) tracing-cache miss of a jitted function,
i.e. per retrace.  The listener is registered lazily on first use and
then **never removed** (``jax.monitoring`` keeps listeners for the life
of the process).  Until something registers it, tracked totals read 0
and no callback runs (telemetry disabled really is disabled).

:class:`CompileTracker` is a window over the monotonic process total::

    with CompileTracker() as t:
        engine.trace(rays)        # steady state: everything cached
    assert t.compiles == 0

Nested and overlapping trackers are fine — each just subtracts its own
baseline.  When the global registry is enabled, every retrace also
increments the ``jit.retraces`` counter there.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .metrics import default_registry

__all__ = ["CompileTracker", "hook_installed", "install_hook",
           "total_compiles"]

#: the jax monitoring event recorded once per jit tracing-cache miss
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

#: monotonic process-wide retrace count (valid once the listener is in)
_COUNT = [0]
_INSTALLED = False
_LOCK = threading.Lock()  # retraces can happen on serving threads

#: pre-created so the listener's registry path is one attribute check
_RETRACES = default_registry().counter("jit.retraces")


def _on_event(event: str, duration: float, **kwargs) -> None:
    if event == TRACE_EVENT:
        with _LOCK:
            _COUNT[0] += 1
        _RETRACES.inc()


def install_hook() -> bool:
    """Register the retrace-counting listener (idempotent).  Returns
    whether it is active (always True: the event is public jax API)."""
    global _INSTALLED
    with _LOCK:
        if not _INSTALLED:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _INSTALLED = True
    return True


def hook_installed() -> bool:
    return _INSTALLED


def total_compiles() -> int:
    """Process-wide retraces since the listener went in (0 before)."""
    return _COUNT[0]


class CompileTracker:
    """A window over the process retrace counter.

    Use as a context manager (the test idiom the suite runs on) or via
    explicit :meth:`start` / :meth:`stop`; :attr:`compiles` is the number
    of jit tracings that happened inside the window.  Constructing a
    tracker registers the listener if it is not in yet.
    """

    def __init__(self):
        self.available = install_hook()
        self._start: Optional[int] = None
        self._stop: Optional[int] = None

    def start(self) -> "CompileTracker":
        self._start = _COUNT[0]
        self._stop = None
        return self

    def stop(self) -> int:
        self._stop = _COUNT[0]
        return self.compiles

    @property
    def compiles(self) -> int:
        """Retraces since :meth:`start` (live while the window is open,
        frozen once stopped; 0 before the window opens)."""
        if self._start is None:
            return 0
        end = _COUNT[0] if self._stop is None else self._stop
        return end - self._start

    def __enter__(self) -> "CompileTracker":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def __repr__(self):
        return (f"CompileTracker(compiles={self.compiles}, "
                f"available={self.available})")
