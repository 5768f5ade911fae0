"""Reduce a profiler trace to the benchmark's device numbers.

A ``--trace 1`` run records the measured window with ``jax.profiler``.  The
host spans come from the benchmark's own ``TraceAnnotation``s
(``bench.window``, ``bench.call``, ``bench.wait``, ``bench.generate``,
``bench.drain``); the device's work from the TPU planes of the
``.xplane.pb`` file: the ``XLA Ops`` line (one event per operation run)
and the ``XLA Modules`` line (one event per program run).  Both are read by
time alone, not by XLA's module names, which a refactor changes.

Everything below :func:`extract` works on plain intervals, so the tests
check it on hand-made ones.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Callable, NamedTuple, Optional

#: the planes that hold a chip's own timeline
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start: float  # ns
    end: float  # ns


class Trace(NamedTuple):
    """What a run needs of a trace: its host spans and, per device, the
    operations and program runs."""

    spans: list  # of Event: the benchmark's annotations
    ops: dict  # device -> list of Event
    modules: dict  # device -> list of Event


def short(name: str, width: int = 160) -> str:
    """An operation as the TPU's trace names it (its HLO instruction),
    without layouts and cut to ``width`` characters."""
    while True:
        bare = re.sub(r"\{[^{}]*\}", "", name)
        if bare == name:
            return name[:width]
        name = bare


def tpu_rule(plane: str, line: str, event) -> Optional[tuple]:
    """``("op" | "module", device)`` for an event of a chip's timeline."""
    if DEVICE_PLANE.match(plane):
        if line == OPS_LINE:
            return "op", plane
        if line == MODULES_LINE:
            return "module", plane
    return None


def extract(path: str, rule: Optional[Callable] = None) -> Trace:
    """Read an ``.xplane.pb`` file: the host spans named ``bench.*``, and
    the events that ``rule`` (by default :func:`tpu_rule`) files under a
    device."""
    from jax.profiler import ProfileData

    rule = rule or tpu_rule
    data = ProfileData.from_file(path)
    spans, ops, modules = [], defaultdict(list), defaultdict(list)
    for plane in data.planes:
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            for e in line.events:
                ev = Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                if host and e.name.startswith(SPAN_PREFIX):
                    spans.append(ev)
                    continue
                kind = rule(plane.name, line.name, e)
                if kind is not None:
                    (ops if kind[0] == "op" else modules)[kind[1]].append(
                        ev._replace(name=short(e.name)))
    return Trace(sorted(spans, key=lambda s: s.start),
                 {d: sorted(v, key=lambda s: s.start) for d, v in ops.items()},
                 {d: sorted(v, key=lambda s: s.start)
                  for d, v in modules.items()})


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(events, lo: float = float("-inf"),
          hi: float = float("inf")) -> list:
    """The disjoint (start, end) intervals covered by ``events``, clipped
    to [lo, hi], in order."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def covered(intervals) -> float:
    return sum(t - s for s, t in intervals)


def idle(busy: list, lo: float, hi: float) -> list:
    """The (start, end) gaps in [lo, hi] that ``busy`` leaves uncovered."""
    out, at = [], lo
    for s, t in busy:
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events, lo: float = float("-inf"),
               hi: float = float("inf")) -> dict:
    """Time per name in [lo, hi], each event less the events nested in it
    (one line of a timeline nests, it never overlaps partly)."""
    out: dict = defaultdict(float)
    stack: list = []  # (end, name) of the open enclosing events

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= e.start:
            stack.pop()
        s, t = max(e.start, lo), min(e.end, hi)
        own = max(0.0, t - s)
        out[e.name] += own
        if stack:
            out[stack[-1][1]] -= own
        stack.append((e.end, e.name))
    return dict(out)


def innermost(spans, at: float) -> str:
    """The name of the latest-opened span that covers ``at``."""
    name = "none"
    for s in spans:
        if s.start > at:
            break
        if s.end >= at:
            name = s.name
    return name


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


# ---------------------------------------------------------------------------
# what a run reports
# ---------------------------------------------------------------------------


class Summary:
    """A trace seen through the measured window (the ``bench.window``
    span)."""

    def __init__(self, trace: Trace):
        windows = named(trace.spans, "bench.window")
        if not windows:
            raise ValueError("the trace holds no bench.window span")
        self.trace = trace
        self.lo, self.hi = windows[0].start, windows[0].end
        self.devices = sorted(trace.ops)
        self.busy = {d: union(trace.ops[d], self.lo, self.hi)
                     for d in self.devices}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(covered(b) for b in self.busy.values()) / (
            1e9 * len(self.devices))

    def busy_in(self, lo: float, hi: float) -> float:
        """Device-busy seconds in [lo, hi], averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(covered(union(self.trace.ops[d], lo, hi))
                   for d in self.devices) / (1e9 * len(self.devices))

    def spans(self, name: str) -> list:
        return [s for s in named(self.trace.spans, name)
                if s.start >= self.lo and s.end <= self.hi]

    def op_seconds(self, lo: Optional[float] = None,
                   hi: Optional[float] = None) -> dict:
        """Self time per operation name, in seconds, averaged over the
        devices."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        out: dict = defaultdict(float)
        for d in self.devices:
            for name, ns in self_times(self.trace.ops[d], lo, hi).items():
                out[name] += ns / (1e9 * len(self.devices))
        return dict(out)

    def gaps(self) -> list:
        """Every idle gap of every device in the window, as
        (seconds, host span covering it)."""
        out = []
        for d in self.devices:
            for s, t in idle(self.busy[d], self.lo, self.hi):
                out.append(((t - s) / 1e9,
                            innermost(self.trace.spans, 0.5 * (s + t))))
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gaps(), key=lambda g: -g[0])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for s, n in gaps[:top]]}
