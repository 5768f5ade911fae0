"""The program's own spans and named scopes, for the readers of per-layer
metrics.

``bench/trace.py`` keeps the benchmark's ``bench.*`` spans and each device
operation's name.  The program adds two things it does not keep:

* its host spans (``engine.*``, ``serving.*``; ``repro.obs.span``), which
  the program also holds in memory, on ``time.perf_counter``, while a
  profiler records (``repro.obs.profiled_spans``);
* the ``jax.named_scope`` of each device operation, in the compiled
  programs' metadata (``repro.obs.programs.op_scopes``): an operation is
  matched by its HLO instruction inside the program run that holds it.

A program without them (one older than both) gives ``None`` here, and the
readers then leave their metric out.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import trace as _trace

#: an operation's HLO instruction: the TPU's trace names the operation by
#: its whole instruction (``%fusion.151 = f32[...] fusion(...)``), the
#: CPU's by the instruction's name alone
_INSTRUCTION = re.compile(r"%?([\w.\-]+)")
#: a program run on the ``XLA Modules`` line: the module's name, perhaps
#: followed by ``(<program id>)``
_MODULE = re.compile(r"([^\s(]+)")


def program_spans(name: str, lo: float, hi: float):
    """The program's spans called ``name`` that opened in [lo, hi] on
    ``time.perf_counter``, or ``None`` where the program keeps none."""
    try:
        from repro.obs import profiled_spans
    except ImportError:
        return None
    return [s for s in profiled_spans()
            if s.name == name and lo <= s.start <= hi]


def op_scopes():
    """``{module: {instruction: op_name}}`` of the programs the engine ran,
    or ``None`` where the program does not note them."""
    try:
        from repro.obs.programs import op_scopes as read
    except ImportError:
        return None
    return read() or None


def instruction(op: str) -> str:
    """The HLO instruction's name in an operation's name from the trace."""
    found = _INSTRUCTION.match(op)
    return found.group(1) if found else op


def under(path, scope: str) -> bool:
    """Whether an ``op_name`` path runs inside the named scope ``scope``."""
    return path is not None and scope in path.split("/")


def scoped_ops(summary, lo: float, hi: float, scopes: dict) -> dict:
    """Self time (seconds, averaged over the devices) of each operation in
    [lo, hi], keyed by the operation's ``op_name`` path (``None`` where the
    noted programs do not hold it).  On a device whose trace has program
    runs, an operation is looked up in the program of the run that holds
    it; without them (the CPU's trace), in every noted program."""
    merged: dict = {}
    for ops in scopes.values():
        merged.update(ops)
    out: dict = defaultdict(float)
    n = max(1, len(summary.devices))
    for d in summary.devices:
        runs = [m for m in summary.trace.modules.get(d, ())
                if m.end > lo and m.start < hi]
        windows = ([(max(lo, m.start), min(hi, m.end),
                     scopes.get(_MODULE.match(m.name).group(1), {}))
                    for m in runs] if runs else [(lo, hi, merged)])
        for a, b, ops in windows:
            for op, ns in _trace.self_times(summary.trace.ops[d], a,
                                            b).items():
                out[ops.get(instruction(op))] += ns / (1e9 * n)
    return dict(out)


def call_share(ctx, scope: str):
    """Share of the device-busy time inside the calls' ``bench.call``
    spans spent in operations under ``scope``, in percent; ``None``
    without a trace, without noted programs, or where no operation of the
    calls has a scope at all."""
    if ctx.trace is None:
        return None
    scopes = op_scopes()
    if scopes is None:
        return None
    inside = busy = scoped = 0.0
    for _, span in ctx.call_spans():
        busy += ctx.trace.busy_in(span.start, span.end)
        for path, s in scoped_ops(ctx.trace, span.start, span.end,
                                  scopes).items():
            scoped += s if path is not None else 0.0
            inside += s if under(path, scope) else 0.0
    return 100.0 * inside / busy if busy and scoped else None


def mean_span_ms(ctx, name: str):
    """Mean length of the program's ``name`` spans that opened in the
    window, in milliseconds."""
    w = ctx.window
    spans = program_spans(name, w.start, getattr(w, "waited_until", w.end))
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)


# ---------------------------------------------------------------------------
# a kept trace: every host span, for bench/profile.py
# ---------------------------------------------------------------------------

#: host spans that label the timeline: the benchmark's and the program's
HOST_PREFIXES = ("bench.", "engine.", "serving.")


def host_spans(path: str) -> list:
    """Every host span of an ``.xplane.pb`` file whose name starts with one
    of :data:`HOST_PREFIXES`, in order of opening, on the trace's clock."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIXES):
                    out.append(_trace.Event(e.name, e.start_ns,
                                            e.start_ns + e.duration_ns))
    return sorted(out, key=lambda s: (s.start, -s.end))


def labelled_gaps(summary, spans: list) -> list:
    """Every idle gap of every device in the window, as (seconds, the
    innermost of ``spans`` that covers it)."""
    out = []
    for d in summary.devices:
        for s, t in _trace.idle(summary.busy[d], summary.lo, summary.hi):
            out.append(((t - s) / 1e9,
                        _trace.innermost(spans, 0.5 * (s + t))))
    return out


def device_scopes(summary, scopes: dict, names: tuple) -> dict:
    """Device seconds in the window under each of the named scopes
    ``names``, and under none of them (``"other"``)."""
    out = dict.fromkeys(names + ("other",), 0.0)
    for path, s in scoped_ops(summary, summary.lo, summary.hi,
                              scopes).items():
        hit = next((n for n in names if under(path, n)), "other")
        out[hit] += s
    return out
