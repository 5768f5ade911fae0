"""``repro.obs`` — the telemetry plane (DESIGN.md §11).

One switch, three signals, one export surface, and one timeline:

* **Metrics** — :class:`MetricsRegistry` counters / gauges / streaming
  histograms (``obs/metrics.py``).  The engine records jit-cache hits vs
  compiles, pad waste (padded vs real rows), chunk/shard fan-out, and
  aggregate datapath job counts per backend, summed on the device (no
  call waits for telemetry); the serving layer routes its request
  accounting through a registry.
* **Compile events** — :class:`CompileTracker` (``obs/compile.py``): the
  test suite's jit tracing-cache-miss counter promoted to a public
  window over a process-wide retrace count, so "steady-state compiles
  == 0" is a servable metric, not just a test assertion.
* **Traces** — request-lifecycle spans (admit → coalesce → execute →
  split per served request) in a bounded buffer, exported as
  Chrome-trace/Perfetto JSON (``obs/trace.py``).
* **Spans on the profiler's clock** — :func:`span` (``obs/trace.py``)
  is a ``jax.profiler.TraceAnnotation`` whatever the switch says: under
  ``jax.profiler.start_trace`` the engine's ``engine.*`` and the
  server's ``serving.*`` spans land beside the device's operations; with
  no profiler recording each costs one native flag check.  The engine's
  device programs carry ``jax.named_scope`` s (``wavefront.box``,
  ``nearest.topk``, ...); :func:`programs.op_scopes` maps each compiled
  operation to its scope.

The registry and the span buffer are **off by default** and free while
off: recording sites pre-resolve their instruments and the disabled path
is one attribute check + branch, so engine and serving results are
bit-identical with telemetry on or off — the contract ``tests/test_obs.py``
pins — and turning it on makes no call wait or read the device.

Quickstart::

    from repro import obs

    obs.enable()
    ... run queries / serve traffic ...
    print(obs.snapshot())                    # JSON-able dict
    obs.export_chrome_trace("trace.json")    # open in Perfetto

    jax.profiler.start_trace("/tmp/prof")    # the timeline: engine.* and
    engine.trace(rays)                       # serving.* spans beside the
    jax.profiler.stop_trace()                # device ops, by named scope

    with obs.CompileTracker() as t:
        engine.trace(rays)                   # warm steady state
    assert t.compiles == 0

``python -m repro.obs.dump`` pretty-prints a snapshot (current process,
or a previously saved file).
"""
from __future__ import annotations

import weakref
from typing import Callable

from .compile import CompileTracker, hook_installed, install_hook, total_compiles  # noqa: F401
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, default_registry  # noqa: F401
from . import programs  # noqa: F401
from .trace import TraceBuffer, default_buffer, export_chrome_trace, profiled_spans, span  # noqa: F401

__all__ = [
    "CompileTracker",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "TraceBuffer",
    "default_buffer",
    "default_registry",
    "disable",
    "enable",
    "export_chrome_trace",
    "install_hook",
    "is_enabled",
    "profiled_spans",
    "programs",
    "register_source",
    "registry",
    "snapshot",
    "span",
    "total_compiles",
    "unregister_source",
    "write_snapshot",
]

#: named snapshot sources: subsystems that keep their own always-on
#: registries (the serving layer) attach a zero-arg dict provider here;
#: stored as weak references so a dropped QueryServer vanishes from
#: snapshots instead of pinning the object alive
_SOURCES: dict[str, object] = {}


def registry() -> MetricsRegistry:
    """The process-global default registry (disabled until
    :func:`enable`)."""
    return default_registry()


def enable() -> None:
    """Turn the telemetry plane on: the default registry records, the
    span buffer records, and the compile hook goes in (so
    ``snapshot()['jit']['compiles']`` counts from here on)."""
    install_hook()
    default_registry().enable()


def disable() -> None:
    """Turn recording off.  The compile listener stays registered
    (``jax.monitoring`` has no per-listener removal), but it only bumps
    one integer per retrace — stock-jax behavior otherwise."""
    default_registry().disable()


def is_enabled() -> bool:
    return default_registry().enabled


def register_source(name: str, provider: Callable[[], dict]) -> str:
    """Attach a named snapshot section: ``provider()`` must return a
    JSON-able dict; it is held weakly (bound methods via ``WeakMethod``)
    and called at :func:`snapshot` time.  Returns the (possibly
    ``#n``-suffixed, if taken) name actually registered."""
    base, n = name, 1
    while name in _SOURCES and _deref(_SOURCES[name]) is not None:
        n += 1
        name = f"{base}#{n}"
    try:
        ref: object = weakref.WeakMethod(provider)  # bound method
    except TypeError:
        ref = weakref.ref(provider)  # plain function / callable object
    _SOURCES[name] = ref
    return name


def unregister_source(name: str) -> None:
    _SOURCES.pop(name, None)


def _deref(ref):
    try:
        return ref()
    except Exception:
        return None


def snapshot() -> dict:
    """One stable JSON-able view of the whole telemetry plane::

        {
          "enabled": bool,
          "jit": {"hook_installed": bool, "compiles": int},
          "counters" / "gauges" / "histograms": {...},   # default registry
          "derived": {"pad_waste_fraction": float|None,
                      "cache_hit_rate": float|None},
          "trace": {"spans": int},
          "sources": {"serving": {...}, ...},            # live attachments
        }

    ``pad_waste_fraction`` is 1 - real/padded over every engine call
    recorded so far; ``cache_hit_rate`` is hits/(hits+misses) of the
    engine's compiled-function cache.  Both are None until the engine
    has recorded at least one call.
    """
    reg = default_registry()
    snap = reg.snapshot()
    counters = snap["counters"]
    real = counters.get("engine.rows.real", 0)
    padded = counters.get("engine.rows.padded", 0)
    hits = counters.get("engine.cache.hits", 0)
    misses = counters.get("engine.cache.misses", 0)
    sources = {}
    for name, ref in list(_SOURCES.items()):
        provider = _deref(ref)
        if provider is None:
            _SOURCES.pop(name, None)
            continue
        sources[name] = provider()
    return {
        "enabled": reg.enabled,
        "jit": {"hook_installed": hook_installed(),
                "compiles": total_compiles()},
        "counters": counters,
        "gauges": snap["gauges"],
        "histograms": snap["histograms"],
        "derived": {
            "pad_waste_fraction": (1.0 - real / padded) if padded else None,
            "cache_hit_rate": (hits / (hits + misses)
                               if (hits + misses) else None),
        },
        "trace": {"spans": len(default_buffer())},
        "sources": sources,
    }


def write_snapshot(path: str) -> dict:
    """Dump :func:`snapshot` as JSON at ``path`` (CI artifact form);
    returns the snapshot."""
    import json
    snap = snapshot()
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
        f.write("\n")
    return snap
