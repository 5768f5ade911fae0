"""Benchmark runner: ``python -m benchmarks.run [--quick] [--json PATH]``.

Prints ``name,us_per_call,derived`` CSV rows — one section per paper
table/figure (datapath throughput = Table V, FU census = Table VIII,
randomized soak = §I, traversal = the RayCore workload, kNN = the
generalized modes, model smoke = framework sanity).  The roofline analysis
(production mesh) is separate: ``python -m benchmarks.roofline --all``.

``--json PATH`` additionally writes the rows as machine-readable JSON so
the perf trajectory can be tracked across PRs; ``--quick`` writes
``BENCH_quick.json`` at the repo root even without ``--json`` (CI uploads
it as an artifact on every run).

**Row schema.** Sections append ``(name, us_per_call, derived)`` tuples:

* ``name`` — stable row identifier (the trajectory joins on it).
* ``us_per_call`` — measured wall microseconds per call, or **None** for
  rows that report derived metrics only (FU censuses, build-quality
  ratios).  None serializes as JSON ``null`` and prints as an empty CSV
  field — never ``0.0``, which would read as "measured and
  instantaneous" to a trajectory diff.
* ``derived`` — ``k=v;k=v`` string, parsed into a dict for JSON by
  :func:`parse_derived`.

Rows from the datapath config sweep (``bench_sweep``) lead their derived
string with ``config=<tag>``; the JSON writer *promotes* that key to a
top-level ``config`` column (null for every other section's rows), so the
trajectory can group by datapath twin without parsing row names — the
``BENCH_quick.json`` schema guard in CI pins the column's presence.

Every JSON row additionally carries the provenance columns the
trajectory needs to be comparable across machines and commits:
``device`` (platform kind + count), ``jax_version``, and ``git_rev`` —
plus an ``obs`` column with the telemetry slice of the section that
produced the row (jit compiles, engine cache hits/misses, pad-waste
fraction), taken from ``repro.obs`` which this runner enables
(DESIGN.md §11).  Timings are therefore measured with telemetry *on* —
the recording overhead is a few counter bumps per engine call, and it is
identical for every row, so the trajectory stays self-consistent.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess


def _split_top_level(s: str, sep: str = ";") -> list:
    """Split on ``sep`` only outside (), {}, [] — metric names/values may
    contain separators (e.g. ``ops_vs_tableVIII(add;mul;cmp)={...}``)."""
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == sep and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    parts.append("".join(cur))
    return parts


def parse_derived(derived: str) -> dict:
    """``k=v;k=v`` derived strings -> a metrics dict (floats where they
    parse, strings otherwise; bare fragments collect under ``notes``)."""
    out: dict = {}
    for part in _split_top_level(derived):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            out.setdefault("notes", []).append(part)
            continue
        key, val = part.split("=", 1)
        try:
            out[key] = float(val[:-1] if val.endswith("x") else val)
        except ValueError:
            out[key] = val
    return out


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _obs_slice(before: dict, after: dict) -> dict:
    """The telemetry delta one benchmark section produced: jit compiles,
    engine compiled-fn cache hits/misses, and the section's pad-waste
    fraction (``repro.obs.snapshot()`` keys; DESIGN.md §11)."""
    c0, c1 = before["counters"], after["counters"]

    def delta(key):
        return c1.get(key, 0) - c0.get(key, 0)

    real = delta("engine.rows.real")
    padded = delta("engine.rows.padded")
    return {
        "compiles": after["jit"]["compiles"] - before["jit"]["compiles"],
        "cache_hits": delta("engine.cache.hits"),
        "cache_misses": delta("engine.cache.misses"),
        "pad_waste_fraction": (round(1.0 - real / padded, 6)
                               if padded else None),
    }


def provenance() -> dict:
    """The stable per-row schema columns: where/what produced the row."""
    import jax
    return {
        "device": f"{jax.devices()[0].platform}x{jax.local_device_count()}",
        "jax_version": jax.__version__,
        "git_rev": _git_rev(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slower model-stack section and write "
                         "BENCH_quick.json at the repo root")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as machine-readable JSON")
    args = ap.parse_args()

    json_path = args.json
    if json_path is None and args.quick:
        # --quick always leaves the trajectory artifact behind, wherever
        # it was launched from
        json_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_quick.json")

    from repro import obs
    from repro.runtime.compile_cache import use_compile_cache

    from . import (bench_build, bench_datapath, bench_knn, bench_serving,
                   bench_sweep, bench_traversal)

    use_compile_cache()
    obs.enable()  # every row gets its section's telemetry slice

    rows: list[tuple] = []
    obs_cols: list = []  # parallel to rows: the producing section's slice
    prov = provenance()

    def flush():
        # incremental JSON: rewrite after every section so a crash in a
        # later benchmark still leaves the completed rows on disk (CI
        # uploads the file unconditionally — a partial trajectory beats
        # an empty artifact)
        if not json_path:
            return
        payload = []
        for i, (name, us, derived) in enumerate(rows):
            metrics = parse_derived(derived)
            # the config sweep's datapath-twin tag is a first-class
            # trajectory column, not a buried metric (null elsewhere)
            config = metrics.pop("config", None)
            payload.append(dict(
                name=name,
                us_per_call=None if us is None else round(us, 3),
                config=config, derived=metrics, **prov,
                obs=obs_cols[i] if i < len(obs_cols) else None))
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    flush()  # schema-stable empty file exists from the first moment
    sections = [bench_datapath.run, bench_traversal.run, bench_build.run,
                bench_sweep.run, bench_knn.run,
                lambda rows: bench_serving.run(rows, n_requests=120,
                                               qps=1000.0)
                if args.quick else bench_serving.run(rows)]
    if not args.quick:
        from . import bench_models
        sections.append(bench_models.run)
    for section in sections:
        before = obs.snapshot()
        n0 = len(rows)
        section(rows)
        sl = _obs_slice(before, obs.snapshot())
        obs_cols.extend([sl] * (len(rows) - n0))
        flush()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        us_col = "" if us is None else f"{us:.3f}"
        print(f"{name},{us_col},{derived}")
    if json_path:
        print(f"wrote {len(rows)} rows to {json_path}")


if __name__ == "__main__":
    main()
