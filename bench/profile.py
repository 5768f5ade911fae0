#!/usr/bin/env python3
"""Where one cell's time goes, from a profiler trace that is kept.

    python3 bench/profile.py --workload <cell> --seed <n> --seconds <s> \
        --out <dir>

Runs the cell as ``bench/run.py --trace 1`` does (the same data, warm-up
and window), keeps the trace under ``<dir>`` with the program's
``op_scopes.json`` beside it, and prints one JSON line: the cell's
metrics (the end-to-end ones measured under the profiler, so with its
cost), the top device operations, the device time under each of the
program's named scopes, the idle gaps labelled by the innermost host
span (the program's ``engine.*`` / ``serving.*`` span wherever the host
was inside it), and two checks that the program's spans share the
device's clock.  It checks no answers: ``bench/run.py`` is the
benchmark.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, scopes  # noqa: E402
from bench.trace import Summary, extract  # noqa: E402

#: the named scopes of the program's device code
SCOPES = ("wavefront.pop", "wavefront.box", "wavefront.triangle",
          "wavefront.push", "nearest.distance", "nearest.topk")


def clock_checks(summary, spans: list) -> dict:
    """Calls whose first main-program run starts after the call's first
    ``engine.block`` span opened, and idle gaps of the window that no host
    span covers."""
    calls = [s for s in spans if s.name == "bench.call"
             and summary.lo <= s.start <= summary.hi]
    runs = [m for d in summary.devices
            for m in summary.trace.modules.get(d, ())]
    busy: dict = {}
    for m in runs:
        busy[m.name] = busy.get(m.name, 0.0) + m.end - m.start
    main = max(busy, key=busy.get) if busy else None
    after = 0
    for c in calls:
        blocks = [s.start for s in spans if s.name == "engine.block"
                  and c.start <= s.start <= c.end]
        first = [m.start for m in runs if m.name == main
                 and c.start <= m.start <= c.end]
        after += bool(blocks and first and min(first) >= min(blocks))
    gaps = scopes.labelled_gaps(summary, spans)
    return {"main_program": main, "calls": len(calls),
            "calls_run_after_first_block": after,
            "gaps_labelled_none": sum(1 for _, n in gaps if n == "none")}


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True,
                    help="directory for the trace and op_scopes.json")
    args = ap.parse_args(argv)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    cell = run.Cell(root, args.workload)
    try:
        _, peak = run.accelerator(
            cell.chips, run.load_json(root / "bench" / "peaks.json"))
    except run.NoChip as exc:
        print(f"profile: {exc}", file=sys.stderr)
        return 2
    dep = cell.kind.build(cell.config, cell.mix,
                          run.seed_keys(args.seed)[0])
    measured = run.Measured(str(out_dir))
    window, _ = run.drive(cell, dep, args.seed, args.seconds, measured)
    pb = max(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                       recursive=True), key=os.path.getmtime)
    summary = Summary(extract(pb))
    ctx = run.Context(cell, peak, measured.setup_end - PROCESS_START,
                      window, summary)
    metrics = {m["name"]: cell.reader(m["name"])(ctx)
               for group in ("end_to_end", "per_layer")
               for m in cell.metrics(group)}
    t0 = time.perf_counter()
    op_scopes = scopes.op_scopes() or {}
    print(f"op_scopes: {len(op_scopes)} programs in "
          f"{time.perf_counter() - t0:.3f} s", file=sys.stderr, flush=True)
    (out_dir / "op_scopes.json").write_text(json.dumps(op_scopes))
    spans = scopes.host_spans(pb)
    gaps = sorted(scopes.labelled_gaps(summary, spans), key=lambda g: -g[0])
    print(json.dumps({
        "trace": pb,
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "metrics": metrics,
        "device_ops": summary.breakdown()["device_ops"],
        "device_scopes": scopes.device_scopes(summary, op_scopes, SCOPES),
        "idle_gaps": [[n, s] for s, n in gaps[:10]],
        "clock": clock_checks(summary, spans),
    }), flush=True)
    return 0


if __name__ == "__main__":
    run.use_cache(ROOT)
    sys.exit(main())
