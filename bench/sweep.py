#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate that the
system keeps up with.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 10 \\
        --rates 100,200,400,800

One set-up, then the cell's mix at each rate in turn through a fresh
``QueryServer`` over the same engine, each for ``--seconds``.  One JSON
line per rate: offered and completed rates, the latency quantiles from
the due time (first and second half of the requests apart, so that a
growing backlog shows), how many requests were still unanswered when the
last one was due, and how late the generator ran.  This is how the rate
in a mix file is chosen; the benchmark's runs offer that one rate.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np

from run import (ROOT, Cell, accelerator, load_json, seed_keys,
                 server_targets, use_cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from bench import traffic

    cell = Cell(ROOT, args.workload)
    accelerator(cell.chips, load_json(ROOT / "bench" / "peaks.json"))
    kind, mix, config = cell.kind, cell.mix, cell.config
    k_data, k_traffic = seed_keys(args.seed)
    dep = kind.build(config, mix, k_data)
    pool = kind.pool(dep, config, mix, k_traffic)
    warm = [pool[:n] for n in server_targets(dep.engine, cell)]

    def serve(server, payload):
        return kind.serve(server, payload, config, mix)

    for rate in (float(r) for r in args.rates.split(",")):
        rng = np.random.default_rng(args.seed % (1 << 64))
        requests = traffic.open_requests(dict(mix, rate_per_s=rate),
                                         args.seconds, len(pool), rng)
        w = traffic.open_loop(dep.engine, serve, pool, requests,
                              mix["server"], warm, mix["drain_s"],
                              contextlib.nullcontext())
        close = w.start + requests[-1].due
        lat = np.array([(d if d is not None else w.waited_until)
                        - (w.start + r.due)
                        for r, d in zip(requests, w.done)])
        half = len(lat) // 2
        answered = sum(d is not None for d in w.done)
        stats = w.stats_after[mix["call"]]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(requests),
            "answered": answered,
            "completed_per_s": answered / (w.end - w.start),
            "rows_per_s": sum(r.rows for r, d in zip(requests, w.done)
                              if d is not None) / (w.end - w.start),
            "unanswered_at_close": sum(d is None or d > close
                                       for d in w.done),
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "p95_first_half_ms": 1e3 * float(np.percentile(lat[:half], 95)),
            "p95_second_half_ms": 1e3 * float(np.percentile(lat[half:], 95)),
            "late_p99_ms": 1e3 * float(np.percentile(
                [s - (w.start + r.due) for s, r in zip(w.sent, requests)],
                99)),
            "mean_batch_rows": stats.mean_batch_rows,
            "mean_fill": stats.mean_fill}), flush=True)
    return 0


if __name__ == "__main__":
    use_cache(ROOT)
    sys.exit(main())
