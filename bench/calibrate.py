#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 3

Runs the cell once per seed in this one process, through the same code as
``bench/run.py`` (its set-up, a short window, the comparison), and prints
one JSON line per seed with the numbers compared.  For a control seed it
also prints what the comparison reads of the control: the plain reference
in the precision below the configuration's, in the program's place on the
same inputs.  The last line gives, per number, the largest reading of the
program and the smallest of the control.  The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, run, use_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    program, control = {}, {}
    for seed in seeds:
        out = run(ROOT, args.workload, seed, args.seconds, False,
                  control=seed in controls)
        readings = {k: v["value"] for k, v in out["checks"].items()}
        line = {"seed": seed, "correct": out["correct"],
                "program": readings, "control": out.get("control")}
        print(json.dumps(line), flush=True)
        for k, v in readings.items():
            program[k] = max(program.get(k, v), v)
        for k, v in (out.get("control") or {}).items():
            control[k] = min(control.get(k, v), v)
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "program_max": program, "control_min": control}))
    return 0


if __name__ == "__main__":
    use_cache(ROOT)
    sys.exit(main())
