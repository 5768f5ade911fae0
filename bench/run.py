#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip, and print one JSON
line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A run checks for a TPU whose ``device_kind`` is in ``bench/peaks.json``
(else it exits non-zero and prints no result), makes its data from the
seed, builds the program's engine, warms up every shape that the window
will use (``setup_s`` ends there), measures for ``--seconds``, then reads
the device's peak memory, frees the program's state and compares a sample
of the answers with the configuration's plain reference.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer ones,
from a profiler trace of the window.  ``repro.obs`` stays disabled: every
number comes from the host clock around calls that end in
``block_until_ready``, from result records, from ``QueryServer.stats()``
or from the trace.

The last line of standard output is the result; the numbers compared, each
beside its limit, are the last lines of standard error and the result's
last key.  JAX's compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

#: the platform a run must find; the tests steer it to the CPU
PLATFORM = "tpu"


class NoChip(RuntimeError):
    """No accelerator that this benchmark can measure."""


# ---------------------------------------------------------------------------
# loading by name
# ---------------------------------------------------------------------------


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str, spec: dict) -> bool:
    """Whether ``metric`` is reported in ``cell``: the cells it lists, or,
    without a list, every cell (an end-to-end metric) or every cell that
    reports the end-to-end metric it moves (a per-layer one)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        e2e = {m["name"]: m for m in spec["end_to_end"]}
        return applies(e2e[metric["moves"]], cell, spec)
    return True


class Cell:
    """One entry of ``workloads`` with everything it names, read from the
    files that the names lead to."""

    def __init__(self, root: Path, name: str):
        self.root = root
        spec = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.spec, self.entry, self.name = spec, cells[name], name
        configs = {c["name"]: c for c in spec["configs"]}
        self.config = load_json(root / configs[self.entry["config"]]["file"])
        self.mix = load_json(root / "bench" / "mixes"
                             / f"{self.entry['traffic']}.json")
        self.kind = importlib.import_module(
            f"bench.kinds.{self.config['kind']}")
        self.chips = int(self.entry["chips"])

    def metrics(self, group: str) -> list:
        return [m for m in self.spec[group]
                if applies(m, self.name, self.spec)]

    def reader(self, metric: str):
        """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def accelerator(chips: int, peaks: dict):
    """The devices a cell runs on and the peaks of their kind.  Raises
    :class:`NoChip` when JAX finds no accelerator, too few, or one whose
    ``device_kind`` the peaks table lacks."""
    import jax

    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        raise NoChip(f"JAX finds no {PLATFORM}: its devices are "
                     f"{devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device_kind {kind!r} is not in bench/peaks.json")
    return devices[:chips], peaks[kind]


def peak_memory(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def seed_keys(seed: int):
    """Two PRNG keys, for the data and for the traffic, from all 64 bits
    of ``seed`` (JAX keeps 32 of an int)."""
    import jax

    seed %= 1 << 64
    return jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Context:
    """What a metric's reader gets: the cell, the window, and the trace
    (``None`` in a ``--trace 0`` run)."""

    def __init__(self, cell: Cell, peak: dict, setup_s: float, window,
                 summary):
        self.config, self.mix, self.peak = cell.config, cell.mix, peak
        self.setup_s, self.window, self.trace = setup_s, window, summary

    def call_spans(self) -> list:
        """Each closed-loop call with its ``bench.call`` span."""
        spans = self.trace.spans("bench.call")
        if len(spans) != len(self.window.calls):
            raise ValueError(f"{len(spans)} bench.call spans for "
                             f"{len(self.window.calls)} calls")
        return list(zip(self.window.calls, spans))

    def server_stats(self) -> tuple:
        """The server's ``ServerStats`` for the mix's method, as the window
        opened and once every answer was in."""
        method = self.mix["call"]
        return (self.window.stats_before[method],
                self.window.stats_after[method])


class Measured:
    """Set-up ends, and the window's compile count and profiler trace
    begin, on entry; both stop on exit."""

    def __init__(self, trace_dir):
        from repro.obs import CompileTracker

        self.tracker = CompileTracker()
        self.trace_dir = trace_dir
        self.setup_end = None

    def __enter__(self):
        import jax

        self.setup_end = time.perf_counter()
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
        self.tracker.start()
        return self

    def __exit__(self, *exc):
        import jax

        self.tracker.stop()
        if self.trace_dir:
            jax.profiler.stop_trace()


def distinct_shapes(payloads: list) -> list:
    import jax

    seen, out = set(), []
    for p in payloads:
        key = tuple((x.shape, str(x.dtype))
                    for x in jax.tree_util.tree_leaves(p))
        if key not in seen:
            seen.add(key)
            out.append(p)
    return out


def server_targets(engine, cell: Cell) -> list:
    """Every row count that the mix's server can hand its engine: a batch
    holds up to ``max_batch_rows - 1`` rows plus one largest request, and
    ``QueryServer(quantize_batches=True)`` pads it up a power-of-two
    ladder to the engine's own plan."""
    mix, config = cell.mix, cell.config
    most = mix["server"]["max_batch_rows"] - 1 + mix["rows"]["max"]
    out, q = set(), 1
    while True:
        plan = engine.plan_for(mix["call"], q, k=config.get("k"),
                               metric=config.get("metric", "euclidean"))
        out.add(plan.block * plan.n_blocks)
        if q >= most:
            return sorted(out)
        q *= 2


def drive(cell: Cell, dep, seed: int, seconds: float, measured: Measured):
    """Warm up, then run the window of the cell's mix.  Returns the window
    and what its payloads were cut from: the closed loop's payloads, or
    the open loop's pool of rows."""
    import jax

    from bench import traffic

    kind, mix, config = cell.kind, cell.mix, cell.config
    k_payload = seed_keys(seed)[1]
    if mix["loop"] == "closed":
        payloads = kind.closed_payloads(dep, config, mix, k_payload)

        def call(p):
            return kind.call(dep.engine, p, config, mix)

        for p in distinct_shapes(payloads):
            jax.block_until_ready(call(p))
        with measured:
            window = traffic.closed_loop(call, payloads, kind.rows_of,
                                         seconds)
        return window, payloads
    pool = kind.pool(dep, config, mix, k_payload)
    requests = traffic.open_requests(mix, seconds, len(pool),
                                     np.random.default_rng(seed % (1 << 64)))
    warm = [pool[:n] for n in server_targets(dep.engine, cell)]

    def serve(server, payload):
        return kind.serve(server, payload, config, mix)

    window = traffic.open_loop(dep.engine, serve, pool, requests,
                               mix["server"], warm, mix["drain_s"], measured)
    return window, pool


def sample(cell: Cell, window, source, seed: int):
    """(inputs, answers) of the rows that the check compares, as numpy:
    rows drawn from the seed out of every answer of a closed loop; or
    requests of an open loop, its longest ones and others drawn from the
    seed, every row of each.  ``None`` where nothing was answered."""
    kind, mix = cell.kind, cell.mix
    rng = np.random.default_rng([seed % (1 << 64), 2])
    parts = []  # (inputs, answers, rows) per call or request
    if mix["loop"] == "closed":
        counts = [c.rows for c in window.calls]
        edges = np.cumsum([0] + counts)
        pick = rng.choice(edges[-1], min(mix["check_rows"], edges[-1]),
                          replace=False)
        for i, c in enumerate(window.calls):
            rows = np.sort(pick[(pick >= edges[i]) & (pick < edges[i + 1])]
                           - edges[i])
            if len(rows):
                parts.append(kind.host_rows(source[c.payload], c.result)
                             + (rows,))
    else:
        answered = [i for i, d in enumerate(window.done) if d is not None]
        longest = sorted(answered, key=lambda i: -window.requests[i].rows)
        chosen = set(longest[:mix["check_longest"]])
        total = sum(window.requests[i].rows for i in chosen)
        for i in rng.permutation(answered):
            if total >= mix["check_rows"]:
                break
            if int(i) not in chosen:
                chosen.add(int(i))
                total += window.requests[i].rows
        for i in sorted(chosen):
            r = window.requests[i]
            parts.append(kind.host_rows(source[r.lo:r.lo + r.rows],
                                        window.results[i])
                         + (np.arange(r.rows),))
    if not parts:
        return None, None
    return tuple({k: np.concatenate([p[j][k][p[2]] for p in parts])
                  for k in parts[0][j]} for j in (0, 1))


def run(root: Path, workload: str, seed: int, seconds: float,
        trace: bool, control: bool = False) -> dict:
    """One run of one cell; returns the result's fields.  With ``control``
    (``bench/calibrate.py``, never the benchmark's own runs) the result
    also holds what the same comparison reads of the control: the plain
    reference in the precision below the configuration's, put in the
    program's place on the same inputs."""
    cell = Cell(root, workload)
    devices, peak = accelerator(cell.chips, load_json(root / "bench"
                                                      / "peaks.json"))
    from bench.trace import Summary, extract

    dep = cell.kind.build(cell.config, cell.mix, seed_keys(seed)[0])
    with contextlib.ExitStack() as stack:
        trace_dir = (stack.enter_context(tempfile.TemporaryDirectory())
                     if trace else None)
        measured = Measured(trace_dir)
        window, source = drive(cell, dep, seed, seconds, measured)
        summary = None
        if trace:
            pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
            summary = Summary(extract(pb[0]))
    setup_s = measured.setup_end - PROCESS_START
    memory = peak_memory(devices)
    print(f"compiles_in_window: {measured.tracker.compiles}", flush=True)
    ctx = Context(cell, peak, setup_s, window, summary)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(group):
        value = cell.reader(m["name"])(ctx)
        if value is None:
            if group == "end_to_end":
                raise RuntimeError(f"no reading of {m['name']}")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    if cell.mix["loop"] == "open":
        late = [s - (window.start + r.due)
                for s, r in zip(window.sent, window.requests)
                if s is not None]
        print("generator_late_ms: p50 {} p99 {} max {}".format(
            *(1e3 * np.percentile(late, [50, 99, 100]))), flush=True)
        attempted = len(window.requests)
        failed = sum(1 for d in window.done if d is None)
    else:
        attempted, failed = len(window.calls), 0
    print(f"window: {attempted} attempted, {failed} failed, "
          f"{window.end - window.start:.3f} s", flush=True)

    inputs, answers = sample(cell, window, source, seed)
    unanswered = (sum(1 for d, e in zip(window.done, window.errors)
                      if d is None and e is None)
                  if cell.mix["loop"] == "open" else 0)
    dep.free()
    del window, ctx, source
    gc.collect()
    readings = ({} if inputs is None else
                cell.kind.check(dep.data, inputs, answers, cell.config))
    checks = {name: {"value": readings.get(name), "limit": limit}
              for name, limit in cell.config["limits"].items()}
    controlled = None
    if control and inputs is not None:
        controlled = cell.kind.check(
            dep.data, inputs, cell.kind.control(dep.data, inputs,
                                                cell.config), cell.config)
    if cell.mix["loop"] == "open":
        checks["unanswered"] = {"value": unanswered, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    if controlled is not None:
        out["control"] = controlled
    out["checks"] = checks
    return out


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(root, args.workload, args.seed, args.seconds,
                  bool(args.trace))
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def use_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # bounds the directory, and keeps out any one program larger than it:
    # a nearest program holds its index as a constant (1.4 GB at 10^6 x
    # 128), made anew from every seed, and would fill the disk
    jax.config.update("jax_compilation_cache_max_size", 1 << 30)


if __name__ == "__main__":
    use_cache(ROOT)
    sys.exit(main())
