"""A benchmark checkout at a size that a CPU test run holds.

``tiny_root`` copies the harness's data (BENCHMARK.json, the metric
readers) into a temporary directory and cuts every configuration and mix
to a few hundred rows; the code under test is the harness itself.  The
``cpu_chip`` fixture steers the harness's look for a chip to the CPU and
its trace reduction to the CPU's timeline."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

#: every key changed from the real files, per file
TINY = {
    "configs/frame-1m.json": {"clusters": 16, "triangles_per_cluster": 64,
                              "cluster_spread": 0.6, "triangle_size": 0.4,
                              "frame": [32, 18]},
    "configs/sift-1m.json": {"points": 4096, "components": 16,
                             "engine": {"chunk_size": 64}},
    "mixes/primary.json": {"sets": 2, "check_rows": 1152},
    "mixes/batch.json": {"rows_per_call": 300, "sets": 2, "check_rows": 600},
    "mixes/served.json": {"rate_per_s": 40, "rows": {"min": 1, "max": 8},
                          "pool_rows": 512, "engine": {"pad_multiple": 32},
                          "server": {"max_batch_rows": 24, "max_wait": 0.005,
                                     "queue_limit": 10000,
                                     "policy": "block"},
                          "drain_s": 30, "check_rows": 400,
                          "check_longest": 4},
}


#: the served cell as a later PR would add it: its mix and readers are in
#: ``bench/``, its entries not yet in BENCHMARK.json (PERF.md says why)
SERVED = "sift-1m.served"
SERVED_ENTRIES = {
    "workloads": [{"name": SERVED, "config": "sift-1m", "traffic": "served",
                   "chips": 1, "why": "open loop"}],
    "end_to_end": [{"name": "p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": [SERVED]}],
    "per_layer": [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": "p95_ms", "workloads": [SERVED]}
        for name, unit, better, source, layer in (
            ("serving.batch_rows", "rows", "higher", "program_counter",
             "serving"),
            ("serving.fill", "%", "higher", "program_counter", "serving"),
            ("idle_share.served", "%", "lower", "device_trace", "device"))],
}


def cpu_rule(plane: str, line: str, event):
    """On the CPU, XLA's operations run on host threads and carry their
    ``hlo_op``; each is filed under the device it ran for."""
    stats = dict(event.stats)
    if "hlo_op" in stats:
        return "op", f"cpu:{stats.get('device_ordinal', 0)}"
    return None


@pytest.fixture
def tiny_root(tmp_path: Path) -> Path:
    bench = tmp_path / "bench"
    for sub in ("configs", "mixes"):
        (bench / sub).mkdir(parents=True)
    shutil.copytree(REPO / "bench" / "metrics", bench / "metrics")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for group, entries in SERVED_ENTRIES.items():
        spec[group] += entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for name, changes in TINY.items():
        data = json.loads((REPO / "bench" / name).read_text())
        data.update(changes)
        (bench / name).write_text(json.dumps(data))
    (bench / "peaks.json").write_text(json.dumps({"cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
        "hbm_bytes": 1e10, "source": "a stand-in for the tests"}}))
    return tmp_path


@pytest.fixture
def cpu_chip(monkeypatch):
    from bench import run, trace

    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(trace, "tpu_rule", cpu_rule)
