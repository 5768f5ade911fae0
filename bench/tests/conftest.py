"""A benchmark checkout at a size that a CPU test run holds.

``tiny_root`` copies the harness's data (BENCHMARK.json, the files that
its configurations and mixes name, the metric readers) into a temporary
directory, adds the stand-in entries of ``tiny/entries.json`` that
BENCHMARK.json lacks, and cuts every configuration and mix to a few
hundred rows; the code under test is the harness itself.  The
``cpu_chip`` fixture steers the harness's look for a chip to the CPU and
its trace reduction to the CPU's timeline.

The cuts are data: ``tiny/configs/<config>.json`` and
``tiny/mixes/<traffic>.json`` hold the keys that each changes in the file
of that name.  A new cell brings a cut for each configuration or mix it
adds, as new files; nothing here names a cell."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

#: the size cuts and the stand-in entries, relative to a checkout's root
TINY = Path("bench") / "tests" / "tiny"

#: entries that BENCHMARK.json does not hold yet, rehearsed as a later PR
#: would add them (PERF.md says why they wait)
STAND_INS = json.loads((REPO / TINY / "entries.json").read_text())
SERVED = STAND_INS["workloads"][0]["name"]


def with_stand_ins(spec: dict) -> dict:
    """``spec`` with each stand-in entry whose name it does not hold."""
    out = dict(spec)
    for group, entries in STAND_INS.items():
        have = {e["name"] for e in spec[group]}
        out[group] = spec[group] + [e for e in entries
                                    if e["name"] not in have]
    return out


def cut_files(spec: dict) -> dict:
    """Every configuration and mix file that ``spec`` names, mapped to its
    cut; both relative to the checkout's root."""
    files = {Path(c["file"]): TINY / "configs" / f"{c['name']}.json"
             for c in spec["configs"]}
    for w in spec["workloads"]:
        files[Path("bench", "mixes", f"{w['traffic']}.json")] = (
            TINY / "mixes" / f"{w['traffic']}.json")
    return files


def unknown_keys(cut: dict, real: dict, prefix: str = "") -> list:
    """The keys of ``cut`` that ``real`` lacks, in nested groups too."""
    unknown = []
    for key, value in cut.items():
        if key not in real:
            unknown.append(prefix + key)
        elif isinstance(value, dict) and isinstance(real[key], dict):
            unknown += unknown_keys(value, real[key], f"{prefix}{key}.")
    return unknown


def apply_cuts(root: Path) -> None:
    """Cut, in place, every file that ``root``'s BENCHMARK.json names by
    its cut under ``root / TINY``: each key of the cut replaces the file's
    own, a nested group whole.  A file with no cut, or a cut that names a
    key the file lacks, is an error: nothing reaches the CPU at full size.
    A second application changes nothing."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for real, cut in cut_files(spec).items():
        if not (root / cut).exists():
            raise FileNotFoundError(f"{real} has no size cut: {cut} is "
                                    f"missing")
        changes = json.loads((root / cut).read_text())
        data = json.loads((root / real).read_text())
        unknown = unknown_keys(changes, data)
        if unknown:
            raise KeyError(f"{cut} cuts {unknown}, which {real} lacks")
        data.update(changes)
        (root / real).write_text(json.dumps(data))


def cpu_rule(plane: str, line: str, event):
    """On the CPU, XLA's operations run on host threads and carry their
    ``hlo_op``; each is filed under the device it ran for."""
    stats = dict(event.stats)
    if "hlo_op" in stats:
        return "op", f"cpu:{stats.get('device_ordinal', 0)}"
    return None


@pytest.fixture
def tiny_root(tmp_path: Path) -> Path:
    spec = with_stand_ins(json.loads((REPO / "BENCHMARK.json").read_text()))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(REPO / TINY, tmp_path / TINY)
    for real in cut_files(spec):
        (tmp_path / real).parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(REPO / real, tmp_path / real)
    apply_cuts(tmp_path)
    (tmp_path / "bench" / "peaks.json").write_text(json.dumps({"cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
        "hbm_bytes": 1e10, "source": "a stand-in for the tests"}}))
    return tmp_path


@pytest.fixture
def cpu_chip(monkeypatch):
    from bench import run, trace

    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(trace, "tpu_rule", cpu_rule)
