"""The harness: every piece found by name, the contract's shape, a run of
each mix rehearsed on the CPU through the same code, the refusals, the
control and the faults that the comparison has to catch."""
from __future__ import annotations

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.conftest import REPO, SERVED, SERVED_ENTRIES
from repro.api import QueryEngine

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
#: every cell, with the served one that the tiny checkout adds
ALL = CELLS + [SERVED]
TINY_SPEC = dict(SPEC, **{g: SPEC[g] + e for g, e in SERVED_ENTRIES.items()})
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SEED = 2**33 + 7


def _e2e(cell: str, spec: dict = TINY_SPEC) -> set:
    return {m["name"] for m in spec["end_to_end"]
            if run.applies(m, cell, spec)}


def _per_layer(cell: str) -> set:
    return {m["name"] for m in TINY_SPEC["per_layer"]
            if run.applies(m, cell, TINY_SPEC)}


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files its names lead to
# ---------------------------------------------------------------------------


def test_benchmark_json_has_the_contracts_keys():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in SPEC[group]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"])
               for m in SPEC["end_to_end"] + SPEC["per_layer"])
    assert {m["name"]: m["bound"] for m in SPEC["end_to_end"]}[
        "setup_s"] == 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = _e2e(cell, SPEC)
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = [m for m in SPEC["per_layer"] if run.applies(m, cell, SPEC)]
    assert layers
    assert all(m["moves"] in e2e for m in layers)


def test_every_name_finds_its_file():
    for c in SPEC["configs"]:
        config = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) <= set(config)
        kind = run.Cell(REPO, next(w["name"] for w in SPEC["workloads"]
                                   if w["config"] == c["name"])).kind
        for fn in ("build", "call", "rows_of", "host_rows", "check",
                   "control"):
            assert callable(getattr(kind, fn)), (c["name"], fn)
    for cell in CELLS:
        loaded = run.Cell(REPO, cell)
        assert set(loaded.config["limits"])
        for m in loaded.metrics("end_to_end") + loaded.metrics("per_layer"):
            assert callable(loaded.reader(m["name"]))


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------


def test_no_tpu_exits_nonzero_without_a_result(tiny_root, capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                  root=tiny_root)
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "no tpu" in out.err


def test_unknown_device_kind_is_an_error(tiny_root, cpu_chip, capsys):
    (tiny_root / "bench" / "peaks.json").write_text(json.dumps(
        {"TPU v5 lite": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0,
                         "hbm_bytes": 1.0, "source": "x"}}))
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                  root=tiny_root)
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "not in bench/peaks.json" in out.err


# ---------------------------------------------------------------------------
# every mix rehearsed on the CPU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", ALL)
def test_cell_runs_end_to_end_on_cpu(tiny_root, cpu_chip, cell, capsys):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   "0.5"], root=tiny_root)
    out = capsys.readouterr()
    assert rc == 0
    last = json.loads(out.out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert last["correct"] is True, last["checks"]
    assert last["attempted"] >= 1 and last["failed"] == 0
    assert set(last["metrics"]) == _e2e(cell)
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert "compiles_in_window: 0" in out.out
    err = out.err.strip().splitlines()
    assert all(line.startswith("check ") for line in err[-len(
        last["checks"]):])


@pytest.mark.parametrize("cell", ALL)
def test_traced_run_reports_layers_on_cpu(tiny_root, cpu_chip, cell):
    out = run.run(tiny_root, cell, SEED, 0.5, True)
    assert out["correct"] is True
    # the CPU's trace has no program runs and no Pallas kernel: the
    # readers of those find nothing and leave their metric out
    absent = {"dispatch.block_gap_ms", "nearest.distance_share"}
    assert set(out["metrics"]) == _per_layer(cell) - absent
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"]
    assert list(out)[-1] == "checks"


def test_a_new_cell_needs_only_entries_and_files(tiny_root, cpu_chip):
    """A later cell: a new mix file, a new metric reader and new entries
    in BENCHMARK.json; the harness is not touched."""
    bench = tiny_root / "bench"
    mix = json.loads((bench / "mixes" / "primary.json").read_text())
    mix.update(jitter_pixels=0.25, sets=1)
    (bench / "mixes" / "still.json").write_text(json.dumps(mix))
    (bench / "metrics" / "wavefront.rounds_per_call.py").write_text(
        "def read(ctx):\n"
        "    calls = ctx.window.calls\n"
        "    return sum(int(c.result.rounds) for c in calls) / len(calls)\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "frame-1m.still", "config": "frame-1m",
                              "traffic": "still", "chips": 1, "why": "x"})
    rays = next(m for m in spec["end_to_end"] if m["name"] == "rays_per_s")
    rays["workloads"].append("frame-1m.still")
    spec["per_layer"].append({"name": "wavefront.rounds_per_call",
                              "unit": "rounds", "better": "lower",
                              "source": "program_counter",
                              "layer": "wavefront engine",
                              "moves": "rays_per_s",
                              "workloads": ["frame-1m.still"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run.run(tiny_root, "frame-1m.still", SEED, 0.3, False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"rays_per_s", "setup_s"}
    out = run.run(tiny_root, "frame-1m.still", SEED, 0.3, True)
    assert set(out["metrics"]) == {"wavefront.rounds_per_call"}
    assert out["metrics"]["wavefront.rounds_per_call"]["value"] > 0


# ---------------------------------------------------------------------------
# the comparison: its control fails, and so does each fault
# ---------------------------------------------------------------------------


def _failed(checks: dict) -> list:
    return [n for n, c in checks.items()
            if c["value"] is None or not c["value"] <= c["limit"]]


@pytest.mark.parametrize("cell", ["frame-1m.primary", "sift-1m.batch"])
def test_control_in_lower_precision_fails_the_comparison(tiny_root,
                                                         cpu_chip, cell):
    out = run.run(tiny_root, cell, SEED, 0.3, False, control=True)
    assert out["correct"] is True
    limits = run.Cell(tiny_root, cell).config["limits"]
    failed = [n for n, v in out["control"].items() if not v <= limits[n]]
    assert failed, out["control"]


def _alter_one(res):
    """One answer altered where it is produced: the first hit ray names
    the next triangle, or the first query's nearest id the next id."""
    if hasattr(res, "tri_index"):
        i = jnp.argmax(res.hit)
        return res._replace(tri_index=res.tri_index.at[i].add(1))
    return res._replace(indices=res.indices.at[0, 0].add(1))


def _drop_half(res):
    """Half of the batch left out: every other row answered with the
    answer of the row before it."""
    def leaf(x):
        if x.ndim == 0:
            return x
        odd = jnp.arange(x.shape[0]) % 2 == 1
        prev = jnp.concatenate([x[:1], x[:-1]])
        return jnp.where(odd.reshape((-1,) + (1,) * (x.ndim - 1)), prev, x)

    return jax.tree_util.tree_map(leaf, res)


@pytest.mark.parametrize("fault", [_alter_one, _drop_half])
@pytest.mark.parametrize("cell", ALL)
def test_a_broken_timed_path_comes_out_incorrect(tiny_root, cpu_chip,
                                                 monkeypatch, cell, fault):
    method = run.Cell(tiny_root, cell).mix["call"]
    real = getattr(QueryEngine, method)

    def broken(self, *args, **kwargs):
        return fault(real(self, *args, **kwargs))

    monkeypatch.setattr(QueryEngine, method, broken)
    # one call of a closed loop, so that every row of it is compared
    seconds = 1e-9 if "served" not in cell else 0.5
    out = run.run(tiny_root, cell, SEED, seconds, False)
    assert out["correct"] is False
    assert _failed(out["checks"])


def test_run_is_repeatable_from_its_seed(tiny_root, cpu_chip):
    a = run.run(tiny_root, "sift-1m.batch", 5, 1e-9, False)
    b = run.run(tiny_root, "sift-1m.batch", 5, 1e-9, False)
    assert a["checks"] == b["checks"]


def test_seeds_past_32_bits_make_other_data():
    lo, hi = run.seed_keys(5), run.seed_keys(5 + 2**32)
    assert not bool(jnp.all(lo[0] == hi[0]))
    assert Path(run.__file__).parent.name == "bench"
