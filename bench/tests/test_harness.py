"""The harness: every piece found by name, the contract's shape, a run of
each mix rehearsed on the CPU through the same code, the refusals, the
control and the faults that the comparison has to catch."""
from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.conftest import (REPO, SERVED, TINY, apply_cuts,
                                  cut_files, unknown_keys, with_stand_ins)
from repro.api import QueryEngine

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
#: BENCHMARK.json with the stand-in entries that it lacks, as the tiny
#: checkout holds it, and every cell of that
TINY_SPEC = with_stand_ins(SPEC)
ALL = [w["name"] for w in TINY_SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SEED = 2**33 + 7


def _mix(cell: str) -> dict:
    traffic = next(w["traffic"] for w in TINY_SPEC["workloads"]
                   if w["name"] == cell)
    return json.loads((REPO / "bench" / "mixes"
                       / f"{traffic}.json").read_text())


#: the cells whose one caller waits for each call
CLOSED = [cell for cell in ALL if _mix(cell)["loop"] == "closed"]


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


# ---------------------------------------------------------------------------
# the size cuts of the CPU rehearsal
# ---------------------------------------------------------------------------

CUTS = cut_files(TINY_SPEC)


@pytest.mark.parametrize("real", sorted(map(str, CUTS)))
def test_every_named_file_has_a_cut_of_keys_it_holds(real):
    cut = REPO / CUTS[Path(real)]
    assert cut.exists(), f"{real} has no size cut at {cut}"
    changes = json.loads(cut.read_text())
    assert changes
    assert not unknown_keys(changes, json.loads((REPO / real).read_text()))


@pytest.mark.parametrize("real", sorted(map(str, CUTS)))
def test_a_file_with_no_cut_is_refused(tiny_root, real):
    cut = CUTS[Path(real)]
    (tiny_root / cut).unlink()
    with pytest.raises(FileNotFoundError, match=re.escape(str(cut))):
        apply_cuts(tiny_root)


@pytest.mark.parametrize("real", sorted(map(str, CUTS)))
def test_a_key_renamed_in_its_file_does_not_pass_at_full_size(tiny_root,
                                                              real):
    """The first key that the cut changes, renamed in the real file: the
    full-size value under the new name would reach the CPU uncut."""
    key = next(iter(json.loads((REPO / CUTS[Path(real)]).read_text())))
    data = json.loads((REPO / real).read_text())
    data[key + "_renamed"] = data.pop(key)
    (tiny_root / real).write_text(json.dumps(data))
    with pytest.raises(KeyError, match=re.escape(key)):
        apply_cuts(tiny_root)


def test_cuts_once_applied_change_nothing_more(tiny_root):
    before = {f: (tiny_root / f).read_text() for f in CUTS}
    apply_cuts(tiny_root)
    assert {f: (tiny_root / f).read_text() for f in CUTS} == before


def test_stand_ins_already_in_benchmark_json_are_not_added_again():
    names = [w["name"] for w in TINY_SPEC["workloads"]]
    assert SERVED in names and len(names) == len(set(names))
    assert with_stand_ins(TINY_SPEC) == TINY_SPEC
    assert ALL == names and set(CELLS) <= set(ALL)


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


def test_a_new_configuration_needs_only_entries_and_files(tiny_root,
                                                          cpu_chip):
    """A later configuration, as a PR would add it: a full-size
    configuration file and a full-size mix, the cut of each under
    ``bench/tests/tiny/``, and entries in BENCHMARK.json.  The code that
    cuts every cell cuts these; no file that is there is changed."""
    config = json.loads((REPO / "bench" / "configs"
                         / "sift-1m.json").read_text())
    config["name"] = "cloud-x"
    (tiny_root / "bench" / "configs" / "cloud-x.json").write_text(
        json.dumps(config))
    mix = json.loads((REPO / "bench" / "mixes" / "batch.json").read_text())
    mix.update(rows_per_call=5000, sets=2)
    (tiny_root / "bench" / "mixes" / "halves.json").write_text(
        json.dumps(mix))
    tiny = tiny_root / TINY
    shutil.copyfile(tiny / "configs" / "sift-1m.json",
                    tiny / "configs" / "cloud-x.json")
    (tiny / "mixes" / "halves.json").write_text(json.dumps(
        {"rows_per_call": 200, "sets": 2, "check_rows": 400}))
    cell = "cloud-x.halves"
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "cloud-x", "source": "x",
                            "file": "bench/configs/cloud-x.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": cell, "config": "cloud-x",
                              "traffic": "halves", "chips": 1, "why": "x"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("queries_per_s", "nearest_roofline"):
            m["workloads"].append(cell)
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    apply_cuts(tiny_root)
    loaded = run.Cell(tiny_root, cell)
    assert loaded.config["points"] < config["points"]
    assert loaded.mix["rows_per_call"] == 200
    out = run.run(tiny_root, cell, SEED, 0.3, False)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"queries_per_s", "setup_s"}
    out = run.run(tiny_root, cell, SEED, 0.3, True)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"nearest_roofline"}
    out = run.run(tiny_root, cell, SEED, 1e-9, False, control=True)
    assert [n for n, v in out["control"].items()
            if not v <= loaded.config["limits"][n]], out["control"]


# ---------------------------------------------------------------------------
# the comparison: its control fails, and so does each fault
# ---------------------------------------------------------------------------


def _failed(checks: dict) -> list:
    return [n for n, c in checks.items()
            if c["value"] is None or not c["value"] <= c["limit"]]


@pytest.mark.parametrize("cell", CLOSED)
def test_control_in_lower_precision_fails_the_comparison(tiny_root,
                                                         cpu_chip, cell):
    out = run.run(tiny_root, cell, SEED, 0.3, False, control=True)
    assert out["correct"] is True
    limits = run.Cell(tiny_root, cell).config["limits"]
    failed = [n for n, v in out["control"].items() if not v <= limits[n]]
    assert failed, out["control"]


def _alter_one(res):
    """One answer altered where it is produced: the first hit ray names
    the next triangle, or the first query's nearest id the next id.  A
    record with neither is an error, not a fault that passes unplanted."""
    if hasattr(res, "tri_index"):
        i = jnp.argmax(res.hit)
        return res._replace(tri_index=res.tri_index.at[i].add(1))
    if hasattr(res, "indices"):
        return res._replace(indices=res.indices.at[0, 0].add(1))
    raise TypeError(f"no answer to alter in a {type(res).__name__}: give "
                    f"_alter_one the field that this kind answers with")


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
    seconds = 1e-9 if cell in CLOSED else 0.5
    out = run.run(tiny_root, cell, SEED, seconds, False)
    assert out["correct"] is False
    assert _failed(out["checks"])


def test_alter_one_refuses_a_record_with_no_answer_it_knows():
    from collections import namedtuple

    with pytest.raises(TypeError, match="no answer to alter"):
        _alter_one(namedtuple("Neighbours", "ids dists")(
            jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3))))


@pytest.mark.parametrize("cell", CLOSED)
def test_run_is_repeatable_from_its_seed(tiny_root, cpu_chip, cell):
    a = run.run(tiny_root, cell, 5, 1e-9, False)
    b = run.run(tiny_root, cell, 5, 1e-9, False)
    assert a["checks"] == b["checks"]


def test_seeds_past_32_bits_make_other_data():
    lo, hi = run.seed_keys(5), run.seed_keys(5 + 2**32)
    assert not bool(jnp.all(lo[0] == hi[0]))
    assert Path(run.__file__).parent.name == "bench"
