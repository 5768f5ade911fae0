"""The readers of the program's spans and named scopes: a trace recorded on
the CPU around an engine call, hand-made traces with scopes, the readers
end to end on the CPU, and the readers that were there before, pinned on
the old fixture."""
from __future__ import annotations

import glob
import json
import os
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import profile, run, scopes, trace
from bench.tests.conftest import REPO, SERVED, cpu_rule

FIXTURE = Path(__file__).parent / "fixtures" / "cpu.xplane.pb"
SEED = 2**33 + 11
E = trace.Event


def _record(tmp_path, n_blocks: int = 3) -> str:
    """A CPU trace of one chunked ``nearest`` call inside the benchmark's
    ``bench.window`` and ``bench.call`` spans."""
    from repro.api import VectorIndex

    rng = np.random.default_rng(0)
    db = jnp.asarray(rng.normal(size=(256, 8)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(8 * n_blocks - 3, 8)).astype(
        np.float32))
    engine = VectorIndex.from_database(db).engine(pad_multiple=8,
                                                  chunk_size=8, shard=1)
    jax.block_until_ready(engine.nearest(q, 4))  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.call"):
            jax.block_until_ready(engine.nearest(q, 4))
    jax.profiler.stop_trace()
    return glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]


def test_host_spans_keep_the_engines_spans_in_order(tmp_path):
    pb = _record(tmp_path)
    spans = scopes.host_spans(pb)
    names = [s.name for s in spans]
    assert names == ["bench.window", "bench.call", "engine.nearest",
                     "engine.prepare", "engine.block", "engine.block",
                     "engine.block", "engine.assemble"]
    call = spans[names.index("engine.nearest")]
    inner = spans[names.index("engine.prepare"):]
    assert all(call.start <= s.start and s.end <= call.end for s in inner)
    assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))
    # the benchmark's own reduction keeps only its spans, as before
    assert [s.name for s in trace.extract(pb, cpu_rule).spans] == [
        "bench.window", "bench.call"]


def test_gaps_take_the_innermost_host_span():
    spans = [E("bench.window", 0, 100), E("bench.call", 0, 100),
             E("engine.nearest", 0, 30), E("engine.prepare", 0, 10),
             E("engine.block", 10, 20), E("engine.assemble", 20, 30)]
    ops = {"d": [E("a", 12, 40), E("b", 50, 90)]}
    s = trace.Summary(trace.Trace(spans, ops, {}))
    gaps = sorted(scopes.labelled_gaps(s, spans))
    assert gaps == [(pytest.approx(10e-9), "bench.call"),
                    (pytest.approx(10e-9), "bench.call"),
                    (pytest.approx(12e-9), "engine.prepare")]


# ---------------------------------------------------------------------------
# the scope readers on hand-made traces
# ---------------------------------------------------------------------------

#: a program ``jit_run`` whose instructions carry scopes, and an eager pad
SCOPES = {"jit_run": {
    "fusion.1": "jit(run)/while/body/wavefront.box/gather",
    "fusion.2": "jit(run)/while/body/wavefront.triangle/div",
    "fusion.3": "jit(run)/nearest.topk/top_k",
    "custom-call.4": "jit(run)/nearest.distance/pallas_call",
    "while.5": "jit(run)/while"}}


def _ctx(ops, modules, calls=((0, 1000),)):
    spans = [E("bench.window", 0, 1000)] + [E("bench.call", a, b)
                                            for a, b in calls]
    summary = trace.Summary(trace.Trace(spans, {"/device:TPU:0": ops},
                                        {"/device:TPU:0": modules}))
    return SimpleNamespace(
        trace=summary,
        call_spans=lambda: [(None, s) for s in summary.spans("bench.call")])


def _reader(name):
    return run.Cell(REPO, "frame-1m.primary").reader(name)


def _with_scopes(monkeypatch, table=SCOPES):
    monkeypatch.setattr(scopes, "op_scopes", lambda: table)


@pytest.mark.parametrize("metric, share", [
    ("wavefront.box_share", 30.0), ("wavefront.triangle_share", 50.0)])
def test_wavefront_shares_by_scope(monkeypatch, metric, share):
    """A loop of 800 ns: 240 ns box, 400 ns triangle, 160 ns in the loop
    itself (no stage); an eager pad of 100 ns outside the program is
    busy time in no scope."""
    _with_scopes(monkeypatch)
    ops = [E("%while.5 = (s32[]) while(...)", 100, 900),
           E("%fusion.1 = f32[36864,4,3] fusion(...)", 100, 340),
           E("%fusion.2 = f32[36864,4] fusion(...)", 400, 800),
           E("%pad.1 = f32[36864] pad(...)", 900, 1000)]
    modules = [E("jit_run(7)", 100, 900), E("jit_pad(3)", 900, 1000)]
    assert _reader(metric)(_ctx(ops, modules)) == pytest.approx(
        share * 800 / 900)


def test_topk_share_by_scope(monkeypatch):
    """The kernel 400 ns, top-k 500 ns, the index pad (no scope here)
    100 ns: the scopes' shares add up to no more than the whole."""
    _with_scopes(monkeypatch)
    ops = [E("%custom-call.4 = f32[256,1000064] custom-call(...)", 0, 400),
           E("%fusion.3 = (f32[256,10], s32[256,10]) fusion(...)", 400, 900),
           E("%pad.2 = f32[1000064,128] pad(...)", 900, 1000)]
    ctx = _ctx(ops, [E("jit_run(9)", 0, 1000)])
    assert _reader("nearest.topk_share")(ctx) == pytest.approx(50.0)
    by_path = scopes.scoped_ops(ctx.trace, 0, 1000, SCOPES)
    assert by_path[SCOPES["jit_run"]["custom-call.4"]] == pytest.approx(
        400e-9)
    assert by_path[None] == pytest.approx(100e-9)  # the pad: no scope


def test_an_operation_is_looked_up_in_its_own_program(monkeypatch):
    """``fusion.1`` of another program is not the box stage."""
    _with_scopes(monkeypatch)
    ops = [E("%fusion.1 = f32[4] fusion(...)", 0, 100),
           E("%fusion.1 = f32[36864,4,3] fusion(...)", 100, 300)]
    ctx = _ctx(ops, [E("jit_other(2)", 0, 100), E("jit_run(7)", 100, 300)])
    assert _reader("wavefront.box_share")(ctx) == pytest.approx(
        100 * 200 / 300)


def test_scope_readers_leave_out_what_they_cannot_attribute(monkeypatch):
    ops = [E("fusion.9", 0, 100)]
    _with_scopes(monkeypatch, None)  # a program that notes nothing
    assert _reader("nearest.topk_share")(_ctx(ops, [])) is None
    _with_scopes(monkeypatch)  # no operation of the calls has a scope
    assert _reader("nearest.topk_share")(_ctx(ops, [])) is None
    assert _reader("nearest.topk_share")(SimpleNamespace(trace=None)) is None


# ---------------------------------------------------------------------------
# end to end on the CPU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell, metric", [
    ("frame-1m.primary", "engine.host_ms.rays"),
    ("sift-1m.batch", "engine.host_ms.queries")])
def test_engine_host_ms_end_to_end(tiny_root, cpu_chip, cell, metric):
    out = run.run(tiny_root, cell, SEED, 0.3, True)
    assert out["correct"] is True
    host = out["metrics"][metric]["value"]
    window = 1e3 * out["device"]["window_s"]
    calls = out["attempted"]
    # the engine holds the caller for part of each call, never more
    assert 0 < host < window / calls * 1.05


def test_serving_host_ms_on_the_served_mix(tiny_root, cpu_chip):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "serving.host_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serving", "moves": "p95_ms",
        "workloads": [SERVED]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run.run(tiny_root, SERVED, SEED, 0.5, True)
    assert out["correct"] is True
    assert out["metrics"]["serving.host_ms"]["value"] > 0


def test_profile_keeps_the_trace_and_checks_the_clock(tiny_root, cpu_chip,
                                                      tmp_path, capsys):
    kept = tmp_path / "profile"
    rc = profile.main(["--workload", "sift-1m.batch", "--seed", str(SEED),
                       "--seconds", "0.3", "--out", str(kept)],
                      root=tiny_root)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert os.path.exists(out["trace"])
    assert (kept / "op_scopes.json").exists()
    assert out["metrics"]["nearest.topk_share"] > 0
    assert out["device_scopes"]["nearest.topk"] > 0
    assert out["clock"]["calls"] >= 1
    assert out["clock"]["gaps_labelled_none"] == 0
    assert {n for n, _ in out["idle_gaps"]} <= {
        "bench.window", "bench.call", "engine.nearest", "engine.prepare",
        "engine.block", "engine.assemble"}


# ---------------------------------------------------------------------------
# the readers that were there before, on the old fixture
# ---------------------------------------------------------------------------

#: the fixture's two calls: device-busy ns inside each (test_trace.py)
BUSY = ((36244 + 4216), (30766 + 3996))


def _fixture_ctx():
    summary = trace.Summary(trace.extract(str(FIXTURE), cpu_rule))
    calls = [SimpleNamespace(rows=256, result=SimpleNamespace(rounds=2))
             for _ in range(2)]
    return SimpleNamespace(
        trace=summary, config={"points": 4096, "dim": 128, "k": 10},
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        call_spans=lambda: list(zip(calls, summary.spans("bench.call"))))


@pytest.mark.parametrize("metric, expected", [
    ("wavefront.round_ms", 1e3 * sum(BUSY) * 1e-9 / 4),
    ("idle_share.rays", 100 * (1 - sum(BUSY) / 4445801)),
    ("idle_share.queries", 100 * (1 - sum(BUSY) / 4445801)),
    ("nearest_roofline", 100 * 2 * max(
        2 * 256 * 4096 * 128 / 1e12,
        (4 * 4096 * 128 + 4 * 256 * 128 + 8 * 256 * 10) / 1e11)
     / (sum(BUSY) * 1e-9)),
    # the CPU's trace has no program runs and no Pallas kernel
    ("dispatch.block_gap_ms", None),
    ("nearest.distance_share", None)])
def test_existing_readers_read_the_old_fixture_as_before(metric, expected):
    value = _reader(metric)(_fixture_ctx())
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)
