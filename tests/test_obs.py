"""Telemetry-plane contracts (``repro.obs``, DESIGN.md §11).

What this file pins, in rough order of importance:

* **Disabled is invisible.**  The default registry ships disabled;
  instruments mutate nothing while it is off, and engine results are
  bit-identical with telemetry on vs off — on one device and on a forced
  8-device mesh.
* **Registry semantics.**  Same name -> same instrument object;
  counters/gauges/histograms count what they are told; ``reset`` zeroes
  in place without invalidating held references.
* **Histogram resolution.**  ``percentile(q)`` is within one log2 bucket
  (a factor of 2) of the true order statistic and clamped to the
  observed [min, max].
* **Compile tracking.**  ``CompileTracker`` reads 0 over a warm function
  and > 0 over a fresh tracing.
* **Engine metrics.**  Cache hits/misses, real vs padded rows (pad
  waste), chunk fan-out, and per-backend job counters match values
  computable by hand from the plan; with telemetry on, an engine call
  still moves nothing from the device to the host.
* **Spans on the profiler's clock.**  Under a profiler an engine call
  opens ``engine.<method>`` around ``engine.prepare``, one
  ``engine.block`` per block and ``engine.assemble``, in order; the
  device programs' named scopes reach the compiled metadata.
* **Serving back-compat.**  ``stats()`` still returns the pre-telemetry
  ``ServerStats`` shape (field set pinned), and the server appears as a
  named source in ``obs.snapshot()``.
* **Trace export.**  ``export_chrome_trace`` writes valid JSON in the
  Chrome trace-event format, microsecond-converted, with each request's
  admit -> coalesce -> execute -> split chain internally consistent.
* **Benchmark row schema.**  Census/quality rows carry ``None`` timing
  (JSON ``null``), never a fake ``0.0``.
"""
import gc
import glob
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import Scene, VectorIndex, make_ray
from repro.core import Triangle
from repro.obs.device import DeviceTotal
from repro.obs.metrics import HIST_BINS, MetricsRegistry
from repro.obs.trace import TraceBuffer
from repro.serving.query_server import ServerStats


@pytest.fixture
def telemetry():
    """Enable the global plane for one test; restore the prior switch
    (the registry is process-global — tests must measure deltas, not
    absolutes)."""
    reg = obs.registry()
    was = reg.enabled
    obs.enable()
    yield reg
    reg.enabled = was


def _counters():
    return dict(obs.snapshot()["counters"])


def _scene_engine(**kw):
    rng = np.random.default_rng(7)
    ctr = rng.uniform(-1, 1, (80, 3)).astype(np.float32)
    tri = Triangle(
        jnp.asarray(ctr),
        jnp.asarray(ctr + rng.normal(scale=0.1, size=(80, 3)).astype(np.float32)),
        jnp.asarray(ctr + rng.normal(scale=0.1, size=(80, 3)).astype(np.float32)))
    return Scene.from_triangles(tri).engine(**kw)


def _rays(n, seed=1):
    rng = np.random.default_rng(seed)
    org = rng.uniform(-3, -2, (n, 3)).astype(np.float32)
    tgt = rng.uniform(-0.5, 0.5, (n, 3)).astype(np.float32)
    return make_ray(jnp.asarray(org), jnp.asarray(tgt - org))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_registry_disabled_is_noop():
    reg = MetricsRegistry()  # disabled is the default
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc()
    c.inc(5)
    g.set(3.5)
    h.observe(1.0)
    assert c.value == 0 and g.value == 0.0 and h.count == 0
    reg.enable()
    c.inc(2)
    g.set(3.5)
    h.observe(1.0)
    assert c.value == 2 and g.value == 3.5 and h.count == 1
    reg.disable()
    c.inc()
    assert c.value == 2  # frozen again


def test_same_name_same_instrument():
    reg = MetricsRegistry(enabled=True)
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("x") is reg.histogram("x")
    assert reg.gauge("x") is reg.gauge("x")


def test_reset_preserves_identity():
    reg = MetricsRegistry(enabled=True)
    c, h = reg.counter("c"), reg.histogram("h")
    c.inc(9)
    h.observe(2.0)
    reg.reset()
    assert c is reg.counter("c") and c.value == 0
    assert h.count == 0 and h.buckets == [0] * HIST_BINS
    c.inc()
    assert reg.counter("c").value == 1


def test_registry_snapshot_is_jsonable():
    reg = MetricsRegistry(enabled=True)
    reg.counter("a").inc(3)
    reg.gauge("b").set(1.5)
    reg.histogram("ms").observe(4.2)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"] == {"a": 3}
    assert snap["gauges"] == {"b": 1.5}
    assert snap["histograms"]["ms"]["count"] == 1
    # empty histograms export None, not NaN (NaN is not valid JSON)
    reg.histogram("empty")
    s = reg.snapshot()["histograms"]["empty"]
    assert s["count"] == 0 and s["p50"] is None and s["min"] is None


def test_histogram_percentile_within_bucket_factor():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("lat")
    rng = np.random.default_rng(0)
    vals = np.exp(rng.uniform(np.log(1e-3), np.log(1e3), 500))
    for v in vals:
        h.observe(float(v))
    for q in (0.1, 0.5, 0.9, 0.99):
        est, true = h.percentile(q), float(np.quantile(vals, q))
        assert true / 2 <= est <= true * 2, (q, est, true)
        assert h.min <= est <= h.max
    assert h.percentile(0.5) <= h.percentile(0.99)
    assert math.isclose(h.mean(), float(vals.mean()), rel_tol=1e-9)


# ---------------------------------------------------------------------------
# compile tracking
# ---------------------------------------------------------------------------


def test_compile_tracker_counts_fresh_and_warm():
    @jax.jit
    def f(x):
        return x * 2 + 1

    x = jnp.arange(7.0)
    with obs.CompileTracker() as t_cold:
        f(x)
    assert t_cold.available
    assert t_cold.compiles >= 1
    with obs.CompileTracker() as t_warm:
        f(x)
    assert t_warm.compiles == 0
    assert obs.total_compiles() >= t_cold.compiles


# ---------------------------------------------------------------------------
# engine metrics + bit parity
# ---------------------------------------------------------------------------


def test_engine_results_bit_identical_telemetry_on_off(telemetry):
    engine = _scene_engine(pad_multiple=8, shard=1)
    rays = _rays(12)
    obs.disable()
    off = engine.trace(rays)
    obs.enable()
    on = engine.trace(rays)
    obs.disable()
    off2 = engine.trace(rays)
    for field in ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs"):
        np.testing.assert_array_equal(
            np.asarray(getattr(off, field)), np.asarray(getattr(on, field)),
            err_msg=field)
        np.testing.assert_array_equal(
            np.asarray(getattr(on, field)), np.asarray(getattr(off2, field)),
            err_msg=field)
    assert int(off.rounds) == int(on.rounds) == int(off2.rounds)


def test_engine_metrics_pinned_against_plan(telemetry):
    rng = np.random.default_rng(3)
    db = rng.normal(size=(64, 16)).astype(np.float32)
    q = rng.normal(size=(12, 16)).astype(np.float32)
    engine = VectorIndex.from_database(jnp.asarray(db)).engine(
        pad_multiple=8, shard=1)
    before = _counters()
    engine.nearest(jnp.asarray(q), 5)
    engine.nearest(jnp.asarray(q), 5)  # second call: cache hit, same plan
    after = _counters()

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    # 12 rows pad to one 16-row block: real 12, padded 16, 1 chunk/call
    assert delta("engine.cache.misses") == 1
    assert delta("engine.cache.hits") == 1
    assert delta("engine.rows.real") == 24
    assert delta("engine.rows.padded") == 32
    assert delta("engine.chunks") == 2
    assert delta("engine.calls.nearest.mxu") == 2
    # the engine no longer times calls itself (its engine.nearest span
    # and the device trace do, without a block): no per-call histogram
    assert not [h for h in obs.snapshot()["histograms"]
                if h.startswith("engine.")]

    # snapshot's derived block agrees with its own counters
    snap = obs.snapshot()
    c = snap["counters"]
    real, padded = c["engine.rows.real"], c["engine.rows.padded"]
    assert snap["derived"]["pad_waste_fraction"] == pytest.approx(
        1.0 - real / padded)
    hits, misses = c["engine.cache.hits"], c["engine.cache.misses"]
    assert snap["derived"]["cache_hit_rate"] == pytest.approx(
        hits / (hits + misses))


def test_engine_job_counters_match_result(telemetry):
    engine = _scene_engine(pad_multiple=8, shard=1)
    rays = _rays(10, seed=4)
    before = _counters()
    res = engine.trace(rays, backend="wavefront")
    after = _counters()
    assert (after.get("engine.jobs.quadbox.wavefront", 0)
            - before.get("engine.jobs.quadbox.wavefront", 0)
            ) == int(np.asarray(res.quadbox_jobs).sum())
    assert (after.get("engine.jobs.triangle.wavefront", 0)
            - before.get("engine.jobs.triangle.wavefront", 0)
            ) == int(np.asarray(res.triangle_jobs).sum())


def test_telemetry_on_moves_nothing_to_the_host(telemetry):
    """``_obs_record`` neither blocks nor reads the device: the job totals
    stay on the device until ``snapshot()``, and still equal the records'
    sums there."""
    engine = _scene_engine(pad_multiple=8, shard=1, chunk_size=8)
    rays = _rays(20, seed=6)
    engine.trace(rays, backend="wavefront")  # compile outside the guard
    before = _counters()
    with jax.transfer_guard_device_to_host("disallow"):
        res = engine.trace(rays, backend="wavefront")
        res2 = engine.trace(rays, backend="wavefront")
    after = _counters()
    for job, field in (("quadbox", "quadbox_jobs"),
                       ("triangle", "triangle_jobs")):
        key = f"engine.jobs.{job}.wavefront"
        assert after[key] - before.get(key, 0) == (
            int(np.asarray(getattr(res, field)).sum())
            + int(np.asarray(getattr(res2, field)).sum()))


def test_device_total_carries_past_32_bits():
    total = DeviceTotal()
    big = jnp.full((4,), 2**30 - 1, jnp.int32)  # 2^32 - 4 per addition
    for _ in range(3):
        total.add(big)
    total.add(jnp.arange(5, dtype=jnp.int32))
    assert int(total) == 3 * 4 * (2**30 - 1) + 10


def test_engine_records_nothing_while_disabled():
    assert not obs.is_enabled()  # the process default
    engine = _scene_engine(pad_multiple=8, shard=1)
    before = _counters()
    engine.trace(_rays(9, seed=5))
    after = _counters()
    assert before == after


def test_engine_parity_and_metrics_8dev(multidev):
    multidev("""
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.api import Scene, make_ray
from repro.core import Triangle
rng = np.random.default_rng(11)
ctr = rng.uniform(-1, 1, (90, 3)).astype(np.float32)
tri = Triangle(jnp.asarray(ctr),
               jnp.asarray(ctr + rng.normal(scale=0.1, size=(90, 3)).astype(np.float32)),
               jnp.asarray(ctr + rng.normal(scale=0.1, size=(90, 3)).astype(np.float32)))
engine = Scene.from_triangles(tri).engine(pad_multiple=8, shard=8)
org = rng.uniform(-3, -2, (100, 3)).astype(np.float32)
tgt = rng.uniform(-0.5, 0.5, (100, 3)).astype(np.float32)
rays = make_ray(jnp.asarray(org), jnp.asarray(tgt - org))
off = engine.trace(rays)
obs.enable()
on = engine.trace(rays)
for f in ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs"):
    np.testing.assert_array_equal(np.asarray(getattr(off, f)),
                                  np.asarray(getattr(on, f)), err_msg=f)
assert int(off.rounds) == int(on.rounds)
snap = obs.snapshot()
assert snap["gauges"]["engine.shards"] == 8.0, snap["gauges"]
assert snap["counters"]["engine.rows.real"] == 100
assert snap["counters"]["engine.cache.hits"] == 1  # the telemetry-on call
print("8dev telemetry parity OK")
""", n_devices=8)


# ---------------------------------------------------------------------------
# spans on the profiler's clock, named scopes
# ---------------------------------------------------------------------------


def _profiled(tmp_path, fn):
    """``fn()`` under a CPU profiler trace; the engine's host spans from
    the trace file, in order of opening."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    pb = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                   recursive=True)[0]
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for p in ProfileData.from_file(pb).planes
                   if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith("engine.")),
                  key=lambda s: (s[1], -s[2]))


def test_engine_spans_nest_in_order_under_a_profiler(tmp_path):
    rng = np.random.default_rng(3)
    engine = VectorIndex.from_database(jnp.asarray(
        rng.normal(size=(64, 16)).astype(np.float32))).engine(
            pad_multiple=8, shard=1, chunk_size=8)
    q = jnp.asarray(rng.normal(size=(30, 16)).astype(np.float32))
    jax.block_until_ready(engine.nearest(q, 5))
    spans = _profiled(tmp_path, lambda: jax.block_until_ready(
        engine.nearest(q, 5)))
    assert [s[0] for s in spans] == (["engine.nearest", "engine.prepare"]
                                     + ["engine.block"] * 4
                                     + ["engine.assemble"])
    outer, inner = spans[0], spans[1:]
    assert all(outer[1] <= s[1] and s[2] <= outer[2] for s in inner)
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    # the same spans, with their args, in memory
    kept = obs.profiled_spans()[-7:]
    assert [s.name for s in kept] == (["engine.prepare"]
                                      + ["engine.block"] * 4
                                      + ["engine.assemble",
                                         "engine.nearest"])
    assert [s.args["rows"] for s in kept[1:5]] == [8, 8, 8, 6]


def test_spans_are_kept_only_while_a_profiler_records():
    n = len(obs.profiled_spans())
    with obs.span("engine.test", index=1):
        pass
    assert len(obs.profiled_spans()) == n


def test_op_scopes_name_the_wavefront_and_nearest_stages():
    engine = _scene_engine(pad_multiple=8, shard=1)
    jax.block_until_ready(engine.trace(_rays(9, seed=2),
                                       backend="wavefront"))
    rng = np.random.default_rng(4)
    index = VectorIndex.from_database(jnp.asarray(
        rng.normal(size=(64, 16)).astype(np.float32))).engine(
            pad_multiple=8, shard=1)
    jax.block_until_ready(index.nearest(
        jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32)), 3))
    from jax._src import test_util as jtu
    with jtu.count_jit_and_pmap_lowerings() as lowered, \
            jtu.count_jit_compilation_cache_miss() as compiled:
        paths = [p for ops in obs.programs.op_scopes().values()
                 for p in ops.values()]
    stages = {part for p in paths for part in p.split("/")}
    assert {"wavefront.pop", "wavefront.box", "wavefront.triangle",
            "wavefront.push", "nearest.distance",
            "nearest.topk"} <= stages
    # the jit caches hold every noted program: nothing is built again
    assert lowered() == 0 and compiled() == 0


def test_dropped_engine_drops_out_of_op_scopes():
    rng = np.random.default_rng(5)
    engine = VectorIndex.from_database(jnp.asarray(
        rng.normal(size=(48, 4)).astype(np.float32))).engine(
            pad_multiple=8, shard=1)
    engine.nearest(jnp.asarray(rng.normal(size=(8, 4)).astype(
        np.float32)), 2)
    fn = next(iter(engine._cache.values()))
    assert id(fn) in obs.programs._NOTED
    key = id(fn)
    del engine, fn
    gc.collect()
    assert key not in obs.programs._NOTED


# ---------------------------------------------------------------------------
# trace spans + Chrome export
# ---------------------------------------------------------------------------


def test_chrome_trace_export_format(tmp_path):
    buf = TraceBuffer(enabled=True)
    buf.record("admit", 1.0, 0.25, tid=42, cat="serving",
               args={"rows": 3})
    buf.record("execute", 1.25, 0.5, tid=42, cat="serving")
    path = tmp_path / "trace.json"
    assert buf.export_chrome_trace(str(path)) == 2
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    ev = doc["traceEvents"][0]
    assert ev == {"name": "admit", "cat": "serving", "ph": "X",
                  "ts": 1_000_000, "dur": 250_000, "pid": 0, "tid": 42,
                  "args": {"rows": 3}}
    e2 = doc["traceEvents"][1]
    assert e2["ts"] == ev["ts"] + ev["dur"]  # seconds -> integer us


def test_trace_buffer_follows_global_switch(telemetry):
    buf = TraceBuffer()  # enabled=None: follows the default registry
    obs.disable()
    buf.record("x", 0.0, 1.0)
    assert len(buf) == 0
    obs.enable()
    buf.record("x", 0.0, 1.0)
    assert len(buf) == 1


def test_serving_span_chains_consistent(tmp_path, telemetry):
    import asyncio

    from repro.core.session import PointCloudScene
    from repro.serving import QueryServer

    obs.default_buffer().clear()
    rng = np.random.default_rng(0)
    engine = PointCloudScene.from_points(
        jnp.asarray(rng.normal(size=(512, 3)).astype(np.float32))).engine(
            pad_multiple=8, shard=1)

    async def drive():
        async with QueryServer(engine, max_batch_rows=32,
                               max_wait=2e-3) as server:
            qs = [jnp.asarray(rng.normal(size=(3, 3)).astype(np.float32))
                  for _ in range(6)]
            await asyncio.gather(*[server.nearest(q, k=4) for q in qs])
            return server.stats()

    stats = asyncio.run(drive())
    assert stats["nearest"].requests == 6
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(str(path))
    doc = json.load(open(path))
    chains: dict = {}
    for ev in doc["traceEvents"]:
        if ev["cat"] == "serving":
            chains.setdefault(ev["tid"], {})[ev["name"]] = ev
    assert len(chains) == 6
    for tid, evs in chains.items():
        assert set(evs) == {"admit", "coalesce", "execute", "split"}, tid
        # each phase starts no earlier than the previous one ended
        # (1 us slack for integer-microsecond rounding)
        assert evs["admit"]["ts"] <= evs["coalesce"]["ts"] + 1
        assert (evs["coalesce"]["ts"] + evs["coalesce"]["dur"]
                <= evs["execute"]["ts"] + 1)
        assert (evs["execute"]["ts"] + evs["execute"]["dur"]
                <= evs["split"]["ts"] + 1)
        assert all(e["dur"] >= 0 for e in evs.values())
    obs.default_buffer().clear()


# ---------------------------------------------------------------------------
# serving stats back-compat + snapshot sources
# ---------------------------------------------------------------------------


def test_server_stats_shape_pinned():
    """The pre-telemetry ``stats()`` surface: exact field set, in order.
    Extending is fine — renames/removals break bench_serving and every
    stats() consumer, so they must show up here first."""
    assert ServerStats._fields == (
        "requests", "rows", "batches", "queue_depth", "requests_per_batch",
        "mean_batch_rows", "mean_fill", "flush_full", "flush_timer",
        "flush_deadline", "flush_drain", "shed", "p50_ms", "p99_ms")


def test_server_counts_with_global_telemetry_off(tmp_path):
    """Serving accounting predates the telemetry plane: it must keep
    exact counts with the global registry disabled (its registry is
    private and always on), and surface as a snapshot source."""
    import asyncio

    from repro.core.session import PointCloudScene
    from repro.serving import QueryServer

    assert not obs.is_enabled()
    rng = np.random.default_rng(1)
    engine = PointCloudScene.from_points(
        jnp.asarray(rng.normal(size=(512, 3)).astype(np.float32))).engine(
            pad_multiple=8, shard=1)

    async def drive(server_box):
        async with QueryServer(engine, max_batch_rows=32,
                               max_wait=2e-3) as server:
            server_box.append(server)
            qs = [jnp.asarray(rng.normal(size=(2, 3)).astype(np.float32))
                  for _ in range(4)]
            await asyncio.gather(*[server.nearest(q, k=4) for q in qs])
            return server.stats()

    box: list = []
    stats = asyncio.run(drive(box))
    s = stats["nearest"]
    assert s.requests == 4 and s.rows == 8
    assert s.batches >= 1 and s.requests_per_batch >= 1.0
    assert s.p50_ms <= s.p99_ms

    # the server is a named source in the global snapshot, weakly held
    snap = obs.snapshot()
    name = box[0]._source_name
    assert name in snap["sources"]
    section = snap["sources"][name]
    assert section["nearest"]["requests"] == 4
    queued = section["nearest"]["queue_ms"]  # enqueue -> execute, each
    assert queued["count"] == 4 and queued["min"] >= 0.0
    assert "admission" in section
    json.dumps(snap)  # the whole snapshot must be strictly JSON-able

    box.clear()
    del stats, s, section, snap
    gc.collect()
    assert name not in obs.snapshot()["sources"]


# ---------------------------------------------------------------------------
# benchmark row schema
# ---------------------------------------------------------------------------


def test_census_bench_rows_have_null_timing():
    """Census-style rows report derived metrics only: us_per_call must be
    None (JSON null), never a fake 0.0 that reads as 'measured and
    instantaneous' (benchmarks/run.py documents the row schema)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import run as bench_run
    from benchmarks.bench_datapath import bench_fu_census

    rows: list = []
    bench_fu_census(rows)
    assert rows, "census produced no rows"
    for name, us, derived in rows:
        assert name.startswith("fu_census_")
        assert us is None, f"{name}: census rows must not carry a timing"
        assert "ops_vs_tableVIII" in derived
    # and the runner's JSON writer keeps None as null end to end
    payload = json.loads(json.dumps(
        [dict(name=n, us_per_call=None if u is None else round(u, 3),
              derived=bench_run.parse_derived(d)) for n, u, d in rows]))
    assert all(r["us_per_call"] is None for r in payload)
