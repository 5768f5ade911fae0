"""Ray-query serving subsystem (DESIGN.md §10).

Three layers, three test styles:

* the **coalescer** is a synchronous state machine driven by explicit
  timestamps, so every flush trigger (batch-full, max-wait timer,
  deadline pressure) and the shed path are pinned with a fake clock —
  no sleeps, no event loop;
* **admission control** is plain accounting — verdicts and counters;
* the **server** is pinned to the hard contract: responses to coalesced
  concurrent requests are *bit-identical* — hits, indices, scores, and
  job counters, `rounds` included — to calling ``QueryEngine`` directly
  per request, for every servable method, on 1 device here and on a
  forced 8-device mesh in the multidev test.
"""
import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import PointCloudScene, QueryEngine, Scene, make_ray
from repro.serving import (
    FLUSH_DEADLINE,
    FLUSH_FULL,
    FLUSH_TIMER,
    AdmissionController,
    Coalescer,
    QueryServer,
    QueueFull,
    RequestShed,
)
from repro.serving.batching import make_request

TRACE_FIELDS = ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs")


# ---------------------------------------------------------------------------
# coalescer: fake-clock unit tests (no sleeps, no event loop)
# ---------------------------------------------------------------------------


def _req(method="trace", params=(("ray_type", "closest"),), rows=4,
         now=0.0, deadline=None):
    return make_request(method, params, {"x": jnp.zeros((rows, 3))}, rows,
                        now, deadline=deadline)


def test_coalescer_batch_full_flush():
    c = Coalescer(max_batch_rows=16, max_wait=10.0)
    assert c.add(_req(rows=6, now=0.0)) is None
    assert c.add(_req(rows=6, now=0.1)) is None
    batch = c.add(_req(rows=6, now=0.2))  # 18 >= 16: the bucket flushes
    assert batch is not None and batch.reason == FLUSH_FULL
    assert batch.rows == 18 and len(batch.requests) == 3
    assert batch.sizes == [6, 6, 6]
    assert c.depth == 0  # flushed bucket is gone


def test_coalescer_oversized_request_flushes_alone():
    c = Coalescer(max_batch_rows=16, max_wait=10.0)
    batch = c.add(_req(rows=100, now=0.0))
    assert batch is not None and batch.reason == FLUSH_FULL
    assert batch.rows == 100 and len(batch.requests) == 1


def test_coalescer_timer_flush():
    c = Coalescer(max_batch_rows=1024, max_wait=5.0)
    c.add(_req(rows=4, now=0.0))
    c.add(_req(rows=4, now=3.0))
    assert c.poll(4.999) == []  # oldest has waited 4.999 < 5
    assert c.next_due() == 5.0  # oldest (t=0) + max_wait
    [batch] = c.poll(5.0)
    assert batch.reason == FLUSH_TIMER and len(batch.requests) == 2
    assert c.poll(100.0) == [] and c.next_due() is None


def test_coalescer_deadline_pressure_flush():
    """A tight deadline overrides the (much longer) max-wait timer."""
    c = Coalescer(max_batch_rows=1024, max_wait=60.0, deadline_margin=1.0)
    c.add(_req(rows=4, now=0.0))
    c.add(_req(rows=4, now=0.0, deadline=5.0))  # earliest deadline t=5
    assert c.next_due() == 4.0  # deadline - margin, not oldest + max_wait
    assert c.poll(3.999) == []
    [batch] = c.poll(4.0)
    assert batch.reason == FLUSH_DEADLINE and len(batch.requests) == 2
    assert c.depth == 0


def test_coalescer_buckets_split_by_method_and_params():
    c = Coalescer(max_batch_rows=1024, max_wait=5.0)
    c.add(_req(params=(("ray_type", "closest"),), now=0.0))
    c.add(_req(params=(("ray_type", "shadow"),), now=0.0))
    c.add(_req(method="nearest", params=(("k", 4),), now=0.0))
    assert c.depth == 3 and len(c._buckets) == 3
    assert c.depth_for("trace") == 2 and c.depth_for("nearest") == 1
    batches = c.poll(5.0)
    assert len(batches) == 3  # one batch per bucket, never mixed
    keys = {(b.method, b.params) for b in batches}
    assert len(keys) == 3


def test_coalescer_evict_oldest_sheds_across_buckets():
    c = Coalescer(max_batch_rows=1024, max_wait=60.0)
    r1 = _req(rows=4, now=1.0)
    r2 = _req(method="nearest", params=(("k", 8),), rows=4, now=0.5)
    r3 = _req(rows=4, now=2.0)
    for r in (r1, r2, r3):
        c.add(r)
    victim = c.evict_oldest()
    assert victim is r2  # globally oldest, whatever the bucket
    assert c.depth == 2 and c.depth_for("nearest") == 0
    assert c.evict_oldest() is r1
    assert c.evict_oldest() is r3
    assert c.evict_oldest() is None  # nothing queued -> nothing sheddable


def test_coalescer_flush_all_drains():
    c = Coalescer(max_batch_rows=1024, max_wait=60.0)
    c.add(_req(now=0.0))
    c.add(_req(method="nearest", params=(("k", 2),), now=0.0))
    batches = c.flush_all()
    assert len(batches) == 2 and c.depth == 0
    assert all(b.reason == "drain" for b in batches)


def test_coalescer_validation():
    with pytest.raises(ValueError, match="max_batch_rows"):
        Coalescer(max_batch_rows=0)
    with pytest.raises(ValueError, match="max_wait"):
        Coalescer(max_wait=-1.0)
    with pytest.raises(ValueError, match="deadline_margin"):
        Coalescer(deadline_margin=-0.1)


# ---------------------------------------------------------------------------
# admission control: verdicts + accounting
# ---------------------------------------------------------------------------


def test_admission_block_policy():
    a = AdmissionController(2, policy="block")
    assert a.try_admit() == "admit" and a.try_admit() == "admit"
    assert a.try_admit() == "wait"  # full: submitter must wait
    assert a.depth == 2 and not a.has_capacity
    a.release()
    assert a.has_capacity
    a.admit_after_wait()
    s = a.stats()
    assert (s.depth, s.admitted, s.blocked) == (2, 3, 1)


def test_admission_reject_policy():
    a = AdmissionController(1, policy="reject")
    assert a.try_admit() == "admit"
    assert a.try_admit() == "reject"
    assert a.stats().rejected == 1
    a.release()
    assert a.try_admit() == "admit"


def test_admission_shed_policy():
    a = AdmissionController(1, policy="shed")
    assert a.try_admit() == "admit"
    assert a.try_admit() == "shed"
    a.admit_after_shed()  # victim's slot transfers: depth unchanged
    s = a.stats()
    assert (s.depth, s.admitted, s.shed) == (1, 2, 1)
    a.shed_failed()  # nothing sheddable -> counted as a rejection
    assert a.stats().rejected == 1


def test_admission_validation():
    with pytest.raises(ValueError, match="limit"):
        AdmissionController(0)
    with pytest.raises(ValueError, match="policy"):
        AdmissionController(4, policy="drop")
    a = AdmissionController(2)
    with pytest.raises(ValueError, match="release"):
        a.release(1)  # nothing admitted yet


# ---------------------------------------------------------------------------
# the server: coalesced == per-request, bit for bit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    """One engine over a triangle scene AND a point cloud, so a single
    server coalesces every servable method."""
    rng = np.random.default_rng(11)
    n_tri = 150
    ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
    d1 = rng.normal(scale=0.12, size=(n_tri, 3)).astype(np.float32)
    d2 = rng.normal(scale=0.12, size=(n_tri, 3)).astype(np.float32)
    scene = Scene.from_triangles(np.stack([ctr, ctr + d1, ctr + d2], 1))
    cloud = PointCloudScene.from_points(
        rng.normal(size=(400, 3)).astype(np.float32))
    return QueryEngine(scene=scene, cloud=cloud, pad_multiple=8, shard=1)


def _rays(n, seed):
    rng = np.random.default_rng(seed)
    org = rng.uniform(-3, -2, (n, 3)).astype(np.float32)
    tgt = rng.uniform(-0.5, 0.5, (n, 3)).astype(np.float32)
    return make_ray(jnp.asarray(org), jnp.asarray(tgt - org))


def _queries(n, seed):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=(n, 3)).astype(np.float32))


def _assert_trace_equal(got, ref, msg=""):
    for f in TRACE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f"{msg} field={f}")
    assert int(got.rounds) == int(ref.rounds), msg


def test_server_mixed_methods_bitparity(engine):
    """The acceptance bar: many small concurrent mixed-method requests,
    coalesced into shared batches, each response bit-identical to a
    direct engine call — job counters and per-request rounds included."""
    jobs = []  # (kind, payload, kwargs)
    for i in range(9):
        ray_type = ("closest", "any", "shadow")[i % 3]
        jobs.append(("trace", _rays(2 + i % 4, 50 + i),
                     dict(ray_type=ray_type)))
    for i in range(4):
        jobs.append(("nearest", _queries(1 + i % 3, 80 + i), dict(k=5)))
        # the tree route's records carry job counters and rounds too
        jobs.append(("nearest", _queries(2 + i % 3, 60 + i),
                     dict(k=5, backend="tree_wavefront")))
        jobs.append(("within", _queries(2 + i % 2, 90 + i),
                     dict(radius=1.0, k=6)))
        jobs.append(("count_within", _queries(3, 70 + i),
                     dict(radius=0.8)))

    async def serve():
        async with QueryServer(engine, max_batch_rows=64,
                               max_wait=0.02) as server:
            tasks = [asyncio.ensure_future(
                getattr(server, kind)(payload, **kw))
                for kind, payload, kw in jobs]
            results = await asyncio.gather(*tasks)
            return results, server.stats()

    results, stats = asyncio.run(serve())

    for (kind, payload, kw), got in zip(jobs, results):
        ref = getattr(engine, kind)(payload, **kw)
        if kind == "trace":
            _assert_trace_equal(got, ref, msg=f"{kind} {kw}")
        elif kind == "count_within":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=kind)
        else:
            for g, r in zip(got, ref):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                              err_msg=f"{kind} {kw}")
    # coalescing demonstrably happened: fewer engine calls than requests
    assert stats["nearest"].requests_per_batch > 1
    assert stats["count_within"].requests_per_batch > 1
    total_batches = sum(s.batches for s in stats.values())
    assert total_batches < len(jobs)
    # flush accounting is consistent
    for s in stats.values():
        assert (s.flush_full + s.flush_timer + s.flush_deadline
                + s.flush_drain) == s.batches
        assert s.queue_depth == 0


def test_server_full_flush_and_param_buckets(engine):
    """Same-params requests share a batch (full-flush fires); different
    static params never mix."""
    async def serve():
        async with QueryServer(engine, max_batch_rows=8,
                               max_wait=30.0) as server:
            # 4 + 4 rows of k=5 fill the 8-row bucket -> full flush, no
            # timer needed despite the 30 s max_wait
            t1 = asyncio.ensure_future(server.nearest(_queries(4, 1), k=5))
            t2 = asyncio.ensure_future(server.nearest(_queries(4, 2), k=5))
            r1, r2 = await asyncio.gather(t1, t2)
            # different k -> different bucket, flushed only by drain
            t3 = asyncio.ensure_future(server.nearest(_queries(4, 3), k=3))
            await asyncio.sleep(0)
            await server.drain()
            r3 = await t3
            return (r1, r2, r3), server.stats()

    (r1, r2, r3), stats = asyncio.run(serve())
    for res, seed, k in ((r1, 1, 5), (r2, 2, 5), (r3, 3, 3)):
        ref = engine.nearest(_queries(4, seed), k=k)
        np.testing.assert_array_equal(np.asarray(res.indices),
                                      np.asarray(ref.indices))
        np.testing.assert_array_equal(np.asarray(res.scores),
                                      np.asarray(ref.scores))
    s = stats["nearest"]
    assert s.flush_full >= 1  # the k=5 pair went out full
    assert s.flush_drain >= 1  # the k=3 singleton went out on drain
    assert s.requests == 3 and s.batches == 2


def test_server_deadline_triggers_early_flush(engine):
    """A request deadline flushes the bucket long before max_wait."""
    async def serve():
        async with QueryServer(engine, max_batch_rows=1024, max_wait=30.0,
                               deadline_margin=0.001) as server:
            res = await asyncio.wait_for(
                server.nearest(_queries(3, 7), k=4, timeout=0.01),
                timeout=10.0)  # must NOT take the 30 s timer path
            return res, server.stats()

    res, stats = asyncio.run(serve())
    ref = engine.nearest(_queries(3, 7), k=4)
    np.testing.assert_array_equal(np.asarray(res.indices),
                                  np.asarray(ref.indices))
    assert stats["nearest"].flush_deadline == 1


def test_server_reject_policy(engine):
    async def serve():
        async with QueryServer(engine, max_batch_rows=1024, max_wait=30.0,
                               queue_limit=2, policy="reject") as server:
            f1 = await server.enqueue("nearest", _queries(2, 1),
                                      (("backend", None), ("k", 3),
                                       ("metric", "euclidean")))
            f2 = await server.enqueue("nearest", _queries(2, 2),
                                      (("backend", None), ("k", 3),
                                       ("metric", "euclidean")))
            with pytest.raises(QueueFull):
                await server.nearest(_queries(2, 3), k=3)
            assert server.admission_stats().rejected == 1
            await server.drain()
            return await asyncio.gather(f1, f2)

    r1, r2 = asyncio.run(serve())
    ref = engine.nearest(_queries(2, 1), k=3)
    np.testing.assert_array_equal(np.asarray(r1.indices),
                                  np.asarray(ref.indices))


def test_server_shed_policy(engine):
    """At the limit, the oldest queued request is dropped (its future
    fails with RequestShed) and the newcomer takes its slot."""
    async def serve():
        async with QueryServer(engine, max_batch_rows=1024, max_wait=30.0,
                               queue_limit=2, policy="shed") as server:
            params = (("backend", None), ("k", 3), ("metric", "euclidean"))
            f1 = await server.enqueue("nearest", _queries(2, 1), params)
            f2 = await server.enqueue("nearest", _queries(2, 2), params)
            f3 = await server.enqueue("nearest", _queries(2, 3), params)
            with pytest.raises(RequestShed):
                await f1  # the oldest was the victim
            await server.drain()
            r2, r3 = await asyncio.gather(f2, f3)
            return r2, r3, server.stats(), server.admission_stats()

    r2, r3, stats, adm = asyncio.run(serve())
    assert adm.shed == 1 and stats["nearest"].shed == 1
    for res, seed in ((r2, 2), (r3, 3)):
        ref = engine.nearest(_queries(2, seed), k=3)
        np.testing.assert_array_equal(np.asarray(res.indices),
                                      np.asarray(ref.indices))


def test_server_empty_request_short_circuits(engine):
    async def serve():
        async with QueryServer(engine) as server:
            return await server.trace(_rays(0, 0))

    res = asyncio.run(serve())
    assert res.t.shape == (0,) and int(res.rounds) == 0


def test_server_rejects_bad_requests_eagerly(engine):
    """Malformed static params fail in the submitter, before they can
    poison a shared batch."""
    async def serve():
        async with QueryServer(engine) as server:
            with pytest.raises(ValueError, match="ray_type"):
                await server.trace(_rays(2, 0), ray_type="laser")
            with pytest.raises(ValueError, match="k must be"):
                await server.nearest(_queries(2, 0), k=0)
            with pytest.raises(ValueError, match="radius"):
                await server.within(_queries(2, 0), radius=float("nan"),
                                    k=3)
            with pytest.raises(ValueError, match="method"):
                await server.enqueue("explode", _queries(2, 0), ())

    asyncio.run(serve())


def test_server_not_running_raises(engine):
    server = QueryServer(engine)

    async def go():
        with pytest.raises(RuntimeError, match="not running"):
            await server.trace(_rays(2, 0))

    asyncio.run(go())


def test_server_quantized_batches_reuse_compiled_fns(engine):
    """The power-of-two row ladder: batches whose row counts differ only
    within a ladder step hit the same compiled program (the jit cache
    gains at most one entry for the second batch)."""
    eng = QueryEngine(scene=engine.scene, cloud=engine.cloud,
                      pad_multiple=8, shard=1)

    async def serve():
        async with QueryServer(eng, max_batch_rows=64,
                               max_wait=0.005) as server:
            await server.nearest(_queries(9, 1), k=4)   # pads to 16-ladder
            before = eng.cache_info().entries
            await server.nearest(_queries(12, 2), k=4)  # same 16-ladder
            await server.nearest(_queries(15, 3), k=4)
            return before, eng.cache_info()

    before, after = asyncio.run(serve())
    assert after.entries == before  # no new programs for 12 or 15 rows
    assert after.hits >= 2


# ---------------------------------------------------------------------------
# the acceptance criterion on a forced 8-device mesh
# ---------------------------------------------------------------------------


def test_server_bitparity_8dev(multidev):
    multidev("""
import asyncio
import numpy as np, jax, jax.numpy as jnp
assert jax.local_device_count() == 8
from repro.api import PointCloudScene, QueryEngine, Scene, make_ray
from repro.serving import QueryServer

rng = np.random.default_rng(5)
n_tri = 200
ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
d1 = rng.normal(scale=0.12, size=(n_tri, 3)).astype(np.float32)
d2 = rng.normal(scale=0.12, size=(n_tri, 3)).astype(np.float32)
scene = Scene.from_triangles(np.stack([ctr, ctr + d1, ctr + d2], 1))
cloud = PointCloudScene.from_points(
    rng.normal(size=(300, 3)).astype(np.float32))
engine = QueryEngine(scene=scene, cloud=cloud, pad_multiple=8,
                     shard="auto")  # sharded over the 8-dev mesh

def rays_of(n, seed):
    r = np.random.default_rng(seed)
    org = r.uniform(-3, -2, (n, 3)).astype(np.float32)
    tgt = r.uniform(-0.5, 0.5, (n, 3)).astype(np.float32)
    return make_ray(jnp.asarray(org), jnp.asarray(tgt - org))

def queries_of(n, seed):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=(n, 3)).astype(np.float32))

jobs = []
for i in range(6):
    jobs.append(("trace", rays_of(3 + i % 4, i),
                 dict(ray_type=("closest", "any", "shadow")[i % 3])))
for i in range(4):
    jobs.append(("nearest", queries_of(2 + i % 3, 40 + i), dict(k=4)))
    jobs.append(("count_within", queries_of(2, 60 + i), dict(radius=0.7)))

async def serve():
    async with QueryServer(engine, max_batch_rows=64,
                           max_wait=0.05) as server:
        tasks = [asyncio.ensure_future(getattr(server, kind)(p, **kw))
                 for kind, p, kw in jobs]
        res = await asyncio.gather(*tasks)
        return res, server.stats()

results, stats = asyncio.run(serve())
for (kind, payload, kw), got in zip(jobs, results):
    ref = getattr(engine, kind)(payload, **kw)
    if kind == "trace":
        for f in ("t", "tri_index", "hit", "quadbox_jobs",
                  "triangle_jobs"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(ref, f)),
                err_msg=f"{kind} {kw} {f}")
        assert int(got.rounds) == int(ref.rounds), (kind, kw)
    elif kind == "count_within":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    else:
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
assert sum(s.requests_per_batch > 1 for s in stats.values()) >= 1
print("serving 8-dev bit-parity OK")
""", n_devices=8)
