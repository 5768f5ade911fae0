"""Session query API: backend parity, padding round-trips, jit-cache hits.

The contract (DESIGN.md §5): ``QueryEngine`` is a *session* over the same
engines the free functions expose — every backend must return the shared
result record with values bit-identical to its legacy entry point
(``trace_rays`` / ``trace_wavefront`` / ``knn`` / ``radius_search``), the
pad → query → unpad round trip must be an identity, and repeated
same-shape queries must re-enter the compiled cache without retracing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import CompileTracker

from repro.api import (QueryEngine, Scene, VectorIndex, distance_backends,
                       make_ray, trace_backends)
from repro.core import (Triangle, cosine_similarity, knn, radius_count,
                        radius_search, trace_rays, trace_wavefront)

TRACE_FIELDS = ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs")


def _soup(rng, n_tri, scale=0.15):
    ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
    d1 = rng.normal(scale=scale, size=(n_tri, 3)).astype(np.float32)
    d2 = rng.normal(scale=scale, size=(n_tri, 3)).astype(np.float32)
    return Triangle(a=jnp.asarray(ctr), b=jnp.asarray(ctr + d1),
                    c=jnp.asarray(ctr + d2))


def _rays(rng, n):
    org = rng.uniform(-3, -2, (n, 3)).astype(np.float32)
    tgt = rng.uniform(-0.5, 0.5, (n, 3)).astype(np.float32)
    return make_ray(jnp.asarray(org), jnp.asarray(tgt - org))


def _scene_and_rays(seed, n_tri, n_rays):
    rng = np.random.default_rng(seed)
    scene = Scene.from_triangles(_soup(rng, n_tri))
    return scene, _rays(rng, n_rays)


def _vectors(seed=0, n_q=17, n_db=211, dim=24):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(n_q, dim)).astype(np.float32))
    db = jnp.asarray(rng.normal(size=(n_db, dim)).astype(np.float32))
    return q, db


SCENES = [(7, 230, 64), (17, 3, 32)]  # random soup + root-is-leaf-parent


# ---------------------------------------------------------------------------
# trace: every backend x ray type bit-matches its legacy entry point
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,n_tri,n_rays", SCENES)
@pytest.mark.parametrize("backend,ray_type", [
    ("per_ray", "closest"),
    ("wavefront", "closest"),
    ("wavefront", "any"),
    ("wavefront", "shadow"),
    ("pallas", "closest"),
    ("pallas", "any"),
    ("pallas", "shadow"),
])
def test_trace_bitmatches_legacy(seed, n_tri, n_rays, backend, ray_type):
    scene, rays = _scene_and_rays(seed, n_tri, n_rays)
    engine = scene.engine(pad_multiple=16)  # 64 -> 64, 32 -> 32 (+ pad path)
    got = engine.trace(rays, ray_type=ray_type, backend=backend)
    if backend == "per_ray":
        ref = trace_rays(scene.bvh, rays, scene.depth)
    else:
        # the wavefront free function is the oracle for both the batch
        # engine and the fused Pallas kernel (shared stage helpers)
        ref = trace_wavefront(scene.bvh, rays, scene.depth,
                              ray_type=ray_type)
    for field in TRACE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(ref, field)), err_msg=field)
    if backend == "per_ray":
        # per-ray oracle reports the equivalent batch-round count
        assert int(got.rounds) == int(np.asarray(ref.quadbox_jobs).max())
    else:
        assert int(got.rounds) == int(ref.rounds)


@pytest.mark.parametrize("ray_type", ["closest", "any", "shadow"])
def test_trace_padded_roundtrip_identity(ray_type):
    """pad -> query -> unpad is an identity: a padded batch returns exactly
    the unpadded batch's results (rays are row-independent in every
    backend)."""
    scene, rays = _scene_and_rays(7, 230, 50)  # 50 pads to 64
    tight = scene.engine(pad_multiple=1)
    padded = scene.engine(pad_multiple=16)
    a = tight.trace(rays, ray_type=ray_type)
    b = padded.trace(rays, ray_type=ray_type)
    assert b.t.shape == (50,)
    for field in TRACE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)),
                                      err_msg=field)
    assert int(a.rounds) == int(b.rounds)


def test_trace_occluded_matches_occlusion_test():
    from repro.core import occlusion_test
    scene, rays = _scene_and_rays(23, 230, 64)
    got = scene.engine(pad_multiple=8).occluded(rays, t_min=1e-3)
    ref = occlusion_test(scene.bvh, rays, scene.depth, t_min=1e-3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_trace_backend_validation():
    scene, rays = _scene_and_rays(11, 100, 8)
    engine = scene.engine()
    with pytest.raises(ValueError, match="per_ray"):
        engine.trace(rays, ray_type="any", backend="per_ray")
    with pytest.raises(ValueError, match="unknown trace backend"):
        engine.trace(rays, backend="warp")
    with pytest.raises(ValueError, match="ray_type"):
        engine.trace(rays, ray_type="refracted")
    with pytest.raises(ValueError, match="no Scene"):
        QueryEngine().trace(rays)
    assert "per_ray" in trace_backends() and "wavefront" in trace_backends()
    assert "pallas" in trace_backends()


def test_trace_backend_registry_metadata():
    """The registry knows each backend's ray types and the fused kernel's
    lane multiple matches the kernel's actual tile width."""
    from repro.core.session import PALLAS_TRACE_LANES, trace_backend_ray_types
    from repro.kernels.common import LANES

    assert PALLAS_TRACE_LANES == LANES
    assert trace_backend_ray_types("per_ray") == ("closest",)
    assert set(trace_backend_ray_types("pallas")) == {"closest", "any",
                                                      "shadow"}
    assert set(trace_backend_ray_types("wavefront")) == {"closest", "any",
                                                         "shadow"}
    with pytest.raises(ValueError, match="unknown trace backend"):
        trace_backend_ray_types("warp")


def test_auto_backend_policy():
    scene, rays = _scene_and_rays(11, 100, 8)
    engine = scene.engine()
    # every batch trace goes to the wavefront engine, on every platform
    # (the fused kernel does not lower under Mosaic, so "auto" never
    # picks it; all three bit-match, so the policy is pure scheduling)
    batch = "wavefront"
    assert engine.resolve_trace_backend("closest", 4) == "per_ray"
    assert engine.resolve_trace_backend("closest", 500) == batch
    assert engine.resolve_trace_backend("shadow", 4) == batch
    # queries the per-ray oracle cannot express route to the batch
    # engine, so a tiny closest-hit batch with an epsilon/round cap must
    # still work
    assert engine.resolve_trace_backend("closest", 4, t_min=1e-3) == batch
    assert engine.resolve_trace_backend("closest", 4,
                                        max_rounds=2) == batch
    # ...and so does any sharded batch (a multi-device frontier is not tiny)
    assert engine.resolve_trace_backend("closest", 4, shards=2) == batch
    small = jax.tree_util.tree_map(lambda x: x[:4], rays)
    rec = engine.trace(small, t_min=1e-3)  # auto: must not hit per_ray
    assert rec.t.shape == (4,)
    with pytest.raises(ValueError, match="max_rounds"):
        engine.trace(small, backend="per_ray", max_rounds=2)
    assert engine.resolve_distance_backend() == (
        "pallas" if jax.default_backend() == "tpu" else "mxu")
    # an engine-wide default backend overrides the auto policy...
    # (shard=1 pins the single-device policy whatever mesh the host has)
    forced = scene.engine(backend="wavefront", shard=1)
    forced.trace(small)
    assert all(key[1] == "wavefront" for key in forced._cache)
    # ...and a per-call backend="auto" re-enables it
    forced.trace(small, backend="auto")
    assert any(key[1] == "per_ray" for key in forced._cache)


def test_pallas_prepared_ctx_cached_per_version():
    """The fused backend's packed BVH operands are prepared once per
    scene version (not per chunk/call) through one jitted prepare
    function; a refit evicts the stale version's ctx and re-packs with
    zero new compiles."""
    from repro.core import Triangle as Tri

    scene, rays = _scene_and_rays(7, 230, 64)
    engine = scene.engine(pad_multiple=8, shard=1, chunk_size=16)
    a = engine.trace(rays, backend="pallas")  # 4 chunks, 1 prepare
    misses0 = engine.cache_info().misses
    keys = [k for k in engine._placed if k[0] == "trace_ctx"]
    assert len(keys) == 1 and keys[0][3] == 0  # (kind, name, shards, ver)
    ctx0 = engine._placed[keys[0]]
    engine.trace(rays, backend="pallas")
    assert engine.cache_info().misses == misses0  # fully cached
    assert engine._placed[keys[0]] is ctx0  # same prepared operands
    tri = scene.bvh.triangles
    scene.refit(Tri(tri.a + 0.25, tri.b + 0.25, tri.c + 0.25))
    b = engine.trace(rays, backend="pallas")
    assert engine.cache_info().misses == misses0  # zero-retrace refit
    keys = [k for k in engine._placed if k[0] == "trace_ctx"]
    assert len(keys) == 1 and keys[0][3] == 1  # old version evicted
    ref = trace_wavefront(scene.bvh, rays, scene.depth)
    for field in TRACE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(b, field)),
                                      np.asarray(getattr(ref, field)),
                                      err_msg=field)


def test_auto_backend_tpu_routes_to_fused_kernel_within_budget(monkeypatch):
    """On TPU, "auto" routes only to kernels that compile for the chip:
    batch traces go to the wavefront engine whatever the scene's size
    (the fused traversal kernel, which Mosaic refuses, is never an auto
    route and there is no on-chip budget left to gate it), qualifying
    cloud queries to ``tree_wavefront``, and brute distance queries to
    the Pallas distance kernels, which do compile."""
    from repro.api import PointCloudScene

    scene, _ = _scene_and_rays(11, 100, 8)
    engine = scene.engine()
    cloud = PointCloudScene.from_points(
        jax.random.uniform(jax.random.PRNGKey(3), (5000, 3))).engine(shard=1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engine.resolve_trace_backend("closest", 500) == "wavefront"
    assert engine.resolve_trace_backend("shadow", 4) == "wavefront"
    assert engine.resolve_trace_backend("any", 1 << 21) == "wavefront"
    assert engine.resolve_trace_backend("closest", 4) == "per_ray"  # tiny
    assert engine.resolve_distance_backend() == "pallas"
    assert cloud.resolve_neighbor_backend("nearest", "euclidean",
                                          k=16) == "tree_wavefront"
    assert cloud.resolve_neighbor_backend("within", "euclidean",
                                          radius=0.01) == "tree_wavefront"
    assert cloud.resolve_neighbor_backend("nearest", "euclidean",
                                          k=5000) == "pallas"  # brute
    # batch sizing follows the route: no fused kernel's 128-lane tile
    assert cloud.batch_multiple("nearest", k=16) == cloud.pad_multiple
    assert engine.batch_multiple("trace") == engine.pad_multiple


@pytest.mark.parametrize("method", ["trace", "neighbor_search", "nearest"])
def test_auto_backend_tpu_never_resolves_to_fused_kernels(monkeypatch,
                                                          method):
    """Whichever entry point resolves "auto" on TPU, the chosen backend is
    never the fused trace kernel or ``tree_pallas``; the engine cache key
    records the backend each call actually ran."""
    from repro.api import PointCloudScene

    if method == "trace":
        scene, rays = _scene_and_rays(11, 100, 300)
        engine = scene.engine(shard=1)
        call = lambda: engine.trace(rays)  # noqa: E731
    else:
        pts = jax.random.uniform(jax.random.PRNGKey(4), (5000, 3))
        engine = PointCloudScene.from_points(pts).engine(shard=1)
        q = pts[:300] + 1e-3
        call = ((lambda: engine.neighbor_search(q, 8, radius=0.05))
                if method == "neighbor_search"
                else (lambda: engine.nearest(q, 8)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    call()
    used = {key[1] for key in engine._cache if key[0] != "prepare"}
    assert used and used.isdisjoint({"pallas", "tree_pallas"}), used


@pytest.mark.parametrize("backend", ["pallas", "tree_pallas"])
def test_explicit_fused_backend_on_tpu_compiles_for_real(monkeypatch,
                                                         backend):
    """An explicit fused-kernel request on TPU compiles with Mosaic and
    surfaces the compiler's error; it never drops to interpret mode.
    With the platform reported as TPU on this CPU host, the real compile
    path is the one CPU refuses ("Only interpret mode ...")."""
    from repro.api import PointCloudScene

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if backend == "pallas":
        scene, rays = _scene_and_rays(11, 100, 8)
        call = lambda: scene.engine(shard=1).trace(  # noqa: E731
            rays, backend="pallas")
    else:
        pts = jax.random.uniform(jax.random.PRNGKey(5), (64, 3))
        call = lambda: PointCloudScene.from_points(pts).engine(  # noqa: E731
            shard=1).neighbor_search(pts[:8], 4, backend="tree_pallas")
    with pytest.raises(ValueError, match="interpret mode"):
        call()


# ---------------------------------------------------------------------------
# compiled-function cache: same shape re-enters without retracing
# ---------------------------------------------------------------------------


def test_same_shape_query_hits_compiled_cache():
    scene, rays = _scene_and_rays(7, 230, 64)
    # shard=1: this test pins the *single-device* pad-bucket policy (under
    # auto-sharding the shard rounding merges more shapes into one bucket,
    # which tests/test_dispatch.py covers)
    engine = scene.engine(pad_multiple=8, shard=1)
    first = engine.trace(rays)
    assert engine.cache_info().misses == 1
    # second same-shape call: engine cache hit AND zero new jit traces
    with CompileTracker() as tracker:
        second = engine.trace(rays)
    assert tracker.compiles == 0, "same-shape query retraced its compiled function"
    info = engine.cache_info()
    assert info.hits == 1 and info.misses == 1 and info.entries == 1
    np.testing.assert_array_equal(np.asarray(first.t), np.asarray(second.t))

    # a different shape (not a pad-multiple neighbour) compiles a new entry
    sub = jax.tree_util.tree_map(lambda x: x[:16], rays)
    engine.trace(sub)
    assert engine.cache_info().entries == 2
    # ...but shapes inside the same pad bucket share one entry (the first
    # call only traces the eager pad ops; the compiled query fn is reused)
    sub9 = jax.tree_util.tree_map(lambda x: x[:9], rays)
    engine.trace(sub9)  # pads to 16: same compiled fn as sub
    assert engine.cache_info().entries == 2
    with CompileTracker() as tracker:
        engine.trace(sub9)
    assert tracker.compiles == 0


def test_distance_cache_and_stats():
    q, db = _vectors()
    engine = VectorIndex.from_database(db).engine(pad_multiple=8)
    engine.nearest(q, 5)
    with CompileTracker() as tracker:
        engine.nearest(q, 5)
    assert tracker.compiles == 0
    assert engine.cache_info().hits == 1
    engine.nearest(q, 7)  # different k -> different compiled fn
    assert engine.cache_info().entries == 2
    engine.cache_clear()
    assert engine.cache_info() == (0, 0, 0)


# ---------------------------------------------------------------------------
# nearest / within / count_within / similarity vs the legacy free functions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["euclidean", "angular", "cosine"])
def test_nearest_matches_knn(metric):
    q, db = _vectors()
    index = VectorIndex.from_database(db)
    engine = index.engine(pad_multiple=8)
    got = engine.nearest(q, 5, metric)
    # the engine IS the legacy oracle jitted with the index's precomputed
    # norms: bit-identical
    ref_s, ref_i = jax.jit(
        lambda qq, cc, nn: knn(qq, cc, 5, metric, c_sq_norms=nn))(
            q, db, index.sq_norms)
    np.testing.assert_array_equal(np.asarray(got.scores), np.asarray(ref_s))
    np.testing.assert_array_equal(np.asarray(got.indices), np.asarray(ref_i))
    # vs the plain legacy call (inline norms): identical neighbours; scores
    # may differ by one FMA contraction (precomputed norms arrive as an
    # input, so XLA fuses the combine differently)
    leg_s, leg_i = knn(q, db, 5, metric)
    np.testing.assert_array_equal(np.asarray(got.indices), np.asarray(leg_i))
    np.testing.assert_allclose(np.asarray(got.scores), np.asarray(leg_s),
                               rtol=1e-5, atol=1e-5)


def test_mxu_backend_is_jitted_legacy_bitwise():
    """The defining identity: every engine distance query == jax.jit of the
    legacy free function fed the index's precomputed ||c||^2."""
    q, db = _vectors()
    index = VectorIndex.from_database(db)
    engine = index.engine(pad_multiple=8)
    ref = jax.jit(lambda qq, cc, nn: radius_search(
        qq, cc, 5.0, 12, c_sq_norms=nn))(q, db, index.sq_norms)
    got = engine.within(q, 5.0, 12)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sim = jax.jit(lambda qq, cc, nn: cosine_similarity(
        qq, cc, c_sq_norms=nn))(q, db, index.sq_norms)
    np.testing.assert_array_equal(np.asarray(engine.similarity(q)),
                                  np.asarray(sim))


def test_within_matches_radius_search():
    q, db = _vectors(seed=3, n_q=9, n_db=120, dim=16)
    index = VectorIndex.from_database(db)
    engine = index.engine(pad_multiple=8)
    for metric, radius in (("euclidean", 5.0), ("cosine", 0.2)):
        got = engine.within(q, radius, 12, metric)
        ref = jax.jit(lambda qq, cc, nn: radius_search(
            qq, cc, radius, 12, metric, c_sq_norms=nn))(
                q, db, index.sq_norms)
        for a, b, name in zip(got, ref, ("scores", "indices", "within")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        counts = engine.count_within(q, radius, metric)
        np.testing.assert_array_equal(
            np.asarray(counts),
            np.asarray(jax.jit(lambda qq, cc, nn: radius_count(
                qq, cc, radius, metric, c_sq_norms=nn))(
                    q, db, index.sq_norms)))
        # the in-range sets agree with the plain (eager) legacy call too
        leg = radius_search(q, db, radius, 12, metric)
        np.testing.assert_array_equal(np.asarray(got.indices),
                                      np.asarray(leg[1]))


def test_distance_padded_roundtrip_identity():
    """Padded query batches return exactly the unpadded results."""
    q, db = _vectors(n_q=21)  # pads to 24
    index = VectorIndex.from_database(db)
    tight = index.engine(pad_multiple=1)
    padded = index.engine(pad_multiple=8)
    for metric in ("euclidean", "angular", "cosine"):
        a = tight.nearest(q, 5, metric)
        b = padded.nearest(q, 5, metric)
        assert b.scores.shape == (21, 5)
        np.testing.assert_array_equal(np.asarray(a.scores),
                                      np.asarray(b.scores))
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))
    np.testing.assert_array_equal(
        np.asarray(tight.count_within(q, 5.0)),
        np.asarray(padded.count_within(q, 5.0)))


def test_empty_batches_return_empty_results():
    """Zero-row queries short-circuit to typed empty results: correct
    shapes and dtypes, nothing compiled or executed (the old path padded
    a dummy lane and paid a full compile for a no-op query)."""
    q, db = _vectors()
    engine = VectorIndex.from_database(db).engine(pad_multiple=8)
    res = engine.nearest(q[:0], 4)
    assert res.scores.shape == (0, 4) and res.indices.shape == (0, 4)
    assert res.scores.dtype == jnp.float32
    assert res.indices.dtype == jnp.int32
    counts = engine.count_within(q[:0], 5.0)
    assert counts.shape == (0,) and counts.dtype == jnp.int32
    w = engine.within(q[:0], 5.0, 3)
    assert w.within.shape == (0, 3) and w.within.dtype == bool
    assert engine.scores(q[:0]).shape == (0, db.shape[0])
    assert engine.cache_info().entries == 0, "empty query compiled something"
    # validation still fires before the empty short-circuit
    with pytest.raises(ValueError, match="unknown distance backend"):
        engine.nearest(q[:0], 4, backend="warp")

    scene, rays = _scene_and_rays(11, 100, 8)
    empty = jax.tree_util.tree_map(lambda x: x[:0], rays)
    tre = scene.engine(pad_multiple=8)
    rec = tre.trace(empty)
    assert rec.t.shape == (0,) and rec.tri_index.shape == (0,)
    assert rec.hit.dtype == bool and rec.quadbox_jobs.dtype == jnp.int32
    assert int(rec.rounds) == 0
    assert tre.cache_info().entries == 0, "empty trace compiled something"
    assert tre.occluded(empty).shape == (0,)
    with pytest.raises(ValueError, match="ray_type"):
        tre.trace(empty, ray_type="refracted")


# ---------------------------------------------------------------------------
# sharding / chunking knobs (single-device semantics; the sharded paths are
# fuzzed on an 8-device mesh in test_fuzz_backends.py / test_dispatch.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ray_type", ["closest", "any", "shadow"])
def test_chunked_trace_is_bit_identical(ray_type):
    """chunk_size microbatching returns exactly the one-shot results,
    including the batch round counter, through ONE compiled entry."""
    scene, rays = _scene_and_rays(7, 230, 50)
    ref = scene.engine(pad_multiple=8, shard=1).trace(
        rays, ray_type=ray_type, backend="wavefront")
    chunked = scene.engine(pad_multiple=8, shard=1, chunk_size=16)
    got = chunked.trace(rays, ray_type=ray_type, backend="wavefront")
    for field in TRACE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(ref, field)),
                                      err_msg=field)
    assert int(got.rounds) == int(ref.rounds)
    # 50 rays in 16-row blocks = 4 chunked calls, one compiled function
    assert chunked.cache_info() == (0, 1, 1)
    with CompileTracker() as tracker:
        chunked.trace(rays, ray_type=ray_type, backend="wavefront")
    assert tracker.compiles == 0, "chunked re-query retraced its compiled function"


def test_chunked_distance_is_bit_identical():
    q, db = _vectors()  # 17 queries
    index = VectorIndex.from_database(db)
    ref = index.engine(pad_multiple=8, shard=1)
    chunked = index.engine(pad_multiple=8, shard=1, chunk_size=4)
    for metric in ("euclidean", "angular", "cosine"):
        a = ref.nearest(q, 5, metric)
        b = chunked.nearest(q, 5, metric)
        np.testing.assert_array_equal(np.asarray(a.scores),
                                      np.asarray(b.scores), err_msg=metric)
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices), err_msg=metric)
    np.testing.assert_array_equal(
        np.asarray(ref.count_within(q, 5.0)),
        np.asarray(chunked.count_within(q, 5.0)))
    # per-call override beats the engine default
    np.testing.assert_array_equal(
        np.asarray(ref.scores(q)), np.asarray(chunked.scores(q, chunk_size=7)))


def test_shard_and_chunk_validation():
    scene, rays = _scene_and_rays(11, 100, 8)
    engine = scene.engine()
    with pytest.raises(ValueError, match="exceeds"):
        engine.trace(rays, shard=jax.local_device_count() + 1)
    with pytest.raises(ValueError, match="shard"):
        engine.trace(rays, shard=0)
    with pytest.raises(ValueError, match="chunk_size"):
        engine.trace(rays, chunk_size=0)
    # shard=1 / shard="auto" always valid, whatever the host mesh
    engine.trace(rays, shard=1)
    engine.trace(rays, shard="auto")


def test_similarity_matches_cosine():
    q, db = _vectors()
    index = VectorIndex.from_database(db)
    got = index.engine(pad_multiple=8).similarity(q)
    ref = cosine_similarity(q, db, c_sq_norms=index.sq_norms)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_vector_index_owns_norms():
    _, db = _vectors()
    index = VectorIndex.from_database(db)
    np.testing.assert_array_equal(
        np.asarray(index.sq_norms),
        np.asarray(jnp.sum(db.astype(jnp.float32) ** 2, axis=-1)))
    assert index.size == 211 and index.dim == 24


def test_pallas_backend_agrees():
    """The Pallas kernel backend returns the same neighbours (scores to
    kernel tolerance: the tiled accumulator sums K in blocks)."""
    assert "pallas" in distance_backends() and "mxu" in distance_backends()
    q, db = _vectors(n_q=16, n_db=64, dim=32)
    engine = VectorIndex.from_database(db).engine(pad_multiple=8)
    ref = engine.nearest(q, 5, "euclidean", backend="mxu")
    got = engine.nearest(q, 5, "euclidean", backend="pallas")
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(ref.indices))
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(ref.scores), rtol=1e-4, atol=1e-4)
    sim_ref = engine.similarity(q, backend="mxu")
    sim_got = engine.similarity(q, backend="pallas")
    np.testing.assert_allclose(np.asarray(sim_got), np.asarray(sim_ref),
                               rtol=1e-4, atol=1e-5)


def test_distance_validation():
    q, db = _vectors()
    engine = VectorIndex.from_database(db).engine()
    with pytest.raises(ValueError, match="unknown metric"):
        engine.nearest(q, 5, "manhattan")
    with pytest.raises(ValueError, match="radius metric"):
        engine.within(q, 1.0, 5, "angular")
    with pytest.raises(ValueError, match="unknown distance backend"):
        engine.nearest(q, 5, backend="gpu")
    with pytest.raises(ValueError, match="no VectorIndex"):
        QueryEngine().nearest(q, 5)


# ---------------------------------------------------------------------------
# satellites: serving precondition
# ---------------------------------------------------------------------------


def test_serving_engine_rejects_overlong_prompt():
    """The max_len precondition must be a ValueError (asserts vanish under
    ``python -O``), raised before any compute touches the model."""
    from repro.serving import Engine
    eng = Engine(cfg=None, params=None, max_len=8)  # cfg unused pre-check
    with pytest.raises(ValueError, match="max_len"):
        eng.generate(jnp.zeros((1, 6), jnp.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="batch_chunk"):
        Engine(cfg=None, params=None, batch_chunk=0)


def test_serving_engine_batch_chunk_matches_unchunked():
    """batch_chunk microbatching (the serving twin of the query layer's
    chunk_size) returns the same tokens as the one-shot batch, and empty
    request batches short-circuit."""
    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.serving import Engine
    cfg = get_smoke("smollm-360m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (5, 8)), jnp.int32)
    ref = Engine(cfg, params, max_len=16).generate(toks, max_new_tokens=4)
    chunked = Engine(cfg, params, max_len=16, batch_chunk=2)
    got = chunked.generate(toks, max_new_tokens=4)  # 2 + 2 + 1(pad to 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert chunked.generate(toks[:0], max_new_tokens=4).shape == (0, 4)
    # sampled decode folds the chunk offset into rng: identical prompts in
    # different chunks must not draw identical "random" continuations
    same = jnp.broadcast_to(toks[:1], (4, toks.shape[1]))
    sampled = chunked.generate(same, max_new_tokens=4, temperature=1.0,
                               rng=jax.random.PRNGKey(7))
    assert not np.array_equal(np.asarray(sampled[0]), np.asarray(sampled[2]))
