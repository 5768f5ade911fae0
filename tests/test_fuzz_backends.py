"""Differential fuzzing: every backend against its oracle, under hypothesis.

The parity contract (DESIGN.md §5–§6) says results are *bit-identical*
across execution strategies, not merely close.  This suite hammers that
with hypothesis-generated random scenes / rays / databases:

* every trace backend × ray type × **acceleration-structure builder**
  (``"lbvh"`` / ``"sah"``, drawn as a hypothesis parameter) against the
  per-ray / free-function oracles (``trace_rays``, ``trace_wavefront``)
  on that builder's own tree, bit for bit including the per-ray job
  counters and the batch round count — including the fused Pallas
  traversal kernel (``backend="pallas"``, interpret mode off-TPU), which
  shares the ``core/datapath`` stage helpers and so carries no ulp
  caveat, unlike the tiled distance kernels below;
* every distance backend × metric against the jitted free functions fed
  precomputed ``||c||^2`` — bit-exact for the MXU form, and for the Pallas
  tiled accumulator the documented score caveat (rank-equivalent
  neighbours, scores to ~1e-4);
* the sharded + chunked dispatch paths on a forced 8-device host mesh
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` subprocess),
  against the single-device unchunked engine.

Scenes / databases are drawn from a small seeded domain and cached per
(seed, size) so the compile count stays bounded while the geometry itself
remains hypothesis-chosen.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="install via requirements-dev.txt")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.api import PointCloudScene, Scene, VectorIndex, make_ray
from repro.core import (Triangle, knn, radius_count, radius_search,
                        trace_rays, trace_wavefront)
from repro.core.bvh import DatapathConfig
from repro.core.build import build

TRACE_FIELDS = ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs",
                "stack_overflow")

# small seeded domains so engines/BVHs cache across hypothesis examples
N_TRI = (1, 3, 17, 230)  # single-triangle, root-is-leaf-parent, mid, deep
SCENE_SEEDS = (0, 1, 2, 3)
BUILDERS = ("lbvh", "sah")
DB_SHAPES = ((37, 8), (211, 24))

_scenes: dict = {}
_indexes: dict = {}


def _scene(seed, n_tri, builder="lbvh"):
    key = (seed, n_tri, builder)
    if key not in _scenes:
        rng = np.random.default_rng(1000 * seed + n_tri)
        ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
        d1 = rng.normal(scale=0.2, size=(n_tri, 3)).astype(np.float32)
        d2 = rng.normal(scale=0.2, size=(n_tri, 3)).astype(np.float32)
        tri = Triangle(jnp.asarray(ctr), jnp.asarray(ctr + d1),
                       jnp.asarray(ctr + d2))
        scene = Scene.from_triangles(tri, builder=builder)
        _scenes[key] = (scene, scene.engine(pad_multiple=8, shard=1),
                        scene.engine(pad_multiple=8, shard=1, chunk_size=8))
    return _scenes[key]


def _index(seed, shape):
    key = (seed, shape)
    if key not in _indexes:
        rng = np.random.default_rng(7000 + 100 * seed + shape[0])
        db = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        index = VectorIndex.from_database(db)
        _indexes[key] = (index, index.engine(pad_multiple=8, shard=1),
                         index.engine(pad_multiple=8, shard=1, chunk_size=8))
    return _indexes[key]


def _rays(rng, n):
    org = rng.uniform(-3, -2, (n, 3)).astype(np.float32)
    tgt = rng.uniform(-0.6, 0.6, (n, 3)).astype(np.float32)
    extent = np.where(rng.uniform(size=n) < 0.3,
                      rng.uniform(1.0, 6.0, n), np.inf).astype(np.float32)
    return make_ray(jnp.asarray(org), jnp.asarray(tgt - org),
                    extent=jnp.asarray(extent))


# ---------------------------------------------------------------------------
# trace backends × ray types vs the per-ray / free-function oracles
# ---------------------------------------------------------------------------


@given(scene_seed=st.sampled_from(SCENE_SEEDS),
       n_tri=st.sampled_from(N_TRI),
       builder=st.sampled_from(BUILDERS),
       ray_seed=st.integers(0, 2**31 - 1),
       n_rays=st.integers(1, 24),
       ray_type=st.sampled_from(["closest", "any", "shadow"]))
@settings(max_examples=25, deadline=None)
def test_fuzz_trace_backends_bitmatch_oracles(scene_seed, n_tri, builder,
                                              ray_seed, n_rays, ray_type):
    scene, engine, chunked = _scene(scene_seed, n_tri, builder)
    rays = _rays(np.random.default_rng(ray_seed), n_rays)

    ref = trace_wavefront(scene.bvh, rays, scene.depth, ray_type=ray_type)
    results = {
        "engine/wavefront": engine.trace(rays, ray_type=ray_type,
                                         backend="wavefront"),
        "engine/wavefront/chunked": chunked.trace(rays, ray_type=ray_type,
                                                  backend="wavefront"),
        # the fused Pallas traversal (interpret mode off-TPU) carries NO
        # score caveat, unlike the tiled distance kernels: it calls the
        # same core/datapath stage helpers as the wavefront engine, so
        # hits AND job counters are compared bit-for-bit
        "engine/pallas": engine.trace(rays, ray_type=ray_type,
                                      backend="pallas"),
        "engine/pallas/chunked": chunked.trace(rays, ray_type=ray_type,
                                               backend="pallas"),
    }
    if ray_type == "closest":
        # the vmapped per-ray while_loop is the semantic oracle: the
        # wavefront free function and both engine backends must bit-match
        oracle = trace_rays(scene.bvh, rays, scene.depth)
        for f in TRACE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(oracle, f)),
                err_msg=f"wavefront vs per-ray oracle: {f}")
        results["engine/per_ray"] = engine.trace(rays, backend="per_ray")
        results["engine/per_ray/chunked"] = chunked.trace(
            rays, backend="per_ray")
    for name, got in results.items():
        for f in TRACE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(ref, f)),
                err_msg=f"{name}: {f}")
        if "per_ray" not in name:
            assert int(got.rounds) == int(ref.rounds), name


# ---------------------------------------------------------------------------
# datapath config twins: every (arity, stack, precision, codec) draw vs
# the BVH4-fp32 oracle
# ---------------------------------------------------------------------------

# drawn as strategy components so hypothesis explores the grid while the
# per-(config, builder) scenes/engines cache across examples
CONFIG_ARITIES = (4, 8)
CONFIG_STACKS = (16, 64)
CONFIG_CODECS = (("fp32", "fp32"), ("bf16", "fp32"), ("bf16", "compressed"))

_config_scenes: dict = {}


def _config_scene(seed, n_tri, builder, config):
    key = (seed, n_tri, builder, config)
    if key not in _config_scenes:
        rng = np.random.default_rng(1000 * seed + n_tri)
        ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
        d1 = rng.normal(scale=0.2, size=(n_tri, 3)).astype(np.float32)
        d2 = rng.normal(scale=0.2, size=(n_tri, 3)).astype(np.float32)
        tri = Triangle(jnp.asarray(ctr), jnp.asarray(ctr + d1),
                       jnp.asarray(ctr + d2))
        scene = Scene.from_triangles(tri, builder=builder, config=config)
        _config_scenes[key] = (scene, scene.engine(pad_multiple=8, shard=1))
    return _config_scenes[key]


@given(scene_seed=st.sampled_from(SCENE_SEEDS[:2]),
       n_tri=st.sampled_from((17, 230)),
       builder=st.sampled_from(BUILDERS),
       arity=st.sampled_from(CONFIG_ARITIES),
       stack_size=st.sampled_from(CONFIG_STACKS),
       codec=st.sampled_from(CONFIG_CODECS),
       ray_seed=st.integers(0, 2**31 - 1),
       n_rays=st.integers(1, 24),
       ray_type=st.sampled_from(["closest", "any", "shadow"]))
@settings(max_examples=30, deadline=None)
def test_fuzz_datapath_configs_honor_contracts(scene_seed, n_tri, builder,
                                               arity, stack_size, codec,
                                               ray_seed, n_rays, ray_type):
    """Every drawn :class:`DatapathConfig` twin honors its contract:

    * wavefront and fused-Pallas engines bit-match on EVERY field under
      every config (cross-engine parity is structural, not fp32-only);
    * closest-hit ``t``/``tri_index``/``hit`` bit-match the default
      BVH4-fp32 wavefront oracle — the conservative codecs only widen
      boxes, and triangle tests stay exact f32, so reduced precision can
      add visited nodes but never change the committed hit;
    * any/shadow ``hit`` flags agree with the oracle (the accepted ``t``
      of an any-hit may legitimately differ — first hit found wins);
    * job counters are a superset (>=) of the SAME builder+arity's exact
      fp32 twin — the conservative-interval cost is measurable, ordered
      and never negative.
    """
    precision, node_format = codec
    config = DatapathConfig(arity=arity, stack_size=stack_size,
                            precision=precision, node_format=node_format)
    scene, engine = _config_scene(scene_seed, n_tri, builder, config)
    rays = _rays(np.random.default_rng(ray_seed), n_rays)

    ref = trace_wavefront(scene.bvh, rays, scene.depth, ray_type=ray_type,
                          config=config)
    got = engine.trace(rays, ray_type=ray_type, backend="pallas")
    for f in TRACE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(ref, f)),
            err_msg=f"pallas vs wavefront under {config.tag}: {f}")
    assert int(got.rounds) == int(ref.rounds), config.tag
    if ray_type == "closest":
        oracle = trace_rays(scene.bvh, rays, scene.depth, config)
        for f in TRACE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(oracle, f)),
                err_msg=f"wavefront vs per-ray under {config.tag}: {f}")

    # --- contracts vs the default BVH4-fp32 oracle scene -------------------
    base_scene, _ = _scene(scene_seed, n_tri, builder)[:2]
    base = trace_wavefront(base_scene.bvh, rays, base_scene.depth,
                           ray_type=ray_type)
    np.testing.assert_array_equal(np.asarray(ref.hit), np.asarray(base.hit),
                                  err_msg=f"{config.tag}: hit flags")
    if ray_type == "closest":
        for f in ("t", "tri_index"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(base, f)),
                err_msg=f"{config.tag}: closest-hit {f} vs BVH4-fp32 oracle")

    # --- superset contract: vs the exact-precision twin of the SAME tree --
    if not config.exact_boxes:
        exact = DatapathConfig(arity=arity, stack_size=stack_size)
        exact_scene, _ = _config_scene(scene_seed, n_tri, builder, exact)
        ex = trace_wavefront(exact_scene.bvh, rays, exact_scene.depth,
                             ray_type=ray_type, config=exact)
        if ray_type == "closest":  # any-hit walks stop at different nodes
            assert np.all(np.asarray(ref.quadbox_jobs)
                          >= np.asarray(ex.quadbox_jobs)), config.tag
            assert np.all(np.asarray(ref.triangle_jobs)
                          >= np.asarray(ex.triangle_jobs)), config.tag


# ---------------------------------------------------------------------------
# distance backends × metrics vs the jitted free functions
# ---------------------------------------------------------------------------


@given(db_seed=st.sampled_from(SCENE_SEEDS),
       shape=st.sampled_from(DB_SHAPES),
       q_seed=st.integers(0, 2**31 - 1),
       n_q=st.integers(1, 24),
       k=st.integers(1, 8),
       metric=st.sampled_from(["euclidean", "angular", "cosine"]))
@settings(max_examples=25, deadline=None)
def test_fuzz_mxu_backend_bitmatches_free_functions(db_seed, shape, q_seed,
                                                    n_q, k, metric):
    index, engine, chunked = _index(db_seed, shape)
    rng = np.random.default_rng(q_seed)
    q = jnp.asarray(rng.normal(size=(n_q, shape[1])).astype(np.float32))

    ref_s, ref_i = jax.jit(
        lambda qq, cc, nn: knn(qq, cc, k, metric, c_sq_norms=nn))(
            q, index.database, index.sq_norms)
    for eng in (engine, chunked):
        got = eng.nearest(q, k, metric, backend="mxu")
        np.testing.assert_array_equal(np.asarray(got.scores),
                                      np.asarray(ref_s))
        np.testing.assert_array_equal(np.asarray(got.indices),
                                      np.asarray(ref_i))
    if metric != "angular":
        radius = 4.0 if metric == "euclidean" else 0.1
        ref = jax.jit(lambda qq, cc, nn: radius_search(
            qq, cc, radius, k, metric, c_sq_norms=nn))(
                q, index.database, index.sq_norms)
        got = chunked.within(q, radius, k, metric, backend="mxu")
        for a, b, name in zip(got, ref, ("scores", "indices", "within")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(chunked.count_within(q, radius, metric,
                                            backend="mxu")),
            np.asarray(jax.jit(lambda qq, cc, nn: radius_count(
                qq, cc, radius, metric, c_sq_norms=nn))(
                    q, index.database, index.sq_norms)))


@given(db_seed=st.sampled_from(SCENE_SEEDS[:2]),
       q_seed=st.integers(0, 2**31 - 1),
       n_q=st.integers(1, 16),
       metric=st.sampled_from(["euclidean", "angular", "cosine"]))
@settings(max_examples=10, deadline=None)
def test_fuzz_pallas_backend_rank_equivalent(db_seed, q_seed, n_q, metric):
    """The Pallas tiled accumulator carries the documented score caveat
    (block-summed K), so neighbours are checked by *rank equivalence*:
    every returned neighbour's oracle score matches the oracle's k-th
    scores to kernel tolerance — exact index equality would flake on ties.
    """
    index, engine, _ = _index(db_seed, (211, 24))
    rng = np.random.default_rng(q_seed)
    q = jnp.asarray(rng.normal(size=(n_q, 24)).astype(np.float32))
    k = 5
    ref = engine.nearest(q, k, metric, backend="mxu")
    got = engine.nearest(q, k, metric, backend="pallas")
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(ref.scores), rtol=1e-4, atol=1e-4)
    oracle_scores = np.asarray(engine.scores(q, metric, backend="mxu"))
    picked = np.take_along_axis(oracle_scores, np.asarray(got.indices), 1)
    np.testing.assert_allclose(picked, np.asarray(ref.scores),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# tree-backed neighbor path: engines vs each other (bit) and the oracle
# ---------------------------------------------------------------------------

CLOUD_SIZES = (5, 61, 230)
CLOUD_RADII = (0.0, 0.5, 1.25)
NEIGHBOR_FIELDS = ("dist_sq", "index", "valid", "count", "box_jobs",
                   "point_jobs")

_clouds: dict = {}


def _cloud(seed, n, builder):
    key = (seed, n, builder)
    if key not in _clouds:
        rng = np.random.default_rng(3000 * seed + n)
        pts = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        cloud = PointCloudScene.from_points(pts, builder=builder)
        _clouds[key] = (cloud, cloud.engine(pad_multiple=8, shard=1),
                        cloud.engine(pad_multiple=8, shard=1, chunk_size=8))
    return _clouds[key]


@given(seed=st.sampled_from(SCENE_SEEDS[:2]),
       n=st.sampled_from(CLOUD_SIZES),
       builder=st.sampled_from(BUILDERS),
       q_seed=st.integers(0, 2**31 - 1),
       n_q=st.integers(1, 16),
       radius=st.sampled_from(CLOUD_RADII))
@settings(max_examples=15, deadline=None)
def test_fuzz_tree_neighbors_match_brute(seed, n, builder, q_seed, n_q,
                                         radius):
    """Both tree backends vs the brute oracle on hypothesis clouds.

    The two tree engines (and the chunked twin) share stage helpers and
    must bit-match each other, *job counters included*.  Against the
    brute oracle the leaf test computes the difference form while the
    oracle computes the expanded MXU form, which rounds by up to ~eps *
    (|q|^2 + |c|^2) — so membership is compared exactly away from the
    radius boundary and left free inside a +-tol band (exactness against
    the leaf's own form lives in ``test_neighbor.py``).
    """
    cloud, engine, chunked = _cloud(seed, n, builder)
    rng = np.random.default_rng(q_seed)
    q = jnp.asarray(rng.normal(size=(n_q, 3)).astype(np.float32))

    # k = N so the record can hold every in-radius point: set comparisons
    # are meaningful (k < count would truncate legitimately)
    ref = engine.neighbor_search(q, n, radius=radius,
                                 backend="tree_wavefront")
    others = {
        "tree_pallas": engine.neighbor_search(q, n, radius=radius,
                                              backend="tree_pallas"),
        "tree_wavefront/chunked": chunked.neighbor_search(
            q, n, radius=radius, backend="tree_wavefront"),
    }
    for name, rec in others.items():
        for f in NEIGHBOR_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(rec, f)), np.asarray(getattr(ref, f)),
                err_msg=f"{name}: {f}")
        assert int(rec.rounds) == int(ref.rounds), name

    oracle = np.asarray(engine.scores(q, "euclidean", backend="mxu"))
    r_sq = radius * radius
    tol = 1e-5 * (1.0 + r_sq)
    w = np.asarray(ref.valid)
    idx = np.asarray(ref.index)
    for i in range(n_q):
        got = set(idx[i][w[i]])
        must = set(np.flatnonzero(oracle[i] <= r_sq - tol))
        may = set(np.flatnonzero(oracle[i] <= r_sq + tol))
        assert must <= got <= may, (i, got, must, may)
    counts = np.asarray(ref.count)
    assert ((oracle <= r_sq - tol).sum(1) <= counts).all()
    assert (counts <= (oracle <= r_sq + tol).sum(1)).all()

    # nearest: rank-equivalent vs the brute top-k (near-ties may permute
    # under the contraction difference, so compare through oracle scores)
    k = min(5, n)
    brute = engine.nearest(q, k, backend="mxu")
    for backend in ("tree_wavefront", "tree_pallas"):
        tree = engine.nearest(q, k, backend=backend)
        picked = np.take_along_axis(oracle, np.asarray(tree.indices), 1)
        np.testing.assert_allclose(picked, np.asarray(brute.scores),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=backend)


# ---------------------------------------------------------------------------
# sharded + chunked dispatch on a forced 8-device host mesh
# ---------------------------------------------------------------------------


def test_fuzz_sharded_trace_8dev(multidev):
    multidev("""
import numpy as np, jax, jax.numpy as jnp
assert jax.local_device_count() == 8
from hypothesis import given, settings, strategies as st
from repro.api import Scene, make_ray
from repro.core import Triangle

_cache = {}
def scene_pair(seed, n_tri):
    key = (seed, n_tri)
    if key not in _cache:
        rng = np.random.default_rng(1000 * seed + n_tri)
        ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
        d1 = rng.normal(scale=0.2, size=(n_tri, 3)).astype(np.float32)
        d2 = rng.normal(scale=0.2, size=(n_tri, 3)).astype(np.float32)
        s = Scene.from_triangles(Triangle(jnp.asarray(ctr),
                                          jnp.asarray(ctr + d1),
                                          jnp.asarray(ctr + d2)))
        _cache[key] = (s.engine(pad_multiple=8, shard=1),
                       s.engine(pad_multiple=8, shard=8, chunk_size=16))
    return _cache[key]

FIELDS = ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs")

@given(seed=st.sampled_from((0, 1)), n_tri=st.sampled_from((3, 230)),
       ray_seed=st.integers(0, 2**31 - 1), n_rays=st.integers(1, 40),
       ray_type=st.sampled_from(["closest", "any", "shadow"]))
@settings(max_examples=10, deadline=None)
def check(seed, n_tri, ray_seed, n_rays, ray_type):
    single, sharded = scene_pair(seed, n_tri)
    rng = np.random.default_rng(ray_seed)
    org = rng.uniform(-3, -2, (n_rays, 3)).astype(np.float32)
    tgt = rng.uniform(-0.6, 0.6, (n_rays, 3)).astype(np.float32)
    rays = make_ray(jnp.asarray(org), jnp.asarray(tgt - org))
    ref = single.trace(rays, ray_type=ray_type, backend="wavefront")
    got = sharded.trace(rays, ray_type=ray_type, backend="wavefront")
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f"{ray_type}: {f}")
    assert int(got.rounds) == int(ref.rounds)

check()

# fused Pallas traversal on the same 8-way mesh: fixed cases (the kernel
# pads each shard to its 128-lane tile, so one shape covers them all)
single, sharded = scene_pair(0, 230)
for ray_seed, ray_type in ((7, "closest"), (8, "any"), (9, "shadow")):
    rng = np.random.default_rng(ray_seed)
    org = rng.uniform(-3, -2, (40, 3)).astype(np.float32)
    tgt = rng.uniform(-0.6, 0.6, (40, 3)).astype(np.float32)
    rays = make_ray(jnp.asarray(org), jnp.asarray(tgt - org))
    ref = single.trace(rays, ray_type=ray_type, backend="wavefront")
    got = sharded.trace(rays, ray_type=ray_type, backend="pallas")
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f"pallas {ray_type}: {f}")
    assert int(got.rounds) == int(ref.rounds)
print("sharded trace fuzz OK")
""", n_devices=8)


def test_fuzz_sharded_distance_8dev(multidev):
    multidev("""
import numpy as np, jax, jax.numpy as jnp
assert jax.local_device_count() == 8
from hypothesis import given, settings, strategies as st
from repro.api import VectorIndex

rng0 = np.random.default_rng(42)
db = jnp.asarray(rng0.normal(size=(211, 24)).astype(np.float32))
index = VectorIndex.from_database(db)
single = index.engine(pad_multiple=8, shard=1)
sharded = index.engine(pad_multiple=8, shard=8, chunk_size=16)

@given(q_seed=st.integers(0, 2**31 - 1), n_q=st.integers(1, 40),
       k=st.sampled_from((1, 5)),
       metric=st.sampled_from(["euclidean", "angular", "cosine"]))
@settings(max_examples=10, deadline=None)
def check(q_seed, n_q, k, metric):
    rng = np.random.default_rng(q_seed)
    q = jnp.asarray(rng.normal(size=(n_q, 24)).astype(np.float32))
    a = single.nearest(q, k, metric, backend="mxu")
    b = sharded.nearest(q, k, metric, backend="mxu")
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))
    if metric != "angular":
        radius = 4.0 if metric == "euclidean" else 0.1
        for x, y in zip(single.within(q, radius, k, metric, backend="mxu"),
                        sharded.within(q, radius, k, metric, backend="mxu")):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(
            np.asarray(single.count_within(q, radius, metric,
                                           backend="mxu")),
            np.asarray(sharded.count_within(q, radius, metric,
                                            backend="mxu")))

check()
# pallas sharded: indices rank-equivalent, scores to the documented caveat
rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(size=(21, 24)).astype(np.float32))
a = single.nearest(q, 5, "euclidean", backend="pallas")
b = sharded.nearest(q, 5, "euclidean", backend="pallas")
np.testing.assert_allclose(np.asarray(a.scores), np.asarray(b.scores),
                           rtol=1e-6, atol=1e-4)
oracle = np.asarray(single.scores(q, "euclidean", backend="mxu"))
picked = np.take_along_axis(oracle, np.asarray(b.indices), 1)
np.testing.assert_allclose(picked, np.asarray(a.scores), rtol=1e-4,
                           atol=1e-4)
print("sharded distance fuzz OK")
""", n_devices=8)
