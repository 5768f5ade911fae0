"""Traversal-backed neighbor search: stage units, engine parity, the
deterministic tree-vs-brute exactness contract (at unit scale and at
LiDAR coordinates), the auto policy, and the sharded + chunked scale
acceptance run (DESIGN.md §9).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import PointCloudScene, VectorIndex
from repro.core import Box, neighbor
from repro.core.build.points import build_point_bvh, refit_points
from repro.core.bvh import level_offset
from repro.core.datapath import point_box_test
from repro.core.neighbor import (insert_sorted, leaf_dist_sq,
                                 neighbor_wavefront, point_queries)

BUILDERS = ("lbvh", "sah")
NEIGHBOR_FIELDS = ("dist_sq", "index", "valid", "count", "box_jobs",
                   "point_jobs")


def _pts(n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))


# ---------------------------------------------------------------------------
# stage units
# ---------------------------------------------------------------------------


def test_point_box_test_hand_values():
    boxes = Box(lo=jnp.asarray([[-1.0, -1, -1], [1, 2, 0],
                                [-3, -3, -3], [0, 0, 2]], jnp.float32),
                hi=jnp.asarray([[1.0, 1, 1], [2, 3, 1],
                                [-2, -2, -2], [1, 1, 3]], jnp.float32))
    res = point_box_test(jnp.zeros((3,), jnp.float32), boxes)
    # containment -> 0; outside -> sum of per-axis gap^2; sorted ascending
    np.testing.assert_allclose(np.asarray(res.dist_sq), [0.0, 4.0, 5.0, 12.0])
    np.testing.assert_array_equal(np.asarray(res.box_index), [0, 3, 1, 2])


def test_point_box_test_batched_matches_per_point():
    rng = np.random.default_rng(3)
    p = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
    lo = rng.uniform(-2, 0, (6, 4, 3)).astype(np.float32)
    boxes = Box(lo=jnp.asarray(lo),
                hi=jnp.asarray(lo + rng.uniform(0, 2, (6, 4, 3))
                               .astype(np.float32)))
    batched = point_box_test(p, boxes)
    for i in range(6):
        one = point_box_test(p[i], Box(boxes.lo[i], boxes.hi[i]))
        np.testing.assert_array_equal(np.asarray(batched.dist_sq[i]),
                                      np.asarray(one.dist_sq))


def test_insert_sorted_matches_sorted_prefix():
    k, lanes = 3, 2
    best_d = jnp.full((k, lanes), jnp.inf, jnp.float32)
    best_i = jnp.full((k, lanes), -1, jnp.int32)
    cands = [(5.0, 0), (3.0, 1), (4.0, 2), (1.0, 3), (2.0, 4)]
    accept1 = [True, False, True, False, False]  # lane 1 stays underfilled
    kept = ([], [])
    for (d, i), a1 in zip(cands, accept1):
        best_d, best_i = insert_sorted(
            best_d, best_i, jnp.full((lanes,), d, jnp.float32),
            jnp.full((lanes,), i, jnp.int32),
            jnp.asarray([True, a1]))
        kept[0].append((d, i))
        if a1:
            kept[1].append((d, i))
    for lane in range(lanes):
        want = sorted(kept[lane])[:k]
        got_d = np.asarray(best_d[:, lane])[:len(want)]
        got_i = np.asarray(best_i[:, lane])[:len(want)]
        np.testing.assert_allclose(got_d, [d for d, _ in want])
        np.testing.assert_array_equal(got_i, [i for _, i in want])
    # unfilled slots stay at the empty sentinel (lane 1 holds 2 of k=3)
    assert int(best_i[-1, 1]) == -1 and np.isinf(float(best_d[-1, 1]))


# ---------------------------------------------------------------------------
# point builds + refit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("builder", BUILDERS)
def test_build_point_bvh_structure(builder):
    n = 37
    pts = _pts(n, seed=5)
    res = build_point_bvh(pts, builder=builder)
    bvh, depth = res.bvh, res.depth
    lt = np.asarray(bvh.leaf_tri)
    assert sorted(lt[lt >= 0]) == list(range(n))  # every point, exactly once
    # live leaf nodes are the degenerate per-point boxes (lo == hi == point)
    base = level_offset(depth)
    p = np.asarray(pts)
    for slot in np.flatnonzero(lt >= 0):
        np.testing.assert_array_equal(
            np.asarray(bvh.node_lo[base + slot]), p[lt[slot]])
        np.testing.assert_array_equal(
            np.asarray(bvh.node_hi[base + slot]), p[lt[slot]])
    np.testing.assert_array_equal(np.asarray(bvh.node_lo[0]), p.min(0))
    np.testing.assert_array_equal(np.asarray(bvh.node_hi[0]), p.max(0))


def test_build_point_bvh_validation():
    with pytest.raises(ValueError, match="point builder"):
        build_point_bvh(_pts(8), builder="nope")
    with pytest.raises(ValueError, match="leaf slots"):
        build_point_bvh(_pts(100), depth=1)
    with pytest.raises(ValueError, match=r"\(N, 3\)"):
        build_point_bvh(jnp.zeros((4, 8)))
    with pytest.raises(ValueError, match="finite"):
        PointCloudScene.from_points(
            jnp.asarray([[0.0, 0.0, jnp.nan]], jnp.float32))


def test_refit_points_preserves_topology():
    pts = _pts(21, seed=6)
    bvh = build_point_bvh(pts).bvh
    moved = pts * 1.5 + jnp.asarray([10.0, -3.0, 0.5])
    new = refit_points(bvh, moved)
    np.testing.assert_array_equal(np.asarray(new.leaf_tri),
                                  np.asarray(bvh.leaf_tri))
    np.testing.assert_array_equal(np.asarray(new.leaf_perm),
                                  np.asarray(bvh.leaf_perm))
    m = np.asarray(moved)
    np.testing.assert_array_equal(np.asarray(new.node_lo[0]), m.min(0))
    np.testing.assert_array_equal(np.asarray(new.node_hi[0]), m.max(0))
    with pytest.raises(ValueError, match="21 points"):
        refit_points(bvh, _pts(22))


def test_point_queries_extent():
    q = _pts(4, seed=7)
    assert float(point_queries(q).extent[0]) == float("inf")
    np.testing.assert_allclose(np.asarray(point_queries(q, 0.25).extent),
                               0.25)


# ---------------------------------------------------------------------------
# engine parity: the fused kernel bit-matches the wavefront loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("builder", BUILDERS)
@pytest.mark.parametrize("mode", ("within", "nearest"))
def test_fused_bitmatches_wavefront(builder, mode):
    from repro.kernels.traverse import neighbor_fused

    res = build_point_bvh(_pts(300, seed=8), builder=builder)
    rays = point_queries(_pts(70, seed=9),
                         0.8 if mode == "within" else None)
    a = neighbor_wavefront(res.bvh, rays, res.depth, k=8, mode=mode)
    b = neighbor_fused(res.bvh, rays, res.depth, 8, mode=mode)
    for f in NEIGHBOR_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f)
    assert int(a.rounds) == int(b.rounds)


# ---------------------------------------------------------------------------
# deterministic tree-vs-brute exactness (fixed seeds)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ("tree_wavefront", "tree_pallas"))
def test_tree_matches_brute_exactly(backend):
    """Exact against a brute pass in the leaf's own difference form; within
    the expanded form's rounding band of the MXU oracle."""
    n, m = 500, 40
    pts = _pts(n, seed=10)
    cloud = PointCloudScene.from_points(pts)
    engine = cloud.engine(pad_multiple=8, shard=1)
    q = _pts(m, seed=11)
    leaf = np.asarray(leaf_dist_sq(q, pts))  # (m, n), no tree
    for radius in (0.3, 0.9):
        inside = leaf <= radius * radius
        assert inside.sum(1).max() < n
        rec = engine.neighbor_search(q, n, radius=radius, backend=backend)
        w, idx = np.asarray(rec.valid), np.asarray(rec.index)
        for i in range(m):
            assert set(idx[i][w[i]]) == set(np.flatnonzero(inside[i]))
        np.testing.assert_array_equal(np.asarray(rec.count),
                                      inside.sum(1))
    near = engine.nearest(q, 7, backend=backend)
    np.testing.assert_array_equal(np.asarray(near.indices),
                                  np.argsort(leaf, axis=1)[:, :7])
    oracle = np.asarray(engine.scores(q, "euclidean", backend="mxu"))
    brute = engine.nearest(q, 7, backend="mxu")
    picked = np.take_along_axis(oracle, np.asarray(near.indices), 1)
    band = 4 * np.finfo(np.float32).eps * (
        np.sum(np.square(np.asarray(q)), 1, keepdims=True)
        + np.max(np.sum(np.square(np.asarray(pts)), 1)))
    assert (np.abs(picked - np.asarray(brute.scores)) <= band).all()


# ---------------------------------------------------------------------------
# LiDAR coordinates: metres from the origin, neighbours centimetres apart
# ---------------------------------------------------------------------------


def _lidar(n_patches=8, per_patch=256, n_q=64, seed=20):
    """Patches of points with a 2 cm spread at coordinates up to 30 m from
    the origin, and queries drawn the same way."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-30.0, 30.0, (n_patches, 3))
    pts = (np.repeat(centers, per_patch, axis=0)
           + rng.normal(scale=0.02, size=(n_patches * per_patch, 3)))
    q = (centers[rng.integers(0, n_patches, n_q)]
         + rng.normal(scale=0.02, size=(n_q, 3)))
    return pts.astype(np.float32), q.astype(np.float32)


def _expanded_leaf_dist_sq(p, pts):
    """The expanded form ``max(|q|^2 - 2 q.c + |c|^2, 0)``, which the leaf
    computed before it took the difference form."""
    q2 = jnp.sum(p * p, axis=-1)
    qc = jnp.sum(p[..., None, :] * pts, axis=-1)
    return jnp.maximum(q2[..., None] - 2.0 * qc
                       + jnp.sum(pts * pts, axis=-1), 0.0)


def _rows_off_exact_knn(pts, q, idx, k):
    """Rows whose ``k`` reported ids are not the float64 brute top-k, where
    a swap counts only between ids whose distances lie more than 2 ulp
    (float32) from the k-th distance."""
    d = np.sum(np.square(q[:, None, :].astype(np.float64)
                         - pts[None].astype(np.float64)), axis=2)
    want = np.argsort(d, axis=1)[:, :k]
    kth = np.take_along_axis(d, want[:, -1:], axis=1)[:, 0]
    tie = 2.0 * np.spacing(kth.astype(np.float32)).astype(np.float64)
    off = 0
    for i in range(len(q)):
        got = set(idx[i].tolist())
        if len(got) != k or min(got) < 0:
            off += 1
        elif any(abs(d[i, j] - kth[i]) > tie[i]
                 for j in got ^ set(want[i].tolist())):
            off += 1
    return off


@pytest.mark.parametrize("backend", ("tree_wavefront", "tree_pallas"))
def test_lidar_scale_nearest_is_exact(backend):
    pts, q = _lidar()
    engine = PointCloudScene.from_points(jnp.asarray(pts)).engine(
        pad_multiple=8, shard=1)
    near = engine.nearest(jnp.asarray(q), 16, backend=backend)
    assert _rows_off_exact_knn(pts, q, np.asarray(near.indices), 16) == 0


def test_expanded_leaf_form_fails_at_lidar_scale(monkeypatch):
    """The same data through the expanded form: its cancellation (about
    eps * |q|^2, 1e-4 m^2 at 30 m) outweighs distances of a few cm^2."""
    monkeypatch.setattr(neighbor, "leaf_dist_sq", _expanded_leaf_dist_sq)
    pts, q = _lidar()
    engine = PointCloudScene.from_points(jnp.asarray(pts)).engine(
        pad_multiple=8, shard=1)
    near = engine.nearest(jnp.asarray(q), 16, backend="tree_wavefront")
    assert _rows_off_exact_knn(pts, q, np.asarray(near.indices),
                               16) > len(q) // 10


@pytest.mark.parametrize("jit", (False, True), ids=("eager", "jit"))
def test_point_leaf_equals_its_degenerate_box(jit):
    """Bit for bit: a candidate's leaf distance and the point-box distance
    of its degenerate leaf box (lo == hi == the point)."""
    pts, q = _lidar(n_patches=4, per_patch=128, n_q=128, seed=21)
    c = jnp.asarray(pts.reshape(128, 4, 3))
    q = jnp.asarray(q)

    def box_dist(q, c):  # one candidate per call: no sort between slots
        return jnp.stack([point_box_test(q, Box(lo=jnp.broadcast_to(
            c[:, j:j + 1], c.shape), hi=jnp.broadcast_to(
            c[:, j:j + 1], c.shape))).dist_sq[:, 0] for j in range(4)], 1)

    leaf, box = ((jax.jit(leaf_dist_sq), jax.jit(box_dist)) if jit
                 else (leaf_dist_sq, box_dist))
    np.testing.assert_array_equal(
        np.asarray(leaf(q, c)).view(np.uint32),
        np.asarray(box(q, c)).view(np.uint32))


def test_enclosing_box_never_farther_than_its_point():
    """Every ancestor box's point-box distance is at most its point's leaf
    distance in f32, so the pruning bound needs no slack."""
    pts, q = _lidar(per_patch=64, n_q=32, seed=22)
    res = build_point_bvh(jnp.asarray(pts))
    bvh, depth = res.bvh, res.depth
    lt = np.asarray(bvh.leaf_tri)
    slots = np.flatnonzero(lt >= 0)
    q = jnp.asarray(q)
    d_pt = np.asarray(leaf_dist_sq(q, jnp.asarray(pts[lt[slots]])))
    node = level_offset(depth) + slots
    levels = 0
    while True:
        lo = jnp.broadcast_to(bvh.node_lo[node][None, :, None, :],
                              (q.shape[0], len(node), 4, 3))
        hi = jnp.broadcast_to(bvh.node_hi[node][None, :, None, :],
                              lo.shape)
        p = jnp.broadcast_to(q[:, None, :], (q.shape[0], len(node), 3))
        d_box = np.asarray(point_box_test(p, Box(lo=lo, hi=hi)).dist_sq)
        assert (d_box[..., 0] <= d_pt).all(), levels
        levels += 1
        if node[0] == 0:
            break
        node = (node - 1) // 4
    assert levels == depth + 1


def test_nearest_reports_the_tree_walks_counters():
    pts, q = _lidar(n_q=16, seed=23)
    engine = PointCloudScene.from_points(jnp.asarray(pts)).engine(
        pad_multiple=8, shard=1)
    q = jnp.asarray(q)
    near = engine.nearest(q, 16, backend="tree_wavefront")
    rec = engine.neighbor_search(q, 16, mode="nearest",
                                 backend="tree_wavefront")
    for f in ("box_jobs", "point_jobs", "rounds"):
        np.testing.assert_array_equal(np.asarray(getattr(near, f)),
                                      np.asarray(getattr(rec, f)),
                                      err_msg=f)
    assert int(near.rounds) == int(np.max(np.asarray(near.box_jobs)))
    brute = engine.nearest(q, 16, backend="mxu")
    assert (brute.box_jobs, brute.point_jobs, brute.rounds) == (None,) * 3


def test_refit_reroutes_results():
    pts = _pts(200, seed=12)
    cloud = PointCloudScene.from_points(pts)
    engine = cloud.engine(pad_multiple=8, shard=1)
    q = _pts(10, seed=13)
    before = np.asarray(engine.count_within(q, 0.6,
                                            backend="tree_wavefront"))
    cloud.refit(pts + 0.5)
    after = np.asarray(engine.count_within(q, 0.6,
                                           backend="tree_wavefront"))
    want = (np.asarray(engine.scores(q, "euclidean", backend="mxu"))
            <= 0.36).sum(1)
    np.testing.assert_array_equal(after, want)
    assert (before != after).any()


# ---------------------------------------------------------------------------
# the "auto" tree-vs-brute policy
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def big_cloud_engine():
    return PointCloudScene.from_points(_pts(5000, seed=14)).engine(
        pad_multiple=8, shard=1)


def test_auto_policy_routes(big_cloud_engine):
    eng = big_cloud_engine
    brute = eng.resolve_distance_backend()
    # selective queries on a big cloud: the tree wins
    assert eng.resolve_neighbor_backend("nearest", "euclidean",
                                        k=8).startswith("tree_")
    assert eng.resolve_neighbor_backend("within", "euclidean",
                                        radius=0.05).startswith("tree_")
    # unselective queries: the brute matmul wins
    assert eng.resolve_neighbor_backend("nearest", "euclidean",
                                        k=5000) == brute
    assert eng.resolve_neighbor_backend("within", "euclidean",
                                        radius=100.0) == brute
    # non-euclidean metrics never route through the tree
    assert eng.resolve_neighbor_backend("nearest", "cosine", k=8) == brute
    # a small cloud stays brute whatever the query
    small = PointCloudScene.from_points(_pts(64, seed=15)).engine()
    assert small.resolve_neighbor_backend("nearest", "euclidean",
                                          k=4) == brute
    # no cloud at all (plain VectorIndex): brute, and tree backends refuse
    flat = VectorIndex.from_database(_pts(64, seed=16)).engine()
    assert flat.resolve_neighbor_backend("nearest", "euclidean",
                                         k=4) == brute
    with pytest.raises(ValueError, match="PointCloudScene"):
        flat.nearest(_pts(2, seed=17), 4, backend="tree_wavefront")


def test_tree_backend_rejects_non_euclidean(big_cloud_engine):
    with pytest.raises(ValueError, match="euclidean"):
        big_cloud_engine.nearest(_pts(2, seed=18), 4, "cosine",
                                 backend="tree_wavefront")


def test_neighbor_search_reports_pruned_work(big_cloud_engine):
    q = _pts(16, seed=19)
    rec = big_cloud_engine.neighbor_search(q, 32, radius=0.2,
                                           backend="tree_wavefront")
    box_jobs = np.asarray(rec.box_jobs)
    point_jobs = np.asarray(rec.point_jobs)
    assert (box_jobs > 0).all() and int(rec.rounds) > 0
    # the point of the tree: far fewer distance jobs than brute's N per query
    assert point_jobs.mean() < 0.25 * 5000


# ---------------------------------------------------------------------------
# scale acceptance: 1e5-point cloud, shard=8 + chunking, both backends
# ---------------------------------------------------------------------------


def test_neighbor_scale_sharded_8dev(multidev):
    multidev("""
import numpy as np, jax, jax.numpy as jnp
assert jax.local_device_count() == 8
from repro.api import PointCloudScene

N, M = 100_000, 256
rng = np.random.default_rng(77)
pts = jnp.asarray(rng.normal(size=(N, 3)).astype(np.float32))
cloud = PointCloudScene.from_points(pts)
single = cloud.engine(pad_multiple=8, shard=1)
sharded = cloud.engine(pad_multiple=8, shard=8, chunk_size=64)
q = jnp.asarray(rng.normal(size=(M, 3)).astype(np.float32))
radius, k, knn_k = 0.12, 96, 16

oracle = np.asarray(single.scores(q, "euclidean", backend="mxu"))
inside = oracle <= radius * radius
assert 0 < inside.sum(1).max() < k  # k can hold every in-radius set

FIELDS = ("dist_sq", "index", "valid", "count", "box_jobs", "point_jobs")
brute = single.nearest(q, knn_k, backend="mxu")
for backend in ("tree_wavefront", "tree_pallas"):
    rec = sharded.neighbor_search(q, k, radius=radius, backend=backend)
    w, idx = np.asarray(rec.valid), np.asarray(rec.index)
    for i in range(M):
        assert set(idx[i][w[i]]) == set(np.flatnonzero(inside[i])), \\
            (backend, i)
    np.testing.assert_array_equal(np.asarray(rec.count), inside.sum(1),
                                  err_msg=backend)
    # the walk prunes: distance jobs per query are a sliver of brute's N
    assert float(np.asarray(rec.point_jobs).mean()) < 0.05 * N, backend
    # nearest: rank-equivalent vs the brute top-k
    near = sharded.nearest(q, knn_k, backend=backend)
    picked = np.take_along_axis(oracle, np.asarray(near.indices), 1)
    np.testing.assert_allclose(picked, np.asarray(brute.scores),
                               rtol=1e-4, atol=1e-5, err_msg=backend)
    # sharded + chunked == single-device, bit for bit, counters included
    solo = single.neighbor_search(q, k, radius=radius, backend=backend)
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(rec, f)),
                                      np.asarray(getattr(solo, f)),
                                      err_msg=f"{backend}: {f}")
print("neighbor scale acceptance OK")
""", n_devices=8)
