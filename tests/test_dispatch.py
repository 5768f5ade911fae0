"""Dispatch layer: plan math, chunk streaming, sharded execution parity.

The contract (DESIGN.md §6): an ``ExecPlan`` schedules a batch into
fixed-size blocks whose rows divide evenly over the mesh with per-shard
lane padding, every block re-enters one compiled function, and the whole
pad -> shard -> query -> unshard -> unpad pipeline is a bit-exact identity
against the single-device unchunked path (the acceptance criterion, pinned
here on a forced 8-device host mesh).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dispatch import (ceil_to, concat_rows, device_mesh,
                                 make_plan, pad_leading, resolve_shards,
                                 take_block)


# ---------------------------------------------------------------------------
# plan math (pure, single-process)
# ---------------------------------------------------------------------------


def test_plan_degenerates_to_single_padded_call():
    """shards=1, chunk_size=None == the original ceil_to(n, pad) behavior."""
    plan = make_plan(50, pad_multiple=8)
    assert (plan.block, plan.n_blocks, plan.shards) == (56, 1, 1)
    plan = make_plan(64, pad_multiple=8)
    assert (plan.block, plan.n_blocks) == (64, 1)
    assert plan.mesh is None


def test_plan_chunking():
    plan = make_plan(50, pad_multiple=8, chunk_size=16)
    assert (plan.block, plan.n_blocks) == (16, 4)  # 16+16+16+2pad6
    # chunk_size larger than the batch clamps to one block
    plan = make_plan(10, pad_multiple=8, chunk_size=1000)
    assert (plan.block, plan.n_blocks) == (16, 1)
    # chunk_size rounds up to the lane multiple
    plan = make_plan(100, pad_multiple=8, chunk_size=3)
    assert plan.block == 8


def test_plan_per_shard_lane_padding():
    """Each shard receives a lane multiple of rows: block = shards *
    ceil(rows_per_shard to pad_multiple)."""
    plan = make_plan(50, pad_multiple=8, shards=4)
    assert plan.block == 4 * ceil_to(-(-50 // 4), 8) == 64
    assert plan.n_blocks == 1
    plan = make_plan(50, pad_multiple=8, shards=4, chunk_size=16)
    assert plan.block == 4 * 8 == 32  # 4 rows/shard -> padded to 8
    assert plan.n_blocks == 2
    assert plan.key == (4, 32)


def test_plan_backend_lane_multiple():
    """A backend-declared tile width (the fused Pallas traversal's
    128-lane tiles) raises the per-shard multiple to
    ``max(pad_multiple, lane_multiple)`` so kernels always receive whole
    tiles — per shard, per chunk."""
    plan = make_plan(50, pad_multiple=8, lane_multiple=128)
    assert (plan.block, plan.n_blocks) == (128, 1)
    # composes with sharding: every shard gets a whole tile
    plan = make_plan(50, pad_multiple=8, shards=4, lane_multiple=128)
    assert (plan.block, plan.shards) == (4 * 128, 4)
    # composes with chunking: a sub-tile chunk_size still yields one tile
    plan = make_plan(300, pad_multiple=8, chunk_size=16, lane_multiple=128)
    assert (plan.block, plan.n_blocks) == (128, 3)
    # a pad_multiple above the tile width wins (max, not override)
    plan = make_plan(50, pad_multiple=256, lane_multiple=128)
    assert plan.block == 256
    # None = unchanged legacy behavior
    assert make_plan(50, pad_multiple=8, lane_multiple=None).block == 56


def test_plan_validation():
    with pytest.raises(ValueError, match="n >= 1"):
        make_plan(0, pad_multiple=8)
    with pytest.raises(ValueError, match="chunk_size"):
        make_plan(10, pad_multiple=8, chunk_size=0)


def test_resolve_shards():
    n_dev = jax.local_device_count()
    assert resolve_shards(None) == 1
    assert resolve_shards(1) == 1
    assert resolve_shards("auto") == n_dev
    assert resolve_shards("auto", n_rows=1) == 1  # capped at the batch
    assert resolve_shards(n_dev) == n_dev
    with pytest.raises(ValueError, match="exceeds"):
        resolve_shards(n_dev + 1)
    with pytest.raises(ValueError, match=">= 1"):
        resolve_shards(-2)


def test_split_concat_roundtrip_identity():
    """split -> pad -> concat -> slice is the identity on any row count."""
    rng = np.random.default_rng(0)
    for n, chunk in ((1, 4), (7, 4), (8, 4), (50, 16), (5, None)):
        tree = {"a": jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)),
                "b": jnp.arange(n, dtype=jnp.int32)}
        plan = make_plan(n, pad_multiple=4, chunk_size=chunk)
        blocks = [take_block(tree, plan, i) for i in range(plan.n_blocks)]
        assert len(blocks) == plan.n_blocks
        assert all(b["a"].shape[0] == plan.block for b in blocks)
        out = concat_rows(blocks, n)
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))
        np.testing.assert_array_equal(np.asarray(out["b"]),
                                      np.asarray(tree["b"]))


def test_pad_leading_empty_and_full():
    padded = pad_leading(jnp.zeros((0, 2)), 4)
    assert padded.shape == (4, 2)
    x = jnp.arange(6, dtype=jnp.float32)
    padded = pad_leading(x, 8)
    np.testing.assert_array_equal(np.asarray(padded[:6]), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(padded[6:]),
                                  np.zeros(2) + float(x[0]))


def test_device_mesh_is_cached():
    m1 = device_mesh(1)
    assert device_mesh(1) is m1


# ---------------------------------------------------------------------------
# the acceptance criterion: sharded + chunked == single-device unchunked,
# bit for bit, on a forced 8-device host mesh
# ---------------------------------------------------------------------------


def test_sharded_chunked_bitparity_8dev(multidev):
    multidev("""
import numpy as np, jax, jax.numpy as jnp
assert jax.local_device_count() == 8
from repro.api import Scene, VectorIndex, make_ray
from repro.core import Triangle

rng = np.random.default_rng(7)
n_tri, n_rays = 230, 50
ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
d1 = rng.normal(scale=0.15, size=(n_tri, 3)).astype(np.float32)
d2 = rng.normal(scale=0.15, size=(n_tri, 3)).astype(np.float32)
tri = Triangle(jnp.asarray(ctr), jnp.asarray(ctr + d1), jnp.asarray(ctr + d2))
scene = Scene.from_triangles(tri)
org = rng.uniform(-3, -2, (n_rays, 3)).astype(np.float32)
tgt = rng.uniform(-0.5, 0.5, (n_rays, 3)).astype(np.float32)
rays = make_ray(jnp.asarray(org), jnp.asarray(tgt - org))

single = scene.engine(pad_multiple=8, shard=1)
FIELDS = ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs")
for ray_type in ("closest", "any", "shadow"):
    ref = single.trace(rays, ray_type=ray_type, backend="wavefront")
    for shard, chunk in (("auto", None), (8, None), (8, 16), (4, 8), (2, None)):
        eng = scene.engine(pad_multiple=8, shard=shard, chunk_size=chunk)
        got = eng.trace(rays, ray_type=ray_type, backend="wavefront")
        for f in FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(ref, f)),
                err_msg=f"{ray_type} shard={shard} chunk={chunk} {f}")
        assert int(got.rounds) == int(ref.rounds), (ray_type, shard, chunk)
# per-ray oracle backend shards identically too
ref = single.trace(rays, backend="per_ray")
got = scene.engine(pad_multiple=8, shard=8, chunk_size=16).trace(
    rays, backend="per_ray")
for f in FIELDS:
    np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                  np.asarray(getattr(ref, f)), err_msg=f)
assert int(got.rounds) == int(ref.rounds)
print("trace sharded+chunked bit-parity OK")

q = jnp.asarray(rng.normal(size=(21, 24)).astype(np.float32))
db = jnp.asarray(rng.normal(size=(211, 24)).astype(np.float32))
index = VectorIndex.from_database(db)
s1 = index.engine(pad_multiple=8, shard=1)
for metric in ("euclidean", "angular", "cosine"):
    a = s1.nearest(q, 5, metric, backend="mxu")
    b = index.engine(pad_multiple=8, shard="auto", chunk_size=8).nearest(
        q, 5, metric, backend="mxu")
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
sharded = index.engine(pad_multiple=8, shard=8)
for a, b in zip(s1.within(q, 5.0, 12), sharded.within(q, 5.0, 12)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
np.testing.assert_array_equal(np.asarray(s1.count_within(q, 5.0)),
                              np.asarray(sharded.count_within(q, 5.0)))
np.testing.assert_array_equal(np.asarray(s1.scores(q)),
                              np.asarray(sharded.scores(q)))
# pallas backend: neighbour indices exact, scores to the documented caveat
a = s1.nearest(q, 5, "euclidean", backend="pallas")
b = sharded.nearest(q, 5, "euclidean", backend="pallas")
np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
np.testing.assert_allclose(np.asarray(a.scores), np.asarray(b.scores),
                           rtol=1e-6, atol=1e-4)
print("distance sharded+chunked bit-parity OK")
""", n_devices=8)


def test_sharded_chunk_cache_reuse_8dev(multidev):
    """All chunks of a sharded query re-enter ONE compiled function, and a
    repeat query retraces nothing."""
    multidev("""
import numpy as np, jax, jax.numpy as jnp
from repro.api import Scene, make_ray
from repro.core import Triangle
from repro.obs import CompileTracker
rng = np.random.default_rng(3)
ctr = rng.uniform(-1, 1, (100, 3)).astype(np.float32)
tri = Triangle(jnp.asarray(ctr),
               jnp.asarray(ctr + rng.normal(scale=0.1, size=(100, 3)).astype(np.float32)),
               jnp.asarray(ctr + rng.normal(scale=0.1, size=(100, 3)).astype(np.float32)))
scene = Scene.from_triangles(tri)
org = rng.uniform(-3, -2, (120, 3)).astype(np.float32)
tgt = rng.uniform(-0.5, 0.5, (120, 3)).astype(np.float32)
rays = make_ray(jnp.asarray(org), jnp.asarray(tgt - org))
engine = scene.engine(pad_multiple=8, shard=8, chunk_size=40)
engine.trace(rays)
assert engine.cache_info() == (0, 1, 1), engine.cache_info()
with CompileTracker() as tracker:
    engine.trace(rays)
assert tracker.compiles == 0, "sharded chunked re-query retraced"
assert engine.cache_info().hits == 1
print("sharded chunk cache reuse OK")
""", n_devices=8)


# ---------------------------------------------------------------------------
# eager count validation + the serving batch-slice contract
# ---------------------------------------------------------------------------

def test_check_count_rejects_bad_values():
    from repro.core.dispatch import check_count
    assert check_count("chunk_size", None) is None
    assert check_count("chunk_size", 7) == 7
    assert check_count("shard", np.int64(3)) == 3  # any Integral is fine
    for bad in (0, -1, 2.5, True, "4"):
        with pytest.raises(ValueError, match="chunk_size"):
            check_count("chunk_size", bad)


def test_plan_strict_count_types():
    """Counts must be real integers — no silent float truncation, and no
    bool-as-int (shard=True used to mean shard=1)."""
    with pytest.raises(ValueError, match="chunk_size"):
        make_plan(10, pad_multiple=8, chunk_size=2.5)
    with pytest.raises(ValueError, match="shard"):
        resolve_shards(True)
    with pytest.raises(ValueError, match="shard"):
        resolve_shards(1.0)


def test_engine_validates_counts_eagerly():
    """Bad chunk_size/shard raise at call (or construction) time — even
    for an empty batch, long before any compile or dispatch."""
    from repro.api import Scene, make_ray
    rng = np.random.default_rng(0)
    ctr = rng.uniform(-1, 1, (4, 3)).astype(np.float32)
    tris = np.stack([ctr, ctr + 0.1, ctr + np.float32([0.1, 0, 0.1])], 1)
    scene = Scene.from_triangles(tris)
    with pytest.raises(ValueError, match="chunk_size"):
        scene.engine(chunk_size=0)
    with pytest.raises(ValueError, match="shard"):
        scene.engine(shard=-2)
    with pytest.raises(ValueError, match="shard"):
        scene.engine(shard=True)
    engine = scene.engine(pad_multiple=8)
    rays0 = make_ray(jnp.zeros((0, 3)), jnp.ones((0, 3)))
    for bad in (0, -3, 2.5, True):
        with pytest.raises(ValueError, match="chunk_size"):
            engine.trace(rays0, chunk_size=bad)  # n=0: still validated


def test_slice_rows_splits_and_unpads():
    from repro.core.dispatch import slice_rows
    tree = {"a": jnp.arange(12), "b": jnp.arange(24).reshape(12, 2)}
    parts = slice_rows(tree, [3, 0, 5])  # 8 real rows + 4 pad rows
    assert [int(p["a"].shape[0]) for p in parts] == [3, 0, 5]
    np.testing.assert_array_equal(np.asarray(parts[0]["a"]), [0, 1, 2])
    np.testing.assert_array_equal(np.asarray(parts[2]["a"]),
                                  [3, 4, 5, 6, 7])  # pad rows 8..11 dropped
    np.testing.assert_array_equal(np.asarray(parts[2]["b"]),
                                  np.arange(24).reshape(12, 2)[3:8])
    with pytest.raises(ValueError, match=">= 0"):
        slice_rows(tree, [2, -1])


def test_engine_plan_introspection():
    """plan_for/batch_multiple expose the planner the serving layer sizes
    batches with; the plan must match what a real call would use."""
    from repro.api import Scene
    rng = np.random.default_rng(1)
    ctr = rng.uniform(-1, 1, (4, 3)).astype(np.float32)
    tris = np.stack([ctr, ctr + 0.1, ctr + np.float32([0.1, 0, 0.1])], 1)
    engine = Scene.from_triangles(tris).engine(pad_multiple=8, shard=1)
    m = engine.batch_multiple("trace")
    assert m >= 8 and m % 8 == 0
    plan = engine.plan_for("trace", 10)
    assert plan.n == 10 and plan.block * plan.n_blocks >= 10
    assert (plan.block * plan.n_blocks) % m == 0
    # pallas trace pads to its lane width
    lanes = engine.batch_multiple("trace", "pallas")
    assert lanes % 128 == 0
    with pytest.raises(ValueError, match="n >= 1"):
        engine.plan_for("trace", 0)
    with pytest.raises(ValueError, match="method"):
        engine.plan_for("warp", 4)
