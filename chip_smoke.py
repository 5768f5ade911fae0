#!/usr/bin/env python3
"""Chip smoke run: the query engine's main path, once, at deployment sizes.

Drives the public API (``repro.api`` + ``repro.serving.QueryServer``) in
one process on a TPU and checks every phase against the repo's own
oracles:

  b. render frame   256x144 primary closest-hit rays + one shadow ray per
                    pixel over a 2^20-triangle clustered scene (lbvh, BVH4
                    depth 10); a 4096-ray sample bit-equal to the per-ray
                    oracle (``core/traversal.py``) in t, tri_index and hit
  c. vector search  4096 euclidean k=10 queries over a 10^6 x 128 f32
                    Gaussian mixture (SIFT-1M shape); 64 queries against the
                    brute ``core/knn.py`` oracle
  d. neighbours     8192 kNN (k=16) queries and one count_within radius at
                    ~0.1% selectivity over 10^6 clustered 3-D points; 256
                    queries against the brute path
  e. serving        a QueryServer over the scene and index answers 64 mixed
                    trace/nearest requests of 1-4096 rows, each equal to a
                    direct engine call; an identical second pass compiles
                    nothing (``obs.CompileTracker``)

Every phase prints the backend ``auto`` chose, its row counts, its
``chunk_size``, and two host-clock readings: ``first_s`` (the first call,
compilation included) and ``steady_s`` (a second call, ending in
``block_until_ready``).  These are smoke readings, not benchmark numbers.
The last line of standard output is one JSON object naming the device.

Two sizes are cut below their deployments, both because the wavefront
engines are gather-bound on a v5e (about 0.24 ms per ray for a depth-10
trace, so a 1920x1080 frame would take about 9 minutes per trace): the
frame is 256x144 rays instead of 1920x1080, and the cloud takes 8192
queries instead of 65,536.  The scene, the index and the cloud keep their
deployment sizes.

No phase catches an exception: any mismatch or error exits non-zero.  With
no TPU (e.g. ``JAX_PLATFORMS=cpu``) the script exits non-zero before any
phase and prints no result.

Run from the repo root:

    python chip_smoke.py              # one chip, phases b-e
    python chip_smoke.py --chips 4    # four chips: the frame trace and the
                                      # nearest query, shard="auto" vs
                                      # shard=1, bit-identical

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.api import (PointCloudScene, QueryEngine, Scene,  # noqa: E402
                       VectorIndex, make_ray)
from repro.core.build.quality import clustered_soup  # noqa: E402
from repro.core.knn import knn, pairwise_scores  # noqa: E402
from repro.core.neighbor import leaf_dist_sq  # noqa: E402
from repro.core.traversal import trace_rays  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.serving import QueryServer  # noqa: E402

#: per-ray fields the per-ray oracle pins (the closest-hit contract)
ORACLE_FIELDS = ("t", "tri_index", "hit")


def report(phase: str, **fields) -> None:
    print(phase + ": " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def timed(fn, arrays=lambda out: out):
    """(result, seconds) of one call, on the host clock, ending in
    ``block_until_ready`` on ``arrays(result)`` so the device work is
    inside the window."""
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(arrays(out))
    return out, time.perf_counter() - t0


def first_and_steady(fn):
    """Call ``fn`` twice: the first reading includes compilation."""
    out, first = timed(fn)
    out2, steady = timed(fn)
    return out2, first, steady


def assert_tree_equal(a, b, what: str) -> None:
    """Bit-equality of every leaf of two result records."""
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what}: {name}")


def check_ranks(oracle, got_scores, got_idx, ref_scores, ref_idx, band):
    """Neighbour ids equal to the oracle's, except where two ids tie inside
    ``band`` (per row) under the oracle's own scores; scores equal to the
    oracle's top-k within the band.  Returns (max |score diff|, swaps)."""
    got_scores, got_idx = np.asarray(got_scores), np.asarray(got_idx)
    ref_scores, ref_idx = np.asarray(ref_scores), np.asarray(ref_idx)
    band = np.asarray(band, np.float64).reshape(-1, 1)
    diff = np.abs(got_scores.astype(np.float64) - ref_scores)
    assert (diff <= band).all(), (
        f"scores off the oracle by up to {diff.max()} (band {band.min()})")
    swapped = got_idx != ref_idx
    picked = np.take_along_axis(np.asarray(oracle), got_idx, axis=1)
    tie = np.abs(picked.astype(np.float64) - ref_scores) <= band
    assert (tie | ~swapped).all(), (
        f"{int((swapped & ~tie).sum())} neighbour ids differ outside the "
        "tie band")
    return float(diff.max()), int(swapped.sum())


# ---------------------------------------------------------------------------
# data, made from the seed
# ---------------------------------------------------------------------------


def frame_rays(width: int, height: int):
    """Pinhole camera on -z whose frame spans the scene volume ([-4, 4]^3)
    at its centre depth."""
    tan = 0.2  # vertical half-angle tangent; horizontal follows the aspect
    ys, xs = jnp.meshgrid(jnp.linspace(tan, -tan, height),
                          jnp.linspace(-tan * width / height,
                                       tan * width / height, width),
                          indexing="ij")
    n = width * height
    dirs = jnp.stack([xs.ravel(), ys.ravel(), jnp.ones((n,))], axis=1)
    org = jnp.broadcast_to(jnp.asarray([0.0, 0.0, -14.0]), (n, 3))
    return make_ray(org, dirs)


def shadow_rays(primary, rec, light=(6.0, 20.0, -10.0)):
    """One extent-limited ray per pixel from the primary hit (the eye on a
    miss) to a point light: the hit is occluded iff t in (t_min, 1]."""
    t = jnp.where(rec.hit, rec.t, 0.0)[:, None]
    org = primary.origin + t * primary.direction
    return make_ray(org, jnp.asarray(light, jnp.float32) - org, extent=1.0)


@functools.partial(jax.jit, static_argnums=1)
def mixture(key, n: int, centers, sigma):
    """``n`` Gaussian-mixture rows of spread ``sigma`` around ``centers``
    (components drawn uniformly), made on the device."""
    k1, k2 = jax.random.split(key)
    pick = jax.random.randint(k1, (n,), 0, centers.shape[0])
    return centers[pick] + sigma * jax.random.normal(
        k2, (n, centers.shape[1]))


def frame_scene(seed: int, n_clusters: int, per_cluster: int) -> Scene:
    """The clustered procedural scene, built with the default builder."""
    tri = clustered_soup(np.random.default_rng(seed), n_clusters,
                         per_cluster)
    return Scene.from_triangles(tri)


def vector_data(seed: int, n_db: int, dim: int, n_queries: int):
    """SIFT-shaped Gaussian mixture: (database, queries) from the seed."""
    kc, kd, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    centers = jax.random.normal(kc, (1024, dim))
    return (mixture(kd, n_db, centers, 0.3),
            mixture(kq, n_queries, centers, 0.3))


# ---------------------------------------------------------------------------
# phases (sizes are arguments; main() alone holds the deployment sizes)
# ---------------------------------------------------------------------------


def phase_frame(seed: int, n_clusters: int, per_cluster: int, width: int,
                height: int, n_check: int):
    """b. Render frame: build, primary + shadow traces, oracle sample."""
    scene, build_s = timed(
        lambda: frame_scene(seed, n_clusters, per_cluster), lambda s: s.bvh)
    report("frame.build", builder=scene.builder,
           triangles=scene.num_triangles, depth=scene.depth,
           build_s=build_s)
    rays = frame_rays(width, height)
    n = width * height
    engine = scene.engine()
    plan = engine.plan_for("trace", n)
    backend = engine.resolve_trace_backend("closest", n, shards=plan.shards)
    rec, first, steady = first_and_steady(lambda: engine.trace(rays))
    hit_frac = float(jnp.mean(rec.hit))
    assert 0.0 < hit_frac < 1.0, f"degenerate frame: hit fraction {hit_frac}"
    assert bool(jnp.all(jnp.isfinite(jnp.where(rec.hit, rec.t, 0.0))))
    report("frame.primary", backend=backend, rows=n,
           image=f"{width}x{height}", chunk_size=engine.default_chunk_size,
           shards=plan.shards, first_s=first, steady_s=steady,
           hit_fraction=hit_frac,
           rounds=int(rec.rounds),
           mean_quadbox_jobs=float(jnp.mean(rec.quadbox_jobs)))

    shadow = shadow_rays(rays, rec)
    s_backend = engine.resolve_trace_backend("shadow", n, shards=plan.shards)
    srec, s_first, s_steady = first_and_steady(
        lambda: engine.trace(shadow, ray_type="shadow"))
    report("frame.shadow", backend=s_backend, rows=n,
           chunk_size=engine.default_chunk_size,
           first_s=s_first, steady_s=s_steady,
           occluded_fraction=float(jnp.mean(srec.hit)),
           rounds=int(srec.rounds))

    rng = np.random.default_rng(seed)
    pick = np.sort(rng.choice(n, size=min(n_check, n), replace=False))
    sample = jax.tree_util.tree_map(lambda x: x[pick], rays)
    oracle = jax.jit(trace_rays, static_argnums=(2, 3))(
        scene.bvh, sample, scene.depth, scene.config)
    for f in ORACLE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(rec, f))[pick],
                                      np.asarray(getattr(oracle, f)),
                                      err_msg=f"frame vs per-ray oracle: {f}")
    report("frame.check", oracle="core/traversal.trace_rays",
           rows=len(pick), fields="+".join(ORACLE_FIELDS), result="bit-equal")
    return scene, rays


def phase_vector(seed: int, n_db: int, dim: int, n_queries: int, k: int,
                 chunk_size: int, n_check: int):
    """c. Vector search: SIFT-shaped Gaussian mixture, euclidean k-NN."""
    (db, queries), gen_s = timed(
        lambda: vector_data(seed, n_db, dim, n_queries))
    index = VectorIndex.from_database(db)
    engine = index.engine(chunk_size=chunk_size)
    backend = engine.resolve_distance_backend()
    plan = engine.plan_for("nearest", n_queries, k=k)
    res, first, steady = first_and_steady(
        lambda: engine.nearest(queries, k))
    report("vector.nearest", backend=backend, rows=n_queries,
           database=f"{n_db}x{dim}", k=k, chunk_size=chunk_size,
           blocks=plan.n_blocks, shards=plan.shards, gen_s=gen_s,
           first_s=first, steady_s=steady)

    q = queries[:n_check]
    ref_scores, ref_idx = knn(q, db, k, "euclidean")
    oracle = pairwise_scores(q, db, "euclidean")
    # rounding band of the expanded q^2 - 2qc + c^2 form summed in another
    # order: 2 sqrt(D) ulps of the operand magnitude
    scale = (jnp.sum(q * q, axis=1) + jnp.max(index.sq_norms))
    band = 2.0 * math.sqrt(dim) * np.finfo(np.float32).eps * np.asarray(scale)
    max_diff, swaps = check_ranks(oracle, res.scores[:n_check],
                                  res.indices[:n_check], ref_scores, ref_idx,
                                  band)
    report("vector.check", oracle="core/knn.knn", rows=n_check,
           max_abs_score_diff=max_diff, tie_swaps=swaps,
           band=float(band.max()), result="ids equal outside ties")
    return index, queries


def phase_cloud(seed: int, n_points: int, n_queries: int, k: int,
                selectivity: float, n_check: int):
    """d. RTNN-style neighbour search over a clustered 3-D cloud: 4096
    clusters of spread 0.01 in the unit cube, queries drawn alike."""
    kc, kp, kq = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    centers = jax.random.uniform(kc, (4096, 3))
    sigma = 0.01
    points = mixture(kp, n_points, centers, sigma)
    cloud, build_s = timed(lambda: PointCloudScene.from_points(points),
                           lambda c: c.bvh)
    report("cloud.build", builder=cloud.builder, points=n_points,
           depth=cloud.depth, build_s=build_s)
    queries = mixture(kq, n_queries, centers, sigma)
    engine = cloud.engine()
    backend = engine.resolve_neighbor_backend("nearest", "euclidean", k=k)
    near, first, steady = first_and_steady(lambda: engine.nearest(queries, k))
    report("cloud.nearest", backend=backend, rows=n_queries, k=k,
           chunk_size=None, first_s=first, steady_s=steady)

    radius = (3.0 * selectivity * cloud.root_volume()
              / (4.0 * math.pi)) ** (1.0 / 3.0)
    r_backend = engine.resolve_neighbor_backend("count_within", "euclidean",
                                                radius=radius)
    counts, r_first, r_steady = first_and_steady(
        lambda: engine.count_within(queries, radius))
    report("cloud.count_within", backend=r_backend, rows=n_queries,
           radius=radius, selectivity=selectivity, chunk_size=None,
           first_s=r_first, steady_s=r_steady,
           mean_count=float(jnp.mean(counts)), max_count=int(jnp.max(counts)))

    q = queries[:n_check]
    brute = cloud.index
    r_sq = radius * radius
    # Two brute references over every point, no tree.  The leaf test's own
    # difference form (core/neighbor.leaf_dist_sq): the traversal must
    # match it exactly.  The core/knn matmul oracle scores in the expanded
    # form, which rounds by up to ~eps * (|q|^2 + |c|^2), so the two may
    # disagree on points inside that band of the radius (DESIGN.md §9):
    # counts must lie inside that band of it.
    leaf = np.asarray(jax.jit(jax.vmap(
        lambda p: leaf_dist_sq(p, brute.database)))(q))
    oracle = np.asarray(pairwise_scores(q, brute.database, "euclidean",
                                        c_sq_norms=brute.sq_norms))
    band = 4.0 * np.finfo(np.float32).eps * np.asarray(
        jnp.sum(q * q, axis=1) + jnp.max(brute.sq_norms))
    got = np.asarray(counts[:n_check])
    np.testing.assert_array_equal(got, (leaf <= r_sq).sum(1),
                                  err_msg="count_within vs brute leaf form")
    must = (oracle <= r_sq - band[:, None]).sum(1)
    may = (oracle <= r_sq + band[:, None]).sum(1)
    assert ((must <= got) & (got <= may)).all(), (
        "count_within off the core/knn oracle outside the rounding band")
    boundary = int((got != (oracle <= r_sq).sum(1)).sum())

    within = engine.within(q, radius, k)
    w_in = np.arange(k)[None, :] < np.minimum(got, k)[:, None]
    np.testing.assert_array_equal(np.asarray(within.within), w_in,
                                  err_msg="within mask vs brute leaf form")
    ref_idx = np.argsort(leaf, axis=1, kind="stable")[:, :k]
    ref_scores = np.take_along_axis(leaf, ref_idx, axis=1)
    check_ranks(leaf, np.where(w_in, within.scores, ref_scores),
                np.where(w_in, within.indices, ref_idx), ref_scores,
                ref_idx, band)
    # nearest: the leaf form's own top-k, to the ulp that two programs may
    # round a sum differently in (ids may swap only between such ties)
    check_ranks(leaf, near.scores[:n_check], near.indices[:n_check],
                ref_scores, ref_idx, 2.0 * np.spacing(ref_scores[:, -1]))
    ref_scores, ref_idx = brute.knn(q, k)
    max_diff, swaps = check_ranks(oracle, near.scores[:n_check],
                                  near.indices[:n_check], ref_scores,
                                  ref_idx, band)
    report("cloud.check", oracle="brute leaf form + core/knn", rows=n_check,
           counts="exact vs leaf form", within_masks="exact vs leaf form",
           knn="leaf form to 2 ulp",
           counts_off_knn_at_boundary=boundary,
           knn_max_abs_score_diff=max_diff, knn_tie_swaps=swaps,
           result="pass")


def serving_requests(seed: int, rays, queries, n_requests: int,
                     max_rows: int, k: int):
    """Alternating trace / nearest requests, rows log-uniform in
    [1, max_rows], sliced from the frame's rays and the vector queries."""
    rng = np.random.default_rng(seed + 2)
    out = []
    for i in range(n_requests):
        n = int(round(math.exp(rng.uniform(0.0, math.log(max_rows)))))
        if i % 2 == 0:
            lo = int(rng.integers(0, rays.origin.shape[0] - n + 1))
            out.append(("trace", jax.tree_util.tree_map(
                lambda x, lo=lo, n=n: x[lo:lo + n], rays), {}))
        else:
            lo = int(rng.integers(0, queries.shape[0] - n + 1))
            out.append(("nearest", queries[lo:lo + n], {"k": k}))
    return out


async def _serve_pass(server, requests):
    """Fire every request at once (they enqueue in order within one event
    loop step, so a repeated pass coalesces into the same batches)."""
    tasks = [asyncio.ensure_future(getattr(server, method)(payload, **kw))
             for method, payload, kw in requests]
    return await asyncio.gather(*tasks)


async def _serve(engine, requests, max_rows):
    async with QueryServer(engine, max_batch_rows=max_rows,
                           max_wait=5e-3) as server:
        t0 = time.perf_counter()
        first = jax.block_until_ready(await _serve_pass(server, requests))
        first_s = time.perf_counter() - t0
        with obs.CompileTracker() as tracker:
            t0 = time.perf_counter()
            second = jax.block_until_ready(
                await _serve_pass(server, requests))
            steady_s = time.perf_counter() - t0
        stats = server.stats()
    return first, second, first_s, steady_s, tracker, stats


def phase_serving(seed: int, scene, index, rays, queries, n_requests: int,
                  max_rows: int, k: int, chunk_size: int):
    """e. Serving: coalesced responses == direct calls; warm pass compiles
    nothing."""
    engine = QueryEngine(scene=scene, index=index, chunk_size=chunk_size)
    requests = serving_requests(seed, rays, queries, n_requests, max_rows, k)
    first, second, first_s, steady_s, tracker, stats = asyncio.run(
        _serve(engine, requests, max_rows))
    for (method, payload, kw), got, again in zip(requests, first, second):
        ref = getattr(engine, method)(payload, **kw)
        assert_tree_equal(got, ref, f"served {method} vs direct call")
        assert_tree_equal(again, got, f"second pass {method}")
    assert tracker.available, "CompileTracker cannot count compiles"
    assert tracker.compiles == 0, (
        f"{tracker.compiles} compiles in the second, identical serving pass")
    rows = sum(int(jax.tree_util.tree_leaves(p)[0].shape[0])
               for _, p, _ in requests)
    report("serving", backends=(
               f"trace:{engine.resolve_trace_backend('closest', max_rows)},"
               f"nearest:{engine.resolve_distance_backend()}"),
           requests=len(requests), rows=rows, chunk_size=chunk_size,
           batches=sum(s.batches for s in stats.values()),
           first_s=first_s, steady_s=steady_s,
           second_pass_compiles=tracker.compiles,
           result="responses == direct calls")


def phase_sharded(seed: int, frame, vector) -> None:
    """Four chips: the frame trace and the nearest query with
    ``shard="auto"`` against ``shard=1`` in one process, every field
    bit-identical (job counters and rounds included)."""
    n_clusters, per_cluster, width, height = frame
    scene = frame_scene(seed, n_clusters, per_cluster)
    rays = frame_rays(width, height)
    n_db, dim, n_queries, k, chunk_size = vector
    db, queries = vector_data(seed, n_db, dim, n_queries)
    index = VectorIndex.from_database(db)

    cases = (
        ("frame.trace", rays.origin.shape[0],
         lambda shard: scene.engine(shard=shard), "trace",
         lambda e: e.trace(rays)),
        ("vector.nearest", n_queries,
         lambda shard: index.engine(shard=shard, chunk_size=chunk_size),
         "nearest", lambda e: e.nearest(queries, k)),
    )
    for name, n, make, method, call in cases:
        out = {}
        for shard in ("auto", 1):
            engine = make(shard)
            plan = engine.plan_for(method, n, k=k)
            out[shard], first, steady = first_and_steady(
                lambda engine=engine: call(engine))
            report(f"sharded.{name}", shard=shard, shards=plan.shards,
                   rows=n, chunk_size=engine.default_chunk_size,
                   first_s=first, steady_s=steady,
                   sharding=jax.tree_util.tree_leaves(out[shard])[0].sharding)
        assert_tree_equal(out["auto"], out[1], f"{name} shard=auto vs 1")
        report(f"sharded.{name}.check", result="bit-identical")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-vs-single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but jax sees {len(devices)} device(s)",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    report("device", kind=devices[0].device_kind, count=len(devices),
           jax=jax.__version__, compile_cache=cache)

    t0 = time.perf_counter()
    # 2^20 triangles in 1024 clusters (~60% of pixels see geometry); the
    # frame is cut to 256x144 rays (module docstring)
    frame = (1024, 1024, 256, 144)
    vector = (10**6, 128, 4096, 10, 256)  # SIFT-1M shape, k=10, chunk 256
    if args.chips == 4:
        phase_sharded(args.seed, frame, vector)
    else:
        scene, rays = phase_frame(args.seed, *frame, n_check=4096)
        index, queries = phase_vector(args.seed, *vector, n_check=64)
        phase_cloud(args.seed, 10**6, 8192, 16, 1e-3, n_check=256)
        phase_serving(args.seed, scene, index, rays, queries,
                      n_requests=64, max_rows=4096, k=10, chunk_size=256)
    report("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
