"""Vector search with the session query API, both ways (DESIGN.md §9):

* the **brute path** — a high-dimensional ``VectorIndex``, exact kNN
  under all three metrics through the MXU/Pallas distance backends;
* the **tree path** — a 3-D ``PointCloudScene`` whose BVH the neighbor
  queries *traverse* (RTNN mapping: AABB-per-point leaves, radius as ray
  extent), cross-checked against the brute oracle with the per-query
  traversal work it saved.

Plus the MoE-router connection (expert selection IS angular top-k).

Run:  PYTHONPATH=src python examples/knn_search.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import PointCloudScene, VectorIndex
from repro.runtime.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    rng = np.random.default_rng(0)
    n_db, n_q, dim = 8192, 64, 128
    # clustered database so neighbours are meaningful
    centers = rng.normal(size=(16, dim)).astype(np.float32) * 3
    assign = rng.integers(0, 16, n_db)
    db = (centers[assign] + rng.normal(size=(n_db, dim)).astype(np.float32))
    queries = (centers[rng.integers(0, 16, n_q)]
               + 0.5 * rng.normal(size=(n_q, dim)).astype(np.float32))
    dbj, qj = jnp.asarray(db), jnp.asarray(queries)

    # built once: the index owns the database and its ||c||^2 norms; the
    # engine owns the per-(shape, backend, metric) compiled-function cache.
    # shard="auto" data-parallels query rows over every local device
    # (database replicated, results bit-identical to one device)
    index = VectorIndex.from_database(dbj)
    engine = index.engine(shard="auto")
    print(f"devices: {jax.local_device_count()} "
          f"(shard='auto' data-parallels query batches across them)")

    for metric in ("euclidean", "angular", "cosine"):
        engine.nearest(qj, 8, metric)  # warm the compiled cache
        t0 = time.perf_counter()
        res = engine.nearest(qj, 8, metric)
        jax.block_until_ready(res.scores)
        dt = time.perf_counter() - t0
        # recall@8 vs numpy exact
        if metric == "euclidean":
            ref = ((queries[:, None] - db[None]) ** 2).sum(-1)
            ref_idx = np.argsort(ref, 1)[:, :8]
        else:
            sims = queries @ db.T
            if metric == "cosine":
                sims /= (np.linalg.norm(queries, axis=1)[:, None]
                         * np.linalg.norm(db, axis=1)[None])
            ref_idx = np.argsort(-sims, 1)[:, :8]
        recall = np.mean([len(set(a) & set(b)) / 8
                          for a, b in zip(np.asarray(res.indices), ref_idx)])
        print(f"{metric:10s} top-8: recall@8={recall:.3f}  "
              f"({n_q} queries x {n_db} db in {dt * 1e3:.1f} ms)")

    # radius query (RTNN-style range-limited search: the vector-search twin
    # of the traversal engine's extent-limited shadow rays)
    radius = 18.0  # ~ within-cluster distance at dim=128
    engine.within(qj, radius, 8)  # warm both compiled functions
    engine.count_within(qj, radius)
    t0 = time.perf_counter()
    res = engine.within(qj, radius, 8)
    counts = engine.count_within(qj, radius)
    jax.block_until_ready(counts)
    dt = time.perf_counter() - t0
    # sanity: the returned neighbours really are the nearest in-range ones
    d_near = np.asarray(res.scores)[np.asarray(res.within)]
    nearest = f"{d_near.min() ** 0.5:.1f}" if d_near.size else "n/a (none in range)"
    print(f"radius={radius}: avg {float(counts.mean()):.1f} db points in "
          f"range per query, {float(res.within.mean()):.2f} of top-8 slots "
          f"filled, nearest in-range dist {nearest} "
          f"(idx sample {np.asarray(res.indices)[0, :3].tolist()}) "
          f"in {dt * 1e3:.1f} ms")

    # streaming: the same batch through fixed-size microbatch chunks — the
    # peak intermediate is (chunk, n_db) instead of (n_q, n_db), and every
    # chunk re-enters one compiled function; results are bit-identical.
    # shard=1 pins the block to chunk_size (under shard="auto" the block
    # rounds up to a per-shard lane multiple, merging the chunks)
    chunked = index.engine(shard=1, chunk_size=16)
    res_c = chunked.nearest(qj, 8, "euclidean")
    res_u = engine.nearest(qj, 8, "euclidean")
    assert (np.asarray(res_c.indices) == np.asarray(res_u.indices)).all()
    print(f"chunk_size=16: {n_q} queries in {-(-n_q // 16)} chunks through "
          f"{chunked.cache_info().entries} compiled function(s), "
          f"indices identical to the one-shot batch")

    # pluggable backends: the same query through the Pallas kernel path
    # (tiled multi-beat accumulator) instead of the jnp MXU form
    d_k = engine.scores(qj, "euclidean", backend="pallas")
    ref = ((queries[:, None] - db[None]) ** 2).sum(-1)
    print(f"pallas euclidean backend max rel err: "
          f"{np.abs(np.asarray(d_k) - ref).max() / ref.max():.2e}")
    print(f"compiled-function cache: {engine.cache_info()}")

    # the tree path: a 3-D point cloud becomes a BVH of point-leaves and
    # neighbor queries run as extent-limited *traversals* (DESIGN.md §9).
    # backend="auto" picks tree-vs-brute per query; here we force both and
    # cross-check — membership is exact, and the record reports how much
    # of the brute path's N distance jobs the walk pruned away
    n_pts, n_cq, radius = 50_000, 256, 0.1
    pts = jnp.asarray(rng.normal(size=(n_pts, 3)).astype(np.float32))
    cq = jnp.asarray(rng.normal(size=(n_cq, 3)).astype(np.float32))
    cloud_engine = PointCloudScene.from_points(pts).engine()
    rec = cloud_engine.neighbor_search(cq, 32, radius=radius,
                                       backend="tree_wavefront")
    brute = cloud_engine.within(cq, radius, 32, backend="mxu")
    w_t, w_b = np.asarray(rec.valid), np.asarray(brute.within)
    assert all(set(np.asarray(rec.index)[i][w_t[i]])
               == set(np.asarray(brute.indices)[i][w_b[i]])
               for i in range(n_cq)), "tree vs brute in-radius set mismatch"
    jobs = float(np.asarray(rec.box_jobs).mean()
                 + np.asarray(rec.point_jobs).mean())
    auto = cloud_engine.resolve_neighbor_backend("within", "euclidean",
                                                 radius=radius)
    print(f"tree path: {n_pts} points, radius={radius}: avg "
          f"{float(np.asarray(rec.count).mean()):.1f} in range, "
          f"{jobs:.0f} traversal jobs/query vs {n_pts} brute "
          f"({jobs / n_pts * 100:.2f}%), sets identical to brute "
          f"(auto picks {auto!r} here)")

    # the MoE-router connection: expert selection IS angular-mode top-k
    # (router_scores builds a VectorIndex over the expert embeddings)
    from repro.models.moe import router_scores, router_topk
    from repro.models.config import MoEConfig
    m = MoEConfig(num_experts=16, top_k=2, d_ff_expert=1)
    scores = router_scores(m, qj, jnp.asarray(centers))
    w, experts, aux = router_topk(m, scores)
    top1 = np.asarray(experts)[:, 0]
    true_cluster = np.argmax(queries @ centers.T, axis=1)
    print(f"MoE router (= OpAngular top-k): top-1 expert == nearest "
          f"centroid for {np.mean(top1 == true_cluster) * 100:.0f}% of tokens")


if __name__ == "__main__":
    main()
