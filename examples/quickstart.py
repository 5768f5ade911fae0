"""Quickstart: the four datapath operations, exactly as the paper's IO spec.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.api import Scene, VectorIndex, trace_backends
from repro.core import (OP_ANGULAR, OP_EUCLIDEAN, OP_QUADBOX, OP_TRIANGLE,
                        Box, Triangle, make_ray, unified_stream)
from repro.core.stream import make_jobs
from repro.runtime.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    print("== OpQuadbox: one ray vs four AABBs ==")
    jobs = make_jobs(4)
    ray = make_ray(jnp.asarray([[-2.0, 0.5, 0.5]] * 4),
                   jnp.asarray([[1.0, 0.0, 0.0]] * 4))
    # four boxes at staggered distances; the datapath sorts hits near-to-far
    lo = jnp.asarray([[[1 + i, 0, 0] for i in (2, 0, 3, 1)]] * 4, jnp.float32)
    hi = lo + 0.8
    jobs = jobs._replace(opcode=jnp.full((4,), OP_QUADBOX, jnp.int32),
                         ray=ray, boxes=Box(lo, hi))
    _, out = unified_stream(jobs)
    print("  sorted tmin   :", np.asarray(out.tmin[0]))
    print("  box indices   :", np.asarray(out.box_index[0]))
    print("  is_intersect  :", np.asarray(out.is_intersect[0]))

    print("== OpTriangle: watertight Woop test ==")
    tri = Triangle(a=jnp.asarray([[0., 0., 1.]] * 4),
                   b=jnp.asarray([[0., 1., 1.]] * 4),
                   c=jnp.asarray([[1., 0., 1.]] * 4))
    ray = make_ray(jnp.asarray([[0.2, 0.2, 0.]] * 4),
                   jnp.asarray([[0., 0., 1.]] * 4))
    jobs = jobs._replace(opcode=jnp.full((4,), OP_TRIANGLE, jnp.int32),
                         ray=ray, triangle=tri)
    _, out = unified_stream(jobs)
    t = out.t_num[0] / out.t_denom[0]  # the division is external (paper!)
    print(f"  hit={bool(out.triangle_hit[0])}  t={float(t):.3f} "
          f"(t_num/t_denom = external division)")

    print("== OpEuclidean: multi-beat accumulation (32-dim vector) ==")
    rng = np.random.default_rng(0)
    a = rng.normal(size=32).astype(np.float32)
    b = rng.normal(size=32).astype(np.float32)
    jobs = make_jobs(2)
    jobs = jobs._replace(
        opcode=jnp.full((2,), OP_EUCLIDEAN, jnp.int32),
        vec_a=jnp.asarray([a[:16], a[16:]]), vec_b=jnp.asarray([b[:16], b[16:]]),
        reset_accum=jnp.asarray([True, False]))
    _, out = unified_stream(jobs)
    print(f"  datapath ||a-b||^2 = {float(out.euclidean_accumulator[1]):.4f} "
          f"(numpy: {((a - b) ** 2).sum():.4f})")

    print("== OpAngular -> cosine similarity (external sqrt+divide) ==")
    # session API: the candidate set is indexed once (||c||^2 precomputed),
    # then every query flows through one jit-cached engine
    q = rng.normal(size=(3, 24)).astype(np.float32)
    c = rng.normal(size=(5, 24)).astype(np.float32)
    engine = VectorIndex.from_database(jnp.asarray(c)).engine()
    sims = engine.similarity(jnp.asarray(q))
    print("  cosine matrix:\n", np.asarray(sims).round(3))
    res = engine.nearest(jnp.asarray(q), k=2, metric="cosine")
    print("  top-2 neighbours per query:", np.asarray(res.indices).tolist())

    print("== Traversal backends: one scene, bit-identical engines ==")
    # a tetrahedron traced by every registered backend — the wavefront
    # batch loop and the fused Pallas kernel (loop on-chip, DESIGN.md §8)
    # return the same hits AND the same per-ray datapath job counters
    v = np.asarray([[1, 1, 1], [1, -1, -1], [-1, 1, -1], [-1, -1, 1]],
                   np.float32)
    faces = [(0, 1, 2), (0, 3, 1), (0, 2, 3), (1, 3, 2)]
    verts = np.stack([np.stack([v[a], v[b], v[c]]) for a, b, c in faces])
    scene = Scene.from_triangles(verts)
    tracer = scene.engine(shard=1)
    org = np.asarray([[-3.0, 0.1 * i, 0.05 * i] for i in range(4)],
                     np.float32)
    rays = make_ray(jnp.asarray(org), jnp.asarray(-org))
    print("  registered:", trace_backends())
    for backend in ("wavefront", "pallas"):
        rec = tracer.trace(rays, backend=backend)
        print(f"  {backend:9s} t={np.asarray(rec.t).round(3)} "
              f"quadbox_jobs={np.asarray(rec.quadbox_jobs).tolist()}")


if __name__ == "__main__":
    main()
