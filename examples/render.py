"""Render a triangle-mesh sphere over a ground plane with the session query
API and write a PGM image.

The scene is prepared once (``Scene.from_triangles`` owns the BVH4 and its
depth); every query goes through one ``QueryEngine``: primary rays are
closest-hit traces, hard shadows are extent-limited ``"shadow"`` traces
toward a point light — the sphere casts a shadow onto the plane.

The engine is built with ``shard="auto"`` (data-parallel rays across every
local device — replicated scene, bit-identical image) and a ``chunk_size``
so the whole framebuffer streams through fixed-size microbatches of rays
sharing one compiled trace.

``--trace-backend`` selects the traversal engine (``auto`` | ``per_ray``
| ``wavefront`` | ``pallas``); every backend renders the identical image
(the bit-parity contract), so the flag is pure scheduling — ``pallas``
runs the fused kernel that keeps the traversal loop on-chip (DESIGN.md
§8; interpret mode off-TPU).

Run:  PYTHONPATH=src python examples/render.py [out.pgm]
      PYTHONPATH=src python examples/render.py --trace-backend pallas
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
          PYTHONPATH=src python examples/render.py  # same image, 8-way
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Scene, Triangle, make_ray, trace_backends
from repro.core.session import trace_backend_ray_types
from repro.runtime.compile_cache import use_compile_cache


def icosphere(subdiv=3):
    """Geodesic sphere triangles via icosahedron subdivision."""
    phi = (1 + 5 ** 0.5) / 2
    verts = np.asarray([
        [-1, phi, 0], [1, phi, 0], [-1, -phi, 0], [1, -phi, 0],
        [0, -1, phi], [0, 1, phi], [0, -1, -phi], [0, 1, -phi],
        [phi, 0, -1], [phi, 0, 1], [-phi, 0, -1], [-phi, 0, 1]],
        np.float64)
    verts /= np.linalg.norm(verts, axis=1, keepdims=True)
    faces = [(0, 11, 5), (0, 5, 1), (0, 1, 7), (0, 7, 10), (0, 10, 11),
             (1, 5, 9), (5, 11, 4), (11, 10, 2), (10, 7, 6), (7, 1, 8),
             (3, 9, 4), (3, 4, 2), (3, 2, 6), (3, 6, 8), (3, 8, 9),
             (4, 9, 5), (2, 4, 11), (6, 2, 10), (8, 6, 7), (9, 8, 1)]
    tris = [tuple(verts[i] for i in f) for f in faces]
    for _ in range(subdiv):
        out = []
        for a, b, c in tris:
            ab, bc, ca = (a + b) / 2, (b + c) / 2, (c + a) / 2
            ab, bc, ca = (v / np.linalg.norm(v) for v in (ab, bc, ca))
            out += [(a, ab, ca), (b, bc, ab), (c, ca, bc), (ab, bc, ca)]
        tris = out
    arr = np.asarray(tris, np.float32)  # (N, 3verts, 3)
    return arr


def ground_plane(y=-1.0, half=6.0):
    """Two triangles spanning a square at height y."""
    c = [[-half, y, -half], [half, y, -half], [half, y, half], [-half, y, half]]
    c = np.asarray(c, np.float32)
    return np.stack([np.stack([c[0], c[2], c[1]]),
                     np.stack([c[0], c[3], c[2]])])


def build_scene():
    tris = np.concatenate([icosphere(3), ground_plane()], axis=0)
    # two-sided: add reversed winding (the datapath culls backfaces)
    tris = np.concatenate([tris, tris[:, ::-1, :]], axis=0)
    tri = Triangle(jnp.asarray(tris[:, 0]), jnp.asarray(tris[:, 1]),
                   jnp.asarray(tris[:, 2]))
    return tris, tri


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("out", nargs="?", default="/tmp/render.pgm")
    # only backends that can serve the shadow pass are offered (per_ray
    # is closest-hit only)
    shadow_capable = tuple(b for b in trace_backends()
                           if "shadow" in trace_backend_ray_types(b))
    ap.add_argument("--trace-backend", default="auto",
                    choices=("auto",) + shadow_capable,
                    help="traversal engine (every choice renders the "
                         "identical image)")
    ap.add_argument("--res", type=int, default=96,
                    help="framebuffer resolution (res x res rays)")
    args = ap.parse_args()
    out_path = args.out
    tris, tri = build_scene()
    scene = Scene.from_triangles(tri)
    # shard="auto": rays data-parallel over every local device (scene
    # replicated, image bit-identical); chunk_size: the framebuffer streams
    # through fixed-size ray microbatches sharing one compiled trace
    engine = scene.engine(shard="auto", chunk_size=4096,
                          backend=args.trace_backend)
    print(f"scene: {scene.num_triangles} triangles (sphere + ground), "
          f"BVH4 depth {scene.depth}, {jax.local_device_count()} device(s), "
          f"chunk_size=4096, trace_backend={args.trace_backend}")

    # pinhole camera above the sphere looking slightly down: sphere, ground
    # and the sphere's cast shadow are all in frame
    res = args.res
    eye = np.asarray([0.0, 1.0, -3.6], np.float32)
    ys, xs = np.meshgrid(np.linspace(0.75, -0.75, res),
                         np.linspace(-0.75, 0.75, res), indexing="ij")
    fwd = np.asarray([0.0, -0.35, 1.0]); fwd /= np.linalg.norm(fwd)
    right = np.asarray([1.0, 0.0, 0.0])
    up = np.cross(fwd, right)
    dirs = (fwd[None] + xs.ravel()[:, None] * right[None]
            + ys.ravel()[:, None] * up[None]).astype(np.float32)
    org = np.tile(eye[None], (res * res, 1))
    rays = make_ray(jnp.asarray(org), jnp.asarray(dirs))
    rec = engine.trace(rays)  # closest-hit, auto backend

    hit = np.asarray(rec.hit)
    t = np.asarray(rec.t)
    tri_idx = np.asarray(rec.tri_index)
    pts = org + np.where(hit, t, 0.0)[:, None] * dirs

    # geometric normal of the hit triangle, flipped toward the camera
    v = tris[np.maximum(tri_idx, 0)]  # (R, 3verts, 3)
    n = np.cross(v[:, 1] - v[:, 0], v[:, 2] - v[:, 0])
    n /= np.maximum(np.linalg.norm(n, axis=1, keepdims=True), 1e-12)
    n = np.where((n * dirs).sum(1, keepdims=True) > 0, -n, n)

    # hard shadows: extent-limited any-hit rays toward a point light
    light_pos = np.asarray([2.0, 3.0, -2.0], np.float32)
    to_light = light_pos - pts
    dist = np.linalg.norm(to_light, axis=1)
    ldir = to_light / np.maximum(dist[:, None], 1e-12)
    shadow_org = (pts + 1e-3 * n).astype(np.float32)
    shadow_rays = make_ray(jnp.asarray(shadow_org), jnp.asarray(ldir),
                           extent=jnp.asarray(dist.astype(np.float32)))
    occluded = np.asarray(engine.occluded(shadow_rays, t_min=1e-3))

    lambert = np.clip((n * ldir).sum(1), 0.0, 1.0)
    shade = 0.12 + 0.88 * lambert * np.where(hit & occluded, 0.15, 1.0)
    img = np.where(hit, 20 + 235 * shade, 8).reshape(res, res)

    with open(out_path, "wb") as f:
        f.write(f"P5\n{res} {res}\n255\n".encode())
        f.write(np.clip(img, 0, 255).astype(np.uint8).tobytes())
    n_shadow = int((hit & occluded).sum())
    print(f"hits: {hit.sum()}/{hit.size}  shadowed: {n_shadow}  "
          f"avg quadbox jobs/ray: {float(rec.quadbox_jobs.mean()):.1f}  "
          f"avg triangle jobs/ray: {float(rec.triangle_jobs.mean()):.1f}  "
          f"wavefront rounds: {int(rec.rounds)}")
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
