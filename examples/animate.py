"""Animated scene via ``Scene.refit``: dynamic geometry, zero retraces.

A sphere bounces over the ground plane.  The scene is built ONCE (binned-
SAH builder); every animation frame moves the sphere's triangles and calls
``Scene.refit`` — the O(depth) AABB re-sweep that keeps the tree topology
and every static shape, so all frames after the first re-enter the same
compiled trace (watch the engine cache: entries/misses stop growing after
frame 1).  No rebuild, no retrace, per frame.

Run:  PYTHONPATH=src python examples/animate.py [--frames 8] [--res 64]
          [--out /tmp/animate]
      writes frame_00.pgm .. frame_NN.pgm plus per-frame job stats.
"""
import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from render import ground_plane, icosphere  # noqa: E402  (sibling example)

from repro.api import Scene, Triangle, make_ray  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402


def build_soup():
    sphere = icosphere(2)
    ground = ground_plane()
    tris = np.concatenate([sphere, ground], axis=0)
    tris = np.concatenate([tris, tris[:, ::-1, :]], axis=0)  # two-sided
    # which triangles belong to the (animated) sphere, in both windings
    n_sph, n_all = len(sphere), len(sphere) + len(ground)
    animated = np.zeros(2 * n_all, bool)
    animated[:n_sph] = True
    animated[n_all:n_all + n_sph] = True
    return tris, animated


def frame_soup(tris, animated, t):
    """Sphere bounces: y-shift by |sin t|, squash slightly at the bottom."""
    bounce = 0.8 * abs(np.sin(t))
    squash = 1.0 - 0.25 * max(0.0, 0.3 - bounce)
    out = tris.copy()
    ys = out[animated][:, :, 1]
    out[animated] = np.concatenate(
        [out[animated][:, :, :1], (ys * squash + bounce)[:, :, None],
         out[animated][:, :, 2:]], axis=2)
    return Triangle(jnp.asarray(out[:, 0]), jnp.asarray(out[:, 1]),
                    jnp.asarray(out[:, 2]))


def camera_rays(res):
    eye = np.asarray([0.0, 1.2, -4.0], np.float32)
    ys, xs = np.meshgrid(np.linspace(0.8, -0.8, res),
                         np.linspace(-0.8, 0.8, res), indexing="ij")
    fwd = np.asarray([0.0, -0.25, 1.0]); fwd /= np.linalg.norm(fwd)
    right = np.asarray([1.0, 0.0, 0.0])
    up = np.cross(fwd, right)
    dirs = (fwd[None] + xs.ravel()[:, None] * right[None]
            + ys.ravel()[:, None] * up[None]).astype(np.float32)
    org = np.tile(eye[None], (res * res, 1))
    return org, dirs


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--res", type=int, default=64)
    ap.add_argument("--out", default="/tmp/animate")
    args = ap.parse_args()

    tris, animated = build_soup()
    scene = Scene.from_triangles(frame_soup(tris, animated, 0.0),
                                 builder="sah")
    engine = scene.engine(shard=1, chunk_size=4096)
    print(f"{scene!r}: {int(animated.sum())} animated of "
          f"{scene.num_triangles} triangles; builder-quality "
          f"sah_cost={scene.stats().sah_cost:.2f}")

    os.makedirs(args.out, exist_ok=True)
    org, dirs = camera_rays(args.res)
    rays = make_ray(jnp.asarray(org), jnp.asarray(dirs))

    for k in range(args.frames):
        t = k * (np.pi / max(args.frames - 1, 1))
        if k > 0:  # frame 0 traces the tree as built
            scene.refit(frame_soup(tris, animated, t))
        rec = engine.trace(rays)
        img = np.where(np.asarray(rec.hit),
                       40 + np.clip(215 * (1.0 - np.asarray(rec.t) / 8.0),
                                    0, 215),
                       8).reshape(args.res, args.res)
        path = os.path.join(args.out, f"frame_{k:02d}.pgm")
        with open(path, "wb") as f:
            f.write(f"P5\n{args.res} {args.res}\n255\n".encode())
            f.write(np.clip(img, 0, 255).astype(np.uint8).tobytes())
        info = engine.cache_info()
        print(f"frame {k}: hits {int(rec.hit.sum()):5d}  "
              f"jobs/ray {float(rec.quadbox_jobs.mean()) + float(rec.triangle_jobs.mean()):6.1f}  "
              f"rounds {int(rec.rounds):3d}  "
              f"cache entries={info.entries} misses={info.misses}")

    if engine.cache_info().misses != 1:
        raise SystemExit("refit frames recompiled the trace — the "
                         "zero-retrace contract is broken")
    print(f"{args.frames} frames, 1 compiled trace, 0 rebuilds -> "
          f"{args.out}/frame_*.pgm")


if __name__ == "__main__":
    main()
