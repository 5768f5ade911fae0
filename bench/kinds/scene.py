"""Triangle scenes: a clustered procedural soup, traced by pinhole-camera
frames through ``Scene.engine().trace``, and checked against a plain
brute-force ray-triangle reference over every triangle of the soup.

The reference imports nothing of the program.  It is the Moller-Trumbore
test with the program's stated semantics: closest hit, ``t > 0``, front
faces only (the watertight datapath test culls triangles whose geometric
normal ``(b - a) x (c - a)`` points along the ray).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Triangle, make_ray, Scene

#: Rounding band of the comparison.  Both the program's test and the
#: reference round each coordinate relative to the eye (|x| <~ 20) to f32,
#: about 1e-6 absolute, against triangles of size ~0.03: barycentric
#: coordinates and the facing test move by ~3e-5 at most.  A ray closer
#: than BARY to an edge, or a triangle seen closer than FACING to edge-on,
#: may go either way; hits farther inside must be found.  ORDER is the
#: share by which a surely-hit triangle must be nearer than the program's
#: hit before the program is said to have missed it.  bfloat16 moves these
#: by ~1e-2, so the band still separates float32 from anything lower.
BARY = 1e-3
FACING = 1e-3
ORDER = 1e-4

#: triangles per step of the brute pass (rays x BLOCK pairs live at once)
BLOCK = 2048


class Data(NamedTuple):
    """What the benchmark made: the triangle soup."""

    a: jax.Array  # (N, 3) f32 vertices
    b: jax.Array
    c: jax.Array


class Deployment:
    """The scene's data, and the program's engine over it."""

    def __init__(self, data: Data, engine):
        self.data = data
        self.engine = engine

    def free(self) -> None:
        self.engine = None


@functools.partial(jax.jit, static_argnums=(1, 2))
def soup(key, n_clusters: int, per_cluster: int, spread, size, extent):
    """Tight clusters of small triangles flung across a wide volume, made
    on the device."""
    kc, kt, k1, k2 = jax.random.split(key, 4)
    n = n_clusters * per_cluster
    centers = jax.random.uniform(kc, (n_clusters, 3), jnp.float32,
                                 -extent, extent)
    ctr = (jnp.repeat(centers, per_cluster, axis=0)
           + spread * jax.random.normal(kt, (n, 3)))
    return Data(ctr, ctr + size * jax.random.normal(k1, (n, 3)),
                ctr + size * jax.random.normal(k2, (n, 3)))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def frame_directions(key, width: int, height: int, n_frames: int, tan,
                     jitter):
    """Directions of ``n_frames`` pinhole frames looking along +z, each
    ray offset within its pixel by up to ``jitter`` / 2 pixels."""
    ys, xs = jnp.meshgrid(
        jnp.linspace(tan, -tan, height),
        jnp.linspace(-tan * width / height, tan * width / height, width),
        indexing="ij")
    step = jnp.asarray([2.0 * tan * width / height / (width - 1),
                        2.0 * tan / (height - 1)], jnp.float32)
    offset = jitter * step * jax.random.uniform(
        key, (n_frames, width * height, 2), jnp.float32, -0.5, 0.5)
    xy = jnp.stack([xs.ravel(), ys.ravel()], axis=1)[None] + offset
    return jnp.concatenate(
        [xy, jnp.ones((n_frames, width * height, 1), jnp.float32)], axis=2)


def scene_keys(config: dict):
    """Keys of the scene and of its camera's frames: one of each per
    configuration, so that every run traces the same work (a run's seed
    orders the frames, ``closed_payloads``)."""
    return jax.random.split(jax.random.PRNGKey(config["scene_seed"]))


def build(config: dict, mix: dict, key) -> Deployment:
    data = soup(scene_keys(config)[0], config["clusters"],
                config["triangles_per_cluster"], config["cluster_spread"],
                config["triangle_size"], config["scene_extent"])
    scene = Scene.from_triangles(Triangle(*data), builder=config["builder"])
    return Deployment(data, scene.engine(**config["engine"],
                                         **mix.get("engine", {})))


def closed_payloads(dep: Deployment, config: dict, mix: dict, key) -> list:
    """The mix's frames, rays set up by the program's ``make_ray``, in an
    order drawn from ``key``."""
    width, height = config["frame"]
    cam = config["camera"]
    dirs = frame_directions(scene_keys(config)[1], width, height,
                            mix["sets"], cam["tan_half_fov_y"],
                            mix["jitter_pixels"])
    eye = jnp.broadcast_to(jnp.asarray(cam["eye"], jnp.float32),
                           (width * height, 3))
    order = np.asarray(jax.random.permutation(key, mix["sets"]))
    return [make_ray(eye, dirs[i]) for i in order]


def call(engine, payload, config: dict, mix: dict):
    return engine.trace(payload, ray_type=mix["ray_type"])


def rows_of(payload) -> int:
    return int(payload.origin.shape[0])


def host_rows(payload, result) -> tuple[dict, dict]:
    """(inputs, answers) of one call, per row, as numpy."""
    return ({"origin": np.asarray(payload.origin),
             "direction": np.asarray(payload.direction)},
            {"t": np.asarray(result.t), "tri": np.asarray(result.tri_index),
             "hit": np.asarray(result.hit)})


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def _sub(x, y):
    return tuple(xi - yi for xi, yi in zip(x, y))


def _dot(x, y):
    return x[0] * y[0] + x[1] * y[1] + x[2] * y[2]


def _cross(x, y):
    return (x[1] * y[2] - x[2] * y[1], x[2] * y[0] - x[0] * y[2],
            x[0] * y[1] - x[1] * y[0])


def _terms(o, d, a, b, c):
    """Moller-Trumbore, unnormalised: (det, u, v, t, |n||d|) with the
    barycentrics and distance still scaled by det.  Vectors are triples of
    arrays (one per axis), so that a (rays, triangles) block keeps its two
    long axes in the chip's tiles."""
    e1, e2 = _sub(b, a), _sub(c, a)
    p = _cross(d, e2)
    s = _sub(o, a)
    q = _cross(s, e1)
    n = _cross(e1, e2)
    det, u, v, t = _dot(e1, p), _dot(s, p), _dot(d, q), _dot(e2, q)
    return det, u, v, t, jnp.sqrt(_dot(n, n) * _dot(d, d))


def _axes(x) -> tuple:
    """An (n, 3) array as a triple of (n,) arrays, one per axis."""
    return tuple(x[:, i] for i in range(3))


def _expand(v, axis: int) -> tuple:
    return tuple(jnp.expand_dims(x, axis) for x in v)


def _may_must(det, u, v, t, nd):
    """May the ray hit the triangle, within the band; must it?"""
    adet = jnp.abs(det)
    may = ((det > -FACING * nd) & (u >= -BARY * adet) & (v >= -BARY * adet)
           & (u + v <= (1.0 + BARY) * adet) & (t * det > 0))
    must = ((det > 0) & (det >= FACING * nd) & (u >= BARY * det)
            & (v >= BARY * det) & (u + v <= (1.0 - BARY) * det) & (t > 0))
    return may, must


def _blocks(data: Data, dtype):
    """The soup as (steps, BLOCK) blocks per vertex axis, padded, with a
    validity mask."""
    n = data.a.shape[0]
    steps = -(-n // BLOCK)

    def blk(x):
        x = jnp.pad(x.astype(dtype), ((0, steps * BLOCK - n), (0, 0)))
        return tuple(x[:, i].reshape(steps, BLOCK) for i in range(3))

    valid = (jnp.arange(steps * BLOCK) < n).reshape(steps, BLOCK)
    return blk(data.a), blk(data.b), blk(data.c), valid


@jax.jit
def _nearest_sure_hit(data: Data, o, d):
    """Per ray, the distance of the nearest triangle that it surely hits
    (inf where none), over every triangle, in float32."""
    a, b, c, valid = _blocks(data, jnp.float32)
    o, d = _expand(_axes(o), 1), _expand(_axes(d), 1)

    def step(best, blk):
        ab, bb, cb, vb = blk
        det, u, v, t, nd = _terms(o, d, _expand(ab, 0), _expand(bb, 0),
                                  _expand(cb, 0))
        _, must = _may_must(det, u, v, t, nd)
        th = jnp.where(must & vb[None], t / det, jnp.inf)
        return jnp.minimum(best, jnp.min(th, axis=1)), None

    best0 = jnp.full((o[0].shape[0],), jnp.inf, jnp.float32)
    return jax.lax.scan(step, best0, (a, b, c, valid))[0]


@jax.jit
def _judge(data: Data, o, d, t_p, tri_p, hit_p):
    n = data.a.shape[0]
    t_sure = _nearest_sure_hit(data, o, d)
    index_ok = (tri_p >= 0) & (tri_p < n)
    i = jnp.clip(tri_p, 0, n - 1)
    det, u, v, t, nd = _terms(_axes(o), _axes(d), _axes(data.a[i]),
                              _axes(data.b[i]), _axes(data.c[i]))
    may, _ = _may_must(det, u, v, t, nd)
    t_ref = t / det
    found = hit_p & index_ok & may
    wrong = jnp.where(hit_p, ~found | (t_ref > t_sure * (1.0 + ORDER)),
                      jnp.isfinite(t_sure))
    err = jnp.where(found, jnp.abs(t_p - t_ref) / jnp.abs(t_ref), 0.0)
    return jnp.sum(wrong), jnp.max(err)


def check(data: Data, inputs: dict, answers: dict, config: dict) -> dict:
    """The numbers compared: rays answered wrong, and the widest relative
    gap between a reported distance and the reference's distance to the
    triangle reported."""
    wrong, err = _judge(data, jnp.asarray(inputs["origin"]),
                        jnp.asarray(inputs["direction"]),
                        jnp.asarray(answers["t"]), jnp.asarray(answers["tri"]),
                        jnp.asarray(answers["hit"]))
    return {"wrong_rays": int(wrong), "t_rel_err": float(err)}


@jax.jit
def _control(data: Data, o, d):
    """The reference put in the program's place, in bfloat16: the nearest
    triangle hit by the strict test, every operand rounded to bfloat16."""
    a, b, c, valid = _blocks(data, jnp.bfloat16)
    o = _expand(_axes(o.astype(jnp.bfloat16)), 1)
    d = _expand(_axes(d.astype(jnp.bfloat16)), 1)

    def step(carry, blk):
        best_t, best_i, base = carry
        ab, bb, cb, vb = blk
        det, u, v, t, _ = _terms(o, d, _expand(ab, 0), _expand(bb, 0),
                                 _expand(cb, 0))
        hit = (vb[None] & (det > 0) & (u >= 0) & (v >= 0) & (u + v <= det)
               & (t > 0))
        th = jnp.where(hit, t / det, jnp.inf).astype(jnp.float32)
        j = jnp.argmin(th, axis=1).astype(jnp.int32)
        tj = jnp.min(th, axis=1)
        better = tj < best_t
        return (jnp.where(better, tj, best_t),
                jnp.where(better, base + j, best_i), base + BLOCK), None

    rays = o[0].shape[0]
    init = (jnp.full((rays,), jnp.inf, jnp.float32),
            jnp.full((rays,), -1, jnp.int32), jnp.int32(0))
    best_t, best_i, _ = jax.lax.scan(step, init, (a, b, c, valid))[0]
    return best_t, best_i, best_i >= 0


def control(data: Data, inputs: dict, config: dict) -> dict:
    t, tri, hit = _control(data, jnp.asarray(inputs["origin"]),
                           jnp.asarray(inputs["direction"]))
    return {"t": np.asarray(t), "tri": np.asarray(tri),
            "hit": np.asarray(hit)}
