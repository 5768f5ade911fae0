"""LiDAR maps: merged sweeps of a 64-beam sensor driven down a street,
searched for the k nearest map points of each return of a new scan through
``PointCloudScene.engine().nearest``, and checked against a plain
brute-force reference over every map point.

The world is a street along x: a ground plane, building fronts on both
sides in blocks whose setbacks are drawn from the configuration's seed,
side walls that close the blocks, and a wall at each end.  Each sweep casts
every (ring, azimuth) beam of the sensor from its pose, keeps the nearest
surface it meets and adds range noise; everything stays in metres in one
world frame.  Map and scans are made on the device from ``cloud_seed``, so
every run searches the same map with the same scans, in an order drawn
from the run's seed.

The reference imports nothing of the program.  It ranks every map point
by the direct form ``sum((q - c)^2)`` in float32, which has no
cancellation at metre coordinates.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import PointCloudScene

#: map points per step of the brute pass (queries x BLOCK distances at once)
BLOCK = 65536


class Data(NamedTuple):
    """What the benchmark made: the map and the query scans."""

    map: jax.Array  # (sweeps * rings * azimuths, 3) f32, metres
    scans: jax.Array  # (scans, rings * azimuths // stride, 3) f32


class Deployment:
    """The map, and the program's engine over it."""

    def __init__(self, data: Data, engine):
        self.data = data
        self.engine = engine

    def free(self) -> None:
        self.engine = None


# ---------------------------------------------------------------------------
# the sweeps
# ---------------------------------------------------------------------------


def _beams(rings: int, azimuths: int, elevation, phase):
    """Unit directions of every beam of one sweep, ring-major: ``rings``
    elevations spread evenly over ``elevation`` (degrees, top first), and
    ``azimuths`` steps around the full turn from ``phase`` (radians)."""
    el = jnp.deg2rad(jnp.linspace(elevation[0], elevation[1], rings))
    az = phase + 2.0 * jnp.pi * jnp.arange(azimuths) / azimuths
    el, az = jnp.meshgrid(el, az, indexing="ij")
    return jnp.stack([jnp.cos(el) * jnp.cos(az), jnp.cos(el) * jnp.sin(az),
                      jnp.sin(el)], axis=-1).reshape(-1, 3)


def _cast(d, px, height, street, block, setback):
    """Range along unit directions ``d`` (B, 3) from a sensor at (px, 0,
    height) to the nearest surface: the ground z = 0, the front of each
    block on either side (y = +setback[0, j] / -setback[1, j] for x in
    block j), the side walls between neighbouring blocks, and the end walls
    x = street[0], street[1]."""
    dx, dy, dz = d[:, 0:1], d[:, 1:2], d[:, 2:3]
    n = setback.shape[1]
    edges = street[0] + block * jnp.arange(n + 1, dtype=jnp.float32)
    far = jnp.float32(jnp.inf)

    def ahead(t, ok):
        return jnp.where(ok & (t > 0), t, far)

    ts = [ahead(-height / dz, dz < 0)]
    for side, sign in ((0, 1.0), (1, -1.0)):
        w = setback[side][None, :]  # (1, n)
        t = sign * w / dy
        x = px + t * dx
        ts.append(ahead(t, (sign * dy > 0) & (x >= edges[None, :-1])
                        & (x <= edges[None, 1:])))
        lo = jnp.minimum(setback[side][:-1], setback[side][1:])[None, :]
        hi = jnp.maximum(setback[side][:-1], setback[side][1:])[None, :]
        t = (edges[None, 1:-1] - px) / dx
        y = sign * t * dy
        ts.append(ahead(t, (y >= lo) & (y <= hi)))
    ts.append(ahead((street[0] - px) / dx, dx < 0))
    ts.append(ahead((street[1] - px) / dx, dx > 0))
    return jnp.min(jnp.concatenate(
        [jnp.broadcast_to(t, (d.shape[0], t.shape[1])) for t in ts],
        axis=1), axis=1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def sweeps(key, poses: int, rings: int, azimuths: int, blocks: int,
           elevation, height, noise, step, setbacks, block):
    """``poses`` sweeps, one per pose ``step`` metres apart along x, each
    (rings * azimuths, 3) returns in the world frame.  The street runs
    from ``setbacks[1]`` before the first pose through ``blocks`` blocks
    of ``block`` metres; each block's front stands back from the street's
    axis by a setback drawn uniformly from ``setbacks``."""
    kw, kp, kn = jax.random.split(key, 3)
    street = jnp.stack([-setbacks[1], blocks * block - setbacks[1]])
    setback = jax.random.uniform(kw, (2, blocks), jnp.float32,
                                 setbacks[0], setbacks[1])
    phases = jax.random.uniform(kp, (poses,), jnp.float32, 0.0,
                                2.0 * jnp.pi / azimuths)

    def one(args):
        i, phase, kz = args
        d = _beams(rings, azimuths, elevation, phase)
        px = i.astype(jnp.float32) * step
        r = _cast(d, px, height, street, block, setback)
        r = r + noise * jax.random.normal(kz, r.shape)
        origin = jnp.stack([px, jnp.float32(0.0), height])
        return origin[None, :] + r[:, None] * d

    return jax.lax.map(one, (jnp.arange(poses), phases,
                             jax.random.split(kn, poses)))


def make_data(config: dict, scans: int) -> Data:
    """The map's sweeps, then ``scans`` more at the next poses, each cut to
    every ``query_stride``-th return."""
    n = config["sweeps"]
    far = config["facade_range"][1]
    length = (n + scans - 1) * config["pose_step"] + 2.0 * far
    blocks = max(1, math.ceil(length / config["facade_block"]))
    s = sweeps(jax.random.PRNGKey(config["cloud_seed"]), n + scans,
               config["rings"], config["azimuths"], blocks,
               jnp.asarray(config["elevation_deg"], jnp.float32),
               jnp.float32(config["sensor_height"]),
               jnp.float32(config["range_noise"]),
               jnp.float32(config["pose_step"]),
               jnp.asarray(config["facade_range"], jnp.float32),
               jnp.float32(config["facade_block"]))
    return Data(s[:n].reshape(-1, 3), s[n:, ::config["query_stride"]])


def build(config: dict, mix: dict, key) -> Deployment:
    data = make_data(config, mix["sets"])
    cloud = PointCloudScene.from_points(data.map, builder=config["builder"])
    engine = cloud.engine(**config["engine"], **mix.get("engine", {}))
    route = engine.resolve_neighbor_backend("nearest", config["metric"],
                                            k=config["k"])
    assert route == "tree_wavefront", (
        f"auto routes this map's nearest to {route!r}, not the tree")
    return Deployment(data, engine)


def closed_payloads(dep: Deployment, config: dict, mix: dict, key) -> list:
    """The query scans, in an order drawn from ``key``."""
    scans = dep.data.scans
    assert scans.shape[1] == mix["rows_per_call"], (
        f"a scan holds {scans.shape[1]} returns, the mix asks for "
        f"{mix['rows_per_call']}")
    order = np.asarray(jax.random.permutation(key, scans.shape[0]))
    return [scans[i] for i in order]


def call(engine, payload, config: dict, mix: dict):
    return engine.nearest(payload, config["k"], config["metric"])


def rows_of(payload) -> int:
    return int(payload.shape[0])


def host_rows(payload, result) -> tuple[dict, dict]:
    """(inputs, answers) of one call, per row, as numpy."""
    return ({"queries": np.asarray(payload)},
            {"scores": np.asarray(result.scores),
             "ids": np.asarray(result.indices),
             "valid": np.asarray(result.valid)})


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def _direct(q, c):
    """``sum((q - c)^2)`` over the three axes, (M, 1) x (1, B) per axis."""
    return sum(jnp.square(q[:, i:i + 1] - c[None, :, i]) for i in range(3))


def _top(q, points, keep: int, dtype):
    """The ``keep`` nearest map points per query by the direct form, over
    every point in blocks of BLOCK, operands rounded to ``dtype`` and the
    differences taken in float32."""
    n = points.shape[0]
    steps = -(-n // BLOCK)
    blocks = jnp.pad(points.astype(dtype).astype(jnp.float32),
                     ((0, steps * BLOCK - n), (0, 0))).reshape(
                         steps, BLOCK, 3)
    valid = (jnp.arange(steps * BLOCK) < n).reshape(steps, BLOCK)
    q = q.astype(dtype).astype(jnp.float32)

    def step(carry, blk):
        best_s, best_i, lo = carry
        cb, vb = blk
        s = jnp.where(vb[None], _direct(q, cb), jnp.inf)
        ids = lo + jnp.arange(BLOCK, dtype=jnp.int32)
        neg, j = jax.lax.top_k(-jnp.concatenate([best_s, s], axis=1), keep)
        all_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        return (-neg, jnp.take_along_axis(all_i, j, axis=1),
                lo + BLOCK), None

    m = q.shape[0]
    init = (jnp.full((m, keep), jnp.inf, jnp.float32),
            jnp.full((m, keep), -1, jnp.int32), jnp.int32(0))
    best_s, best_i, _ = jax.lax.scan(step, init, (blocks, valid))[0]
    return best_s, best_i


def _exact(q, points, ids):
    """The direct form for each query's listed map points."""
    c = points[jnp.clip(ids, 0, points.shape[0] - 1)]
    return sum(jnp.square(q[:, None, i] - c[..., i]) for i in range(3))


@functools.partial(jax.jit, static_argnums=3)
def _judge(points, q, answers, k: int):
    scores, ids, valid = answers
    n = points.shape[0]
    d_ref, _ = _top(q, points, k, jnp.float32)
    d_got = _exact(q, points, ids)
    pairs = ids[:, :, None] == ids[:, None, :]
    dup = jnp.sum(pairs, axis=(1, 2)) > k
    bad = jnp.any((ids < 0) | (ids >= n) | ~valid, axis=1) | dup
    tiny = jnp.finfo(jnp.float32).tiny
    gap = (jnp.sort(d_got, axis=1) - d_ref) / jnp.maximum(d_ref, tiny)
    err = jnp.abs(scores - d_got) / jnp.maximum(d_got, tiny)
    ok = ~bad[:, None]
    return (jnp.sum(bad), jnp.max(jnp.where(ok, gap, 0.0)),
            jnp.max(jnp.where(ok, err, 0.0)))


def check(data: Data, inputs: dict, answers: dict, config: dict) -> dict:
    """The numbers compared: rows answered wrong (an id out of range,
    repeated or not valid), the widest relative gap between the exact
    distance of a reported neighbour and the reference's neighbour of the
    same rank, and the widest relative error of a reported score against
    the exact distance of its id."""
    wrong, gap, err = _judge(
        data.map, jnp.asarray(inputs["queries"]),
        (jnp.asarray(answers["scores"]), jnp.asarray(answers["ids"]),
         jnp.asarray(answers["valid"])), config["k"])
    return {"wrong_rows": int(wrong), "rank_gap": float(gap),
            "score_err": float(err)}


@functools.partial(jax.jit, static_argnums=2)
def _control(points, q, k: int):
    return _top(q, points, k, jnp.bfloat16)


def control(data: Data, inputs: dict, config: dict) -> dict:
    """The reference put in the program's place, in bfloat16: queries and
    map points rounded to bfloat16, differences and squares in float32."""
    scores, ids = _control(data.map, jnp.asarray(inputs["queries"]),
                           config["k"])
    return {"scores": np.asarray(scores), "ids": np.asarray(ids),
            "valid": np.asarray(ids >= 0)}
