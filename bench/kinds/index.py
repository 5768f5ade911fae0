"""Vector indexes: a Gaussian mixture at an ann-benchmarks shape, searched
through ``VectorIndex.engine().nearest`` (or a ``QueryServer`` over it), and
checked against a plain brute-force k-nearest-neighbour reference.

The reference imports nothing of the program.  It picks candidates from
every base vector by the expanded form at full float32 precision, then
ranks them, and the program's answers, by the direct form
``sum((q - c)^2)``, which has no cancellation.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import VectorIndex

#: base vectors per step of the brute pass (queries x BLOCK scores at once)
BLOCK = 65536

#: candidates kept per query before the exact ranking: enough that rounding
#: in the expanded form cannot push a true neighbour out of them
CANDIDATES = 32


class Data(NamedTuple):
    """What the benchmark made: the mixture's centres and the base."""

    centers: jax.Array  # (components, dim) f32
    base: jax.Array  # (points, dim) f32


class Deployment:
    """The index's data, and the program's engine over it."""

    def __init__(self, data: Data, engine, sigma: float):
        self.data = data
        self.engine = engine
        self.sigma = sigma

    def free(self) -> None:
        self.engine = None


@functools.partial(jax.jit, static_argnums=1)
def mixture(key, n: int, centers, sigma):
    """``n`` rows of spread ``sigma`` around ``centers`` (components drawn
    uniformly), made on the device."""
    k1, k2 = jax.random.split(key)
    pick = jax.random.randint(k1, (n,), 0, centers.shape[0])
    return centers[pick] + sigma * jax.random.normal(
        k2, (n, centers.shape[1]))


def build(config: dict, mix: dict, key) -> Deployment:
    kc, kb = jax.random.split(key)
    centers = jax.random.normal(kc, (config["components"], config["dim"]))
    base = mixture(kb, config["points"], centers, config["sigma"])
    engine = VectorIndex.from_database(base).engine(
        **config["engine"], **mix.get("engine", {}))
    return Deployment(Data(centers, base), engine, config["sigma"])


def closed_payloads(dep: Deployment, config: dict, mix: dict, key) -> list:
    """The mix's query sets, on the device."""
    return [mixture(k, mix["rows_per_call"], dep.data.centers, dep.sigma)
            for k in jax.random.split(key, mix["sets"])]


def pool(dep: Deployment, config: dict, mix: dict, key) -> np.ndarray:
    """Query rows that open-loop requests are cut from, on the host."""
    return np.asarray(mixture(key, mix["pool_rows"], dep.data.centers,
                              dep.sigma))


def call(engine, payload, config: dict, mix: dict):
    return engine.nearest(payload, config["k"], config["metric"])


def serve(server, payload, config: dict, mix: dict):
    return server.nearest(payload, config["k"], config["metric"])


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


def _blocks(base, dtype):
    n = base.shape[0]
    steps = -(-n // BLOCK)
    x = jnp.pad(base.astype(dtype), ((0, steps * BLOCK - n), (0, 0)))
    valid = (jnp.arange(steps * BLOCK) < n).reshape(steps, BLOCK)
    return x.reshape(steps, BLOCK, -1), valid


def _top(q, base, keep: int, dtype, precision):
    """The ``keep`` best of every base vector per query by the expanded
    form ``|q|^2 - 2 q.c + |c|^2``, operands in ``dtype``, sums in f32."""
    blocks, valid = _blocks(base, dtype)
    q = q.astype(dtype)
    q2 = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=1, keepdims=True)

    def step(carry, blk):
        best_s, best_i, lo = carry
        cb, vb = blk
        c2 = jnp.sum(jnp.square(cb.astype(jnp.float32)), axis=1)
        dots = jnp.dot(q, cb.T, precision=precision,
                       preferred_element_type=jnp.float32)
        s = jnp.where(vb[None], q2 - 2.0 * dots + c2[None], jnp.inf)
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


def _exact(q, base, ids):
    """``sum((q - c)^2)`` for each query's listed base vectors."""
    c = base[jnp.clip(ids, 0, base.shape[0] - 1)]
    return jnp.sum(jnp.square(q[:, None, :] - c), axis=2)


@functools.partial(jax.jit, static_argnums=3)
def _judge(base, q, answers, k: int):
    scores, ids, valid = answers
    n = base.shape[0]
    _, cand = _top(q, base, CANDIDATES, jnp.float32,
                   jax.lax.Precision.HIGHEST)
    d_ref = jnp.sort(_exact(q, base, cand), axis=1)[:, :k]
    d_got = _exact(q, base, ids)
    pairs = ids[:, :, None] == ids[:, None, :]
    dup = jnp.sum(pairs, axis=(1, 2)) > k
    bad = (jnp.any((ids < 0) | (ids >= n) | ~valid, axis=1) | dup)
    gap = (jnp.sort(d_got, axis=1) - d_ref) / d_ref
    err = jnp.abs(scores - d_got) / d_got
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
        data.base, jnp.asarray(inputs["queries"]),
        (jnp.asarray(answers["scores"]), jnp.asarray(answers["ids"]),
         jnp.asarray(answers["valid"])), config["k"])
    return {"wrong_rows": int(wrong), "rank_gap": float(gap),
            "score_err": float(err)}


@functools.partial(jax.jit, static_argnums=2)
def _control(base, q, k: int):
    return _top(q, base, k, jnp.bfloat16, None)


def control(data: Data, inputs: dict, config: dict) -> dict:
    """The reference put in the program's place, in bfloat16: operands
    rounded to bfloat16, products summed in float32."""
    scores, ids = _control(data.base, jnp.asarray(inputs["queries"]),
                           config["k"])
    return {"scores": np.asarray(scores), "ids": np.asarray(ids),
            "valid": np.asarray(ids >= 0)}
