"""Pallas TPU kernel: generalized distance modes on the MXU.

The paper's OpEuclidean/OpAngular stream one vector pair per beat through
shared adders/multipliers, accumulating partial sums across beats.  On TPU
the shared functional unit worth feeding is the **MXU**, so the batched form
is matmul-shaped (DESIGN.md §2):

    euclidean:  D[m, n] = ||q_m||^2 - 2 q_m.c_n + ||c_n||^2
    angular:    S[m, n] = q_m.c_n            and   N[n] = ||c_n||^2

The K (feature) dimension is blocked and accumulated in a VMEM scratch tile
across grid steps -- the direct analogue of the paper's multi-beat internal
accumulator (Table V), with the lane-validity bitmask realised as K-padding.

Grid iteration order is (m, n, k) with k innermost so the accumulator tile
lives in VMEM for the whole K sweep (revisiting semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import resolve_interpret

# MXU-aligned default blocks.
BM, BN, BK = 256, 256, 512


def _distance_kernel(q_ref, c_ref, out_ref, acc_ref, *, mode: str, nk: int):
    """q (BM, BK), c (BN, BK) -> out (BM, BN); acc is VMEM f32 scratch.

    mode == 'euclidean': out = sum_k (q-c)^2 via the expanded matmul form.
    mode == 'angular':   out = sum_k q*c.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    # the shared multiplier array: one MXU pass per beat.  HIGHEST pins
    # full-f32 contraction, the precision of the brute oracle
    # (core/knn.py) this kernel's neighbour ids are checked against
    qc = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    if mode == "euclidean":
        q2 = jnp.sum(q * q, axis=1, keepdims=True)  # (BM, 1)
        c2 = jnp.sum(c * c, axis=1, keepdims=True).T  # (1, BN)
        acc_ref[...] += q2 - 2.0 * qc + c2
    else:
        acc_ref[...] += qc

    @pl.when(k == nk - 1)
    def _done():
        out = acc_ref[...]
        if mode == "euclidean":
            out = jnp.maximum(out, 0.0)
        out_ref[...] = out


def _norm_kernel(c_ref, out_ref):
    """Row-norms ||c_n||^2: (BN, BK) tiles accumulated into (1, BN)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = c_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.sum(c * c, axis=1, keepdims=True).T


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk", "interpret"))
def distance_pallas(q, c, *, mode="euclidean", bm=BM, bn=BN, bk=BK, interpret=None):
    """Pairwise distance/dot scores.  q: (M, D), c: (N, D), padded to blocks.

    Returns (M, N) f32: squared Euclidean distances or dot products.
    ``interpret=None`` auto-selects: interpret off-TPU, compiled on TPU.
    """
    interpret = resolve_interpret(interpret)
    m, d = q.shape
    n, d2 = c.shape
    assert d == d2 and m % bm == 0 and n % bn == 0 and d % bk == 0, (q.shape, c.shape)
    nk = d // bk
    grid = (m // bm, n // bn, nk)
    kernel = functools.partial(_distance_kernel, mode=mode, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        # the kernel's name in a profile, whatever the Python around it
        # is called (``nearest.distance_share`` matches it)
        name="distance_pallas",
    )(q, c)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def norms_pallas(c, *, bn=BN, bk=BK, interpret=None):
    """||c_n||^2 for every row: (N, D) -> (1, N)."""
    interpret = resolve_interpret(interpret)
    n, d = c.shape
    assert n % bn == 0 and d % bk == 0, c.shape
    grid = (n // bn, d // bk)
    return pl.pallas_call(
        _norm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, bk), lambda j, k: (j, k))],
        out_specs=pl.BlockSpec((1, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(c)


def angular_pallas(q, c, **kw):
    """OpAngular batched: (dots (M,N), norms (1,N))."""
    return distance_pallas(q, c, mode="angular", **kw), norms_pallas(c, **{
        k: v for k, v in kw.items() if k in ("bn", "bk", "interpret")})
