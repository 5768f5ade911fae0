"""Traversal-backed neighbor search: kNN/radius queries *on* the BVH walk.

The paper's thesis is that one RT datapath serves both tracing and
distance workloads; RTNN closes the loop by showing neighbor search can
run on the *traversal* side of that datapath rather than as brute-force
pairwise scoring.  The mapping (mirrored from the builders' side in
:mod:`repro.core.build.points`):

* each database point is an AABB-per-point leaf of an ordinary
  :class:`~repro.core.bvh.BVH4`;
* a query is a :class:`~repro.core.types.Ray` whose ``extent`` is the
  search radius (direction is irrelevant — traversal orders by *box
  distance*, :func:`~repro.core.datapath.point_box_test`, the neighbor
  twin of OpQuadbox);
* a leaf visit issues OpEuclidean-style jobs against <=4 candidate
  points and folds them into a per-query sorted top-k insertion network
  (the QuadSort analogue for running best lists).

Two engines share this module's stage helpers, exactly like the trace
side: :func:`neighbor_wavefront` here (batch-level frontier loop) and the
fused Pallas kernel in :mod:`repro.kernels.traverse` — so their leaf
arithmetic is bit-identical by construction.

Oracle contract
---------------
:func:`leaf_dist_sq` computes the squared distance in the difference
form ``(g0*g0 + g1*g1) + g2*g2`` with ``g = c - q``: at metre
coordinates, with neighbours centimetres apart, ``c - q`` is exact in
f32 and the form keeps full relative precision, where the expanded form
``||q||^2 - 2 q.c + ||c||^2`` cancels away more than the distances it
ranks.  It is associated as :func:`~repro.core.datapath.point_box_test`
is, so a point leaf and its degenerate box give the same f32 number, and
f32 rounding being monotone, every enclosing box's distance is at most
its point's: pruning on box distance against the running bound never
drops a point the leaf test would accept, with no slack.

So the tree is exact against a brute pass in its own difference form:
the same in-radius sets and counts, and the same k nearest up to ties.
Against the brute MXU path (:mod:`repro.core.knn`, the expanded form) it
agrees within that form's rounding band, about ``eps * (||q||^2 +
||c||^2)``: narrow at unit scale, wider than the distances themselves at
LiDAR coordinates.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .bvh import BVH4, child_boxes, level_offset
from .datapath import fmin, point_box_test
from .traversal import STACK_SIZE
from .types import Ray, make_ray

NEIGHBOR_MODES = ("within", "nearest")


class NeighborRecord(NamedTuple):
    """Per-query results plus the frontier-level scheduling statistics."""

    dist_sq: jax.Array  # (R, k) f32 squared distances, ascending, inf pad
    index: jax.Array  # (R, k) i32 database indices, -1 pad
    valid: jax.Array  # (R, k) bool slot holds a real neighbor
    count: jax.Array  # (R,) i32 exact in-radius count ("within" mode)
    box_jobs: jax.Array  # (R,) i32 per-query point-box jobs issued
    point_jobs: jax.Array  # (R,) i32 per-query point-distance jobs issued
    rounds: jax.Array  # ()   i32 batched rounds


def point_queries(points: jax.Array, radius=None) -> Ray:
    """Wrap query points as extent-limited "rays" for the neighbor engines.

    The direction is a dummy +x axis: neighbor traversal never consumes
    it (ordering comes from box distance), but packing a full Ray keeps
    every downstream pipe — dispatch padding, the Pallas ray operand
    layout — identical to the trace path.
    """
    points = jnp.asarray(points, jnp.float32)
    direction = jnp.broadcast_to(
        jnp.asarray([1.0, 0.0, 0.0], jnp.float32), points.shape)
    extent = jnp.inf if radius is None else radius
    return make_ray(points, direction, extent)


def leaf_dist_sq(p: jax.Array, pts: jax.Array) -> jax.Array:
    """Query-to-candidate squared distances in the difference form.

    p: (..., 3) queries; pts: (..., 4, 3) candidates.  Per axis ``g = c -
    q``, then ``(g0*g0 + g1*g1) + g2*g2``: the association of
    :func:`~repro.core.datapath.point_box_test`, so a candidate and its
    degenerate leaf box give the same f32 distance.
    """
    g = pts - p[..., None, :]
    sq = g * g
    return (sq[..., 0] + sq[..., 1]) + sq[..., 2]


def insert_sorted(best_d: jax.Array, best_i: jax.Array, d: jax.Array,
                  i: jax.Array, accept: jax.Array):
    """One compare-shift-insert beat of the running top-k network.

    best_d/best_i: (k, L) sorted-ascending running lists (inf / -1 in
    empty slots); d/i/accept: (L,) one candidate per lane.  An accepted
    candidate lands in its rank slot and everything below shifts down one
    — the sequential-insertion analogue of the QuadSort network, O(k)
    comparators per beat with no data-dependent control flow.
    """
    ins = accept[None, :] & (d[None, :] < best_d)  # monotone down the k axis
    first = ins & ~jnp.concatenate(
        [jnp.zeros_like(ins[:1]), ins[:-1]], axis=0)
    shift_d = jnp.concatenate([best_d[:1], best_d[:-1]], axis=0)
    shift_i = jnp.concatenate([best_i[:1], best_i[:-1]], axis=0)
    new_d = jnp.where(first, d[None, :], jnp.where(ins, shift_d, best_d))
    new_i = jnp.where(first, i[None, :], jnp.where(ins, shift_i, best_i))
    return new_d, new_i


def prune_bound(r_sq: jax.Array, kth_best: jax.Array,
                mode: str) -> jax.Array:
    """Node-visit bound: a child is pushed iff its box distance is <= this.

    ``"within"`` prunes on the radius alone (every in-radius point must
    be found — the k-th best can't shrink the search).  ``"nearest"``
    additionally contracts to the current k-th best distance once the
    list fills.  No slack: a box's distance never exceeds its points'
    leaf distances (see the module docstring).
    """
    return r_sq if mode == "within" else fmin(r_sq, kth_best)


def neighbor_wavefront(bvh: BVH4, queries: Ray, depth: int, k: int,
                       mode: str = "within",
                       max_rounds: int | None = None) -> NeighborRecord:
    """Batch-level neighbor traversal (the wavefront engine's distance twin).

    ``bvh`` must be a point BVH (:func:`~repro.core.build.points.
    build_point_bvh`): the cloud is read back as ``bvh.triangles.a``.

    ``queries`` carry the radius as ``extent`` (:func:`point_queries`);
    ``k``/``mode``/``max_rounds`` are static.  Like
    :func:`~repro.core.wavefront.trace_wavefront`, each round pops the
    whole active frontier, issues one batched point-box job and one
    batched round of <=4 point-distance jobs, and pushes surviving
    children far-to-near so the nearest child is explored first.
    """
    if mode not in NEIGHBOR_MODES:
        raise ValueError(
            f"mode must be one of {NEIGHBOR_MODES}, got {mode!r}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    leaf_parent_offset = level_offset(depth - 1)
    leaf_offset = level_offset(depth)
    if max_rounds is None:
        max_rounds = level_offset(depth)  # = number of internal nodes

    points = bvh.triangles.a
    p = queries.origin  # (R, 3)
    r_sq = queries.extent * queries.extent  # inf extent -> inf bound
    n_q = p.shape[0]
    rows = jnp.arange(n_q, dtype=jnp.int32)

    stack0 = jnp.zeros((n_q, STACK_SIZE), jnp.int32)  # root pre-pushed
    state0 = (stack0, jnp.ones((n_q,), jnp.int32),
              jnp.full((k, n_q), jnp.inf, jnp.float32),
              jnp.full((k, n_q), -1, jnp.int32),
              jnp.zeros((n_q,), jnp.int32),
              jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), jnp.int32),
              jnp.int32(0))

    def cond(state):
        _, sp, _, _, _, _, _, rounds = state
        return jnp.any(sp > 0) & (rounds < max_rounds)

    def body(state):
        stack, sp, best_d, best_i, count, n_box, n_pt, rounds = state
        # named scopes label each stage's device operations in a profile,
        # as in core/wavefront.py; they cost nothing at run time
        with jax.named_scope("neighbor.pop"):
            # frontier pop (masked compaction, as in trace_wavefront)
            active = sp > 0
            node = jnp.where(active, stack[rows, jnp.maximum(sp - 1, 0)], 0)
            sp = jnp.where(active, sp - 1, sp)
            is_leaf_parent = node >= leaf_parent_offset

        # ---- one batched point-box job over the whole frontier ----------
        with jax.named_scope("neighbor.box"):
            pb = point_box_test(p, child_boxes(bvh, node))

        # ---- batched point-distance round for the leaf-parent queries ---
        with jax.named_scope("neighbor.leaf"):
            leaf_pos = (4 * node[:, None] + 1 - leaf_offset
                        + jnp.arange(4, dtype=jnp.int32))
            leaf_pos = jnp.clip(leaf_pos, 0, bvh.leaf_tri.shape[0] - 1)
            cand = bvh.leaf_tri[leaf_pos]  # (R, 4), -1 = padded leaf
            d_sq = leaf_dist_sq(p, points[jnp.maximum(cand, 0)])  # (R, 4)
            in_r = (active[:, None] & is_leaf_parent[:, None]
                    & (cand >= 0) & (d_sq <= r_sq[:, None]))
            count = count + jnp.sum(in_r, axis=1)
            for c in range(4):  # static: 4 insertion beats per round
                best_d, best_i = insert_sorted(
                    best_d, best_i, d_sq[:, c], cand[:, c], in_r[:, c])

        # ---- push surviving children far-to-near ------------------------
        bound = prune_bound(r_sq, best_d[k - 1], mode)

        def push_child(c, carry):
            stack, sp = carry
            slot = 3 - c  # reverse order: farthest first, nearest on top
            ok = (active & ~is_leaf_parent
                  & (pb.dist_sq[:, slot] <= bound))
            child = 4 * node + 1 + pb.box_index[:, slot]
            pos = jnp.minimum(sp, STACK_SIZE - 1)
            cur = stack[rows, pos]
            stack = stack.at[rows, pos].set(jnp.where(ok, child, cur))
            sp = jnp.where(ok, sp + 1, sp)
            return stack, sp

        with jax.named_scope("neighbor.push"):
            stack, sp = jax.lax.fori_loop(0, 4, push_child, (stack, sp))
            n_box = n_box + active.astype(jnp.int32)
            n_pt = n_pt + jnp.where(active & is_leaf_parent, 4, 0)
        return stack, sp, best_d, best_i, count, n_box, n_pt, rounds + 1

    (_, _, best_d, best_i, count, n_box, n_pt, rounds) = jax.lax.while_loop(
        cond, body, state0)
    return NeighborRecord(dist_sq=best_d.T, index=best_i.T,
                          valid=(best_i >= 0).T, count=count,
                          box_jobs=n_box, point_jobs=n_pt, rounds=rounds)
