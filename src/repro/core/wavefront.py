"""Wavefront BVH4 traversal: one batched datapath job stream per round.

:func:`repro.core.traversal.trace_rays` vmaps a per-ray ``lax.while_loop``:
every ray owns a private loop, so under vmap the whole batch iterates until
the *slowest* ray's stack drains and every other lane idles along masked.
The hardware the paper models does the opposite — a scheduler keeps one
fixed-latency pipeline full of heterogeneous jobs drawn from *all* in-flight
rays (RTNN-style wavefront/batched query scheduling).

This module is that scheduler's TPU analogue.  The loop lives at the *batch*
level and each round issues:

* one batched **OpQuadbox** job over the whole active frontier (every active
  ray pops its stack top and tests the node's 4 child AABBs at once), and
* one batched round of **OpTriangle** jobs (4 per active leaf-parent ray),

both through the shared stage helpers in :mod:`repro.core.datapath` — the
same functional units the per-ray engine uses, so closest-hit results
bit-match :func:`trace_rays` (it remains the semantic oracle).

State is SoA across the batch (stacks ``(R, STACK_SIZE)``, stack pointers
``(R,)``); terminated rays are compacted out of each round via masking, and
the loop carries a fixed round bound with early exit once the frontier is
empty (DESIGN.md §3).

Three query types (CrossRT-style closest-hit/any-hit split):

* ``"closest"`` — full closest-hit traversal (identical results to
  :func:`trace_rays`),
* ``"any"``     — any-hit / occlusion: a ray retires on its *first* accepted
  hit inside the extent; no closest-hit ordering is paid for,
* ``"shadow"``  — any-hit for extent-limited shadow rays, with a ``t_min``
  epsilon so a ray leaving a surface does not re-hit it at t ~ 0.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .bvh import BVH4, DatapathConfig, child_boxes, level_offset, resolve_config
from .datapath import ray_box_test, ray_triangle_test
from .traversal import STACK_SIZE, _gather_triangles

RAY_TYPES = ("closest", "any", "shadow")


class WavefrontRecord(NamedTuple):
    """Per-ray results plus the frontier-level scheduling statistics."""

    t: jax.Array  # (R,) f32  hit distance (inf = miss)
    tri_index: jax.Array  # (R,) i32  index into the soup, -1 = miss
    hit: jax.Array  # (R,) bool
    quadbox_jobs: jax.Array  # (R,) i32  per-ray OpQuadbox jobs issued
    triangle_jobs: jax.Array  # (R,) i32  per-ray OpTriangle jobs issued
    stack_overflow: jax.Array  # (R,) bool  a push was dropped at capacity
    rounds: jax.Array  # ()   i32  batched rounds = batched OpQuadbox jobs


def _tile_ray(rays, width: int):
    """(R,)-batched Ray -> (R, width)-batched Ray (shared across lanes)."""
    return type(rays)(*[
        jnp.broadcast_to(f[:, None, ...], (f.shape[0], width) + f.shape[1:])
        for f in rays
    ])


SHADOW_T_MIN = 1e-3  # default self-intersection epsilon for shadow rays


def trace_wavefront(bvh: BVH4, rays, depth: int, ray_type: str = "closest",
                    t_min: float | None = None,
                    max_rounds: int | None = None,
                    config: DatapathConfig | None = None) -> WavefrontRecord:
    """Traverse a whole ray batch with one batch-level loop.

    ``rays`` must carry a single leading batch axis (flatten first).
    ``ray_type`` and ``max_rounds`` are static; ``max_rounds`` defaults to the
    internal-node count (each node is popped at most once per ray, so that
    bound is exact, not a heuristic).  ``t_min`` rejects hits nearer than the
    epsilon; it defaults to 0 (accept everything — hits always have t > 0)
    except for ``"shadow"`` rays, which default to :data:`SHADOW_T_MIN` so a
    ray leaving a surface does not re-hit it at t ~ 0.
    """
    if ray_type not in RAY_TYPES:
        raise ValueError(f"ray_type must be one of {RAY_TYPES}, got {ray_type!r}")
    if t_min is None:
        t_min = SHADOW_T_MIN if ray_type == "shadow" else 0.0
    config = resolve_config(config)
    arity, stack_size = config.arity, config.stack_size
    leaf_parent_offset = level_offset(depth - 1, arity)
    leaf_offset = level_offset(depth, arity)
    if max_rounds is None:
        max_rounds = level_offset(depth, arity)  # = number of internal nodes

    n_rays = rays.origin.shape[0]
    rows = jnp.arange(n_rays, dtype=jnp.int32)
    t_min = jnp.float32(t_min)

    stack0 = jnp.zeros((n_rays, stack_size), jnp.int32)  # root pre-pushed
    state0 = (stack0, jnp.ones((n_rays,), jnp.int32),
              jnp.full((n_rays,), jnp.inf, jnp.float32),
              jnp.full((n_rays,), -1, jnp.int32),
              jnp.zeros((n_rays,), jnp.int32), jnp.zeros((n_rays,), jnp.int32),
              jnp.zeros((n_rays,), bool), jnp.zeros((n_rays,), bool),
              jnp.int32(0))

    def cond(state):
        _, sp, _, _, _, _, _, done, rounds = state
        return jnp.any((sp > 0) & ~done) & (rounds < max_rounds)

    def body(state):
        stack, sp, t_best, best_tri, n_qb, n_tri, overflow, done, rounds = state
        # the named scopes label each stage's device operations in a
        # profile (repro.obs.programs.op_scopes); they cost nothing at run
        # time
        with jax.named_scope("wavefront.pop"):
            # frontier pop (masked compaction: retired rays add no jobs)
            active = (sp > 0) & ~done
            node = jnp.where(active, stack[rows, jnp.maximum(sp - 1, 0)], 0)
            sp = jnp.where(active, sp - 1, sp)
            is_leaf_parent = node >= leaf_parent_offset

        # ---- one batched box-test job over the whole frontier ---------------
        with jax.named_scope("wavefront.box"):
            boxes = child_boxes(bvh, node, arity)  # (R, arity, lo/hi)
            qb = ray_box_test(rays, boxes)

        # ---- batched OpTriangle round for the leaf-parent rays --------------
        with jax.named_scope("wavefront.triangle"):
            leaf_pos = (arity * node[:, None] + 1 - leaf_offset
                        + jnp.arange(arity, dtype=jnp.int32))
            leaf_pos = jnp.clip(leaf_pos, 0, bvh.leaf_tri.shape[0] - 1)
            tri_idx = bvh.leaf_tri[leaf_pos]  # (R, arity), -1 = padded leaf
            tris = _gather_triangles(bvh.triangles, tri_idx)
            tr = ray_triangle_test(_tile_ray(rays, arity), tris)
            t = tr.t_num / tr.t_denom  # external division, as in trace_ray
            valid = (tr.hit & (tri_idx >= 0) & (t < t_best[:, None])
                     & (t <= rays.extent[:, None]) & (t >= t_min))
            t_masked = jnp.where(valid, t, jnp.inf)
            j = jnp.argmin(t_masked, axis=1)
            leaf_t = t_masked[rows, j]
            leaf_better = active & is_leaf_parent & (leaf_t < t_best)
            t_best = jnp.where(leaf_better, leaf_t, t_best)
            best_tri = jnp.where(leaf_better, tri_idx[rows, j], best_tri)
            if ray_type != "closest":  # any-hit: retire on the first hit
                done = done | leaf_better

        # ---- push hit children far-to-near (sort-network output order) ------
        def push_child(i, carry):
            stack, sp, overflow = carry
            slot = arity - 1 - i  # reverse: farthest first, nearest on top
            ok = (active & ~is_leaf_parent & qb.is_intersect[:, slot]
                  & (qb.tmin[:, slot] < t_best))
            child = arity * node + 1 + qb.box_index[:, slot]
            can = ok & (sp < stack_size)  # drop-and-flag at capacity
            overflow = overflow | (ok & (sp >= stack_size))
            pos = jnp.minimum(sp, stack_size - 1)
            cur = stack[rows, pos]
            stack = stack.at[rows, pos].set(jnp.where(can, child, cur))
            sp = jnp.where(can, sp + 1, sp)
            return stack, sp, overflow

        with jax.named_scope("wavefront.push"):
            stack, sp, overflow = jax.lax.fori_loop(
                0, arity, push_child, (stack, sp, overflow))
            n_qb = n_qb + active.astype(jnp.int32)
            n_tri = n_tri + jnp.where(active & is_leaf_parent, arity, 0)
        return (stack, sp, t_best, best_tri, n_qb, n_tri, overflow, done,
                rounds + 1)

    (_, _, t_best, best_tri, n_qb, n_tri, overflow, _, rounds
     ) = jax.lax.while_loop(cond, body, state0)
    return WavefrontRecord(t=t_best, tri_index=best_tri, hit=best_tri >= 0,
                           quadbox_jobs=n_qb, triangle_jobs=n_tri,
                           stack_overflow=overflow, rounds=rounds)


def occlusion_test(bvh: BVH4, rays, depth: int,
                   t_min: float = SHADOW_T_MIN) -> jax.Array:
    """Boolean shadow/visibility query: is anything hit within each ray's
    extent?  Rays should be built with ``extent=`` distance-to-light for
    point lights (extent-limited) or inf for directional lights."""
    return trace_wavefront(bvh, rays, depth, ray_type="shadow", t_min=t_min).hit
