"""Pallas TPU kernel: the *fused* wavefront-traversal loop (`trace` on-chip).

The batch-level engine (``core/wavefront.py``) already schedules one
OpQuadbox + one OpTriangle round per loop trip, but the loop itself is
ordinary jitted JAX: every round the full SoA ray state — the
``(R, STACK_SIZE)`` traversal stacks, stack pointers, best-hit registers,
job counters — is a ``while_loop`` carry that lives in HBM between rounds.
The hardware the paper models never spills that state: the whole
closest/any-hit loop sits behind one fixed-latency pipeline and the
per-ray context stays resident next to the functional units (the CrossRT
"one accelerated entry point per trace" shape).

This kernel is that residency, TPU-style.  One ``pallas_call`` tile owns
``LANES = 128`` rays; each lane's ray registers and its private
``(STACK_SIZE,)`` stack live in VMEM/VREGs as the carry of an *in-kernel*
``lax.while_loop``, and the full pop → OpQuadbox → OpTriangle → commit →
push round loop runs to completion before anything is written back — one
HBM read of rays + BVH in, one HBM write of hit records out, zero loop
round-trips in between (DESIGN.md §8).

Shared-FU principle
-------------------
The round body calls the *same* stage helpers in ``repro.core.datapath``
(:func:`ray_box_test`, :func:`ray_triangle_test`) as the per-ray and
wavefront engines — one implementation of each stage primitive, reused by
every engine, so hits *and* per-ray job counters bit-match the wavefront
oracle.  Mode selection is ``jax.lax.switch``-free: traversal interleaves
only two opcodes, and like the wavefront engine the tile computes the
OpQuadbox result every round and the OpTriangle round for leaf-parent
lanes, committing each under its ``is_leaf_parent`` mask — a 2-way
predicated datapath rather than a 4-way switched one.

Layout and residency notes
--------------------------
* Rays arrive as one ``(N_RAY_ROWS, LANES)`` union operand per tile
  (origin / direction / inv / shear / k / extent rows), the same
  rows-by-lanes convention as every other kernel here.
* The BVH (node boxes, leaf table, triangle soup) is a *runtime* operand
  mapped whole into every tile — ``Scene.refit`` therefore swaps geometry
  with zero retracing, exactly like the other backends.  The whole tree
  must fit on-chip (a few MB covers the benchmark scenes; production
  trees would stream subtrees, which is future work).
* Per-lane child-box / triangle fetches are cross-lane gathers
  (``jnp.take``).  Off-TPU the kernel runs in interpret mode
  (``kernels/common.resolve_interpret``) where gathers are native; on
  Mosaic they lower to the TPU dynamic-gather path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.bvh import BVH4, DatapathConfig, level_offset, resolve_config
from ..core.datapath import point_box_test, ray_box_test, ray_triangle_test
from ..core.neighbor import (
    NEIGHBOR_MODES,
    NeighborRecord,
    insert_sorted,
    leaf_dist_sq,
    prune_bound,
)
from ..core.traversal import STACK_SIZE
from ..core.types import Box, Ray, Triangle
from ..core.wavefront import RAY_TYPES, SHADOW_T_MIN, WavefrontRecord, _tile_ray
from .common import LANES, ceil_to, pad_cols, resolve_interpret

# Ray operand row layout: one (N_RAY_ROWS, LANES) union bundle per tile.
ROW_T_ORG = 0  # rows 0..2   origin
ROW_T_DIR = 3  # rows 3..5   direction (sign bits drive the slab swap)
ROW_T_INV = 6  # rows 6..8   inverse direction
ROW_T_SHEAR = 9  # rows 9..11  shear constants Sx,Sy,Sz
ROW_T_K = 12  # rows 12..14 kx,ky,kz as f32
ROW_T_EXT = 15  # row 15      extent
N_RAY_ROWS = 16  # multiple of 8 (f32 sublane tile)


def _unpack_ray(op: jax.Array) -> Ray:
    """(N_RAY_ROWS, L) operand rows -> an (L,)-batched :class:`Ray`."""
    return Ray(
        origin=op[ROW_T_ORG:ROW_T_ORG + 3].T,
        direction=op[ROW_T_DIR:ROW_T_DIR + 3].T,
        inv=op[ROW_T_INV:ROW_T_INV + 3].T,
        extent=op[ROW_T_EXT],
        kx=op[ROW_T_K].astype(jnp.int32),
        ky=op[ROW_T_K + 1].astype(jnp.int32),
        kz=op[ROW_T_K + 2].astype(jnp.int32),
        shear=op[ROW_T_SHEAR:ROW_T_SHEAR + 3].T,
    )


def _traverse_kernel(ray_ref, nlo_ref, nhi_ref, leaf_ref, tri_ref,
                     t_ref, tri_out_ref, qb_ref, ntri_ref, ovf_ref,
                     rounds_ref, *, depth: int, ray_type: str, t_min: float,
                     max_rounds: int, n_leaf: int, config: DatapathConfig):
    """One tile = 128 rays traversed to completion inside the kernel."""
    arity, stack_size = config.arity, config.stack_size
    ray = _unpack_ray(ray_ref[...])
    # (3, num_nodes_pad); bf16/compressed configs store real bf16 rows —
    # the upcast is lossless (values sit on the bf16 grid by construction),
    # so results stay bit-identical to the wavefront engine's f32 arrays
    node_lo = nlo_ref[...].astype(jnp.float32)
    node_hi = nhi_ref[...].astype(jnp.float32)
    leaf_tri_tab = leaf_ref[0, :]  # (n_leaf_pad,) i32
    tri_rows = tri_ref[...]  # (9, n_tri_pad): rows a.xyz | b.xyz | c.xyz

    leaf_parent_offset = level_offset(depth - 1, arity)
    leaf_offset = level_offset(depth, arity)
    lanes = jnp.arange(LANES, dtype=jnp.int32)
    quad = jnp.arange(arity, dtype=jnp.int32)

    # lane-private traversal state: stacks are (stack_size, LANES) columns,
    # everything is while-carry so it never leaves VMEM/VREGs mid-loop
    stack0 = jnp.zeros((stack_size, LANES), jnp.int32)  # root pre-pushed
    state0 = (stack0, jnp.ones((LANES,), jnp.int32),
              jnp.full((LANES,), jnp.inf, jnp.float32),
              jnp.full((LANES,), -1, jnp.int32),
              jnp.zeros((LANES,), jnp.int32), jnp.zeros((LANES,), jnp.int32),
              jnp.zeros((LANES,), bool), jnp.zeros((LANES,), bool),
              jnp.int32(0))

    def cond(state):
        _, sp, _, _, _, _, _, done, rounds = state
        return jnp.any((sp > 0) & ~done) & (rounds < max_rounds)

    def body(state):
        stack, sp, t_best, best_tri, n_qb, n_tri, overflow, done, rounds = state
        active = (sp > 0) & ~done

        # frontier pop (masked: retired lanes contribute no jobs)
        top = jnp.take_along_axis(stack, jnp.maximum(sp - 1, 0)[None, :],
                                  axis=0)[0]
        node = jnp.where(active, top, 0)
        sp = jnp.where(active, sp - 1, sp)
        is_leaf_parent = node >= leaf_parent_offset
        base = arity * node + 1

        # ---- box test: the popped node's `arity` child AABBs, per lane -----
        cidx = base[:, None] + quad[None, :]  # (L, arity)
        lo = jnp.moveaxis(jnp.take(node_lo, cidx, axis=1), 0, -1)  # (L,A,3)
        hi = jnp.moveaxis(jnp.take(node_hi, cidx, axis=1), 0, -1)
        qb = ray_box_test(ray, Box(lo=lo, hi=hi))  # shared stage helper

        # ---- OpTriangle round for leaf-parent lanes ------------------------
        leaf_pos = base[:, None] - leaf_offset + quad[None, :]
        leaf_pos = jnp.clip(leaf_pos, 0, n_leaf - 1)
        tri_idx = jnp.take(leaf_tri_tab, leaf_pos)  # (L, arity), -1 = padded
        tv = jnp.take(tri_rows, jnp.maximum(tri_idx, 0), axis=1)  # (9,L,A)
        tris = Triangle(a=jnp.moveaxis(tv[0:3], 0, -1),
                        b=jnp.moveaxis(tv[3:6], 0, -1),
                        c=jnp.moveaxis(tv[6:9], 0, -1))
        tr = ray_triangle_test(_tile_ray(ray, arity), tris)  # shared helper
        t = tr.t_num / tr.t_denom  # external division, as everywhere
        valid = (tr.hit & (tri_idx >= 0) & (t < t_best[:, None])
                 & (t <= ray.extent[:, None]) & (t >= t_min))
        t_masked = jnp.where(valid, t, jnp.inf)
        j = jnp.argmin(t_masked, axis=1)
        leaf_t = jnp.take_along_axis(t_masked, j[:, None], axis=1)[:, 0]
        leaf_better = active & is_leaf_parent & (leaf_t < t_best)
        t_best = jnp.where(leaf_better, leaf_t, t_best)
        best_tri = jnp.where(
            leaf_better,
            jnp.take_along_axis(tri_idx, j[:, None], axis=1)[:, 0], best_tri)
        if ray_type != "closest":  # any-hit: retire on first accepted hit
            done = done | leaf_better

        # ---- push hit children far-to-near (sort-network output order) -----
        for i in range(arity):
            slot = arity - 1 - i  # farthest first, nearest ends on top
            ok = (active & ~is_leaf_parent & qb.is_intersect[:, slot]
                  & (qb.tmin[:, slot] < t_best))
            child = base + qb.box_index[:, slot]
            can = ok & (sp < stack_size)  # drop-and-flag at capacity
            overflow = overflow | (ok & (sp >= stack_size))
            pos = jnp.minimum(sp, stack_size - 1)
            cur = jnp.take_along_axis(stack, pos[None, :], axis=0)[0]
            stack = stack.at[pos, lanes].set(jnp.where(can, child, cur))
            sp = jnp.where(can, sp + 1, sp)

        n_qb = n_qb + active.astype(jnp.int32)
        n_tri = n_tri + jnp.where(active & is_leaf_parent, arity, 0)
        return (stack, sp, t_best, best_tri, n_qb, n_tri, overflow, done,
                rounds + 1)

    (_, _, t_best, best_tri, n_qb, n_tri, overflow, _, rounds
     ) = jax.lax.while_loop(cond, body, state0)

    t_ref[0, :] = t_best
    tri_out_ref[0, :] = best_tri
    qb_ref[0, :] = n_qb
    ntri_ref[0, :] = n_tri
    ovf_ref[0, :] = overflow.astype(jnp.int32)
    rounds_ref[0, :] = jnp.full((LANES,), rounds, jnp.int32)


def _pad_cols_repeat(x: jax.Array, n_to: int) -> jax.Array:
    """Pad the last axis to ``n_to`` by repeating column 0 (a valid ray)."""
    pad = n_to - x.shape[-1]
    if pad == 0:
        return x
    rep = jnp.broadcast_to(x[..., :1], x.shape[:-1] + (pad,))
    return jnp.concatenate([x, rep], axis=-1)


def pack_rays(rays: Ray, n_pad: int) -> jax.Array:
    """(R,)-batched rays -> one (N_RAY_ROWS, n_pad) union operand, columns
    past R repeating ray 0 (always valid, results sliced off)."""
    op = jnp.zeros((N_RAY_ROWS, rays.origin.shape[0]), jnp.float32)
    op = op.at[ROW_T_ORG:ROW_T_ORG + 3].set(rays.origin.T)
    op = op.at[ROW_T_DIR:ROW_T_DIR + 3].set(rays.direction.T)
    op = op.at[ROW_T_INV:ROW_T_INV + 3].set(rays.inv.T)
    op = op.at[ROW_T_SHEAR:ROW_T_SHEAR + 3].set(rays.shear.T)
    op = op.at[ROW_T_K:ROW_T_K + 3].set(
        jnp.stack([rays.kx, rays.ky, rays.kz]).astype(jnp.float32))
    op = op.at[ROW_T_EXT].set(rays.extent)
    return _pad_cols_repeat(op, n_pad)


def pack_bvh(bvh: BVH4, config: DatapathConfig | None = None):
    """BVH -> the kernel's resident operands (node boxes transposed to
    rows-by-nodes, leaf table, triangle soup as 9 vertex rows), each
    column-padded to a lane multiple.  Padded node columns carry inverted
    boxes (can never intersect); padded leaf slots carry -1.

    Reduced-precision configs pack the node rows as genuine bf16 — the
    build-side codec already snapped every box to the bf16 grid, so the
    cast is lossless and the kernel's upcast recovers the wavefront
    engine's exact f32 values while halving resident node bytes."""
    config = resolve_config(config)
    n_nodes = bvh.node_lo.shape[0]
    nodes_pad = ceil_to(n_nodes, LANES)
    box_dtype = config.packed_box_dtype
    nlo = pad_cols(bvh.node_lo.T, nodes_pad, jnp.inf).astype(box_dtype)
    nhi = pad_cols(bvh.node_hi.T, nodes_pad, -jnp.inf).astype(box_dtype)
    leaf_pad = ceil_to(bvh.leaf_tri.shape[0], LANES)
    leaf = pad_cols(bvh.leaf_tri[None, :].astype(jnp.int32), leaf_pad, -1)
    tri_pad = ceil_to(bvh.triangles.a.shape[0], LANES)
    tri_rows = pad_cols(
        jnp.concatenate([bvh.triangles.a.T, bvh.triangles.b.T,
                         bvh.triangles.c.T], axis=0), tri_pad)
    return nlo, nhi, leaf, tri_rows


@functools.partial(jax.jit, static_argnames=("depth", "ray_type", "t_min",
                                             "max_rounds", "interpret",
                                             "config"))
def traverse_packed(packed, rays: Ray, depth: int, *,
                    ray_type: str = "closest", t_min: float | None = None,
                    max_rounds: int | None = None,
                    interpret: bool | None = None,
                    config: DatapathConfig | None = None) -> WavefrontRecord:
    """:func:`traverse_fused` on pre-packed BVH operands.

    ``packed`` is :func:`pack_bvh`'s output — the session engine prepares
    it once per scene version and re-feeds it per chunk/shard, so the
    O(scene) transpose/pad work is not re-executed inside every compiled
    call (the backend ``prepare`` hook, DESIGN.md §8).
    """
    if ray_type not in RAY_TYPES:
        raise ValueError(
            f"ray_type must be one of {RAY_TYPES}, got {ray_type!r}")
    config = resolve_config(config)
    if t_min is None:
        t_min = SHADOW_T_MIN if ray_type == "shadow" else 0.0
    if max_rounds is None:
        # exact bound: one pop per node
        max_rounds = level_offset(depth, config.arity)
    interpret = resolve_interpret(interpret)

    n = rays.origin.shape[0]
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        return WavefrontRecord(t=jnp.zeros((0,), jnp.float32), tri_index=z,
                               hit=jnp.zeros((0,), bool), quadbox_jobs=z,
                               triangle_jobs=z,
                               stack_overflow=jnp.zeros((0,), bool),
                               rounds=jnp.int32(0))
    n_pad = ceil_to(n, LANES)
    ray_op = pack_rays(rays, n_pad)
    nlo, nhi, leaf, tri_rows = packed
    n_leaf = config.arity ** depth  # true (pre-padding) leaf count

    kernel = functools.partial(
        _traverse_kernel, depth=depth, ray_type=ray_type, t_min=float(t_min),
        max_rounds=int(max_rounds), n_leaf=n_leaf, config=config)
    whole = lambda shape: pl.BlockSpec(shape, lambda t: (0, 0))  # noqa: E731
    out_t, out_tri, out_qb, out_ntri, out_ovf, out_rounds = pl.pallas_call(
        kernel,
        grid=(n_pad // LANES,),
        in_specs=[
            pl.BlockSpec((N_RAY_ROWS, LANES), lambda t: (0, t)),
            whole(nlo.shape),
            whole(nhi.shape),
            whole(leaf.shape),
            whole(tri_rows.shape),
        ],
        out_specs=(
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        ),
        interpret=interpret,
    )(ray_op, nlo, nhi, leaf, tri_rows)

    best_tri = out_tri[0, :n]
    # batch round count = max over tiles of the per-tile round count (a ray
    # is active for exactly quadbox_jobs consecutive rounds wherever it
    # runs, so this equals the wavefront engine's batch-level value)
    return WavefrontRecord(t=out_t[0, :n], tri_index=best_tri,
                           hit=best_tri >= 0,
                           quadbox_jobs=out_qb[0, :n],
                           triangle_jobs=out_ntri[0, :n],
                           stack_overflow=out_ovf[0, :n] > 0,
                           rounds=jnp.max(out_rounds))


# ---------------------------------------------------------------------------
# Fused neighbor traversal: kNN/radius queries with the loop on-chip
# ---------------------------------------------------------------------------
#
# The distance twin of `_traverse_kernel` (RTNN on the fused engine): same
# tile shape, same lane-private stack residency, same whole-tree runtime
# operands — but rounds order children by point-box *distance* and leaf
# visits feed a running top-k insertion network instead of a best-hit
# register.  The round body calls the same stage helpers as
# `core/neighbor.neighbor_wavefront` (point_box_test, leaf_dist_sq,
# insert_sorted, prune_bound), so both engines' leaf distances and
# acceptance are the same float computation.


def pack_point_bvh(bvh: BVH4):
    """Point BVH4 -> the neighbor kernel's resident operands.

    Node boxes and the leaf table pack exactly like :func:`pack_bvh`; the
    cloud packs as 3 rows (x, y, z), read from the same array the tree
    holds, so re-packing a refit BVH serves the moved points.
    """
    n_nodes = bvh.node_lo.shape[0]
    nodes_pad = ceil_to(n_nodes, LANES)
    nlo = pad_cols(bvh.node_lo.T, nodes_pad, jnp.inf)
    nhi = pad_cols(bvh.node_hi.T, nodes_pad, -jnp.inf)
    leaf_pad = ceil_to(bvh.leaf_tri.shape[0], LANES)
    leaf = pad_cols(bvh.leaf_tri[None, :].astype(jnp.int32), leaf_pad, -1)
    pts = bvh.triangles.a  # the cloud (see core/build/points.py)
    pts_pad = ceil_to(pts.shape[0], LANES)
    pt_rows = pad_cols(pts.T, pts_pad)
    return nlo, nhi, leaf, pt_rows


def _neighbor_kernel(ray_ref, nlo_ref, nhi_ref, leaf_ref, pts_ref,
                     d_ref, i_ref, cnt_ref, bj_ref, pj_ref, rounds_ref, *,
                     depth: int, k: int, mode: str, max_rounds: int,
                     n_leaf: int):
    """One tile = 128 queries searched to completion inside the kernel."""
    ray = _unpack_ray(ray_ref[...])
    node_lo = nlo_ref[...]  # (3, num_nodes_pad)
    node_hi = nhi_ref[...]
    leaf_tab = leaf_ref[0, :]  # (n_leaf_pad,) i32
    pt_rows = pts_ref[...]  # (3, n_pts_pad): rows x | y | z

    p = ray.origin  # (L, 3): the query points
    r_sq = ray.extent * ray.extent  # inf extent -> inf bound

    leaf_parent_offset = level_offset(depth - 1)
    leaf_offset = level_offset(depth)
    lanes = jnp.arange(LANES, dtype=jnp.int32)
    quad = jnp.arange(4, dtype=jnp.int32)

    stack0 = jnp.zeros((STACK_SIZE, LANES), jnp.int32)  # root pre-pushed
    state0 = (stack0, jnp.ones((LANES,), jnp.int32),
              jnp.full((k, LANES), jnp.inf, jnp.float32),
              jnp.full((k, LANES), -1, jnp.int32),
              jnp.zeros((LANES,), jnp.int32),
              jnp.zeros((LANES,), jnp.int32), jnp.zeros((LANES,), jnp.int32),
              jnp.int32(0))

    def cond(state):
        _, sp, _, _, _, _, _, rounds = state
        return jnp.any(sp > 0) & (rounds < max_rounds)

    def body(state):
        stack, sp, best_d, best_i, count, n_box, n_pt, rounds = state
        active = sp > 0

        # frontier pop (masked: retired lanes contribute no jobs)
        top = jnp.take_along_axis(stack, jnp.maximum(sp - 1, 0)[None, :],
                                  axis=0)[0]
        node = jnp.where(active, top, 0)
        sp = jnp.where(active, sp - 1, sp)
        is_leaf_parent = node >= leaf_parent_offset
        base = 4 * node + 1

        # ---- point-box job: the popped node's 4 child AABBs, per lane ----
        cidx = base[:, None] + quad[None, :]  # (L, 4)
        lo = jnp.moveaxis(jnp.take(node_lo, cidx, axis=1), 0, -1)  # (L,4,3)
        hi = jnp.moveaxis(jnp.take(node_hi, cidx, axis=1), 0, -1)
        pb = point_box_test(p, Box(lo=lo, hi=hi))  # shared stage helper

        # ---- point-distance round for leaf-parent lanes ------------------
        leaf_pos = base[:, None] - leaf_offset + quad[None, :]
        leaf_pos = jnp.clip(leaf_pos, 0, n_leaf - 1)
        cand = jnp.take(leaf_tab, leaf_pos)  # (L, 4), -1 = padded leaf
        pv = jnp.take(pt_rows, jnp.maximum(cand, 0), axis=1)  # (3, L, 4)
        pts = jnp.moveaxis(pv, 0, -1)  # (L, 4, 3)
        d_sq = leaf_dist_sq(p, pts)  # difference form, (L, 4)
        in_r = (active[:, None] & is_leaf_parent[:, None]
                & (cand >= 0) & (d_sq <= r_sq[:, None]))
        count = count + jnp.sum(in_r, axis=1)
        for c in range(4):  # static: 4 insertion beats per round
            best_d, best_i = insert_sorted(
                best_d, best_i, d_sq[:, c], cand[:, c], in_r[:, c])

        # ---- push surviving children far-to-near -------------------------
        bound = prune_bound(r_sq, best_d[k - 1], mode)
        for c in range(4):
            slot = 3 - c  # farthest first, nearest ends on top
            ok = (active & ~is_leaf_parent
                  & (pb.dist_sq[:, slot] <= bound))
            child = base + pb.box_index[:, slot]
            pos = jnp.minimum(sp, STACK_SIZE - 1)
            cur = jnp.take_along_axis(stack, pos[None, :], axis=0)[0]
            stack = stack.at[pos, lanes].set(jnp.where(ok, child, cur))
            sp = jnp.where(ok, sp + 1, sp)

        n_box = n_box + active.astype(jnp.int32)
        n_pt = n_pt + jnp.where(active & is_leaf_parent, 4, 0)
        return stack, sp, best_d, best_i, count, n_box, n_pt, rounds + 1

    (_, _, best_d, best_i, count, n_box, n_pt, rounds) = jax.lax.while_loop(
        cond, body, state0)

    d_ref[...] = best_d
    i_ref[...] = best_i
    cnt_ref[0, :] = count
    bj_ref[0, :] = n_box
    pj_ref[0, :] = n_pt
    rounds_ref[0, :] = jnp.full((LANES,), rounds, jnp.int32)


@functools.partial(jax.jit, static_argnames=("depth", "k", "mode",
                                             "max_rounds", "interpret"))
def neighbor_packed(packed, queries: Ray, depth: int, k: int, *,
                    mode: str = "within", max_rounds: int | None = None,
                    interpret: bool | None = None) -> NeighborRecord:
    """:func:`neighbor_fused` on pre-packed point-BVH operands.

    ``packed`` is :func:`pack_point_bvh`'s output — prepared once per
    cloud version by the session engine and re-fed per chunk/shard,
    mirroring :func:`traverse_packed`.
    """
    if mode not in NEIGHBOR_MODES:
        raise ValueError(
            f"mode must be one of {NEIGHBOR_MODES}, got {mode!r}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if max_rounds is None:
        max_rounds = level_offset(depth)  # exact bound: one pop per node
    interpret = resolve_interpret(interpret)

    n = queries.origin.shape[0]
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        return NeighborRecord(
            dist_sq=jnp.zeros((0, k), jnp.float32),
            index=jnp.zeros((0, k), jnp.int32),
            valid=jnp.zeros((0, k), bool), count=z, box_jobs=z,
            point_jobs=z, rounds=jnp.int32(0))
    n_pad = ceil_to(n, LANES)
    ray_op = pack_rays(queries, n_pad)
    nlo, nhi, leaf, pt_rows = packed
    n_leaf = 4 ** depth  # true (pre-padding) leaf count

    kernel = functools.partial(
        _neighbor_kernel, depth=depth, k=int(k), mode=mode,
        max_rounds=int(max_rounds), n_leaf=n_leaf)
    whole = lambda shape: pl.BlockSpec(shape, lambda t: (0, 0))  # noqa: E731
    out_d, out_i, out_cnt, out_bj, out_pj, out_rounds = pl.pallas_call(
        kernel,
        grid=(n_pad // LANES,),
        in_specs=[
            pl.BlockSpec((N_RAY_ROWS, LANES), lambda t: (0, t)),
            whole(nlo.shape),
            whole(nhi.shape),
            whole(leaf.shape),
            whole(pt_rows.shape),
        ],
        out_specs=(
            pl.BlockSpec((k, LANES), lambda t: (0, t)),
            pl.BlockSpec((k, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((k, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        ),
        interpret=interpret,
    )(ray_op, nlo, nhi, leaf, pt_rows)

    best_i = out_i[:, :n].T
    return NeighborRecord(dist_sq=out_d[:, :n].T, index=best_i,
                          valid=best_i >= 0, count=out_cnt[0, :n],
                          box_jobs=out_bj[0, :n], point_jobs=out_pj[0, :n],
                          rounds=jnp.max(out_rounds))


def neighbor_fused(bvh: BVH4, queries: Ray, depth: int, k: int, *,
                   mode: str = "within", max_rounds: int | None = None,
                   interpret: bool | None = None) -> NeighborRecord:
    """Neighbor-search a query batch with the whole round loop on-chip.

    Same contract as :func:`repro.core.neighbor.neighbor_wavefront`
    (whose record type it returns): ``queries`` are
    :func:`~repro.core.neighbor.point_queries` rays carrying the radius
    as extent; ``k`` / ``mode`` / ``max_rounds`` are static.  The packed
    BVH is a runtime argument, so ``PointCloudScene.refit`` re-enters the
    compiled kernel with zero retracing.  Convenience entry point packing
    per call; repeated queries should go through the session engine.
    """
    return neighbor_packed(pack_point_bvh(bvh), queries, depth, k,
                           mode=mode, max_rounds=max_rounds,
                           interpret=interpret)


def traverse_fused(bvh: BVH4, rays: Ray, depth: int, *,
                   ray_type: str = "closest", t_min: float | None = None,
                   max_rounds: int | None = None,
                   interpret: bool | None = None,
                   config: DatapathConfig | None = None) -> WavefrontRecord:
    """Traverse a ray batch with the whole round loop inside one kernel.

    Same contract as :func:`repro.core.wavefront.trace_wavefront` (whose
    record type it returns, bit for bit): ``rays`` carry one leading batch
    axis; ``ray_type`` / ``t_min`` / ``max_rounds`` are static, with the
    same defaults.  The BVH is a runtime argument, so ``Scene.refit``
    re-enters the compiled kernel with zero retracing.
    ``interpret=None`` auto-selects interpret mode off-TPU.

    Convenience entry point packing the BVH per call; repeated queries on
    one scene should go through the session engine, which prepares
    :func:`pack_bvh` once per scene version and calls
    :func:`traverse_packed`.
    """
    config = resolve_config(config)
    return traverse_packed(pack_bvh(bvh, config), rays, depth,
                           ray_type=ray_type, t_min=t_min,
                           max_rounds=max_rounds, interpret=interpret,
                           config=config)
