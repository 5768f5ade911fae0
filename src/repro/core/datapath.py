"""The unified Ray Tracer Datapath, stage-for-stage per paper Table VII.

Each mode is written as a sequence of named stage functions so that the
arithmetic *and its association order* match the hardware pipeline exactly:
the Pallas kernels in ``repro.kernels`` share these stage helpers, which is
the TPU analogue of the paper's "functional units are shared" design choice
(§III-B) — one implementation of each stage primitive, reused by every mode.

FP semantics
------------
* The hardware rounds after every functional unit (§III-D); on TPU every
  VPU op rounds to f32, so computing in f32 reproduces that choice natively.
* Hardware comparators (`RecFNCompareSelect`) return *false* on NaN inputs,
  so min/max built from compare-and-select keep the previous operand when a
  NaN appears.  We mirror that with explicit ``jnp.where(a < b, ...)``
  selects rather than ``jnp.minimum`` (which propagates NaN).  This also
  reproduces the tavianator "boundaries" robustness the paper's ray-box
  algorithm relies on (0 * inf = NaN slabs are ignored, not propagated).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .types import (
    ANGULAR_LANES,
    VECTOR_LANES,
    AngularResult,
    Box,
    DatapathState,
    EuclideanResult,
    PointBoxResult,
    QuadBoxResult,
    Ray,
    Triangle,
    TriangleResult,
)

# ---------------------------------------------------------------------------
# Shared stage primitives (the "functional units")
# ---------------------------------------------------------------------------


def cmp_select(a: jax.Array, b: jax.Array, lt: jax.Array | None = None):
    """Hardware-style compare-and-swap: returns (min-ish, max-ish).

    NaN behaviour matches a comparator+mux: if the compare is false (as it is
    for NaN), the operands pass through unswapped.
    """
    if lt is None:
        lt = a < b
    return jnp.where(lt, a, b), jnp.where(lt, b, a)


def fmax(a: jax.Array, b: jax.Array) -> jax.Array:
    """max via comparator: returns ``b`` when the compare is false (incl. NaN a)."""
    return jnp.where(a > b, a, b)


def fmin(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.where(a < b, a, b)


# Compare-exchange schedules per sort width.  4 is the paper's
# QuadSortRecFN network; 8 is Batcher's odd-even merge sort (19 CE) for the
# BVH8 datapath twin (DatapathConfig.arity == 8).
SORT_NETWORKS = {
    4: [(0, 1), (2, 3), (0, 2), (1, 3), (1, 2)],
    8: [(0, 1), (2, 3), (4, 5), (6, 7),
        (0, 2), (1, 3), (4, 6), (5, 7),
        (1, 2), (5, 6),
        (0, 4), (1, 5), (2, 6), (3, 7),
        (2, 4), (3, 5),
        (1, 2), (3, 4), (5, 6)],
}


def boxsort(keys: jax.Array, *payloads: jax.Array):
    """Fixed-width sorting network over the trailing axis.

    ``keys``: (..., W) with ``W`` in :data:`SORT_NETWORKS`.  Payload arrays
    are permuted alongside the keys.  Width 4 runs the paper's exact
    QuadSortRecFN schedule (see :func:`quadsort`); width 8 runs Batcher's
    odd-even merge network.
    """
    width = keys.shape[-1]
    pairs = SORT_NETWORKS[width]
    cols = [keys[..., i] for i in range(width)]
    pl = [[p[..., i] for i in range(width)] for p in payloads]

    def cas(i, j):
        lt = cols[i] < cols[j]
        cols[i], cols[j] = jnp.where(lt, cols[i], cols[j]), jnp.where(lt, cols[j], cols[i])
        for p in pl:
            p[i], p[j] = jnp.where(lt, p[i], p[j]), jnp.where(lt, p[j], p[i])

    for i, j in pairs:
        cas(i, j)
    out_keys = jnp.stack(cols, axis=-1)
    out_payloads = tuple(jnp.stack(p, axis=-1) for p in pl)
    return (out_keys, *out_payloads)


def quadsort(keys: jax.Array, *payloads: jax.Array):
    """Paper's QuadSortRecFN: 4-input sorting network (5 compare-exchanges).

    ``keys``: (..., 4).  Payload arrays are permuted alongside the keys (this
    is QuadSortRecFNWithIndex when a payload is ``arange(4)``).  Stable for
    the (0,1)(2,3)(0,2)(1,3)(1,2) network under ``<`` compares.
    """
    assert keys.shape[-1] == 4, keys.shape
    return boxsort(keys, *payloads)


# ---------------------------------------------------------------------------
# OpQuadbox: one ray vs four AABBs (Table VII "Box" column)
# ---------------------------------------------------------------------------


def ray_box_test(ray: Ray, boxes: Box) -> QuadBoxResult:
    """Batched ray-vs-W-AABB intersection (W = 4 or 8 child boxes).

    ray fields: (...,) batch; boxes: (..., W, 3) lo/hi.  W is the BVH
    arity (``DatapathConfig.arity``): the 4-wide case is the paper's
    OpQuadbox bit-for-bit; 8-wide swaps in the 8-input sort network.
    """
    o = ray.origin[..., None, :]  # (..., 1, 3)
    inv = ray.inv[..., None, :]

    # stage 2: 24 adders -- translate box planes into ray space
    lo = boxes.lo - o  # (..., 4, 3)
    hi = boxes.hi - o

    # stage 3: 24 multipliers -- slab distances
    t_lo = lo * inv
    t_hi = hi * inv

    # stage 4: sign-based swap + min/max trees (36 comparators) + clamp
    # Paper: if (ray.dir < 0) swap(t_min, t_max).  We key the swap off the
    # sign bit so that dir == -0.0 (inv == -inf) also swaps.
    neg = jnp.signbit(ray.direction)[..., None, :]
    t_near = jnp.where(neg, t_hi, t_lo)  # (..., 4, 3)
    t_far = jnp.where(neg, t_lo, t_hi)

    # tmin = max(t_near_x, t_near_y, t_near_z, 0.0f) -- comparator semantics
    # drop NaN slabs (0 * inf), reproducing the branchless boundary handling.
    zero = jnp.zeros_like(t_near[..., 0])
    tmin = fmax(t_near[..., 2], fmax(t_near[..., 1], fmax(t_near[..., 0], zero)))
    inf = jnp.full_like(tmin, jnp.inf)
    tmax = fmin(t_far[..., 2], fmin(t_far[..., 1], fmin(t_far[..., 0], inf)))

    # stage 5: intersect = (tmin <= tmax)   (W comparators)
    intersect = tmin <= tmax  # (..., W)

    # stage 10: two sorting networks (values and indices) over tmin
    width = boxes.lo.shape[-2]
    idx = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), tmin.shape)
    hit_i = intersect.astype(jnp.int32)
    tmin_sorted, idx_sorted, hit_sorted = boxsort(tmin, idx, hit_i)
    return QuadBoxResult(tmin=tmin_sorted, box_index=idx_sorted,
                         is_intersect=hit_sorted.astype(bool))


def point_box_test(point: jax.Array, boxes: Box) -> PointBoxResult:
    """Batched point-vs-4-AABB squared distance: the neighbor-query twin of
    :func:`ray_box_test` (RTNN traverses by box *distance*, not slab entry).

    point: (..., 3); boxes: (..., 4, 3) lo/hi.  Per axis the gap to the box
    is ``max(lo - p, p - hi, 0)`` — comparator semantics, so an inverted
    empty-pad box (lo=+inf, hi=-inf) yields +inf**2 = +inf and sorts last,
    exactly like a missed slab in the ray path.  The same quad-sort network
    orders the four children near-to-far for the traversal push.
    """
    p = point[..., None, :]  # (..., 1, 3)

    # stage 2: 24 adders -- per-axis signed gaps to both faces
    below = boxes.lo - p  # (..., 4, 3)
    above = p - boxes.hi

    # stage 4: comparator trees clamp to the outside gap (0 inside the slab)
    zero = jnp.zeros_like(below)
    gap = fmax(below, fmax(above, zero))

    # stage 3/8: 12 multipliers + pairwise adds -> squared distance
    sq = gap * gap
    d2 = (sq[..., 0] + sq[..., 1]) + sq[..., 2]  # (..., 4)

    # stage 10: the same quad-sorting network as OpQuadbox
    idx = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), d2.shape)
    d2_sorted, idx_sorted = quadsort(d2, idx)
    return PointBoxResult(dist_sq=d2_sorted, box_index=idx_sorted)


# ---------------------------------------------------------------------------
# OpTriangle: Woop/Benthin/Wald watertight test (Table VII "Triangle" column)
# ---------------------------------------------------------------------------


def _pick_axis(v: jax.Array, k: jax.Array) -> jax.Array:
    """v: (..., 3), k: (...,) int -> v[..., k] elementwise over the batch.

    A three-way select over the three static slices, not a per-lane gather:
    a select returns one of its operands unchanged, so the result is bit for
    bit ``take_along_axis`` (NaN, +-inf, -0.0 and subnormals included), and
    it lowers to elementwise selects where the gather lowered to a general
    gather.  ``make_ray`` only produces ``kx/ky/kz`` in {0, 1, 2}, so "else
    axis 2" covers no other index.
    """
    return jnp.where(k == 0, v[..., 0], jnp.where(k == 1, v[..., 1], v[..., 2]))


def ray_triangle_test(ray: Ray, tri: Triangle) -> TriangleResult:
    """Batched watertight ray-triangle intersection (backface-culling variant).

    Outputs t_num / t_denom; the division is explicitly *not* performed, as in
    the paper (an external unit divides when needed).
    """
    sx = ray.shear[..., 0]
    sy = ray.shear[..., 1]
    sz = ray.shear[..., 2]

    # stage 2: translate vertices by ray origin (9 adders)
    a = tri.a - ray.origin
    b = tri.b - ray.origin
    c = tri.c - ray.origin

    a_kx, a_ky, a_kz = (_pick_axis(a, ray.kx), _pick_axis(a, ray.ky), _pick_axis(a, ray.kz))
    b_kx, b_ky, b_kz = (_pick_axis(b, ray.kx), _pick_axis(b, ray.ky), _pick_axis(b, ray.kz))
    c_kx, c_ky, c_kz = (_pick_axis(c, ray.kx), _pick_axis(c, ray.ky), _pick_axis(c, ray.kz))

    # stage 3: shear products (9 multipliers)
    ax_s = sx * a_kz
    ay_s = sy * a_kz
    az = sz * a_kz
    bx_s = sx * b_kz
    by_s = sy * b_kz
    bz = sz * b_kz
    cx_s = sx * c_kz
    cy_s = sy * c_kz
    cz = sz * c_kz

    # stage 4: shear-subtract (6 adders)
    ax = a_kx - ax_s
    ay = a_ky - ay_s
    bx = b_kx - bx_s
    by = b_ky - by_s
    cx = c_kx - cx_s
    cy = c_ky - cy_s

    # stage 5: edge-function products (6 multipliers)
    u = cx * by
    v = ax * cy
    w = bx * ay
    u_sub = cy * bx
    v_sub = ay * cx
    w_sub = by * ax

    # stage 6: edge functions (3 adders)
    u = u - u_sub
    v = v - v_sub
    w = w - w_sub

    # stage 7: scaled z products (3 multipliers)
    t_num_1 = u * az
    t_num_2 = v * bz
    t_num_3 = w * cz

    # stage 8: (2 adders)
    t_denom = u + v
    t_num = t_num_1 + t_num_2

    # stage 9: (2 adders)
    t_denom = t_denom + w
    t_num = t_num + t_num_3

    # stage 10: hit decision (5 comparators) -- backface-culling variant
    hit = (t_num > 0.0) & (t_denom != 0.0) & (u >= 0.0) & (v >= 0.0) & (w >= 0.0)
    return TriangleResult(t_num=t_num, t_denom=t_denom, hit=hit)


# ---------------------------------------------------------------------------
# OpEuclidean / OpAngular (Table VII columns 3-4): masked lanes + adder tree
# ---------------------------------------------------------------------------


def _mask_lanes(x: jax.Array, mask: jax.Array | None, lanes: int) -> jax.Array:
    x = x[..., :lanes]
    if mask is not None:
        x = jnp.where(mask[..., :lanes], x, 0.0)
    return x


def euclidean_partial(a: jax.Array, b: jax.Array, mask: jax.Array | None = None) -> jax.Array:
    """One beat of OpEuclidean: sum over <=16 lanes of (a-b)^2.

    The reduction is the hardware's pairwise adder tree (16->8->4->2->1),
    reproduced exactly so the kernel/ref/HW agree bit-for-bit in f32.
    """
    d = _mask_lanes(a, mask, VECTOR_LANES) - _mask_lanes(b, mask, VECTOR_LANES)  # stage 2
    d = d * d  # stage 3 (16 muls)
    d = d[..., :8] + d[..., 8:16]  # stage 4 (8 adds)
    d = d[..., :4] + d[..., 4:8]  # stage 6 (4 adds)
    d = d[..., :2] + d[..., 2:4]  # stage 8 (2 adds)
    return d[..., 0] + d[..., 1]  # stage 9 (1 add)


def angular_partial(q: jax.Array, c: jax.Array, mask: jax.Array | None = None):
    """One beat of OpAngular: (sum q*c, sum c*c) over <=8 lanes."""
    qm = _mask_lanes(q, mask, ANGULAR_LANES)
    cm = _mask_lanes(c, mask, ANGULAR_LANES)
    dot = qm * cm  # stage 3 (8 muls)
    nrm = cm * cm  # stage 3 (8 muls)
    dot = dot[..., :4] + dot[..., 4:8]  # stage 4
    nrm = nrm[..., :4] + nrm[..., 4:8]
    dot = dot[..., :2] + dot[..., 2:4]  # stage 6
    nrm = nrm[..., :2] + nrm[..., 2:4]
    dot = dot[..., 0] + dot[..., 1]  # stage 8
    nrm = nrm[..., 0] + nrm[..., 1]
    return dot, nrm


def euclidean_beat(state: DatapathState, a, b, mask=None, reset=False):
    """Full OpEuclidean job incl. accumulator semantics (Table V).

    ``reset`` clears the Euclidean accumulator *for this job* (the angular
    accumulators are untouched -- per-mode isolation).
    """
    partial = euclidean_partial(a, b, mask)
    reset = jnp.asarray(reset)
    accum_in = jnp.where(reset, 0.0, state.euclid_accum)
    out = partial + accum_in  # stage 10 (1 add)
    new_state = state._replace(euclid_accum=out)
    return new_state, EuclideanResult(accumulator=out, reset_accum=reset)


def angular_beat(state: DatapathState, q, c, mask=None, reset=False):
    """Full OpAngular job incl. dual accumulators (dot product and norm)."""
    dot_p, nrm_p = angular_partial(q, c, mask)
    reset = jnp.asarray(reset)
    dot = dot_p + jnp.where(reset, 0.0, state.dot_accum)  # stage 9 (2 adds)
    nrm = nrm_p + jnp.where(reset, 0.0, state.norm_accum)
    new_state = state._replace(dot_accum=dot, norm_accum=nrm)
    return new_state, AngularResult(dot_product=dot, norm=nrm, reset_accum=reset)


def euclidean_distance_sq(a: jax.Array, b: jax.Array) -> jax.Array:
    """Arbitrary-dimension Euclidean distance**2 via multi-beat accumulation.

    a, b: (..., D).  D is padded to a multiple of 16 with masked lanes, then
    scanned 16 lanes per beat exactly like feeding the hardware.
    """
    a, b, mask, beats = _beats(a, b, VECTOR_LANES)

    def step(carry, xs):
        ab, bb, mb, first = xs
        out = euclidean_partial(ab, bb, mb) + jnp.where(first, 0.0, carry)
        return out, None

    first = jnp.arange(beats) == 0
    out, _ = jax.lax.scan(step, jnp.zeros(a.shape[1:-1], jnp.float32), (a, b, mask, first))
    return out


def angular_distance_parts(q: jax.Array, c: jax.Array):
    """Arbitrary-dimension (q . c, ||c||^2) via 8-lane beats."""
    q, c, mask, beats = _beats(q, c, ANGULAR_LANES)

    def step(carry, xs):
        qb, cb, mb, first = xs
        dot_c, nrm_c = carry
        d, n = angular_partial(qb, cb, mb)
        d = d + jnp.where(first, 0.0, dot_c)
        n = n + jnp.where(first, 0.0, nrm_c)
        return (d, n), None

    first = jnp.arange(beats) == 0
    z = jnp.zeros(q.shape[1:-1], jnp.float32)
    (dot, nrm), _ = jax.lax.scan(step, (z, z), (q, c, mask, first))
    return dot, nrm


def _beats(a, b, lanes):
    d = a.shape[-1]
    beats = max(1, -(-d // lanes))
    pad = beats * lanes - d
    af = jnp.pad(a.astype(jnp.float32), [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    bf = jnp.pad(b.astype(jnp.float32), [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    mask = jnp.arange(beats * lanes) < d
    # reshape to (beats, ..., lanes) for scan
    def to_beats(x):
        x = x.reshape(x.shape[:-1] + (beats, lanes))
        return jnp.moveaxis(x, -2, 0)

    mask = jnp.broadcast_to(mask, af.shape[:-1] + (beats * lanes,))
    return to_beats(af), to_beats(bf), to_beats(mask), beats
