"""Session-style query API: one surface over every query engine.

The paper's core design concept is the "defined-once-instantiated-
everywhere" shared datapath: all four opcodes flow through one job/result
schema on shared functional units.  This module applies the same idea one
layer up, at the *query API* (DESIGN.md §5).  Instead of every call site
threading ``(bvh, depth)`` by hand, recomputing ``||c||^2`` per query
batch, and hand-rolling its own ``jax.jit`` wrapper, a session is built
once and queried many times — the RTNN/CrossRT model of declaring queries
against a prepared acceleration structure:

* :class:`Scene` — built once from a triangle soup; owns the ``BVH4``, its
  static ``depth``, and device placement.  Construction is pluggable
  (``builder="lbvh" | "sah"``, the :mod:`repro.core.build` registry,
  DESIGN.md §7), geometry is updatable in place (``Scene.refit`` — zero
  retraces per animation frame, because every trace backend threads the
  BVH as a runtime argument rather than a closure constant), and
  ``Scene.stats()`` reports tree quality (SAH cost + measured jobs/ray).
* :class:`VectorIndex` — built once from a database matrix; owns the
  precomputed ``||c||^2`` norms reused by every distance query.
* :class:`QueryEngine` — the single typed entry point
  (``trace`` / ``nearest`` / ``within`` / ``count_within`` / ``scores``),
  with a pluggable backend registry (``"per_ray"`` oracle, ``"wavefront"``,
  ``"pallas"`` — the fused traversal kernel for traces, the tiled distance
  kernels for scores, DESIGN.md §8 — and ``"auto"``), per-(shape, backend, query)
  compiled-function caching modeled on ``serving/engine.py``, and
  automatic pad-to-lane-multiple batching with result unpadding — the
  padding policy defined once instead of ad hoc in every example.

Execution placement and scheduling live one layer down, in
:mod:`repro.core.dispatch` (DESIGN.md §6): ``shard="auto" | int`` fans a
batch data-parallel across a device mesh (scene/index replicated, rays /
queries row-sharded) and ``chunk_size=`` streams it through fixed-size
microbatch blocks that all re-enter one compiled function.  Both knobs
compose with the padding policy and preserve the bit-parity contract —
the per-shard computation is literally the single-device computation on a
row subset (``tests/test_fuzz_backends.py`` fuzzes the equivalence).

Every backend returns the same result record (:class:`TraceResult`,
:class:`NearestResult`, :class:`WithinResult`), and results are
*bit-identical* to the legacy free functions (``trace_rays``,
``trace_wavefront``, ``knn``, ``radius_search``) — enforced by
``tests/test_session.py`` — so the free functions remain the oracles and
the engine remains swappable.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..obs import programs as _obs_programs
from ..obs.metrics import default_registry as _obs_registry
from ..obs.trace import span
from .build import build as build_structure
from .build import refit as refit_bvh
from .build import tree_stats
from .build.points import build_point_bvh, refit_points
from .build.quality import TreeStats
from .bvh import BVH4, DEFAULT_CONFIG, DatapathConfig, resolve_config
from .dispatch import (
    ExecPlan,
    check_count,
    concat_rows,
    make_plan,
    replicated,
    resolve_shards,
    shard_rows,
    shard_rows_ctx,
    take_block,
)
from .knn import (
    METRICS,
    RADIUS_METRICS,
    angular_scores,
    check_k,
    check_radius,
    cosine_epilogue,
    cosine_similarity,
    count_within_scores,
    knn,
    pairwise_scores,
    radius_count,
    radius_search,
    select_topk,
    select_within,
    squared_norms,
)
from .neighbor import NeighborRecord, neighbor_wavefront, point_queries
from .traversal import trace_rays
from .types import Triangle
from .wavefront import RAY_TYPES, SHADOW_T_MIN, trace_wavefront

__all__ = [
    "CacheInfo",
    "NearestResult",
    "NeighborRecord",
    "PointCloudScene",
    "QueryEngine",
    "Scene",
    "TraceResult",
    "VectorIndex",
    "WithinResult",
    "default_pad_multiple",
    "distance_backends",
    "neighbor_backends",
    "register_distance_backend",
    "register_neighbor_backend",
    "register_trace_backend",
    "trace_backend_ray_types",
    "trace_backends",
]


# ---------------------------------------------------------------------------
# Telemetry (DESIGN.md §11): instruments are resolved once at import so the
# recording sites are pre-bound; with the registry disabled (the default)
# every site below is one attribute check + branch and records nothing —
# results are bit-identical either way (tests/test_obs.py pins this).  The
# engine's spans (``engine.<method>``, ``engine.prepare``, ``engine.block``,
# ``engine.assemble``) do not depend on the registry: they are profiler
# annotations, one native flag check each while no profiler records.
# ---------------------------------------------------------------------------

_OBS = _obs_registry()
_OBS_CACHE_HITS = _OBS.counter("engine.cache.hits")
_OBS_CACHE_MISSES = _OBS.counter("engine.cache.misses")
_OBS_ROWS_REAL = _OBS.counter("engine.rows.real")
_OBS_ROWS_PADDED = _OBS.counter("engine.rows.padded")
_OBS_CHUNKS = _OBS.counter("engine.chunks")
_OBS_SHARDS = _OBS.gauge("engine.shards")


def _spanned(method):
    """Run a query method inside an ``engine.<method>`` span: its length is
    the host time the engine holds the caller, since every block runs
    asynchronously."""
    name = "engine." + method.__name__

    @functools.wraps(method)
    def call(self, *args, **kwargs):
        with span(name):
            return method(self, *args, **kwargs)

    return call


# ---------------------------------------------------------------------------
# Shared result records (one schema per query kind, whatever the backend)
# ---------------------------------------------------------------------------


class TraceResult(NamedTuple):
    """Unified traversal result: identical fields for every trace backend."""

    t: jax.Array  # (R,) f32  hit distance (inf = miss)
    tri_index: jax.Array  # (R,) i32  index into the soup, -1 = miss
    hit: jax.Array  # (R,) bool
    quadbox_jobs: jax.Array  # (R,) i32  per-ray box-test jobs issued
    triangle_jobs: jax.Array  # (R,) i32  per-ray OpTriangle jobs issued
    stack_overflow: jax.Array  # (R,) bool  a push was dropped at capacity
    rounds: jax.Array  # ()   i32  batch-level rounds (= max per-ray jobs)


class NearestResult(NamedTuple):
    """k-nearest result: scores ascending (euclidean) / descending (angular,
    cosine), indices into the database.

    ``valid`` masks the slots that hold a real neighbor — ``k`` is clamped
    to the database size, so with ``k > N`` the trailing slots carry the
    pad convention (inf / -inf score, index -1) and ``valid`` is False.

    The tree routes also report their datapath work, as
    :class:`TraceResult` does; the brute routes leave these ``None``."""

    scores: jax.Array  # (M, k) f32
    indices: jax.Array  # (M, k) i32
    valid: jax.Array  # (M, k) bool  which slots hold real neighbors
    box_jobs: jax.Array | None = None  # (M,) i32  point-box jobs issued
    point_jobs: jax.Array | None = None  # (M,) i32  point-distance jobs
    rounds: jax.Array | None = None  # ()   i32  batch-level rounds


class WithinResult(NamedTuple):
    """Fixed-radius result: top-k by proximity with an in-radius mask."""

    scores: jax.Array  # (M, k) f32
    indices: jax.Array  # (M, k) i32
    within: jax.Array  # (M, k) bool  which of the k slots are in range


class CacheInfo(NamedTuple):
    hits: int
    misses: int
    entries: int


# ---------------------------------------------------------------------------
# Padding policy (defined once, in core/dispatch; every query flows
# through an ExecPlan built there)
# ---------------------------------------------------------------------------


def default_pad_multiple() -> int:
    """Lane multiple for batch padding: TPU vector lanes, else a small
    sublane multiple so CPU tests exercise the same path cheaply."""
    return 128 if jax.default_backend() == "tpu" else 8


def _elem_key(tree) -> tuple:
    """Per-row signature: trailing shapes + dtypes.  Combined with the
    plan's (shards, block) this pins the full padded operand shapes."""
    return tuple((tuple(x.shape[1:]), str(x.dtype))
                 for x in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Backend registries
# ---------------------------------------------------------------------------

# name -> (supported ray types,
#          builder(scene, ray_type, t_min, max_rounds, interpret)
#          returning fn(ctx, rays) -> TraceResult — ``ctx`` is a *runtime*
#          argument (the BVH, or the backend's prepared form of it), not
#          closed over, so Scene.refit swaps in new boxes with zero
#          retracing,
#          lane multiple the backend wants per shard,
#          optional prepare(scene) -> fn(bvh) -> ctx hook: computed once
#          per scene version — not per chunk — and replicated per mesh)
_TRACE_BACKENDS: dict[str, tuple] = {}

#: tile width of the fused Pallas traversal kernel (= kernels.common.LANES,
#: kept literal here so the registry needs no kernel import at module init;
#: tests/test_session.py pins the equality)
PALLAS_TRACE_LANES = 128

# name -> builder(index, metric, interpret) returning fn(queries) -> (M, N)
# score matrix (squared distances for euclidean, similarities otherwise)
_DISTANCE_BACKENDS: dict[str, Callable] = {}

# name -> (builder(cloud, mode, k, interpret) returning fn(ctx, rays) ->
#          NeighborRecord — ``ctx`` is a runtime argument, not closed over,
#          so PointCloudScene.refit swaps clouds with zero retracing,
#          lane multiple the backend wants per shard,
#          optional prepare(cloud) -> fn(bvh) -> ctx hook, once per version)
_NEIGHBOR_BACKENDS: dict[str, tuple] = {}


def register_trace_backend(name: str, ray_types=RAY_TYPES,
                           lane_multiple: int | None = None,
                           prepare: Callable | None = None):
    """Register a traversal backend under ``name``.  The builder receives
    the static query config — ``build(scene, ray_type, t_min, max_rounds,
    interpret)`` — and returns a jit-able ``fn(ctx, rays)``: the scene
    provides static structure (depth), the context arrays arrive per call
    so animated (refit) scenes re-enter the compiled cache.

    ``lane_multiple`` (optional) is the per-shard row multiple the backend
    wants its batches padded to (e.g. the fused Pallas kernel's 128-lane
    tile width); the dispatch planner folds it into every ExecPlan so
    kernel-backed backends always receive whole tiles.

    ``prepare`` (optional) is ``prepare(scene) -> fn(bvh) -> ctx``: a
    jit-able transform of the BVH into the backend's resident operand
    form (the fused kernel's packed rows-by-lanes arrays).  The engine
    runs it once per scene version and feeds the result to every
    chunk/shard, so O(scene) packing is never re-executed per block;
    backends without one receive the BVH itself as ``ctx``."""
    def deco(build):
        _TRACE_BACKENDS[name] = (tuple(ray_types), build, lane_multiple,
                                 prepare)
        return build
    return deco


def trace_backend_ray_types(name: str) -> tuple[str, ...]:
    """The ray types a registered trace backend supports (used by the
    golden-trace suite to iterate every backend × ray type)."""
    if name not in _TRACE_BACKENDS:
        raise ValueError(f"unknown trace backend {name!r} "
                         f"(registered: {trace_backends()})")
    return _TRACE_BACKENDS[name][0]


def register_distance_backend(name: str):
    """Register a distance backend: ``build(index, metric, interpret)`` must
    return a jit-able ``fn(queries) -> (M, N) scores``."""
    def deco(build):
        _DISTANCE_BACKENDS[name] = build
        return build
    return deco


def register_neighbor_backend(name: str, lane_multiple: int | None = None,
                              prepare: Callable | None = None):
    """Register a tree-backed neighbor backend under ``name``.  The builder
    receives the static query config — ``build(cloud, mode, k, interpret)``
    with ``mode`` in :data:`repro.core.neighbor.NEIGHBOR_MODES` — and
    returns a jit-able ``fn(ctx, rays)`` producing a
    :class:`~repro.core.neighbor.NeighborRecord`.  ``lane_multiple`` and
    ``prepare`` mean exactly what they do for trace backends (the rays
    here are :func:`~repro.core.neighbor.point_queries` bundles, so the
    same dispatch padding applies)."""
    def deco(build):
        _NEIGHBOR_BACKENDS[name] = (build, lane_multiple, prepare)
        return build
    return deco


def trace_backends() -> tuple[str, ...]:
    return tuple(_TRACE_BACKENDS)


def distance_backends() -> tuple[str, ...]:
    return tuple(_DISTANCE_BACKENDS)


def neighbor_backends() -> tuple[str, ...]:
    return tuple(_NEIGHBOR_BACKENDS)


@register_trace_backend("per_ray", ray_types=("closest",))
def _build_per_ray(scene: "Scene", ray_type: str, t_min: float,
                   max_rounds, interpret=None):
    """The vmapped per-ray ``while_loop`` oracle (closest-hit only;
    pure jnp, so ``interpret`` does not apply)."""
    if t_min:
        raise ValueError("per_ray backend has no t_min support; "
                         "use backend='wavefront'")
    if max_rounds is not None:
        raise ValueError("per_ray backend has no max_rounds support; "
                         "use backend='wavefront'")

    def run(bvh, rays):
        rec = trace_rays(bvh, rays, scene.depth, scene.config)
        # a ray is active for exactly quadbox_jobs consecutive rounds, so
        # the batch-level round count is the max per-ray job count
        return TraceResult(rec.t, rec.tri_index, rec.hit, rec.quadbox_jobs,
                           rec.triangle_jobs, rec.stack_overflow,
                           jnp.max(rec.quadbox_jobs))

    return run


@register_trace_backend("wavefront", ray_types=RAY_TYPES)
def _build_wavefront(scene: "Scene", ray_type: str, t_min: float,
                     max_rounds, interpret=None):
    """Batch-level frontier loop: closest / any / shadow rays (pure jnp,
    so ``interpret`` does not apply)."""
    def run(bvh, rays):
        rec = trace_wavefront(bvh, rays, scene.depth,
                              ray_type=ray_type, t_min=t_min,
                              max_rounds=max_rounds, config=scene.config)
        return TraceResult(*rec)  # field-for-field identical record

    return run


def _prepare_pallas_trace(scene: "Scene"):
    """The fused kernel's ``prepare`` hook: pack the BVH into its
    resident rows-by-lanes operands once per scene version (the scene's
    config picks the packed node dtype — bf16 configs halve node bytes)."""
    from ..kernels.traverse import pack_bvh  # deferred (circular init)
    return lambda bvh: pack_bvh(bvh, scene.config)


@register_trace_backend("pallas", ray_types=RAY_TYPES,
                        lane_multiple=PALLAS_TRACE_LANES,
                        prepare=_prepare_pallas_trace)
def _build_pallas_trace(scene: "Scene", ray_type: str, t_min: float,
                        max_rounds, interpret=None):
    """Fused Pallas traversal (``kernels/traverse.py``, DESIGN.md §8): the
    whole pop → OpQuadbox → OpTriangle → commit round loop runs inside one
    kernel with per-lane ray state and the traversal stack on-chip, built
    from the same ``core/datapath`` stage helpers — hits and job counters
    bit-match the wavefront engine.  ``ctx`` is the prepared
    (``pack_bvh``) operand form; ``interpret=None`` auto-selects
    interpret mode off-TPU (the engine-wide ``interpret`` knob threads
    through, same as the distance kernels)."""
    # deferred import: repro.kernels imports repro.core submodules, so a
    # top-level import here would be circular during package init
    from ..kernels.traverse import traverse_packed

    depth = scene.depth

    config = scene.config

    def run(ctx, rays):
        rec = traverse_packed(ctx, rays, depth, ray_type=ray_type,
                              t_min=t_min, max_rounds=max_rounds,
                              interpret=interpret, config=config)
        return TraceResult(*rec)  # WavefrontRecord: field-for-field match

    return run


@register_distance_backend("mxu")
def _build_mxu_scores(index: "VectorIndex", metric: str, interpret):
    """MXU matmul form with the index's precomputed ||c||^2 (DESIGN.md §2)."""
    db, c2 = index.database, index.sq_norms
    return lambda q: pairwise_scores(q, db, metric, c_sq_norms=c2)


@register_distance_backend("pallas")
def _build_pallas_scores(index: "VectorIndex", metric: str, interpret):
    """Tiled Pallas kernels (``repro.kernels.distance``): the multi-beat
    accumulator path.  ``interpret=None`` auto-selects interpret mode
    off-TPU."""
    # deferred import: repro.kernels imports repro.core submodules, so a
    # top-level import here would be circular during package init
    from ..kernels import ops as kops

    db = index.database
    if metric == "euclidean":
        return lambda q: kops.euclidean_kernel(q, db, interpret=interpret)
    if metric == "angular":
        # only dots are consumed; the kernel's norms output is DCE'd
        return lambda q: kops.angular_kernel(q, db, interpret=interpret)[0]
    if metric == "cosine":
        c2 = index.sq_norms  # precomputed once, not re-reduced in-kernel

        def cosine(q):
            dots = kops.angular_kernel(q, db, interpret=interpret)[0]
            return cosine_epilogue(dots, c2, q)
        return cosine
    raise ValueError(f"unknown metric: {metric} (want one of {METRICS})")


@register_neighbor_backend("tree_wavefront")
def _build_tree_wavefront(cloud: "PointCloudScene", mode: str, k: int,
                          interpret=None):
    """Batch-level neighbor frontier loop (``core/neighbor.py``): the
    wavefront engine's distance twin (pure jnp, so ``interpret`` does not
    apply)."""
    depth = cloud.depth

    def run(bvh, rays):
        return neighbor_wavefront(bvh, rays, depth, k=k, mode=mode)

    return run


def _prepare_tree_pallas(cloud: "PointCloudScene"):
    from ..kernels.traverse import pack_point_bvh  # deferred (circular init)
    return pack_point_bvh


@register_neighbor_backend("tree_pallas", lane_multiple=PALLAS_TRACE_LANES,
                           prepare=_prepare_tree_pallas)
def _build_tree_pallas(cloud: "PointCloudScene", mode: str, k: int,
                       interpret=None):
    """Fused Pallas neighbor traversal (``kernels/traverse.py``): the whole
    pop → point-box → point-distance → insert → push round loop runs
    inside one kernel with the per-lane top-k registers and traversal
    stack on-chip — results bit-match the wavefront neighbor engine."""
    # deferred import: repro.kernels imports repro.core submodules, so a
    # top-level import here would be circular during package init
    from ..kernels.traverse import neighbor_packed

    depth = cloud.depth

    def run(ctx, rays):
        return neighbor_packed(ctx, rays, depth, k, mode=mode,
                               interpret=interpret)

    return run


# ---------------------------------------------------------------------------
# Scene / VectorIndex: built once, queried everywhere
# ---------------------------------------------------------------------------


def _as_triangles(triangles) -> Triangle:
    """Coerce a :class:`Triangle` soup or ``(N, 3, 3)`` vertex array."""
    if isinstance(triangles, Triangle):
        return triangles
    arr = jnp.asarray(triangles, jnp.float32)
    if arr.ndim != 3 or arr.shape[1:] != (3, 3):
        raise ValueError(
            f"expected Triangle or (N, 3, 3) vertices, got {arr.shape}")
    return Triangle(arr[:, 0], arr[:, 1], arr[:, 2])


def _validate_finite(tri: Triangle, where: str) -> None:
    """Reject non-finite vertices eagerly: a single NaN/inf poisons the
    scene root box, every Morton code / SAH bin, and every traversal that
    follows.  Skipped under tracing so the builders stay jittable."""
    if any(isinstance(f, jax.core.Tracer) for f in tri):
        return
    if not bool(jnp.all(jnp.isfinite(jnp.stack([tri.a, tri.b, tri.c])))):
        raise ValueError(
            f"{where}: triangle vertices must be finite (no NaN/inf) — "
            "a single bad vertex poisons the scene bounds and every "
            "acceleration-structure build")


# refit is jittable with static shapes, so one jit here means every
# animation frame after the first re-enters one compiled sweep
_refit_jit = jax.jit(refit_bvh, static_argnames=("config",))
_refit_points_jit = jax.jit(refit_points)


def _validate_points_finite(points: jax.Array, where: str) -> None:
    """Reject non-finite points eagerly (same rationale as triangle
    scenes: one NaN poisons the root box and every Morton/SAH decision).
    Skipped under tracing so cloud builds stay jittable."""
    if isinstance(points, jax.core.Tracer):
        return
    if not bool(jnp.all(jnp.isfinite(points))):
        raise ValueError(
            f"{where}: points must be finite (no NaN/inf) — a single bad "
            "point poisons the cloud bounds and every tree build")


class Scene:
    """A prepared triangle scene: ``BVH4`` + its static traversal depth.

    Callers stop threading ``(bvh, depth)`` manually — the pair travels
    together, optionally placed on a device at build time.  The
    acceleration structure itself is pluggable
    (``builder="lbvh" | "sah"``, the :mod:`repro.core.build` registry) and
    updatable in place (:meth:`refit` — dynamic scenes without rebuild or
    retrace); :meth:`stats` reports the tree-quality metrics.
    """

    def __init__(self, bvh: BVH4, depth: int, device=None,
                 builder: str = "lbvh",
                 config: DatapathConfig | None = None):
        if device is not None:
            bvh = jax.device_put(bvh, device)
        self.bvh = bvh
        self.depth = int(depth)
        self.builder = builder
        #: the datapath twin the tree was built for (arity, stack size,
        #: box precision, node codec) — every engine traces with it
        self.config = resolve_config(config)
        #: bumped by :meth:`refit`; engines key their replicated copies on
        #: it so sharded queries pick up the new boxes
        self.version = 0

    @classmethod
    def from_triangles(cls, triangles, depth: int | None = None,
                       device=None, builder: str = "lbvh",
                       config: DatapathConfig | None = None) -> "Scene":
        """Build from a :class:`Triangle` soup or an ``(N, 3, 3)`` array of
        per-triangle vertices, with the named registered builder.
        ``config`` selects the datapath twin (arity / stack size / box
        precision / node codec); ``None`` is the BVH4-fp32 default."""
        triangles = _as_triangles(triangles)
        _validate_finite(triangles, "Scene.from_triangles")
        res = build_structure(triangles, builder, depth, config=config)
        return cls(res.bvh, res.depth, device, builder=res.builder,
                   config=res.config)

    def refit(self, triangles) -> "Scene":
        """Update the scene's geometry in place, keeping its topology.

        Re-sweeps the AABBs bottom-up around the moved ``triangles`` (same
        soup, same order; ``depth`` vectorised reductions) without
        re-sorting or re-binning.  All shapes are preserved, and engines
        thread the BVH as a runtime argument, so every compiled query on
        this scene re-enters the jit cache with **zero retracing** —
        the contract animated scenes rely on (``tests/test_build.py``).
        Returns ``self`` for chaining.
        """
        triangles = _as_triangles(triangles)
        _validate_finite(triangles, "Scene.refit")
        # the soup-size precondition lives in refit() itself (shape-static,
        # so it raises identically through the jitted path)
        self.bvh = _refit_jit(self.bvh, triangles, config=self.config)
        self.version += 1
        return self

    def stats(self, rays=None, probes: int = 256) -> TreeStats:
        """Tree-quality metrics: SAH cost plus mean datapath jobs per ray
        measured on ``rays`` (or a deterministic probe batch)."""
        return tree_stats(self.bvh, self.builder, rays=rays, probes=probes,
                          config=self.config)

    @property
    def num_triangles(self) -> int:
        return int(self.bvh.triangles.a.shape[0])

    def engine(self, **kwargs) -> "QueryEngine":
        return QueryEngine(scene=self, **kwargs)

    def __repr__(self):
        return (f"Scene(num_triangles={self.num_triangles}, "
                f"depth={self.depth}, builder={self.builder!r}, "
                f"config={self.config.tag!r})")


class VectorIndex:
    """A prepared vector database: candidate matrix + precomputed ||c||^2.

    The norms are the OpAngular second output; computing them at build time
    means every subsequent ``knn`` / ``radius_search`` / ``radius_count`` /
    ``cosine_similarity`` call reuses them instead of re-reducing the whole
    database per query batch.
    """

    def __init__(self, database: jax.Array,
                 sq_norms: jax.Array | None = None, device=None):
        database = jnp.asarray(database)
        if device is not None:
            database = jax.device_put(database, device)
        self.database = database
        self.sq_norms = squared_norms(database) if sq_norms is None else sq_norms

    @classmethod
    def from_database(cls, database, device=None) -> "VectorIndex":
        return cls(database, device=device)

    @property
    def size(self) -> int:
        return int(self.database.shape[0])

    @property
    def dim(self) -> int:
        return int(self.database.shape[-1])

    # -- direct (unjitted, unpadded) query methods: the session engine wraps
    #    these with caching + padding; the MoE router calls them in-trace --

    def dots(self, queries: jax.Array) -> jax.Array:
        """OpAngular dot products only (router logits).  (M,D) -> (M,N)."""
        return angular_scores(queries, self.database,
                              c_sq_norms=self.sq_norms)[0]

    def cosine_similarity(self, queries: jax.Array) -> jax.Array:
        return cosine_similarity(queries, self.database,
                                 c_sq_norms=self.sq_norms)

    def knn(self, queries: jax.Array, k: int, metric: str = "euclidean"):
        return knn(queries, self.database, k, metric,
                   c_sq_norms=self.sq_norms)

    def radius_search(self, queries: jax.Array, radius: float, k: int,
                      metric: str = "euclidean"):
        return radius_search(queries, self.database, radius, k, metric,
                             c_sq_norms=self.sq_norms)

    def radius_count(self, queries: jax.Array, radius: float,
                     metric: str = "euclidean"):
        return radius_count(queries, self.database, radius, metric,
                            c_sq_norms=self.sq_norms)

    def engine(self, **kwargs) -> "QueryEngine":
        return QueryEngine(index=self, **kwargs)

    def __repr__(self):
        return f"VectorIndex(size={self.size}, dim={self.dim})"


class PointCloudScene:
    """A prepared point cloud: a BVH4 over AABB-per-point leaves *plus* the
    equivalent :class:`VectorIndex` over the same points.

    The RTNN unification surface (DESIGN.md §9): one object serves both
    the traversal-backed neighbor engines (``tree_wavefront`` /
    ``tree_pallas``, which walk the tree with query radii as ray extents)
    and the brute-force distance backends (``mxu`` / ``pallas``, the
    bit-level oracle) — ``QueryEngine`` routes between them per query
    (``backend="auto"``) without the caller re-staging data.

    Construction is pluggable exactly like :class:`Scene`
    (``builder="lbvh" | "sah"``, sharing the triangle builders' slot-
    assignment cores), and clouds are updatable in place (:meth:`refit` —
    same zero-retrace contract, via the cull-free
    :func:`~repro.core.build.points.refit_points`).
    """

    def __init__(self, bvh: BVH4, depth: int, device=None,
                 builder: str = "lbvh",
                 config: DatapathConfig | None = None):
        if device is not None:
            bvh = jax.device_put(bvh, device)
        self.bvh = bvh
        self.depth = int(depth)
        self.builder = builder
        #: the datapath twin the tree was built for (arity, stack size,
        #: box precision, node codec) — every engine traces with it
        self.config = resolve_config(config)
        #: bumped by :meth:`refit`; engines key replicated copies, packed
        #: kernel operands and brute-path closures on it
        self.version = 0
        #: the same points as a brute-force index (shared ||c||^2 norms)
        self.index = VectorIndex(bvh.triangles.a)
        self._root_vol: float | None = None

    @classmethod
    def from_points(cls, points, depth: int | None = None, device=None,
                    builder: str = "lbvh") -> "PointCloudScene":
        """Build from an ``(N, 3)`` point array with the named builder
        core (the tree path is 3-D; higher-dimensional data belongs in a
        plain :class:`VectorIndex`)."""
        points = jnp.asarray(points, jnp.float32)
        _validate_points_finite(points, "PointCloudScene.from_points")
        res = build_point_bvh(points, builder, depth)
        return cls(res.bvh, res.depth, device, builder=res.builder)

    def refit(self, points) -> "PointCloudScene":
        """Update the cloud's points in place, keeping its topology (same
        count, same order).  Zero retraces, like :meth:`Scene.refit`:
        every neighbor backend threads the BVH as a runtime argument, and
        the brute path re-derives its norms through the version bump.
        Returns ``self`` for chaining."""
        points = jnp.asarray(points, jnp.float32)
        _validate_points_finite(points, "PointCloudScene.refit")
        self.bvh = _refit_points_jit(self.bvh, points)
        self.index = VectorIndex(self.bvh.triangles.a)
        self.version += 1
        self._root_vol = None
        return self

    @property
    def points(self) -> jax.Array:
        return self.bvh.triangles.a

    @property
    def size(self) -> int:
        return int(self.bvh.triangles.a.shape[0])

    def root_volume(self) -> float:
        """Volume of the root AABB (cached per version) — the denominator
        of the "auto" policy's radius-selectivity estimate."""
        if self._root_vol is None:
            ext = jnp.maximum(self.bvh.node_hi[0] - self.bvh.node_lo[0],
                              0.0)
            self._root_vol = float(ext[0] * ext[1] * ext[2])
        return self._root_vol

    def engine(self, **kwargs) -> "QueryEngine":
        return QueryEngine(cloud=self, **kwargs)

    def __repr__(self):
        return (f"PointCloudScene(size={self.size}, depth={self.depth}, "
                f"builder={self.builder!r})")


# ---------------------------------------------------------------------------
# QueryEngine: the single typed entry point
# ---------------------------------------------------------------------------


class QueryEngine:
    """Jit-cached session over a :class:`Scene` and/or :class:`VectorIndex`.

    Modeled on ``serving/engine.py``: compiled functions are cached per
    (query kind, backend, static config, padded operand shapes), so
    repeated same-shape queries re-enter the compiled program directly.
    Batches are padded to ``pad_multiple`` (row-0 repetition for rays,
    which is always a valid ray) and results are sliced back — per-ray /
    per-query state is row-independent in every backend, so the pad →
    query → unpad round trip is an identity (``tests/test_session.py``).

    ``backend="auto"`` picks per query: wavefront for traced batches
    (per-ray oracle for tiny closest-hit batches), the tree wavefront for
    qualifying cloud queries, the Pallas distance kernels for brute
    distance queries on TPU and the MXU jnp form elsewhere.

    Two execution knobs ride on every query (``core/dispatch.py``,
    DESIGN.md §6), settable engine-wide here or overridden per call:

    * ``shard="auto" | int`` — data-parallel the batch's rows over a 1-D
      device mesh; the scene / index is replicated once per mesh and the
      per-shard computation is the unchanged single-device computation on
      that shard's rows (no collectives, so results stay bit-identical;
      ``"auto"`` = all local devices, capped at the batch size; ``1``
      disables).
    * ``chunk_size=`` — execute in fixed-size microbatch blocks that all
      re-enter one compiled function (one engine-cache entry however many
      chunks), bounding peak intermediate memory for million-ray batches;
      results are assembled across chunks and wavefront ``rounds`` reduces
      by max, which equals the single-device value exactly.

    Zero-row batches short-circuit to empty typed results without
    compiling or executing anything.
    """

    #: closest-hit batches up to this size go to the per-ray oracle under
    #: "auto" (the batch loop only pays off once the frontier is wide)
    AUTO_PER_RAY_MAX = 8

    #: below this cloud size "auto" keeps neighbor queries on the brute
    #: path: one small MXU matmul beats any traversal's pointer chasing
    AUTO_TREE_MIN_POINTS = 4096

    #: "auto" routes a neighbor query to the tree only while its expected
    #: selectivity (fraction of the cloud each query touches: k/N for
    #: nearest, ball volume / root volume for radius queries) stays under
    #: this — a query that touches most of the cloud visits most of the
    #: tree, and the brute matmul wins
    AUTO_TREE_MAX_SELECTIVITY = 0.05

    def __init__(self, scene: Scene | None = None,
                 index: VectorIndex | None = None,
                 cloud: "PointCloudScene | None" = None, *,
                 backend: str = "auto", pad_multiple: int | None = None,
                 shard: str | int = "auto", chunk_size: int | None = None,
                 interpret: bool | None = None):
        self.scene = scene
        self._index = index
        self.cloud = cloud
        self.default_backend = backend
        # execution knobs are validated eagerly, here and per call — a bad
        # chunk_size/shard must never flow silently into the plan math
        # (floats used to truncate; 0 used to slip past empty batches)
        if shard not in (None, "auto"):
            check_count("shard", shard)
        self.default_shard = shard
        self.default_chunk_size = check_count("chunk_size", chunk_size)
        self.pad_multiple = (default_pad_multiple() if pad_multiple is None
                             else max(1, int(pad_multiple)))
        self.interpret = interpret  # None = auto (off-TPU -> interpret)
        self._cache: dict = {}
        self._placed: dict = {}  # (kind, shards) -> replicated Scene/index
        self._hits = 0
        self._misses = 0

    @property
    def index(self) -> VectorIndex | None:
        """The engine's vector index: the explicit one, else the cloud's
        (a :class:`PointCloudScene` carries its brute-oracle twin, so
        distance queries on a cloud engine need no separate index)."""
        if self._index is None and self.cloud is not None:
            return self.cloud.index
        return self._index

    def _index_version(self) -> int:
        """Version of the backing index data: a cloud refit swaps the
        brute path's database, so closures over it must re-key."""
        if self._index is None and self.cloud is not None:
            return self.cloud.version
        return 0

    # -- cache ------------------------------------------------------------

    def cache_info(self) -> CacheInfo:
        return CacheInfo(self._hits, self._misses, len(self._cache))

    def cache_clear(self) -> None:
        self._cache.clear()
        self._placed.clear()  # replicated scene/index copies are the big
        self._hits = self._misses = 0  # objects; release them too

    def _compiled(self, key, build):
        fn = self._cache.get(key)
        if fn is None:
            self._misses += 1
            _OBS_CACHE_MISSES.inc()
            fn = jax.jit(build())
            self._cache[key] = fn
        else:
            self._hits += 1
            _OBS_CACHE_HITS.inc()
        return fn

    def _obs_record(self, method: str, backend: str, plan: ExecPlan,
                    jobs=()) -> None:
        """Record one enqueued query into the default registry (callers
        gate on ``_OBS.enabled``): real vs padded rows (the pad-waste
        numerator/denominator in ``obs.snapshot()``), chunk/shard
        fan-out, a per-(method, backend) call counter, and whatever
        datapath job totals the backend reports (quadbox/triangle for
        traces, box/point for neighbor queries).  The job totals are
        summed on the device; nothing here waits for the result or reads
        it back, so telemetry does not change how the call runs."""
        _OBS.counter(f"engine.calls.{method}.{backend}").inc()
        _OBS_ROWS_REAL.inc(plan.n)
        _OBS_ROWS_PADDED.inc(plan.block * plan.n_blocks)
        _OBS_CHUNKS.inc(plan.n_blocks)
        _OBS_SHARDS.set(plan.shards)
        for job_name, per_row in jobs:
            _OBS.counter(f"engine.jobs.{job_name}.{backend}").add_device(
                per_row)

    @staticmethod
    def _run_blocks(fn, tree, plan: ExecPlan, *ctx) -> list:
        """Enqueue ``fn(*ctx, block)`` for every block of ``tree``, each
        under an ``engine.block`` span that covers the block's slice and
        pad and the call's enqueue; the program is noted for
        :func:`repro.obs.programs.op_scopes`."""
        outs = []
        for i in range(plan.n_blocks):
            with span("engine.block", index=i,
                      rows=min(plan.block, plan.n - i * plan.block)):
                block = take_block(tree, plan, i)
                outs.append(fn(*ctx, block))
        _obs_programs.note(fn, *ctx, block)
        return outs

    # -- backend resolution ----------------------------------------------

    def resolve_trace_backend(self, ray_type: str, n_rays: int,
                              t_min: float = 0.0,
                              max_rounds: int | None = None,
                              shards: int = 1) -> str:
        """The backend "auto" picks for a trace: per-ray oracle for tiny
        plain closest-hit batches; every other query — including ones the
        oracle cannot express (t_min, max_rounds) and any sharded batch
        (a multi-device frontier is by definition not tiny) — goes to the
        wavefront engine, on every platform.  The fused Pallas traversal
        kernel is not a route "auto" takes: Mosaic does not lower its
        per-lane stack pop (``tests/test_tpu_compile.py`` pins the
        refusal), so it is reachable only as an explicit
        ``backend="pallas"``.  All three return bit-identical results, so
        the policy is pure scheduling."""
        if (shards == 1 and ray_type == "closest"
                and n_rays <= self.AUTO_PER_RAY_MAX
                and not t_min and max_rounds is None):
            return "per_ray"
        return "wavefront"

    def resolve_distance_backend(self) -> str:
        """The backend "auto" picks for distance queries: compiled Pallas
        kernels on TPU, the MXU jnp form elsewhere (interpret mode would
        only add overhead)."""
        return "pallas" if jax.default_backend() == "tpu" else "mxu"

    def resolve_neighbor_backend(self, kind: str, metric: str,
                                 k: int | None = None,
                                 radius: float | None = None) -> str:
        """The backend "auto" picks for ``nearest`` / ``within`` /
        ``count_within``: tree-vs-brute by N, dimension and selectivity.

        The tree path needs a :class:`PointCloudScene` (which pins the
        dimension to 3 — higher-dimensional indexes have no cloud and stay
        brute) and a euclidean metric; below
        :data:`AUTO_TREE_MIN_POINTS` points, or when the query's expected
        selectivity (k/N for nearest; search-ball volume over root-box
        volume for radius queries) exceeds
        :data:`AUTO_TREE_MAX_SELECTIVITY`, the brute matmul wins and
        "auto" stays on the distance backends.  Otherwise the wavefront
        neighbor engine, on every platform (the fused ``tree_pallas``
        kernel does not lower under Mosaic, so it is explicit-only, like
        the fused trace kernel).  The tree routes are exact against a
        brute pass in their own difference-form leaf distance; the brute
        routes score in the expanded MXU form, so the two agree within
        that form's rounding band, ``eps * (||q||^2 + ||c||^2)`` (DESIGN.md
        §9): narrow at unit scale, wider than the distances themselves at
        metre coordinates, where the tree route is the exact one."""
        if self.cloud is None or metric != "euclidean":
            return self.resolve_distance_backend()
        n = self.cloud.size
        if n < self.AUTO_TREE_MIN_POINTS:
            return self.resolve_distance_backend()
        if kind == "nearest":
            selectivity = (1 if k is None else int(k)) / n
        else:
            r = float(radius)
            ball = 4.0 / 3.0 * math.pi * r**3
            vol = self.cloud.root_volume()
            selectivity = ball / vol if (vol > 0.0
                                         and math.isfinite(ball)) else 1.0
        if selectivity > self.AUTO_TREE_MAX_SELECTIVITY:
            return self.resolve_distance_backend()
        return "tree_wavefront"

    # -- execution planning (sharding + chunking, core/dispatch.py) -------

    def _resolve_shards(self, shard, n: int) -> int:
        return resolve_shards(
            self.default_shard if shard is None else shard, n)

    def _plan(self, n: int, shards: int, chunk_size,
              lane_multiple: int | None = None) -> ExecPlan:
        if chunk_size is None:
            chunk_size = self.default_chunk_size
        return make_plan(n, pad_multiple=self.pad_multiple, shards=shards,
                         chunk_size=chunk_size, lane_multiple=lane_multiple)

    # -- plan introspection (what the serving coalescer sizes batches by) --

    #: the query methods the serving layer coalesces (one bucket space per
    #: method; ``repro.serving.query_server`` exposes exactly these)
    SERVABLE_METHODS = ("trace", "nearest", "within", "count_within",
                        "scores")

    def _method_lane_multiple(self, method: str, backend: str | None, *,
                              ray_type: str = "closest",
                              metric: str = "euclidean", n: int = 1 << 20,
                              k: int | None = None,
                              radius: float | None = None) -> int | None:
        """The backend-declared tile width a ``method`` query would pad
        to (None = no hard tile; the plain pad multiple applies).
        ``backend=None/"auto"`` resolves through the same auto policy the
        query itself would use, with a large nominal ``n`` so tiny-batch
        special cases don't leak into sizing decisions."""
        name = backend or self.default_backend
        if method == "trace":
            if name == "auto":
                name = self.resolve_trace_backend(ray_type, n)
            if name not in _TRACE_BACKENDS:
                raise ValueError(f"unknown trace backend {name!r} "
                                 f"(registered: {trace_backends()})")
            return _TRACE_BACKENDS[name][2]
        if method in ("nearest", "within", "count_within", "scores"):
            if name == "auto":
                if method == "scores" or (method != "nearest"
                                          and radius is None):
                    # scores is brute-only; a radius query introspected
                    # without its radius can't be selectivity-routed —
                    # assume the brute path (no hard tile) conservatively
                    name = self.resolve_distance_backend()
                else:
                    name = self.resolve_neighbor_backend(
                        method, metric, k=k, radius=radius)
            if name in _NEIGHBOR_BACKENDS:
                return _NEIGHBOR_BACKENDS[name][1]
            if name in _DISTANCE_BACKENDS:
                return None
            raise ValueError(
                f"unknown distance/neighbor backend {name!r} (registered: "
                f"{distance_backends() + neighbor_backends()})")
        raise ValueError(f"unknown query method {method!r} "
                         f"(servable: {self.SERVABLE_METHODS})")

    def batch_multiple(self, method: str = "trace",
                       backend: str | None = None, *,
                       ray_type: str = "closest",
                       metric: str = "euclidean", k: int | None = None,
                       radius: float | None = None) -> int:
        """The effective per-shard row multiple queries of ``method`` are
        padded to — ``max(pad_multiple, backend tile width)``.  The
        serving coalescer sizes its batch targets with this so a flushed
        batch fills whole lanes/tiles instead of padding them away."""
        lane = self._method_lane_multiple(method, backend,
                                          ray_type=ray_type, metric=metric,
                                          k=k, radius=radius)
        return max(self.pad_multiple, lane or 1)

    def plan_for(self, method: str, n: int, *,
                 backend: str | None = None, ray_type: str = "closest",
                 metric: str = "euclidean", k: int | None = None,
                 radius: float | None = None, shard=None,
                 chunk_size: int | None = None) -> ExecPlan:
        """Introspection: the :class:`~repro.core.dispatch.ExecPlan` an
        ``n``-row ``method`` query would execute under — without
        dispatching anything.  The serving layer uses ``plan.block`` (the
        padded rows actually executed) to quantize batch shapes and to
        report batch occupancy; callers get the same plan the query path
        itself builds, so the numbers cannot drift."""
        if n < 1:
            raise ValueError(f"plan_for needs n >= 1, got {n}")
        shards = self._resolve_shards(shard, n)
        chunk_size = check_count("chunk_size", chunk_size)
        lane = self._method_lane_multiple(method, backend,
                                          ray_type=ray_type, metric=metric,
                                          n=n, k=k, radius=radius)
        return self._plan(n, shards, chunk_size, lane_multiple=lane)

    def _placed_scene(self, plan: ExecPlan) -> "Scene":
        """The scene with its BVH replicated across the plan's mesh
        (placed once per shard count and scene version — a refit bumps the
        version, so animated scenes re-place the new boxes without
        recompiling anything)."""
        if plan.shards == 1:
            return self.scene
        key = ("scene", plan.shards, self.scene.version)
        placed = self._placed.get(key)
        if placed is None:
            self._placed = {k: v for k, v in self._placed.items()
                            if k[0] != "scene" or k[1] != plan.shards}
            placed = Scene(replicated(plan.mesh, self.scene.bvh),
                           self.scene.depth, builder=self.scene.builder)
            self._placed[key] = placed
        return placed

    def _trace_ctx(self, name: str, prepare, plan: ExecPlan):
        """The backend's trace context operand: the (replicated) BVH by
        default, or — when the backend registered a ``prepare`` hook —
        its prepared form (the fused kernel's packed operands), computed
        through one jitted prepare function per backend, once per scene
        version and mesh, then re-fed to every chunk and shard.  A refit
        bumps the version, so animated scenes re-pack (one compiled
        re-execution, zero retraces) without recompiling anything."""
        if prepare is None:
            return self._placed_scene(plan).bvh
        key = ("trace_ctx", name, plan.shards, self.scene.version)
        ctx = self._placed.get(key)
        if ctx is None:
            self._placed = {k: v for k, v in self._placed.items()
                            if k[0] != "trace_ctx" or k[1] != name
                            or k[2] != plan.shards}
            fn = self._compiled(("prepare", name),
                                lambda: prepare(self.scene))
            ctx = fn(self.scene.bvh)
            if plan.shards > 1:
                ctx = replicated(plan.mesh, ctx)
            self._placed[key] = ctx
        return ctx

    def _placed_index(self, plan: ExecPlan) -> "VectorIndex":
        """The index with database + precomputed norms replicated across
        the plan's mesh (keyed on the index version: a cloud refit swaps
        the database, so stale replicas are evicted)."""
        if plan.shards == 1:
            return self.index
        key = ("index", plan.shards, self._index_version())
        placed = self._placed.get(key)
        if placed is None:
            self._placed = {k: v for k, v in self._placed.items()
                            if k[0] != "index" or k[1] != plan.shards}
            index = self.index
            placed = VectorIndex(
                replicated(plan.mesh, index.database),
                sq_norms=replicated(plan.mesh, index.sq_norms))
            self._placed[key] = placed
        return placed

    def _neighbor_ctx(self, name: str, prepare, plan: ExecPlan):
        """The neighbor backend's context operand, mirroring
        :meth:`_trace_ctx`: prepared once per cloud version and mesh
        (packed kernel operands / derived norms), re-fed to every chunk
        and shard.  A refit bumps the version, so moved clouds re-prepare
        (one compiled re-execution, zero retraces) without recompiling."""
        if prepare is None:
            bvh = self.cloud.bvh
            if plan.shards == 1:
                return bvh
        key = ("neighbor_ctx", name, plan.shards, self.cloud.version)
        ctx = self._placed.get(key)
        if ctx is None:
            self._placed = {k: v for k, v in self._placed.items()
                            if k[0] != "neighbor_ctx" or k[1] != name
                            or k[2] != plan.shards}
            if prepare is None:
                ctx = self.cloud.bvh
            else:
                fn = self._compiled(("prepare", name),
                                    lambda: prepare(self.cloud))
                ctx = fn(self.cloud.bvh)
            if plan.shards > 1:
                ctx = replicated(plan.mesh, ctx)
            self._placed[key] = ctx
        return ctx

    # -- traversal queries -------------------------------------------------

    @_spanned
    def trace(self, rays, ray_type: str = "closest", *,
              backend: str | None = None, t_min: float | None = None,
              max_rounds: int | None = None, shard=None,
              chunk_size: int | None = None) -> TraceResult:
        """Traverse a ray batch.  ``ray_type`` is ``"closest"`` | ``"any"``
        | ``"shadow"`` (CrossRT-style split); results are bit-identical to
        the legacy ``trace_rays`` / ``trace_wavefront`` entry points —
        whatever ``shard`` / ``chunk_size`` (None = the engine defaults)
        schedule the batch onto."""
        with span("engine.prepare"):
            prepared = self._prepare_trace(rays, ray_type, backend, t_min,
                                           max_rounds, shard, chunk_size)
        if isinstance(prepared, TraceResult):
            return prepared  # the empty batch's typed result
        fn, ctx, plan, name = prepared
        outs = self._run_blocks(fn, rays, plan, ctx)
        with span("engine.assemble"):
            # streamed assembly: per-ray rows concatenate across chunks;
            # the batch-level round count is the max over chunks and
            # shards, which equals the single-device value (a ray is
            # active for exactly quadbox_jobs consecutive rounds wherever
            # it executes)
            rounds = jnp.max(jnp.stack(
                [jnp.max(jnp.atleast_1d(o.rounds)) for o in outs]))
            rows = concat_rows([o._replace(rounds=None) for o in outs],
                               plan.n)
            res = rows._replace(rounds=rounds)
        if _OBS.enabled:
            self._obs_record("trace", name, plan,
                             jobs=(("quadbox", res.quadbox_jobs),
                                   ("triangle", res.triangle_jobs)))
        return res

    def _prepare_trace(self, rays, ray_type, backend, t_min, max_rounds,
                       shard, chunk_size):
        """Backend, plan, compiled function and context of a trace:
        ``(fn, ctx, plan, backend name)``, or the typed empty result."""
        if self.scene is None:
            raise ValueError("QueryEngine has no Scene; construct with "
                             "QueryEngine(scene=...) or Scene.engine()")
        if ray_type not in RAY_TYPES:
            raise ValueError(
                f"ray_type must be one of {RAY_TYPES}, got {ray_type!r}")
        if t_min is None:
            t_min = SHADOW_T_MIN if ray_type == "shadow" else 0.0
        t_min = float(t_min)
        n = rays.origin.shape[0]
        shards = self._resolve_shards(shard, n)
        chunk_size = check_count("chunk_size", chunk_size)
        name = backend or self.default_backend
        if name == "auto":
            name = self.resolve_trace_backend(ray_type, n, t_min, max_rounds,
                                              shards=shards)
        if name not in _TRACE_BACKENDS:
            raise ValueError(f"unknown trace backend {name!r} "
                             f"(registered: {trace_backends()})")
        supported, build, lane_multiple, prepare = _TRACE_BACKENDS[name]
        if ray_type not in supported:
            raise ValueError(f"backend {name!r} supports ray types "
                             f"{supported}, got {ray_type!r}")
        if n == 0:  # empty guard: typed empty result, nothing compiled
            return TraceResult(
                t=jnp.zeros((0,), jnp.float32),
                tri_index=jnp.zeros((0,), jnp.int32),
                hit=jnp.zeros((0,), bool),
                quadbox_jobs=jnp.zeros((0,), jnp.int32),
                triangle_jobs=jnp.zeros((0,), jnp.int32),
                stack_overflow=jnp.zeros((0,), bool),
                rounds=jnp.int32(0))

        plan = self._plan(n, shards, chunk_size,
                          lane_multiple=lane_multiple)
        key = ("trace", name, ray_type, t_min, max_rounds) + plan.key \
            + _elem_key(rays)

        def build_fn():
            run = build(self.scene, ray_type, t_min, max_rounds,
                        self.interpret)
            if plan.shards == 1:
                return run

            def per_shard(ctx, r):
                rec = run(ctx, r)
                # lift the scalar round count to a length-1 row axis so the
                # shard_map returns one value per shard (reduced below)
                return rec._replace(rounds=jnp.atleast_1d(rec.rounds))

            return shard_rows_ctx(per_shard, plan.mesh)

        fn = self._compiled(key, build_fn)
        return fn, self._trace_ctx(name, prepare, plan), plan, name

    def occluded(self, rays, *, t_min: float = SHADOW_T_MIN,
                 backend: str | None = None, shard=None,
                 chunk_size: int | None = None) -> jax.Array:
        """Boolean shadow/visibility query (extent-limited any-hit)."""
        return self.trace(rays, ray_type="shadow", t_min=t_min,
                          backend=backend, shard=shard,
                          chunk_size=chunk_size).hit

    # -- distance queries --------------------------------------------------

    def _distance_fn(self, kind: str, queries, metric: str,
                     backend: str | None, statics: tuple, epilogue,
                     empty, shard=None, chunk_size: int | None = None,
                     epilogue_scope: str | None = None):
        """Run a brute distance query: the backend's scores under the
        ``<kind>.distance`` named scope, then ``epilogue`` (under
        ``epilogue_scope``, if given) in the same compiled program."""
        with span("engine.prepare"):
            prepared = self._prepare_distance(
                kind, queries, metric, backend, statics, epilogue, shard,
                chunk_size, epilogue_scope)
        if prepared is None:  # empty guard: typed empty result
            return empty()
        fn, q, plan, name = prepared
        outs = self._run_blocks(fn, q, plan)
        with span("engine.assemble"):
            res = concat_rows(outs, plan.n)
        if _OBS.enabled:
            self._obs_record(kind, name, plan)
        return res

    def _prepare_distance(self, kind, queries, metric, backend, statics,
                          epilogue, shard, chunk_size, epilogue_scope):
        """``(fn, queries, plan, backend name)`` of a distance query, or
        None for an empty batch (nothing compiled)."""
        if self.index is None:
            raise ValueError("QueryEngine has no VectorIndex; construct "
                             "with QueryEngine(index=...) or "
                             "VectorIndex.engine()")
        name = backend or self.default_backend
        if name == "auto":
            name = self.resolve_distance_backend()
        if name not in _DISTANCE_BACKENDS:
            raise ValueError(f"unknown distance backend {name!r} "
                             f"(registered: {distance_backends()})")
        q = jnp.asarray(queries)
        n = q.shape[0]
        shards = self._resolve_shards(shard, n)  # validates before guard
        chunk_size = check_count("chunk_size", chunk_size)
        if n == 0:
            return None
        plan = self._plan(n, shards, chunk_size)
        key = ((kind, name, metric, self._index_version()) + statics
               + plan.key + _elem_key(q))
        build_scores = _DISTANCE_BACKENDS[name]

        def build():
            score_fn = build_scores(self._placed_index(plan), metric,
                                    self.interpret)

            def run(qq):
                with jax.named_scope(f"{kind}.distance"):
                    scores = score_fn(qq)
                if epilogue_scope is None:
                    return epilogue(scores)
                with jax.named_scope(epilogue_scope):
                    return epilogue(scores)

            if plan.shards == 1:
                return run
            return shard_rows(run, plan.mesh)

        return self._compiled(key, build), q, plan, name

    def _tree_neighbor(self, kind: str, queries, k: int, radius,
                       name: str, shard=None,
                       chunk_size: int | None = None) -> NeighborRecord:
        """Run a neighbor query through a registered tree backend: pad /
        shard / chunk the query batch exactly like a trace (queries ride
        as :func:`point_queries` ray bundles; the radius is a *runtime*
        extent, so sweeping radii re-enters one compiled function)."""
        if self.cloud is None:
            raise ValueError(
                f"backend {name!r} needs a PointCloudScene; construct "
                "with QueryEngine(cloud=...) or PointCloudScene.engine()")
        with span("engine.prepare"):
            prepared = self._prepare_neighbor(kind, queries, k, radius,
                                              name, shard, chunk_size)
        if isinstance(prepared, NeighborRecord):
            return prepared  # the empty batch's typed result
        fn, ctx, rays, plan, kk = prepared
        n = plan.n
        outs = self._run_blocks(fn, rays, plan, ctx)
        with span("engine.assemble"):
            rounds = jnp.max(jnp.stack(
                [jnp.max(jnp.atleast_1d(o.rounds)) for o in outs]))
            rec = concat_rows([o._replace(rounds=None) for o in outs], n)
            rec = rec._replace(rounds=rounds)
            if kk < k:  # pad the clamped top-k axis back out (k > N)
                pad = k - kk
                rec = rec._replace(
                    dist_sq=jnp.concatenate(
                        [rec.dist_sq,
                         jnp.full((n, pad), jnp.inf, jnp.float32)], axis=1),
                    index=jnp.concatenate(
                        [rec.index, jnp.full((n, pad), -1, jnp.int32)],
                        axis=1),
                    valid=jnp.concatenate(
                        [rec.valid, jnp.zeros((n, pad), bool)], axis=1))
        if _OBS.enabled:
            self._obs_record(kind, name, plan,
                             jobs=(("box", rec.box_jobs),
                                   ("point", rec.point_jobs)))
        return rec

    def _prepare_neighbor(self, kind, queries, k, radius, name, shard,
                          chunk_size):
        """``(fn, ctx, query rays, plan, clamped k)`` of a tree-backed
        neighbor query, or the typed empty result."""
        mode = "nearest" if kind == "nearest" else "within"
        build, lane_multiple, prepare = _NEIGHBOR_BACKENDS[name]
        q = jnp.asarray(queries, jnp.float32)
        if q.ndim != 2 or q.shape[-1] != 3:
            raise ValueError(
                f"tree-backed {kind} expects (M, 3) queries, got "
                f"{tuple(q.shape)}")
        # clamp the top-k register count to the cloud (k > N pads below)
        kk = max(1, min(int(k), self.cloud.size))
        n = q.shape[0]
        shards = self._resolve_shards(shard, n)
        chunk_size = check_count("chunk_size", chunk_size)
        if n == 0:  # empty guard: typed empty result, nothing compiled
            z = jnp.zeros((0,), jnp.int32)
            return NeighborRecord(
                dist_sq=jnp.zeros((0, k), jnp.float32),
                index=jnp.zeros((0, k), jnp.int32),
                valid=jnp.zeros((0, k), bool), count=z, box_jobs=z,
                point_jobs=z, rounds=jnp.int32(0))
        rays = point_queries(q, radius)
        plan = self._plan(n, shards, chunk_size,
                          lane_multiple=lane_multiple)
        key = ("neighbor", name, mode, kk) + plan.key + _elem_key(rays)

        def build_fn():
            run = build(self.cloud, mode, kk, self.interpret)
            if plan.shards == 1:
                return run

            def per_shard(ctx, r):
                rec = run(ctx, r)
                return rec._replace(rounds=jnp.atleast_1d(rec.rounds))

            return shard_rows_ctx(per_shard, plan.mesh)

        fn = self._compiled(key, build_fn)
        return fn, self._neighbor_ctx(name, prepare, plan), rays, plan, kk

    def _resolve_neighbor_name(self, kind: str, metric: str, backend,
                               k=None, radius=None) -> str:
        name = backend or self.default_backend
        if name == "auto":
            name = self.resolve_neighbor_backend(kind, metric, k=k,
                                                 radius=radius)
        if name in _NEIGHBOR_BACKENDS and metric != "euclidean":
            raise ValueError(
                f"tree backend {name!r} supports metric='euclidean' "
                f"only, got {metric!r} (use the mxu/pallas brute "
                "backends for angular/cosine)")
        return name

    @_spanned
    def neighbor_search(self, queries, k: int, radius=None, *,
                        mode: str = "within",
                        backend: str | None = None, shard=None,
                        chunk_size: int | None = None) -> NeighborRecord:
        """Direct tree-backed neighbor query returning the full
        :class:`~repro.core.neighbor.NeighborRecord` (distances, indices,
        exact in-radius counts *and* per-query job statistics — what the
        benchmarks plot).  ``nearest`` / ``within`` / ``count_within``
        are the typed convenience views over this."""
        k = check_k(k)
        if radius is not None:
            radius = check_radius(radius, "euclidean")
        name = backend or self.default_backend
        if name == "auto":
            name = "tree_wavefront"
        if name not in _NEIGHBOR_BACKENDS:
            raise ValueError(f"unknown neighbor backend {name!r} "
                             f"(registered: {neighbor_backends()})")
        kind = "nearest" if mode == "nearest" else "within"
        return self._tree_neighbor(kind, queries, k, radius, name,
                                   shard=shard, chunk_size=chunk_size)

    @_spanned
    def nearest(self, queries, k: int, metric: str = "euclidean", *,
                backend: str | None = None, shard=None,
                chunk_size: int | None = None) -> NearestResult:
        """Exact k-nearest neighbours.  ``k`` is validated eagerly
        (``ValueError`` on ``k <= 0``) and clamped to the database size —
        ``k > N`` pads the trailing slots (inf/-inf score, index -1,
        ``valid`` False) instead of crashing inside ``lax.top_k``.

        With a :class:`PointCloudScene`, ``backend="auto"`` routes
        euclidean queries through the BVH (``tree_wavefront``; the fused
        ``tree_pallas`` is explicit-only) when the tree wins, and the
        result then carries the walk's ``box_jobs`` / ``point_jobs`` /
        ``rounds``.  The tree and the brute backends (``mxu`` /
        ``pallas``) agree within the expanded form's rounding band (see
        :meth:`resolve_neighbor_backend`)."""
        if metric not in METRICS:
            raise ValueError(f"unknown metric: {metric}")
        k = check_k(k)
        name = self._resolve_neighbor_name("nearest", metric, backend,
                                           k=k)
        if name in _NEIGHBOR_BACKENDS:
            rec = self._tree_neighbor("nearest", queries, k, None, name,
                                      shard=shard, chunk_size=chunk_size)
            return NearestResult(rec.dist_sq, rec.index, rec.valid,
                                 rec.box_jobs, rec.point_jobs, rec.rounds)

        def topk(s):
            scores, idx = select_topk(s, k, metric)
            return NearestResult(scores, idx, idx >= 0)

        return self._distance_fn(
            "nearest", queries, metric, name, (k,), topk,
            lambda: NearestResult(jnp.zeros((0, k), jnp.float32),
                                  jnp.zeros((0, k), jnp.int32),
                                  jnp.zeros((0, k), bool)),
            shard=shard, chunk_size=chunk_size, epilogue_scope="nearest.topk")

    @_spanned
    def within(self, queries, radius: float, k: int,
               metric: str = "euclidean", *,
               backend: str | None = None, shard=None,
               chunk_size: int | None = None) -> WithinResult:
        """Fixed-radius query: best ``k`` in-range neighbours (the
        extent-limited shadow-ray twin, DESIGN.md §3).  ``radius`` and
        ``k`` are validated eagerly (``ValueError`` on NaN / negative
        euclidean radius and on ``k <= 0``); ``k > N`` pads like
        :meth:`nearest`.  Routing is as in :meth:`nearest`: tree-backed
        for euclidean cloud queries when the tree wins, in-radius
        membership exact against a brute pass in the route's own
        distance form."""
        if metric not in RADIUS_METRICS:
            raise ValueError(f"unknown radius metric: {metric}")
        radius = check_radius(radius, metric)
        k = check_k(k)
        name = self._resolve_neighbor_name("within", metric, backend,
                                           k=k, radius=radius)
        if name in _NEIGHBOR_BACKENDS:
            rec = self._tree_neighbor("within", queries, k, radius, name,
                                      shard=shard, chunk_size=chunk_size)
            return WithinResult(rec.dist_sq, rec.index, rec.valid)
        return self._distance_fn(
            "within", queries, metric, name, (radius, k),
            lambda s: WithinResult(*select_within(s, radius, k, metric)),
            lambda: WithinResult(jnp.zeros((0, k), jnp.float32),
                                 jnp.zeros((0, k), jnp.int32),
                                 jnp.zeros((0, k), bool)),
            shard=shard, chunk_size=chunk_size)

    @_spanned
    def count_within(self, queries, radius: float,
                     metric: str = "euclidean", *,
                     backend: str | None = None, shard=None,
                     chunk_size: int | None = None) -> jax.Array:
        """How many database points fall within ``radius`` per query.
        ``radius`` is validated eagerly (``ValueError`` on NaN / negative
        euclidean radius); routing is as in :meth:`within`, and the
        tree-backed count is exact (the traversal counts every in-radius
        leaf acceptance, not just the best ``k``)."""
        if metric not in RADIUS_METRICS:
            raise ValueError(f"unknown radius metric: {metric}")
        radius = check_radius(radius, metric)
        name = self._resolve_neighbor_name("count_within", metric,
                                           backend, radius=radius)
        if name in _NEIGHBOR_BACKENDS:
            return self._tree_neighbor(
                "count_within", queries, 1, radius, name,
                shard=shard, chunk_size=chunk_size).count
        return self._distance_fn(
            "count_within", queries, metric, name, (radius,),
            lambda s: count_within_scores(s, radius, metric),
            lambda: jnp.zeros((0,), jnp.int32),
            shard=shard, chunk_size=chunk_size)

    @_spanned
    def scores(self, queries, metric: str = "euclidean", *,
               backend: str | None = None, shard=None,
               chunk_size: int | None = None) -> jax.Array:
        """The raw (M, N) score matrix (squared distances / similarities) —
        what MoE routers consume as logits."""
        if metric not in METRICS:
            raise ValueError(f"unknown metric: {metric}")
        return self._distance_fn(
            "scores", queries, metric, backend, (), lambda s: s,
            lambda: jnp.zeros((0, self.index.size), jnp.float32),
            shard=shard, chunk_size=chunk_size)

    def similarity(self, queries, *, backend: str | None = None,
                   shard=None, chunk_size: int | None = None) -> jax.Array:
        """Full cosine-similarity matrix (external-divider epilogue)."""
        return self.scores(queries, "cosine", backend=backend, shard=shard,
                           chunk_size=chunk_size)

    def __repr__(self):
        return (f"QueryEngine(scene={self.scene!r}, index={self.index!r}, "
                f"cloud={self.cloud!r}, "
                f"backend={self.default_backend!r}, "
                f"pad_multiple={self.pad_multiple}, "
                f"shard={self.default_shard!r}, "
                f"chunk_size={self.default_chunk_size}, "
                f"cache={self.cache_info()})")
