"""Sharded + chunked query execution mechanics (DESIGN.md §6).

The paper's datapath scales by replicating one shared pipeline; RTNN's
batched-query formulation is what keeps such a pipeline saturated.  This
module is the session layer's version of that replication: it decides how a
query batch is *placed* (data-parallel over a 1-D device mesh, scene/index
replicated) and *scheduled* (fixed-size microbatch chunks sharing one
compiled program), without touching any backend's arithmetic.

The execution pipeline for one query is::

    pad -> shard -> query -> unshard -> unpad

* **pad** — each chunk is padded so every *shard* receives a lane multiple
  of rows (``block = shards * ceil(rows_per_shard to pad_multiple)``), by
  repeating the chunk's row 0 (always a valid element; empty guard lives in
  the session layer, which never dispatches 0 rows here).
* **shard** — the chunk's leading axis is split over the mesh
  (``parallel.sharding.batch_sharded``); the scene/index operands are
  replicated once per mesh (``parallel.sharding.replicated``) and closed
  over, so the per-shard computation is *literally* the single-device
  computation on that shard's rows.  No collectives: bit-parity with the
  single-device path is structural, not numerical luck
  (``tests/test_fuzz_backends.py`` fuzzes it).
* **query** — one jitted ``shard_map`` per (backend, static config, block
  shape); every chunk re-enters the same compiled program, so a
  million-ray batch pays one trace and ``n_blocks`` executions with peak
  memory bounded by the block size.
* **unshard/unpad** — per-row outputs concatenate across chunks and slice
  back to the caller's row count; per-chunk scalar statistics (wavefront
  ``rounds``) reduce by ``max``, which matches the single-device value
  exactly (a ray is active for exactly ``quadbox_jobs`` consecutive
  rounds, so the batch round count is the max over rays wherever those
  rays execute).
"""
from __future__ import annotations

import math
import numbers
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.sharding import batch_sharded, replicated  # noqa: F401

#: mesh axis name carrying the data-parallel ray/query batch
BATCH_AXIS = "shards"

_MESHES: dict[tuple[str, int], Mesh] = {}


def available_devices() -> int:
    """Device count the ``shard="auto"`` policy sees."""
    return jax.local_device_count()


def check_count(name: str, value, minimum: int = 1) -> Optional[int]:
    """Eagerly validate an integral execution knob (``shard`` /
    ``chunk_size``): ``None`` passes through (= knob unset); anything else
    must be a true integer (no bools, no floats — ``chunk_size=2.5`` used
    to silently truncate inside the plan math) that is ``>= minimum``.
    Returns the value as a plain ``int``."""
    if value is None:
        return None
    if isinstance(value, bool) or not isinstance(value, numbers.Integral):
        raise ValueError(
            f"{name} must be an int >= {minimum}, got {value!r} "
            f"({type(value).__name__})")
    if value < minimum:
        raise ValueError(f"{name} must be >= {minimum}, got {value!r}")
    return int(value)


def resolve_shards(shard, n_rows: Optional[int] = None) -> int:
    """``shard="auto" | int | None`` -> a concrete shard count.

    ``"auto"`` learns the local device count (capped at the batch size —
    a 3-ray batch on 8 devices gains nothing from 5 idle replicas);
    an explicit value must be a positive integer (validated eagerly, at
    call time) and must not exceed the device count.
    """
    if shard is None:
        return 1
    if shard == "auto":
        shards = available_devices()
        if n_rows is not None:
            shards = max(1, min(shards, n_rows))
        return shards
    shards = check_count("shard", shard)
    if shards == 1:
        return 1
    n_dev = available_devices()
    if shards > n_dev:
        raise ValueError(
            f"shard={shards} exceeds the {n_dev} available device(s)")
    return shards


def device_mesh(shards: int, axis_name: str = BATCH_AXIS) -> Mesh:
    """The (cached) 1-D query mesh over the first ``shards`` devices."""
    key = (axis_name, shards)
    mesh = _MESHES.get(key)
    if mesh is None:
        mesh = _MESHES[key] = jax.make_mesh(
            (shards,), (axis_name,),
            axis_types=(jax.sharding.AxisType.Auto,))
    return mesh


# ---------------------------------------------------------------------------
# Padding policy (one definition; the session layer imports from here)
# ---------------------------------------------------------------------------


def ceil_to(n: int, multiple: int) -> int:
    return max(1, -(-n // multiple) * multiple)


def pad_leading(tree, n_to: int):
    """Pad every leading-axis leaf to ``n_to`` rows by repeating row 0
    (always a valid element, so padded lanes trace/score harmlessly).
    Empty batches pad with zeros — rows are independent in every backend,
    so a degenerate lane is harmless and sliced away on unpad."""
    def pad(x):
        n = x.shape[0]
        if n == n_to:
            return x
        if n:
            rep = jnp.broadcast_to(x[:1], (n_to - n,) + x.shape[1:])
        else:
            rep = jnp.zeros((n_to - n,) + x.shape[1:], x.dtype)
        return jnp.concatenate([x, rep], axis=0)

    return jax.tree_util.tree_map(pad, tree)


# ---------------------------------------------------------------------------
# Execution plan: how one query batch is padded, chunked and sharded
# ---------------------------------------------------------------------------


class ExecPlan(NamedTuple):
    """A resolved (rows, chunking, sharding) schedule for one query."""

    n: int  # caller's row count (> 0; empty batches never reach dispatch)
    block: int  # rows per executed call; shards * lane-multiple per shard
    n_blocks: int  # ceil(n / block) chunked calls through one compiled fn
    shards: int  # 1 = single-device (no shard_map wrapping)

    @property
    def key(self) -> tuple:
        """The plan's contribution to the compiled-function cache key."""
        return (self.shards, self.block)

    @property
    def mesh(self) -> Optional[Mesh]:
        return device_mesh(self.shards) if self.shards > 1 else None


def make_plan(n: int, *, pad_multiple: int, shards: int = 1,
              chunk_size: Optional[int] = None,
              lane_multiple: Optional[int] = None) -> ExecPlan:
    """Schedule ``n`` rows into fixed-size blocks.

    The block is ``chunk_size`` (the whole batch when None) rounded up so
    that each of the ``shards`` shards receives a lane multiple of rows —
    per-shard padding composing with the pad-to-lane policy.  With
    ``shards=1, chunk_size=None`` this degenerates to the original
    single-call ``ceil_to(n, pad_multiple)`` behavior.

    ``lane_multiple`` is a backend-declared hard tile width (e.g. the
    fused Pallas traversal kernel's 128-lane tiles): the effective
    per-shard multiple becomes ``max(pad_multiple, lane_multiple)``, so a
    kernel backend always receives whole tiles per shard per chunk and
    never re-pads internally.  Padding stays the row-0-repeat identity,
    so results are unchanged — only the schedule is.
    """
    if n <= 0:
        raise ValueError("make_plan needs n >= 1; guard empty batches first")
    chunk_size = check_count("chunk_size", chunk_size)
    multiple = (pad_multiple if lane_multiple is None
                else max(pad_multiple, int(lane_multiple)))
    rows = n if chunk_size is None else min(chunk_size, n)
    per_shard = ceil_to(math.ceil(rows / shards), multiple)
    block = per_shard * shards
    return ExecPlan(n=n, block=block, n_blocks=-(-n // block), shards=shards)


def take_block(tree, plan: ExecPlan, i: int):
    """Block ``i`` of the plan: ``plan.block`` rows of ``tree`` from row
    ``i * plan.block`` — the last block padded by repeating its own row 0 —
    and, on a mesh, batch-sharded; so all blocks re-enter one compiled
    function."""
    lo = i * plan.block
    chunk = jax.tree_util.tree_map(lambda x: x[lo:lo + plan.block], tree)
    chunk = pad_leading(chunk, plan.block)
    if plan.shards > 1:
        chunk = batch_sharded(plan.mesh, chunk, BATCH_AXIS)
    return chunk


def slice_rows(tree, sizes):
    """Split per-row leaves into consecutive row groups of ``sizes`` —
    the batch-slice/unpad contract the serving coalescer reuses
    (``repro.serving.batching``): a response computed for a coalesced
    batch is handed back per request by slicing the same row ranges that
    were concatenated on the way in.  Rows beyond ``sum(sizes)`` (lane
    padding) are dropped, so ``slice_rows(padded_result, [n])[0]`` is
    exactly the unpad step of :func:`concat_rows`.  Row independence —
    the property every backend already guarantees for pad -> query ->
    unpad — is what makes this split bit-exact per request."""
    out, lo = [], 0
    for s in sizes:
        s = int(s)
        if s < 0:
            raise ValueError(f"slice sizes must be >= 0, got {s}")
        hi = lo + s
        out.append(jax.tree_util.tree_map(
            lambda x, lo=lo, hi=hi: x[lo:hi], tree))
        lo = hi
    return out


def concat_rows(blocks: list, n: int):
    """Unshard + unpad: stitch per-row block results back together and
    slice to the caller's ``n`` rows.  All leaves must be per-row."""
    if len(blocks) == 1:
        out = blocks[0]
    else:
        out = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *blocks)
    return jax.tree_util.tree_map(lambda x: x[:n], out)


def shard_rows(fn, mesh: Mesh, axis: str = BATCH_AXIS):
    """Data-parallel ``fn`` over rows: each device runs the unchanged
    single-device computation on its row shard (scene/index operands are
    closed over, replicated).  Every output leaf must carry the row axis
    first — scalar statistics must be lifted to a length-1 axis so they
    come back as one value per shard."""
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)


def shard_rows_ctx(fn, mesh: Mesh, axis: str = BATCH_AXIS):
    """:func:`shard_rows` for ``fn(ctx, rows)``: the first argument is a
    replicated context operand (a BVH4 under animation, or a backend's
    prepared form of it — threaded as a runtime argument, not closed
    over, so ``Scene.refit`` swaps its arrays without retracing), the
    second is row-sharded as usual."""
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=P(axis), check_vma=False)
