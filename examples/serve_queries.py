"""Serve ray queries: continuous batching over a live QueryEngine.

A minimal asyncio client/server demo of the serving subsystem
(DESIGN.md §10).  One ``QueryEngine`` holds both a triangle scene and a
point cloud; a ``QueryServer`` wraps it; many concurrent "users" each
fire a handful of tiny requests — rays to trace, points to look up —
over mixed methods.  The server coalesces them into full lane-multiple
batches, executes each batch as one engine call, and splits the
responses back per request, **bit-identical** to what a direct
per-request engine call returns (this script asserts it for every
response, job counters included).

With ``--telemetry DIR`` the demo also exercises the telemetry plane
(DESIGN.md §11): it enables ``repro.obs``, warms every (method, params,
ladder-size) program the run can touch, serves one warmup pass, then
serves the measured pass inside an ``obs.CompileTracker`` and **asserts
zero steady-state compiles** — the serving ladder's whole point — before
writing ``DIR/obs_snapshot.json`` (metrics) and ``DIR/trace.json``
(Chrome-trace spans, one admit → coalesce → execute → split chain per
request; open in Perfetto).  CI runs this mode on every device matrix
entry and uploads both files as artifacts.

Run:  PYTHONPATH=src python examples/serve_queries.py [--users 12]
          [--requests 4] [--max-wait-ms 5] [--telemetry DIR]
"""
import argparse
import asyncio
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api import PointCloudScene, QueryEngine, Scene, make_ray
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import QueryServer

TRACE_FIELDS = ("t", "tri_index", "hit", "quadbox_jobs", "triangle_jobs")


def build_engine(rng):
    """A triangle soup for trace + a point cloud for neighbor queries,
    served by one engine (sharded over whatever mesh is available)."""
    n_tri = 250
    ctr = rng.uniform(-1, 1, (n_tri, 3)).astype(np.float32)
    d1 = rng.normal(scale=0.12, size=(n_tri, 3)).astype(np.float32)
    d2 = rng.normal(scale=0.12, size=(n_tri, 3)).astype(np.float32)
    scene = Scene.from_triangles(np.stack([ctr, ctr + d1, ctr + d2], 1))
    cloud = PointCloudScene.from_points(
        rng.normal(size=(1024, 3)).astype(np.float32))
    return QueryEngine(scene=scene, cloud=cloud, pad_multiple=8,
                       shard="auto")


def make_jobs(rng, n_users, n_requests):
    """Each user's little mixed workload: some rays, some lookups."""
    jobs = []
    for u in range(n_users):
        for r in range(n_requests):
            n = int(rng.integers(1, 7))
            kind = ("trace", "nearest", "trace", "count_within")[r % 4]
            if kind == "trace":
                org = rng.uniform(-3, -2, (n, 3)).astype(np.float32)
                tgt = rng.uniform(-0.5, 0.5, (n, 3)).astype(np.float32)
                rays = make_ray(jnp.asarray(org), jnp.asarray(tgt - org))
                jobs.append((u, "trace", rays,
                             {"ray_type": ("closest", "any", "shadow")[u % 3]}))
            elif kind == "nearest":
                q = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
                jobs.append((u, "nearest", q, {"k": 4}))
            else:
                q = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
                jobs.append((u, "count_within", q, {"radius": 0.6}))
    return jobs


def warm_ladder(engine, jobs, max_rows=128):
    """Compile every (method, static-params, ladder-size) program the
    serving run can touch — power-of-two sizes up to twice the batch cap,
    one pass per distinct request configuration (``ray_type`` buckets
    compile distinct programs).  After this, a served pass re-enters only
    cached programs: the steady state ``--telemetry`` asserts."""
    combos = {}
    for _, kind, payload, kw in jobs:
        combos.setdefault((kind, tuple(sorted(kw.items()))),
                          (kind, payload, kw))
    sizes = [1 << i for i in range(max_rows.bit_length())]
    for kind, payload, kw in combos.values():
        for n in sizes:
            reps = jax.tree_util.tree_map(
                lambda x: jnp.concatenate([x[:1]] * n, axis=0), payload)
            jax.block_until_ready(getattr(engine, kind)(reps, **kw))


async def user_session(server, my_jobs):
    """One client: fire requests concurrently, await the responses."""
    tasks = [asyncio.ensure_future(
        getattr(server, kind)(payload, **kw))
        for _, kind, payload, kw in my_jobs]
    return await asyncio.gather(*tasks)


def check_parity(engine, jobs, responses):
    """Every served response must be bit-identical to a direct call."""
    for (_, kind, payload, kw), got in zip(jobs, responses):
        ref = getattr(engine, kind)(payload, **kw)
        if kind == "trace":
            for f in TRACE_FIELDS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, f)),
                    np.asarray(getattr(ref, f)), err_msg=f"trace {f}")
            assert int(got.rounds) == int(ref.rounds)
        elif kind == "count_within":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        else:
            np.testing.assert_array_equal(np.asarray(got.indices),
                                          np.asarray(ref.indices))
            np.testing.assert_array_equal(np.asarray(got.scores),
                                          np.asarray(ref.scores))


async def serve_pass(engine, jobs, args):
    """One full client/server pass over ``jobs``."""
    async with QueryServer(engine, max_batch_rows=64,
                           max_wait=args.max_wait_ms * 1e-3) as server:
        per_user = [[j for j in jobs if j[0] == u]
                    for u in range(args.users)]
        results = await asyncio.gather(
            *[user_session(server, mine) for mine in per_user])
        stats = server.stats()
    return per_user, results, stats


async def main_async(args):
    rng = np.random.default_rng(0)
    if args.telemetry:
        obs.enable()
    engine = build_engine(rng)
    jobs = make_jobs(rng, args.users, args.requests)
    print(f"devices={jax.local_device_count()}  "
          f"users={args.users}  requests={len(jobs)}")

    tracker = None
    if args.telemetry:
        # ladder warm + one throwaway served pass: everything the
        # measured pass executes (compiled programs AND eager pad/slice
        # shapes) has been traced once, so the tracker below must read 0
        warm_ladder(engine, jobs)
        await serve_pass(engine, jobs, args)
        tracker = obs.CompileTracker().start()

    per_user, results, stats = await serve_pass(engine, jobs, args)

    if tracker is not None:
        tracker.stop()
        print(f"steady-state compiles in measured pass: {tracker.compiles}")
        assert tracker.compiles == 0, (
            f"{tracker.compiles} jit tracings in the steady-state serving "
            "pass — the quantized ladder should have absorbed them all")

    flat = [r for user in per_user for r in user]
    responses = [r for user_res in results for r in user_res]
    check_parity(engine, flat, responses)
    print("bit-parity vs direct engine calls: OK "
          f"({len(responses)} responses)")

    print(f"{'method':>14} {'reqs':>5} {'batches':>7} {'req/batch':>9} "
          f"{'fill':>5} {'p50ms':>7} {'p99ms':>7}  flushes")
    for method in sorted(stats):
        s = stats[method]
        flushes = (f"full={s.flush_full} timer={s.flush_timer} "
                   f"deadline={s.flush_deadline} drain={s.flush_drain}")
        print(f"{method:>14} {s.requests:>5} {s.batches:>7} "
              f"{s.requests_per_batch:>9.2f} {s.mean_fill:>5.2f} "
              f"{s.p50_ms:>7.2f} {s.p99_ms:>7.2f}  {flushes}")
    occupancy = (sum(s.requests for s in stats.values())
                 / max(1, sum(s.batches for s in stats.values())))
    print(f"overall requests/batch: {occupancy:.2f}")
    assert occupancy > 1.0, "coalescing never batched requests together"

    if args.telemetry:
        os.makedirs(args.telemetry, exist_ok=True)
        snap_path = os.path.join(args.telemetry, "obs_snapshot.json")
        trace_path = os.path.join(args.telemetry, "trace.json")
        obs.write_snapshot(snap_path)
        n_events = obs.export_chrome_trace(trace_path)
        print(f"telemetry: wrote {snap_path} and {trace_path} "
              f"({n_events} trace events)")


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=12)
    ap.add_argument("--requests", type=int, default=4,
                    help="requests per user")
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--telemetry", metavar="DIR", default=None,
                    help="enable repro.obs, assert steady-state compiles "
                         "== 0, write obs_snapshot.json + trace.json "
                         "(Chrome trace) into DIR")
    args = ap.parse_args()
    asyncio.run(main_async(args))


if __name__ == "__main__":
    main()
