"""The one traffic generator: a closed loop of whole calls, or an open loop
of requests at a fixed rate through a ``QueryServer``.

What a mix varies is data (``bench/mixes/<mix>.json``); what a payload is
comes from the configuration's kind (``bench/kinds/<kind>.py``).

Open-loop traffic is the same for every seed in everything but order: the
row counts are the quantiles of their distribution and the gaps between
arrivals the quantiles of the exponential, both shuffled by the seed.  So
two seeds offer the same work at the same mean rate, and differ in which
request comes when.
"""
from __future__ import annotations

import asyncio
import math
import time
from typing import Any, Callable, NamedTuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving import QueryServer


class Call(NamedTuple):
    """One completed call of a closed loop."""

    payload: int  # index into the loop's payloads
    rows: int
    done: float  # host clock
    result: Any


class ClosedWindow(NamedTuple):
    start: float
    end: float  # completion of the last call
    calls: list


class Request(NamedTuple):
    """One open-loop request: when it is due (seconds after the window
    opens), which rows of the pool it carries."""

    due: float
    lo: int
    rows: int


class OpenWindow(NamedTuple):
    start: float
    end: float  # completion of the last request answered
    requests: list  # of Request
    sent: list  # host clock at which each request entered the server
    done: list  # host clock at which each answer was ready (None: never)
    results: list  # the answers (None where none came)
    errors: list  # the exception each failed request raised, else None
    waited_until: float  # host clock at which the drain stopped waiting
    stats_before: dict  # QueryServer.stats() as the window opened
    stats_after: dict  # ... and once every answer was in


def closed_loop(call: Callable, payloads: list, rows: Callable,
                seconds: float) -> ClosedWindow:
    """One caller: the next call starts when the previous one's result is
    ready, cycling through ``payloads``; calls start until ``seconds`` have
    passed and the last cycle is whole, so that every run does the same
    work whatever order its seed gave the payloads."""
    calls = []
    with TraceAnnotation("bench.window"):
        start = t0 = time.perf_counter()
        while (not calls or t0 < start + seconds
               or len(calls) % len(payloads)):
            i = len(calls) % len(payloads)
            with TraceAnnotation("bench.call"):
                out = jax.block_until_ready(call(payloads[i]))
            done = time.perf_counter()
            calls.append(Call(i, rows(payloads[i]), done, out))
            t0 = done
    return ClosedWindow(start, calls[-1].done, calls)


def quantiles(n: int) -> np.ndarray:
    """Midpoint quantile levels of ``n`` equal shares of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def open_requests(mix: dict, seconds: float, pool_rows: int,
                  rng: np.random.Generator) -> list:
    """The window's requests: ``rate_per_s * seconds`` of them, row counts
    log-uniform over [rows.min, rows.max], Poisson arrivals."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    lo, hi = mix["rows"]["min"], mix["rows"]["max"]
    sizes = np.rint(np.exp(math.log(lo)
                           + quantiles(n) * (math.log(hi) - math.log(lo))))
    gaps = -np.log1p(-quantiles(n)) / mix["rate_per_s"]
    sizes, gaps = rng.permutation(sizes).astype(int), rng.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    starts = rng.integers(0, pool_rows - sizes + 1)
    return [Request(float(t), int(s0), int(s))
            for t, s0, s in zip(due, starts, sizes)]


async def _open_loop(server, serve: Callable, pool: np.ndarray,
                     requests: list, drain_s: float) -> OpenWindow:
    n = len(requests)
    sent, done, results, errors = [None] * n, [None] * n, [None] * n, \
        [None] * n

    async def one(i: int, req: Request) -> None:
        sent[i] = time.perf_counter()
        try:
            res = await serve(server, pool[req.lo:req.lo + req.rows])
            results[i] = jax.block_until_ready(res)
            done[i] = time.perf_counter()
        except Exception as exc:  # a failed request is counted, not fatal
            errors[i] = exc

    tasks = []
    stats_before = server.stats()
    with TraceAnnotation("bench.window"):
        start = time.perf_counter()
        for i, req in enumerate(requests):
            delay = start + req.due - time.perf_counter()
            if delay > 0:
                with TraceAnnotation("bench.wait"):
                    await asyncio.sleep(delay)
            with TraceAnnotation("bench.generate"):
                tasks.append(asyncio.ensure_future(one(i, req)))
        close = start + requests[-1].due
        with TraceAnnotation("bench.drain"):
            await asyncio.wait(tasks, timeout=max(
                0.0, close + drain_s - time.perf_counter()))
    waited_until = time.perf_counter()
    stats_after = server.stats()
    for t in tasks:
        t.cancel()
    ends = [d for d in done if d is not None]
    return OpenWindow(start, max(ends) if ends else start, requests, sent,
                      done, results, errors, waited_until, stats_before,
                      stats_after)


def open_loop(engine, serve: Callable, pool: np.ndarray, requests: list,
              server_settings: dict, warm: list, drain_s: float,
              measured) -> OpenWindow:
    """Requests at their due times through one ``QueryServer``, each sent
    whether or not earlier ones have been answered.  ``warm`` payloads go
    through the same server first; the window, inside the context manager
    ``measured``, follows."""

    async def run() -> OpenWindow:
        async with QueryServer(engine, **server_settings) as server:
            for payload in warm:
                jax.block_until_ready(await serve(server, payload))
            with measured:
                return await _open_loop(server, serve, pool, requests,
                                        drain_s)

    return asyncio.run(run())
