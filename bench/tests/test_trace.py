"""The trace reduction and the roofline arithmetic, against numbers worked
out by hand."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace
from bench.roofline import nearest_least_seconds
from bench.tests.conftest import cpu_rule

FIXTURE = Path(__file__).parent / "fixtures" / "cpu.xplane.pb"

E = trace.Event


def test_union_merges_overlaps_and_clips():
    events = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40), E("d", 40, 45),
              E("e", 50, 60)]
    assert trace.union(events) == [(0, 20), (30, 45), (50, 60)]
    assert trace.union(events, 8, 55) == [(8, 20), (30, 45), (50, 55)]
    assert trace.covered(trace.union(events, 8, 55)) == 12 + 15 + 5


def test_idle_gaps_between_and_around_busy():
    busy = [(10, 20), (30, 45)]
    assert trace.idle(busy, 0, 50) == [(0, 10), (20, 30), (45, 50)]
    assert trace.idle([], 0, 5) == [(0, 5)]


def test_self_times_subtract_nested_events():
    events = [E("outer", 0, 100), E("inner", 10, 30), E("inner", 50, 60),
              E("next", 100, 120)]
    assert trace.self_times(events) == {"outer": 70, "inner": 30,
                                        "next": 20}
    # clipped to [20, 110]: outer keeps 20..100 less 20..30 and 50..60
    assert trace.self_times(events, 20, 110) == {"outer": 60, "inner": 20,
                                                 "next": 10}


def test_innermost_span_covering_an_instant():
    spans = [E("bench.window", 0, 100), E("bench.call", 10, 40),
             E("bench.wait", 40, 90)]
    assert trace.innermost(spans, 20) == "bench.call"
    assert trace.innermost(spans, 60) == "bench.wait"
    assert trace.innermost(spans, 95) == "bench.window"
    assert trace.innermost(spans, 200) == "none"


def _hand_made() -> trace.Summary:
    spans = [E("bench.window", 0, 1000), E("bench.call", 0, 400),
             E("bench.wait", 400, 600), E("bench.call", 600, 1000)]
    ops = {"/device:TPU:0": [E("fusion", 100, 300), E("top-k", 300, 350),
                             E("fusion", 700, 900)]}
    modules = {"/device:TPU:0": [E("jit_run", 100, 350),
                                 E("jit_run", 700, 900)]}
    return trace.Summary(trace.Trace(spans, ops, modules))


def test_summary_busy_idle_per_op_and_gaps():
    s = _hand_made()
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(450e-9)
    assert s.busy_in(0, 400) == pytest.approx(250e-9)
    assert s.busy_in(600, 1000) == pytest.approx(200e-9)
    assert s.op_seconds() == pytest.approx({"fusion": 400e-9,
                                            "top-k": 50e-9})
    assert s.op_seconds(0, 400) == pytest.approx({"fusion": 200e-9,
                                                  "top-k": 50e-9})
    gaps = sorted(s.gaps(), key=lambda g: -g[0])
    assert gaps == [(pytest.approx(350e-9), "bench.wait"),
                    (pytest.approx(100e-9), "bench.call"),
                    (pytest.approx(100e-9), "bench.call")]
    b = s.breakdown(top=1)
    assert b["device_ops"] == [["fusion", pytest.approx(400e-9)]]
    assert b["idle_gaps"] == [["bench.wait", pytest.approx(350e-9)]]


def test_summary_needs_a_window_span():
    with pytest.raises(ValueError, match="bench.window"):
        trace.Summary(trace.Trace([E("bench.call", 0, 1)], {}, {}))


def test_recorded_cpu_trace():
    """A trace recorded on the CPU: two ``bench.call`` spans around one
    jitted program each (a dot and a fusion), ``bench.wait`` spans of 2 ms
    after each; the numbers are read off the file's events by hand."""
    t = trace.extract(str(FIXTURE), cpu_rule)
    assert [s.name for s in t.spans] == [
        "bench.window", "bench.call", "bench.wait", "bench.call",
        "bench.wait"]
    assert list(t.ops) == ["cpu:0"]
    s = trace.Summary(t)
    assert s.window_s == pytest.approx(4445801e-9)
    calls = s.spans("bench.call")
    assert [(c.start, c.end) for c in calls] == [(72178, 267531),
                                                 (2347342, 2444798)]
    # each call: the dot, then the fusion, 301 ns and 180 ns apart
    assert s.busy_in(calls[0].start, calls[0].end) == pytest.approx(
        (36244 + 4216) * 1e-9)
    assert s.busy_in(calls[1].start, calls[1].end) == pytest.approx(
        (30766 + 3996) * 1e-9)
    assert s.busy_s == pytest.approx((36244 + 4216 + 30766 + 3996) * 1e-9)
    assert s.op_seconds() == pytest.approx({
        "dot_general.1": (36244 + 30766) * 1e-9,
        "broadcast_add_fusion": (4216 + 3996) * 1e-9})
    gaps = sorted(s.gaps(), key=lambda g: -g[0])
    assert [name for _, name in gaps] == [
        "bench.wait", "bench.wait", "bench.call", "bench.call", "bench.call"]
    assert [g for g, _ in gaps] == pytest.approx(
        [e * 1e-9 for e in (2389375 - 231006, 4515295 - 2424317,
                            190245 - 69494, 226790 - 226489,
                            2420321 - 2420141)])


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_nearest_least_time_of_a_sift_call_is_compute_bound():
    # 10,000 queries x 10^6 x 128: 2.56e12 operations at 197e12/s
    t = nearest_least_seconds(10_000, 10**6, 128, 10, PEAK)
    assert t == pytest.approx(2 * 10_000 * 10**6 * 128 / 197e12)
    assert t == pytest.approx(12.995e-3, rel=1e-4)


def test_nearest_least_time_of_a_small_call_is_bandwidth_bound():
    # 256 queries: the base read once (512 MB) outweighs 6.6e10 operations
    t = nearest_least_seconds(256, 10**6, 128, 10, PEAK)
    assert t == pytest.approx((4 * 10**6 * 128 + 4 * 256 * 128
                               + 8 * 256 * 10) / 819e9)


def test_nearest_least_time_does_not_depend_on_chunking():
    """The bound is of the call, whatever blocks it runs in: the base is
    counted once, and compute adds up over rows."""
    whole = nearest_least_seconds(10_240, 10**6, 128, 10, PEAK)
    rows = 2 * 10_240 * 10**6 * 128 / 197e12
    assert whole == pytest.approx(rows)
    per_block = sum(nearest_least_seconds(256, 10**6, 128, 10, PEAK)
                    for _ in range(40))
    assert per_block > whole  # blocks would count the base 40 times
