"""The reduction from a trace to numbers, on a trace recorded on the chip
(three traced steps of ``array_stream``, TPU v5e, PR 23) and on a made-up
four-chip trace with nesting and collectives."""

import json
from pathlib import Path

import pytest

import trace_reduce as T

RECORDED = Path(__file__).resolve().parent / "recorded_stream_trace.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def _union(intervals):
    """An independent union of intervals, the slow obvious way."""
    total, cur_a, cur_b = 0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            total += 0 if cur_b is None else cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    return total + (0 if cur_b is None else cur_b - cur_a)


def test_recorded_planes_and_window(recorded):
    planes = T.device_planes(recorded)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    lo, hi = T.window_of(recorded)
    steps = [e for e in T.host_spans(recorded) if e[0] == "bench.step"]
    assert len(steps) == 3
    assert (lo, hi) == (44137679, 84804558 + 20300830)


def test_recorded_clock_offset(recorded):
    # the first chain of the trace "starts" at 43.109 ms, a millisecond
    # before the host enters the step at 44.138 ms: the chip's clock is
    # behind.  Step 1 ends on the host at 64.456349 ms; the chip's idle gap
    # there begins at 62.292132 ms
    ops = T._line(T.device_planes(recorded)[0], T.OPS_LINE)
    steps = [e for e in T.host_spans(recorded) if e[0] == "bench.step"]
    off = T.clock_offset_ns(ops, steps)
    assert off == pytest.approx(2.2e6, abs=0.1e6)


def test_recorded_busy_idle_and_ops(recorded):
    r = T.reduce_trace(recorded)
    ops = T._line(T.device_planes(recorded)[0], T.OPS_LINE)
    off = int(r["clock_offset_ms"][0] * 1e6)
    lo, hi = T.window_of(recorded)
    clipped = [(max(s + off, lo), min(s + off + d, hi)) for _, s, d in ops
               if s + off < hi and s + off + d > lo]
    assert r["busy_s_fullest"] * 1e9 == pytest.approx(_union(clipped), abs=2)
    assert r["steps"] == 3
    assert r["window_s"] == pytest.approx(0.060967709)
    # 19.2 ms of operations in each 20.3 ms step
    assert r["busy_s_fullest"] / r["window_s"] == pytest.approx(0.944,
                                                                abs=0.01)
    # an operation matched by name: the chain fusion, 10.305 ms a step
    chain = sum(v for k, v in r["ops_fullest"].items()
                if k.startswith("%multiply_add_fusion"))
    assert chain / 3 == pytest.approx(10.305e-3, rel=0.02)
    reduces = sum(v for k, v in r["ops_fullest"].items()
                  if "reduce" in k.split(" = ")[0])
    assert reduces / 3 == pytest.approx(4 * 2.218e-3, rel=0.02)
    # no collective on one chip
    assert r["exposed_collective_s_fullest"] == 0.0
    # the chip idles while the host is inside the chain's dispatch
    assert r["idle_gaps"][0][0] == "bench.op.chain"
    assert r["idle_gaps"][0][1] / 3 == pytest.approx(1.1e-3, rel=0.1)


def _made_up():
    ms = 1_000_000
    host = [["bench.step", 0, 100 * ms], ["bench.dispatch", 0, 10 * ms],
            ["bench.read", 10 * ms, 90 * ms], ["other", 0, 5]]
    dev0 = [["%while.1 = while(...)", 10 * ms, 60 * ms],
            ["%fusion.1 = fusion(...)", 20 * ms, 10 * ms],
            ["%all-to-all.3 = all-to-all(...)", 40 * ms, 20 * ms],
            ["%fusion.2 = fusion(...)", 80 * ms, 10 * ms]]
    dev1 = [["%shard_map.12 = f32[8,8]{1,0} custom-call(f32[8,8] %p)", 10 * ms, 30 * ms],
            ["%fusion.1 = fusion(...)", 50 * ms, 45 * ms]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev0},
                   {"name": "XLA Modules",
                    "events": [["jit_f", 10 * ms, 80 * ms]]}]},
        {"name": "/device:TPU:1",
         "lines": [{"name": "XLA Ops", "events": dev1}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}, ms


def test_nesting_collectives_and_the_fullest_chip():
    trace, ms = _made_up()
    ops0 = trace["planes"][0]["lines"][0]["events"]
    segs = T.segments(ops0, 0, 100 * ms)
    # the while's own time is what its children leave: 10-20, 30-40, 60-70
    assert T.op_seconds(segs)["%while.1 = while(...)"] == pytest.approx(0.030)
    assert T.busy_seconds(segs) == pytest.approx(0.070)
    assert T.exposed_seconds(segs) == pytest.approx(0.020)
    r = T.reduce_trace(trace)
    assert r["busy_s_per_device"] == pytest.approx([0.070, 0.075])
    assert r["busy_s_mean"] == pytest.approx(0.0725)
    assert r["busy_s_fullest"] == pytest.approx(0.075)       # chip 1
    assert r["exposed_collective_s_fullest"] == pytest.approx(0.030)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.dispatch"] == pytest.approx(0.010)
    assert gaps["bench.read"] == pytest.approx(0.015)        # 40-50, 95-100


def test_a_trace_without_a_device_plane_gives_nothing():
    trace, _ = _made_up()
    host_only = {"planes": [p for p in trace["planes"]
                            if p["name"].startswith("/host")]}
    assert T.reduce_trace(host_only) is None
