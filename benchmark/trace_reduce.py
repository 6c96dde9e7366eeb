"""From a profiler trace to numbers: the one reduction every PR shares.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler wrote (with
nothing but JAX) into a plain dict: planes, their lines, and events as
``[name, start_ns, dur_ns]``.  Everything else works on that dict, so it
can be checked on a small recorded trace kept as JSON in ``tests/``.

What a TPU trace looks like (read by hand, PR 23): one plane a chip named
``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event for each
operation the chip ran (nested where an operation contains others, as a
``while`` its body) and whose line ``XLA Modules`` holds one event a
program; one plane ``/host:CPU`` whose lines are host threads, where
``jax.profiler.TraceAnnotation`` spans appear under their names.  The
harness brackets each traced step in a ``bench.step`` span; the traced
window runs from the first span's start to the last span's end.

Device and host events are NOT on one clock: in the traces read, a chip's
events lay about 2 ms before the host spans that caused them (the first
operation of a step "started" before the host had entered the step).  A
closed loop gives an anchor: a step ends when its scalar is on the host, so
the chip's longest idle gap near a ``bench.step`` end begins when that
step's last operation ended.  ``clock_offset_ns`` takes the median of
(step end - gap start) over the steps and the reduction moves the chip's
events by it.  The read-back latency (some 0.1 ms) is thereby counted to
the next step's dispatch; idle gaps are attributed no finer than that.
"""

from __future__ import annotations

import re
from pathlib import Path

__all__ = ["load_xplane", "find_xplane", "summarize_raw", "device_planes",
           "host_spans", "window_of", "head", "segments", "clock_offset_ns", "busy_seconds",
           "op_seconds", "exposed_seconds", "idle_gaps", "reduce_trace"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# operations that move data between chips: XLA's collectives by their HLO
# names, and the program's ring kernels.  Those carry no name of their own
# yet: the trace calls them ``%shard_map.N = ... custom-call(...)`` (read by
# hand on four chips, PR 23), and no other kernel of the package runs under
# shard_map in a cell today; ``ring_`` will match them once they are named
COLLECTIVE = re.compile(
    r"^%?(all-to-all|all-gather|all-reduce|reduce-scatter|"
    r"collective-permute|collective-broadcast|send|recv|ring_)"
    r"|^%?shard_map[.\d]* = .*custom-call\(", re.I)


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path, keep_host=lambda name: name.startswith(SPAN_PREFIX)):
    """The trace as a plain dict.  Device planes keep every event of every
    line; host planes keep only the events ``keep_host`` admits."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if is_dev or keep_host(ev.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def summarize_raw(path, top=12) -> dict:
    """Every plane and line of a trace with its event count and the names
    that took most time: for reading a trace by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            tot, n, first, last = {}, 0, None, None
            for ev in line.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
                first = ev.start_ns if first is None else min(first,
                                                              ev.start_ns)
                last = max(last or 0, ev.start_ns + ev.duration_ns)
            names = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
            out.append({"plane": plane.name, "line": line.name, "events": n,
                        "first_ns": first, "last_ns": last,
                        "top": [[k, v / 1e9] for k, v in names]})
    return {"lines": out}


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def device_planes(trace) -> list[dict]:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes,
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(trace) -> list[list]:
    """Every ``bench.*`` span on any host thread, by start."""
    out = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            out += [e for e in line["events"]
                    if e[0].startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda e: e[1])


def window_of(trace) -> tuple[int, int]:
    """(start_ns, end_ns): first ``bench.step`` start to last one's end."""
    steps = [e for e in host_spans(trace) if e[0] == SPAN_PREFIX + "step"]
    if not steps:
        raise ValueError("the trace holds no bench.step span")
    return steps[0][1], max(e[1] + e[2] for e in steps)


def segments(events, lo, hi) -> list[tuple[int, int, str]]:
    """Disjoint (start, end, name) pieces inside [lo, hi): where events
    nest, the innermost names the piece, so a container's time is only
    what its children leave."""
    evs = sorted(((max(s, lo), min(s + d, hi), name)
                  for name, s, d in events if s < hi and s + d > lo),
                 key=lambda e: (e[0], -e[1]))
    out, stack, cur = [], [], lo          # stack of (end, name), innermost last

    def close_until(t):
        """Emit what the open events cover from ``cur`` up to ``t``."""
        nonlocal cur
        while stack and cur < t:
            end, name = stack[-1]
            stop = min(end, t)
            if stop > cur:
                out.append((cur, stop, name))
                cur = stop
            if end > t:
                break
            stack.pop()

    for s, e, name in evs:
        close_until(s)
        if not stack:
            cur = s                        # the chip was idle up to here
        stack.append((e, name))
    close_until(hi)
    return out


def clock_offset_ns(events, step_spans, search_ns=5_000_000) -> int:
    """How far the chip's clock lies behind the host's: the median over the
    traced steps of (host step end - start of the chip's longest idle gap
    that begins within ``search_ns`` of it).  0 where no such gap exists."""
    spans = sorted((e[1], e[1] + e[2]) for e in events)
    gaps, cur = [], None
    for a, b in spans:
        if cur is not None and a > cur:
            gaps.append((cur, a))
        cur = b if cur is None else max(cur, b)
    deltas = []
    for _, start, dur in step_spans:
        end = start + dur
        near = [g for g in gaps if abs(g[0] - end) <= search_ns]
        if near:
            g = max(near, key=lambda g: g[1] - g[0])
            deltas.append(end - g[0])
    if not deltas:
        return 0
    deltas.sort()
    return deltas[len(deltas) // 2]


def busy_seconds(segs) -> float:
    return sum(e - s for s, e, _ in segs) / 1e9


def op_seconds(segs) -> dict[str, float]:
    out = {}
    for s, e, name in segs:
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def exposed_seconds(segs, pattern=COLLECTIVE) -> float:
    """Seconds in which the chip's only running operation matches
    ``pattern``.  Pieces are disjoint, so a matching piece is exposed."""
    return sum(e - s for s, e, name in segs if pattern.search(name)) / 1e9


def idle_gaps(segs, lo, hi, spans, top=10) -> list[list]:
    """The longest idle gaps of a chip inside the window, summed by the
    innermost host span that was open at the gap's middle."""
    gaps, cur = [], lo
    for s, e, _ in segs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    by = {}
    for a, b in gaps:
        mid = (a + b) // 2
        name = "(no span)"
        best = None
        for sp_name, st, du in spans:
            if st <= mid < st + du and (best is None or st >= best):
                best, name = st, sp_name
        by[name] = by.get(name, 0.0) + (b - a) / 1e9
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])[:top]


def head(trace, steps=3) -> dict:
    """The trace cut to its first ``steps`` traced steps: small enough to
    keep as a recorded trace for the tests."""
    spans = [e for e in host_spans(trace) if e[0] == SPAN_PREFIX + "step"]
    lo, hi = spans[0][1], spans[min(steps, len(spans)) - 1]
    hi = hi[1] + hi[2]
    planes = []
    for p in trace["planes"]:
        lines = [{"name": ln["name"],
                  "events": [e for e in ln["events"]
                             if e[1] + e[2] > lo and e[1] < hi]}
                 for ln in p["lines"]]
        planes.append({"name": p["name"],
                       "lines": [ln for ln in lines if ln["events"]]})
    return {"planes": planes}


def reduce_trace(trace, top=10) -> dict:
    """Everything the per-layer readers take from a trace.  ``None`` where
    the trace has no device plane (a CPU rehearsal): no device number is
    ever made up."""
    planes = device_planes(trace)
    if not planes:
        return None
    lo, hi = window_of(trace)
    spans = host_spans(trace)
    step_spans = [e for e in spans if e[0] == SPAN_PREFIX + "step"]
    per_dev = []
    for p in planes:
        events = _line(p, OPS_LINE)
        off = clock_offset_ns(events, step_spans)
        segs = segments([[n, s + off, d] for n, s, d in events], lo, hi)
        per_dev.append({"plane": p["name"], "segs": segs, "offset_ns": off,
                        "busy_s": busy_seconds(segs),
                        "ops": op_seconds(segs),
                        "exposed_collective_s": exposed_seconds(segs)})
    fullest = max(per_dev, key=lambda d: d["busy_s"])
    steps = len(step_spans)
    ops = sorted(fullest["ops"].items(), key=lambda kv: -kv[1])
    short = lambda name: name if len(name) <= 96 else name[:93] + "..."
    span_s = {}
    for name, _, dur in spans:
        span_s[name] = span_s.get(name, 0.0) + dur / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "steps": steps,
        "busy_s_mean": sum(d["busy_s"] for d in per_dev) / len(per_dev),
        "busy_s_fullest": fullest["busy_s"],
        "busy_s_per_device": [d["busy_s"] for d in per_dev],
        "clock_offset_ms": [d["offset_ns"] / 1e6 for d in per_dev],
        "exposed_collective_s_fullest": fullest["exposed_collective_s"],
        "ops_fullest": fullest["ops"],
        "device_ops": [[short(k), v] for k, v in ops[:top]],
        "idle_gaps": idle_gaps(fullest["segs"], lo, hi, spans, top),
        "host_span_s": span_s,
    }
