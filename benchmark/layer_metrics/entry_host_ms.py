"""Entry points: host time a step spends inside the program's own root
entry spans (``djit``, ``mapreduce``, ``matmul``, ``distribute``, ...: the
journaled spans with no parent that the ``dat.*`` entries open), mean over
the measured window, from ``telemetry.spans()``.

The harness keeps no window start and the span buffer holds the whole
process, so the window is found from what the run does know.  The main
thread's root spans, in order, fall into runs wherever two neighbours lie
more than ``GAP_S`` apart: writing and reading the trace, the audit step
and the reference each stand such a gap away, so the run of the longest
extent holds the measured window.  Where the profiler came up faster than
``GAP_S`` the traced window is that run's tail (no later run is as long
as the traced window was): its known length says where it began, the
step that starts nearest to there (a step starts after a read-back's
pause) is its first, and the entry before that is the measured window's
last.  The window then ends
one read-back later (the last step's time less its dispatch time) and
starts ``window_s`` before that, which cuts off set-up and the warm-up
steps, whose spans hold seconds of compilation and lie in the same run.
Neither warm-up nor the traced window is read.

The spans are on the clock the harness times with (``time.monotonic`` and
``time.perf_counter`` are one clock on Linux).  Where the buffer has lost
the window's head (it keeps the newest 8192 spans) the share of the part
it still covers is scaled to a step.  This number lies below
``dispatch_ms`` by what the harness does between the entries: releasing
the arrays a step replaces, counting misplaced shards, its own dispatch.
Nothing to read (no span, telemetry off) gives nothing."""

import threading

GAP_S = 0.5


def _end(span):
    return span["start"] + span["dur"]


def measured_window(run, spans):
    """(lo, hi) of the measured window on the spans' clock, or ``None``."""
    main = threading.main_thread().ident
    roots = sorted((s for s in spans if s["parent_id"] is None
                    and s["tid"] == main and s["dur"] is not None),
                   key=lambda s: s["start"])
    if not roots:
        return None
    runs, cur = [], [roots[0]]
    for prev, nxt in zip(roots, roots[1:]):
        if nxt["start"] - _end(prev) > GAP_S:
            runs.append(cur)
            cur = []
        cur.append(nxt)
    runs.append(cur)
    at = max(range(len(runs)),
             key=lambda i: _end(runs[i][-1]) - runs[i][0]["start"])
    longest, last = runs[at], runs[at][-1]
    read_s = run.step_s[-1] - run.dispatch_s[-1]
    traced_s = (run.trace or {}).get("window_s")
    if traced_s and not any(
            abs(_end(r[-1]) + read_s - r[0]["start"] - traced_s)
            < 0.25 * traced_s for r in runs[at + 1:]):
        began = _end(last) + read_s - traced_s
        firsts = [i for i in range(1, len(longest)) if longest[i]["start"]
                  - _end(longest[i - 1]) >= 0.5 * read_s]
        if firsts:
            i = min(firsts, key=lambda i: abs(longest[i]["start"] - began))
            last = longest[i - 1]
    hi = _end(last) + read_s
    # a buffer that lost the window's head covers only what it still has
    return max(hi - run.window_s, roots[0]["start"]), hi


def host_ms(run, keep):
    """Host milliseconds a step inside the main thread's spans that
    ``keep`` admits and that start inside the measured window."""
    from distributedarrays_tpu import telemetry as tm
    spans = tm.spans()
    window = measured_window(run, spans)
    if window is None:
        return None
    lo, hi = window
    main = threading.main_thread().ident
    durs = [s["dur"] for s in spans
            if keep(s) and s["tid"] == main and s["dur"] is not None
            and lo <= s["start"] < hi]
    if not durs:
        return None
    if len(durs) % run.steps:
        run.notes.append(
            f"span reader: {len(durs)} spans in a window of {run.steps} "
            f"steps (not a whole number a step): the window was not found "
            f"cleanly, or the span buffer lost its head")
    return 1e3 * (run.window_s / run.steps) * sum(durs) / (hi - lo)


def read(run):
    return host_ms(run, lambda s: s["parent_id"] is None)
