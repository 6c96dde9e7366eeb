"""Tracing / profiling utilities.

The reference has no tracing subsystem (SURVEY.md §5: "Tracing/profiling:
none — only commented-out println debugging", spmd.jl:122,136).  On TPU we
get a real profiler from the platform; this module wraps it in the
framework's terms:

- ``trace(dir)`` — context manager capturing a JAX/XLA profile (viewable
  in Perfetto / TensorBoard) around any block of DArray operations.
- ``annotate(name)`` — a named telemetry span for a host-side phase.
- ``op_timer()`` — lightweight wall-clock accounting of eager ops with
  marginal-cost support.

Framework-level accounting (byte counts, reshard/fallback/retrace
counters, the event journal, hierarchical spans) lives in
``distributedarrays_tpu.telemetry`` — this module is the deep-dive tier
on top, and both hooks ARE telemetry spans: ``annotate(name)`` opens one
span and nothing else, and since every telemetry span is itself a
``jax.profiler.TraceAnnotation`` named ``dat.<name>``
(``telemetry/tracing.py``), the phase shows on the XLA/Perfetto profile
timeline and in the framework journal (with comm-byte attribution)
through that one mechanism; ``OpTimer`` times through the same span
machinery (keeping its local totals and the ``optimer.<name>``
histograms).  Profiler captures are journaled so a telemetry report
names the trace directories that cover it.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict

import jax

from .. import telemetry as _tm

__all__ = ["trace", "annotate", "OpTimer"]


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a JAX profiler trace of the enclosed block.

    View with `tensorboard --logdir <dir>` or ui.perfetto.dev.
    """
    # cold path: bounds a whole profiler capture session
    _tm.event("profile", "trace_start", dir=str(log_dir))  # dalint: disable=DAL003
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        _tm.event("profile", "trace_stop", dir=str(log_dir))  # dalint: disable=DAL003


@contextlib.contextmanager
def annotate(name: str):
    """A telemetry span named ``name``: comm/events inside are
    attributed to it in the framework journal, and a profiler trace
    shows it as ``dat.<name>`` like every other span."""
    with _tm.span(name, src="annotate"):
        yield


class OpTimer:
    """Accumulating wall-clock timer for host-side phases.

    >>> t = OpTimer()
    >>> with t("distribute"): d = distribute(A)
    >>> t.report()
    """

    def __init__(self):
        self.totals = defaultdict(float)
        self.counts = defaultdict(int)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            # a real telemetry span (not just a histogram sample): the
            # phase nests under whatever span is open, shows up in the
            # Perfetto export, and owns the comm bytes it causes
            with _tm.span(name, src="optimer"):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] += dt
            self.counts[name] += 1
            # mirror into the process-wide registry so OpTimer totals show
            # up in telemetry.report() next to the comm/fallback counters
            _tm.observe(f"optimer.{name}", dt)

    def report(self) -> dict:
        return {k: {"total_s": self.totals[k], "calls": self.counts[k],
                    "mean_s": self.totals[k] / self.counts[k]}
                for k in sorted(self.totals)}
