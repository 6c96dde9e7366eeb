"""Models and training whole step: host time a step inside the program's
``train.optax_step`` spans (the call of the compiled step: argument
handling and dispatch; the device works on after it returns), mean over the
measured window, from ``telemetry.spans()``.  It lies under ``dispatch_ms``
by the harness's glue round the call.

The window is found as ``entry_host_ms`` finds it, with one span left out
first: on a cold compile cache the first call's span holds the step's
compilation, half a minute and more, longer than the whole window, and the
finder would take that one span for the longest run.  A span longer than
the measured window cannot lie in it."""

import threading

from layer_metrics.entry_host_ms import measured_window

SPAN = "train.optax_step"


def read(run):
    from distributedarrays_tpu import telemetry as tm
    spans = [s for s in tm.spans()
             if s["dur"] is not None and s["dur"] < run.window_s]
    window = measured_window(run, spans)
    if window is None:
        return None
    lo, hi = window
    main = threading.main_thread().ident
    durs = [s["dur"] for s in spans if s["name"] == SPAN
            and s["tid"] == main and lo <= s["start"] < hi]
    if not durs:
        return None
    if len(durs) != run.steps:
        run.notes.append(
            f"train_host_ms: {len(durs)} {SPAN} spans in a window of "
            f"{run.steps} steps: the window was not found cleanly")
    return 1e3 * (run.window_s / run.steps) * sum(durs) / (hi - lo)
