"""Array ops, whole step: the same least time over the whole step time of
the traced window; it reads the same work whatever implements it."""

import counts


def read(run):
    t = run.trace and run.trace.get("reduced")
    if not t:
        return None
    least, which = counts.least_seconds(run.cost, run.peaks)
    run.notes.append(f"step_roofline: least {least * 1e3:.4f} ms a step, "
                     f"bound by {which}")
    return 100.0 * least / (t["window_s"] / t["steps"])
