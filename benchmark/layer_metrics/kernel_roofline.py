"""Kernels and XLA fusions: the least time the chip could take for the
step's work (``counts.least_seconds`` of the driver's cost) over the
device-busy time a step in the traced window, fullest chip."""

import counts


def read(run):
    t = run.trace and run.trace.get("reduced")
    if not t or not t["busy_s_fullest"]:
        return None
    least, which = counts.least_seconds(run.cost, run.peaks)
    run.notes.append(f"kernel_roofline: least {least * 1e3:.4f} ms a step, "
                     f"bound by {which}")
    return 100.0 * least / (t["busy_s_fullest"] / t["steps"])
