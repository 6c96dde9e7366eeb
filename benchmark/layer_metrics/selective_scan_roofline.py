"""Kernels (``ops/pallas_selective_scan.py``): the least time one chip
could take for the step's selective scans, forward and backward (the
driver's ``scan_cost()``, from ``counts_sambay``: the larger of the
recurrence's operations at the bf16 peak and the HBM bytes of its operands
and results at the peak bandwidth), over the device time of the scan
kernels a step.  Nothing where the driver counts no scan or the trace
holds no scan kernel."""

import counts
from layer_metrics import selective_scan_ms


def read(run):
    ms = selective_scan_ms.read(run)
    if not ms or not hasattr(run.driver, "scan_cost"):
        return None
    least, which = counts.least_seconds(run.driver.scan_cost(), run.peaks)
    run.notes.append(f"selective_scan_roofline: bound by {which}, least "
                     f"{1e3 * least:.3f} ms a step")
    return 100.0 * least / (1e-3 * ms)
