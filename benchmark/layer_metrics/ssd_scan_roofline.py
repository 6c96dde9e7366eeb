"""Kernels (``ops/pallas_ssd.py``): the least time one chip could take for
the step's chunked state-space-duality work, forward and backward (the
driver's ``ssd_cost()``, from ``counts_granite``: the larger of the
products' operations at the bf16 peak and the HBM bytes of the operands
and results at the peak bandwidth), over the device time of the SSD
kernels a step.  Nothing where the cell's step has no ``ssd_cost()`` or the
trace holds no SSD kernel."""

import counts
from layer_metrics import ssd_scan_ms


def read(run):
    ms = ssd_scan_ms.read(run)
    if not ms or not hasattr(run.driver, "ssd_cost"):
        return None
    least, which = counts.least_seconds(run.driver.ssd_cost(), run.peaks)
    run.notes.append(f"ssd_scan_roofline: bound by {which}, least "
                     f"{1e3 * least:.3f} ms a step")
    return 100.0 * least / (1e-3 * ms)
