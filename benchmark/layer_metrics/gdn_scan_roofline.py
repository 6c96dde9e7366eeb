"""Kernels (``ops/pallas_gated_delta.py``): the least time one chip could
take for the step's gated delta-rule work, forward and backward (the
driver's ``gdn_cost()``, from ``counts_olmo_hybrid``: the larger of the
chunked form's operations at the bf16 peak and the HBM bytes of the
operands and results at the peak bandwidth), over the device time of the
delta-rule kernels a step.  Nothing where the cell's step has no
``gdn_cost()`` or the trace holds no delta-rule kernel."""

import counts
from layer_metrics import gdn_scan_ms


def read(run):
    ms = gdn_scan_ms.read(run)
    if not ms or not hasattr(run.driver, "gdn_cost"):
        return None
    least, which = counts.least_seconds(run.driver.gdn_cost(), run.peaks)
    run.notes.append(f"gdn_scan_roofline: bound by {which}, least "
                     f"{1e3 * least:.3f} ms a step")
    return 100.0 * least / (1e-3 * ms)
