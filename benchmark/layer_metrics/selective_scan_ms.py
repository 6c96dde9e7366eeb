"""Kernels (``ops/pallas_selective_scan.py``): device time a step of the
selective-scan kernels, forward and backward, from the traced window: the
events of the fullest chip's ``XLA Ops`` line named ``selective_scan_fwd``
and ``selective_scan_bwd`` (the ``name=`` of their ``pallas_call``s).  A
trace with no such event (a program without the kernels) gives nothing."""

import re

from layer_metrics.flash_fwd_ms import device_ms

PATTERN = re.compile(r"^%?selective_scan_(fwd|bwd)\b")


def read(run):
    return device_ms(run, PATTERN)
