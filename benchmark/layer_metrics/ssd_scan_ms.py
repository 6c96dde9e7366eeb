"""Kernels (``ops/pallas_ssd.py``): device time a step of the chunked
state-space-duality kernels of the Mamba-2 layers, forward and backward,
from the traced window: the events of the fullest chip's ``XLA Ops`` line
named ``ssd_fwd`` and ``ssd_bwd`` (the ``name=`` of their
``pallas_call``s).  A trace with no such event (a program without the
kernels) gives nothing."""

import re

from layer_metrics.flash_fwd_ms import device_ms

PATTERN = re.compile(r"^%?ssd_(fwd|bwd)\b")


def read(run):
    return device_ms(run, PATTERN)
