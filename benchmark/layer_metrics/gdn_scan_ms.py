"""Kernels (``ops/pallas_gated_delta.py``): device time a step of the
chunked gated delta-rule kernels of the linear-attention layers, forward
and backward, from the traced window: the events of the fullest chip's
``XLA Ops`` line named ``gdn_fwd`` and ``gdn_bwd`` (the ``name=`` of their
``pallas_call``s).  A trace with no such event (a program without the
kernels) gives nothing."""

import re

from layer_metrics.flash_fwd_ms import device_ms

PATTERN = re.compile(r"^%?gdn_(fwd|bwd)\b")


def read(run):
    return device_ms(run, PATTERN)
