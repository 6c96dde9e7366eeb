"""Kernels (``ops/pallas_attention.py``): device time a step of the two
flash backward kernels, ``flash_bwd_dq`` and ``flash_bwd_dkv``, read as
``flash_fwd_ms`` reads the forward.  With it, this is the device time
``flash_attn_roofline`` divides by."""

import re

from layer_metrics.flash_fwd_ms import device_ms

PATTERN = re.compile(r"^%?flash_bwd_(dq|dkv)\b")


def read(run):
    return device_ms(run, PATTERN)
