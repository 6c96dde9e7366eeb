"""Kernels and XLA fusions (``models/moe.py``): device time a step of the
routed experts' grouped products, forward and backward, from the traced
window: the events of the fullest chip's ``XLA Ops`` line whose operation is
NAMED ``ragged-dot...`` (XLA's ``%ragged-dot-none.N`` and its small
``%ragged-dot-metadata.N``: what ``lax.ragged_dot`` and its transposes
compile to; the program's since PR 35), or ``moe_gmm...`` where a program
computes them with a kernel of its own: the same work whatever implements
it.  The name is matched at the event's start: a fusion that only reads a
grouped product's result names it among its operands and is not counted.  A
trace with no such event (a program without an expert layer) gives nothing."""

import re

from layer_metrics.flash_fwd_ms import device_ms

PATTERN = re.compile(r"^%?(moe_gmm|ragged-dot)")


def read(run):
    return device_ms(run, PATTERN)
