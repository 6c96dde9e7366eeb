"""Kernels (``ops/pallas_attention.py``): required operations of the
step's flash forward and backward kernels (causal counted as half) at the
bf16 peak, over the device time of those kernels a step in the trace.  The
kernels carry no stable name yet (no ``pallas_call`` in ``ops/`` passes a
``name=``): ``PATTERN`` is what today's trace calls them, the Mosaic custom
calls XLA names after the jitted wrapper they sit in, ``jvp_jit_wrapped__``
for the forward and ``transpose_jvp_jit_wrapped___`` for the two backward
kernels (read by hand, PR 23: 24 and 48 a step at 24 layers).  Once the
kernels are named, ``flash`` matches them.  A trace where nothing matches
gives no reading."""

import re

PATTERN = re.compile(
    r"^%?(transpose_)?jvp_jit_wrapped_+[.\d]* = .*custom-call\(|flash", re.I)


def read(run):
    t = run.trace and run.trace.get("reduced")
    if not t or not hasattr(run.driver, "attention_flops"):
        return None
    secs = sum(v for k, v in t["ops_fullest"].items() if PATTERN.search(k))
    if not secs:
        return None
    least = run.driver.attention_flops() / run.peaks["flops_bf16_per_s"]
    return 100.0 * least / (secs / t["steps"])
