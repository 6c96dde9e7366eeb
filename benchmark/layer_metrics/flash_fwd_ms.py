"""Kernels (``ops/pallas_attention.py``): device time a step of the flash
forward kernel, from the traced window: the events of the fullest chip's
``XLA Ops`` line whose operation is named ``flash_fwd`` (the ``name=`` of
its ``pallas_call``; XLA calls the custom call ``%flash_fwd.N``).  A trace
with no such event (the kernel off the path, or not named) gives nothing."""

import re

PATTERN = re.compile(r"^%?flash_fwd\b")


def device_ms(run, pattern):
    t = run.trace and run.trace.get("reduced")
    if not t:
        return None
    secs = sum(v for k, v in t["ops_fullest"].items() if pattern.search(k))
    if not secs:
        return None
    return 1e3 * secs / t["steps"]


def read(run):
    return device_ms(run, PATTERN)
