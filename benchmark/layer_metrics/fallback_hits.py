"""Accounting: ``fallback.hits`` + ``reshard.collective_fallbacks`` + ring
dispatches that took neither the rdma nor the compiled path, over warm-up
and window.  A count: 0 is a reading."""


def read(run):
    return float(run.fallback_hits)
