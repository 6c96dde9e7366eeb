"""Redistribution: host time a step inside the program's ``reshard`` spans
(children included: compiled-program lookup, dispatch, the ledger), mean
over the measured window, from ``telemetry.spans()``.  The window is found
as ``entry_host_ms`` finds it; planning lies outside the ``reshard`` span
(in ``reshard.plan``, aggregate-only) and is not in this number."""

from layer_metrics.entry_host_ms import host_ms


def read(run):
    return host_ms(run, lambda s: s["name"] == "reshard")
