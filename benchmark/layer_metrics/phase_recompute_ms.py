"""Models and training whole step: device time a step of every phase's
instructions computed again in the backward (``rematted_computation`` on
their ``op_name``: what ``jax.checkpoint`` did not keep), from the traced
window.  It cuts across the ``phase_*_ms`` groups, which hold it too."""

from layer_metrics.phases import split


def read(run):
    got = split(run)
    return (got and got["recompute"]) or None
