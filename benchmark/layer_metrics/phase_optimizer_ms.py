"""Models and training whole step: device time a step under the ``optimizer``
scope, from the traced window: the small leaves' updates and whatever XLA
left unfused (a matrix's update fused into its weight gradient counts to the
gradient's phase).  The join and its refusals are
``layer_metrics/phases.py``'s."""

from layer_metrics.phases import phase_ms


def read(run):
    return phase_ms(run, "optimizer")
