"""Models and training whole step: device time a step of the state-space
layers, from the traced window: the events that join an instruction whose
phase is ``block/mamba`` or ``block/gmu`` (``models/sambay.py``): the scan
kernels, the projections and casts round them, the gated memory units.  The
join and its refusals are ``layer_metrics/phases.py``'s."""

from layer_metrics.phases import phase_ms


def read(run):
    return phase_ms(run, "ssm")
