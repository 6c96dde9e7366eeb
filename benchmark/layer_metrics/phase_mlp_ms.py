"""Models and training whole step: device time a step of the dense
feed-forward layers, from the traced window: the events that join an
instruction whose phase is ``block/mlp`` or ``block/moe/shared`` (the shared
expert of ``models/mla_moe.py``); a matrix's AdamW update fused into its
weight gradient is in it.  The join and its refusals are
``layer_metrics/phases.py``'s."""

from layer_metrics.phases import phase_ms


def read(run):
    return phase_ms(run, "mlp")
