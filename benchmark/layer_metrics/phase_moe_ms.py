"""Models and training whole step: device time a step of the routed expert
layers, from the traced window: the events that join an instruction whose
phase is ``block/moe/route`` or ``block/moe/experts`` (``models/mla_moe.py``,
``models/moe.py``): routing, the buffers' gathers, masks, casts and weighted
sums.  The grouped products themselves carry libtpu's own ``op_name`` and no
scope: ``moe_experts_ms`` reads them and ``phase_unscoped_ms`` holds them.
The join and its refusals are ``layer_metrics/phases.py``'s."""

from layer_metrics.phases import phase_ms


def read(run):
    return phase_ms(run, "moe")
