"""Models and training whole step: device time a step of attention, from the
traced window: the events of the fullest chip's ``XLA Ops`` line that join
an instruction whose phase is ``block/attn`` (``models/transformer.py``),
``block/window``, ``block/full``, ``block/cross`` (``models/sambay.py``) or
``block/mla`` (``models/mla_moe.py``): the flash kernels, the projections
and the copies round them.  The join and its refusals are
``layer_metrics/phases.py``'s."""

from layer_metrics.phases import phase_ms


def read(run):
    return phase_ms(run, "attn")
