"""Models and training whole step: device time a step of the embedding, the
head and the loss, from the traced window: the events that join an
instruction whose phase is ``embed``, ``head_loss``, or ``mtp`` where that is
the innermost scope (the second head's own norms and projection;
``mtp/block/mla`` is attention).  The join and its refusals are
``layer_metrics/phases.py``'s."""

from layer_metrics.phases import phase_ms


def read(run):
    return phase_ms(run, "head_loss")
