"""Models and training whole step: device time a step that no declared scope
claims, from the traced window: joined instructions with no
``jax.named_scope`` of the model on their ``op_name`` (the copies XLA
inserts, a fusion whose root carries no name, libtpu's own kernels), events
that joined no instruction, and a phase no group of
``layer_metrics/phases.py`` lists.  With the six ``phase_*_ms`` groups it
partitions the fullest chip's busy time."""

from layer_metrics.phases import phase_ms


def read(run):
    return phase_ms(run, "unscoped")
