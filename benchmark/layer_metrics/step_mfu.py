"""Models and training, whole step: required forward and backward
operations a token (matmuls and attention, no recomputation) times the
measured window's tokens per second, over chips times the bf16 peak."""


def read(run):
    if "tokens_per_s" not in run.values:
        return None
    flops_per_s = run.cost.flops * run.steps / run.window_s
    return 100.0 * flops_per_s / (run.chips * run.peaks["flops_bf16_per_s"])
