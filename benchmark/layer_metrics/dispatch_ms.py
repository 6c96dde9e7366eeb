"""Entry points: host time a step spends inside the ``dat.*`` calls before
the blocking read, mean over the measured window (the harness's own span)."""


def read(run):
    return 1e3 * sum(run.dispatch_s) / len(run.dispatch_s)
