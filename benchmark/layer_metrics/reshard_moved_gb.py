"""Redistribution: ``telemetry.comm_bytes("reshard")`` over the measured
window a step, summed over chips.  A count the program computes from
shapes (not a wire measurement); it has to repeat exactly."""


def read(run):
    if not run.reshard_bytes_per_step:
        return None
    return run.reshard_bytes_per_step / 1e9
