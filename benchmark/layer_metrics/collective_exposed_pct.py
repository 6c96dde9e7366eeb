"""Collectives: share of the traced window in which a collective or ring
kernel is the only operation running on the fullest chip."""


def read(run):
    t = run.trace and run.trace.get("reduced")
    if not t or not t["exposed_collective_s_fullest"]:
        return None
    return 100.0 * t["exposed_collective_s_fullest"] / t["window_s"]
