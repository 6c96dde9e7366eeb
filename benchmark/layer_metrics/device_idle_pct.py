"""Device: 1 minus the union of operation intervals over the traced
window, fullest chip."""


def read(run):
    t = run.trace and run.trace.get("reduced")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s_fullest"] / t["window_s"])
