"""Models and training whole step: device time a step of the gated
delta-rule linear-attention layers, from the traced window: the events
that join an instruction whose phase is ``block/linear``
(``models/olmo_hybrid.py``): the delta-rule kernels, the projections, the
convolution and the norms round them.  The join, its floor and its
refusals are ``layer_metrics/phases.py``'s; the phase is read from its
split by phase and pass, as ``phases.GROUPS`` lists no ``block/linear``
(there it counts as unscoped)."""

from layer_metrics import phases

PHASE = "block/linear"


def read(run):
    if not hasattr(run, "_linear_ms"):
        run._linear_ms = _read(run)
    return run._linear_ms


def _read(run):
    t = run.trace and run.trace.get("reduced")
    if not t or not t.get("steps"):
        return None
    try:
        from distributedarrays_tpu.telemetry import programs
    except ImportError:              # a program from before the registry
        return None
    progs = programs.live()
    if not progs:
        return None
    maps = [programs.phase_map(p) for p in progs]
    _, _, by_phase, joined = phases.join(t["ops_fullest"], maps)
    busy = t["busy_s_fullest"]
    if not busy or joined < phases.JOIN_FLOOR * busy:
        return None
    secs = sum(s for (phase, _), s in by_phase.items() if phase == PHASE)
    return (secs * 1e3 / t["steps"]) or None
