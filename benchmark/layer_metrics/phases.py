"""Device time by phase of the model: the helper of the ``phase_*_ms``
readers, not a metric.

The traced window's events are named by their HLO instruction
(``run.trace["reduced"]["ops_fullest"]``: device seconds of the fullest
chip by the event's whole name, ``%fusion.489 = <result type>
fusion(<operands>), ...``).  The program keeps the other side
(``distributedarrays_tpu.telemetry.programs``): for every live registered
program, ``{instruction name: (head, phase, pass)}`` from the step it
compiled, ``phase`` being the model's innermost declared
``jax.named_scope`` on the instruction's ``op_name`` and ``pass`` one of
``forward``, ``recompute``, ``backward``.  An event joins an entry by its
instruction's name, where the event's text and the entry's ``head`` agree in
result shape and opcode (``ROOT`` and layout annotations, ``{1,0:T(8,128)}``,
are left out of the comparison: the two texts may print them differently,
as they do an asynchronous operation, ``slice-done`` in the program's text
and ``async-done`` in the trace).
Two programs may both hold a ``%fusion.1``: where the agreeing entries do
not name one phase and pass, the event stays unjoined.

Where less than ``JOIN_FLOOR`` of the fullest chip's busy time joins,
nothing is returned and a note gives the share: a stale or foreign map must
not make numbers up.  A program without the registry (a parent commit laid
under these files) gives nothing either.

The groups below partition the busy time: every joined instruction falls
into the group that lists its phase, and ``unscoped`` takes the joined
instructions with no declared scope on their path, the events that did not
join, and a phase no group lists.  ``recompute`` cuts across the groups.
"""

import re
import time

JOIN_FLOOR = 0.98

GROUPS = {
    "attn": ("block/attn", "block/window", "block/full", "block/cross",
             "block/mla"),
    "ssm": ("block/mamba", "block/gmu"),
    "mlp": ("block/mlp", "block/moe/shared"),
    "moe": ("block/moe/route", "block/moe/experts"),
    "head_loss": ("embed", "head_loss", "mtp"),
    "optimizer": ("optimizer",),
}
_GROUP_OF = {phase: g for g, phases in GROUPS.items() for phase in phases}

_NAME = re.compile(r"^(?:ROOT )?%?([^\s=(]+) = ")
# a layout annotation, or the ``/*index=5*/`` marks of a long tuple type
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
# ``slice-start`` is the program text's spelling of an ``async-start``
_ASYNC = re.compile(r"^.+-(start|update|done)$")


def head_key(text):
    """``(instruction name, result shape without layouts, opcode)`` of an
    event's name or of a map's head, or ``None`` for a text of another
    form."""
    m = _NAME.match(text)
    if m is None:
        return None
    rest = _LAYOUT.sub("", text[m.end():])
    if rest.startswith("("):         # a tuple type, parentheses balanced
        depth = 0
        for j, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        cut = j + 1
    else:
        cut = rest.find(" ")
    if cut <= 0:
        return None
    shape, opcode = rest[:cut], rest[cut:].split("(", 1)[0].strip()
    return m.group(1), shape.replace(" ", ""), _ASYNC.sub(r"async-\1", opcode)


def index_maps(maps):
    """``{(name, shape, opcode): {(phase, pass), ...}}`` over the maps of
    all live programs."""
    index = {}
    for pmap in maps:
        for head, phase, which in pmap.values():
            key = head_key(head)
            if key is not None:
                index.setdefault(key, set()).add((phase, which))
    return index


def join(ops, maps):
    """Split ``ops`` (seconds by event name) by the maps: ``(seconds by
    group, seconds by pass, seconds by (phase, pass), joined seconds)``."""
    index = index_maps(maps)
    by_group = dict.fromkeys((*GROUPS, "unscoped"), 0.0)
    by_pass, by_phase, joined = {}, {}, 0.0
    for name, secs in ops.items():
        found = index.get(head_key(name), ())
        if len(found) != 1:
            by_group["unscoped"] += secs
            continue
        (phase, which), = found
        joined += secs
        by_group[_GROUP_OF.get(phase, "unscoped")] += secs
        by_pass[which] = by_pass.get(which, 0.0) + secs
        by_phase[phase, which] = by_phase.get((phase, which), 0.0) + secs
    return by_group, by_pass, by_phase, joined


def split(run):
    """``{"groups": ms a step by group, "recompute": ms a step}`` of the
    traced window, computed once a run; ``None`` where there is nothing to
    read or too little of the busy time joins."""
    if not hasattr(run, "_phases"):
        run._phases = _split(run)
    return run._phases


def _build_maps(programs, progs):
    """The live programs' maps, and a note of what building them cost: the
    seconds, and the compile requests and persistent-cache hits it made
    (none of either where the process still holds the step's executable)."""
    import harness
    clock = harness.CompileClock()
    t0 = time.perf_counter()
    maps = [programs.phase_map(p) for p in progs]
    secs = time.perf_counter() - t0
    return maps, (
        f"phases: the maps of {len(progs)} live program(s), "
        f"{sum(map(len, maps))} instructions, built in {secs:.2f} s with "
        f"{clock.requests} compile request(s), {clock.hits} from the "
        f"persistent cache, {clock.requests - clock.hits} compiled anew")


def _split(run):
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
    maps, built = _build_maps(programs, progs)
    run.notes.append(built)
    by_group, by_pass, by_phase, joined = join(t["ops_fullest"], maps)
    busy = t["busy_s_fullest"]
    if not busy or joined < JOIN_FLOOR * busy:
        run.notes.append(
            f"phases: {joined:.4f} s of {busy:.4f} s busy "
            f"({100 * joined / busy if busy else 0:.2f}%) join the phase "
            f"maps of {len(progs)} live program(s), under "
            f"{100 * JOIN_FLOOR:.0f}%: no phase_* metric is reported")
        return None
    per_step = 1e3 / t["steps"]
    groups = {g: s * per_step for g, s in by_group.items()}
    rows = sorted(((f"{phase}|{which}", round(s * per_step, 3))
                   for (phase, which), s in by_phase.items()),
                  key=lambda kv: -kv[1])
    run.notes.append(
        f"phases: {100 * joined / busy:.2f}% of busy time joined; the seven "
        f"rows sum to {sum(groups.values()):.3f} ms a step beside "
        f"busy_s_fullest / steps = {busy * per_step:.3f}; by pass "
        f"{ {k: round(v * per_step, 3) for k, v in sorted(by_pass.items())} }"
        f"; by phase and pass {rows}")
    return {"groups": groups,
            "recompute": by_pass.get("recompute", 0.0) * per_step}


def phase_ms(run, group):
    """Device milliseconds a step of ``group``; ``None`` where the split
    has nothing or the group took no time."""
    got = split(run)
    return (got and got["groups"].get(group)) or None
