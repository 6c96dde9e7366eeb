"""Compiled programs: which phase of the model each device instruction
belongs to, what memory the program needs, and one span a call.

A profiler trace names a device event by its HLO instruction
(``%fusion.489 = bf16[1024]{0} fusion(...)``) and says nothing of the
``jax.named_scope`` it was traced under; the compiled program's text does
(``op_name="jit(step)/jvp(block)/attn/..."`` on every instruction).  The
program that compiled the step is the one that can join the two, so it
keeps the other side of the join here:

- :func:`register` wraps a jitted function with the scopes its model
  declares.  The wrapper notes, at its first call, each argument leaf's
  shape, dtype and sharding, runs every call inside ``span(name)``
  (journaled: a root span where the caller has none open) and passes
  ``lower``, ``trace`` and every other attribute of the jitted function
  through.  With telemetry disabled a call is the span's one boolean check:
  nothing is noted and the program never shows in :func:`live`.
- :func:`compiled`, :func:`phase_map`, :func:`memory` are built on demand,
  never in a step: the first lowers the noted arguments and compiles (a hit
  of the persistent compile cache where the process filled one), the others
  read the executable's text and its ``memory_analysis()``.

**The placement rule** (:func:`place`).  An instruction's phase is the
innermost declared scope on its ``op_name`` path once the transformation
wrappers are peeled: ``jvp(block)/attn`` and ``transpose(jvp(block))/attn``
are ``block/attn``, a ``jit(...)`` component is dropped, and components
that are no declared scope (``checkpoint``, ``rematted_computation``,
``while``, ``body``, ``cond``, ``branch``, ``pallas_call``, ``custom_vjp``,
the primitive's own name) are passed over, so ``mtp/block/mla`` is
``block/mla`` and ``mtp/head_loss`` is ``head_loss``.  ``None`` where no
declared scope is on the path.  Its pass is ``recompute`` under
``rematted_computation``, else ``backward`` under ``transpose(``, else
``forward``.  A fusion is placed by its own ``op_name``, which is its
root's: what XLA fused into it from another scope counts to the root's
phase (AdamW's update of a matrix, fused into that matrix's weight
gradient, counts to the gradient's phase and pass, not to ``optimizer``).

Stdlib only at import, like ``core`` and ``tracing``: JAX is looked up
when a program first notes its arguments.
"""

from __future__ import annotations

import re
import weakref

from . import core
from .tracing import span

__all__ = ["Program", "register", "live", "compiled", "phase_map", "memory",
           "place", "parse_hlo"]

# programs that have noted their arguments and are still referenced by
# whoever built them (a model's step lives as long as its caller keeps it)
_live: "weakref.WeakSet[Program]" = weakref.WeakSet()

_WRAPPER = re.compile(r"^([\w.\-]+)\((.*)\)$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=(]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_MEMORY = ("argument", "output", "alias", "temp", "generated_code")


class Program:
    """A jitted function under a name, with its model's declared scopes.
    Made by :func:`register`; call it as the function it wraps."""

    __slots__ = ("name", "scopes", "abstract", "_fn", "_compiled", "_map",
                 "__weakref__")

    def __init__(self, name: str, fn, scopes=()):
        self.name = name
        self.scopes = tuple(scopes)
        self.abstract = None         # (args, kwargs) of ShapeDtypeStructs
        self._fn = fn
        self._compiled = None
        self._map = None

    def __call__(self, *args, **kwargs):
        if not core._ENABLED:        # the span's own single-boolean path
            return self._fn(*args, **kwargs)
        if self.abstract is None:
            self.note(*args, **kwargs)
        with span(self.name):
            return self._fn(*args, **kwargs)

    def __getattr__(self, attr):     # lower, trace, eval_shape, ...
        if attr == "_fn":            # a copy in the making: no function yet
            raise AttributeError(attr)
        return getattr(self._fn, attr)

    def note(self, *args, **kwargs) -> None:
        """Keep the arguments' shapes, dtypes and shardings (what
        :func:`compiled` lowers) and enter :func:`live`.  The first call
        does this by itself; a caller that only has shapes (a compile for
        a described chip) passes ``jax.ShapeDtypeStruct``s."""
        import jax

        def abstract(x):
            if not (hasattr(x, "shape") and hasattr(x, "dtype")):
                return x             # a static argument, a Python number
            # an uncommitted array goes where jit puts it, as in the call:
            # naming its device would lower another module than the call's
            # and miss the compile cache the call filled
            placed = (not isinstance(x, jax.core.Tracer)
                      and getattr(x, "committed", True))
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=getattr(x, "sharding", None) if placed else None)

        self.abstract = jax.tree_util.tree_map(abstract, (args, kwargs))
        self._compiled = self._map = None
        with core._LOCK:
            _live.add(self)

    def __repr__(self):
        state = "noted" if self.abstract is not None else "not called yet"
        return f"<Program {self.name!r} {state}>"


def register(name: str, fn, scopes=()) -> Program:
    """Wrap the jitted ``fn`` as the program ``name`` whose phases are the
    declared ``scopes`` (``"block/attn"``: the path of ``jax.named_scope``s
    as the model nests them).  Several live programs may share a name."""
    return Program(name, fn, scopes)


def live(name: str | None = None) -> list[Program]:
    """Every program that has noted its arguments and is still referenced,
    optionally those called ``name``."""
    with core._LOCK:
        progs = list(_live)
    return [p for p in progs if name is None or p.name == name]


def compiled(p: Program):
    """The executable of ``p`` for the arguments it noted: lowered and
    compiled at the first request, then kept with the program."""
    if p._compiled is None:
        if p.abstract is None:
            raise ValueError(f"{p!r} has noted no arguments: call it once "
                             f"with telemetry enabled, or p.note(*shapes)")
        args, kwargs = p.abstract
        p._compiled = p._fn.lower(*args, **kwargs).compile()
    return p._compiled


def phase_map(p: Program) -> dict:
    """``{instruction name: (head, phase, pass)}`` for every instruction of
    every computation of ``p``'s compiled module (:func:`parse_hlo`)."""
    if p._map is None:
        p._map = parse_hlo(compiled(p).as_text(), p.scopes)
    return p._map


def memory(p: Program) -> dict:
    """Bytes of ``p``'s executable by ``memory_analysis()``: ``argument``,
    ``output``, ``alias``, ``temp``, ``generated_code``, and ``total`` =
    argument + output - alias + temp + generated_code (what the program
    needs of the device while it runs, its scratch included)."""
    stats = compiled(p).memory_analysis()
    out = {k: int(getattr(stats, f"{k}_size_in_bytes")) for k in _MEMORY}
    out["total"] = (out["argument"] + out["output"] - out["alias"]
                    + out["temp"] + out["generated_code"])
    return out


def place(op_name: str, scopes) -> tuple:
    """``(phase, pass)`` of an instruction by its ``op_name`` (the module's
    docstring has the rule).  XLA joins the names of instructions it merged
    with ``;``: the first one that holds a declared scope decides."""
    if "rematted_computation" in op_name:
        which = "recompute"
    elif "transpose(" in op_name:
        which = "backward"
    else:
        which = "forward"
    for path in op_name.split(";"):
        parts = [c for c in map(_peel, path.split("/")) if c]
        # (where it starts, how long it is, the scope): the innermost wins
        found = [(_rfind(parts, scope.split("/")), scope.count("/"), scope)
                 for scope in scopes]
        start, _, scope = max(found, default=(-1, 0, None))
        if start >= 0:
            return scope, which
    return None, which


def _rfind(parts: list, scope: list) -> int:
    """Where ``scope``'s components last lie in a row in ``parts``, or -1."""
    n = len(scope)
    return next((i for i in range(len(parts) - n, -1, -1)
                 if parts[i:i + n] == scope), -1)


def _peel(component: str) -> str:
    """A path component without its transformation wrappers; ``""`` for a
    ``jit(...)``, which names a function and no scope."""
    while True:
        m = _WRAPPER.match(component)
        if m is None:
            return component
        if m.group(1) in ("jit", "pjit"):
            return ""
        component = m.group(2)


def _head(line: str):
    """``(name, head)`` of an instruction line: the text from its name up
    to its operands (``%fusion.489 = <result type> fusion``), or ``None``
    for a line that is no instruction."""
    m = _INSTRUCTION.match(line)
    if m is None:
        return None
    i = m.end()
    if line.startswith("(", i):      # a tuple type, parentheses balanced
        depth = 0
        for j in range(i, len(line)):
            depth += (line[j] == "(") - (line[j] == ")")
            if depth == 0:
                break
        i = j + 1
    i = line.find(" ", i)
    end = line.find("(", i)
    if i < 0 or end < 0:
        return None
    return m.group(1), f"%{m.group(1)} = {line[m.end():end]}"


def parse_hlo(text: str, scopes) -> dict:
    """``{instruction name: (head, phase, pass)}`` from a compiled module's
    text (``compiled.as_text()``), every computation of it: an instruction
    without ``op_name`` (a parameter, a convert inside a fusion) has phase
    ``None`` and pass ``forward``."""
    out, placed = {}, {}
    for line in text.splitlines():
        found = _head(line)
        if found is None:
            continue
        name, head = found
        m = _OP_NAME.search(line)
        op_name = m.group(1) if m else ""
        if op_name not in placed:
            placed[op_name] = place(op_name, scopes)
        out[name] = (head, *placed[op_name])
    return out
