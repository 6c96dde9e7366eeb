"""Declarative per-step schedules for the Pallas RDMA ring kernels.

This module is the single source of truth for the semaphore/credit
protocol of every ring kernel in ``ops/pallas_collectives.py``.  Each
builder returns a :class:`Schedule`: a straight-line program of DMA
starts, semaphore waits, credit grants/takes, and compute steps over
named buffer *regions*, symbolic in the rank (``ME``) and fully unrolled
in the static step/chunk counters.  Two consumers interpret it:

- the **Pallas emitter** (``pallas_collectives._emit``) maps regions to
  ref slices and sems to DMA-semaphore scratch and replays the program
  as ``make_async_remote_copy``/``make_async_copy`` calls at trace time
  — the kernels ARE these schedules;
- the **model checker** (``analysis.protocol``) concretizes the program
  per rank and exhaustively explores rank-asynchronous interleavings,
  proving the docs' prose invariants (semaphores drain to zero, no slot
  is touched while a DMA into/out of it is in flight, write-once regions
  are written exactly once, no wait can starve) and — through the data
  *tokens* each write carries — that every read observes exactly the
  value the protocol intends.

Deliberately stdlib-only: the checker must not require a working JAX
install, and the schedule data must stay hashable/comparable so the
mutation harness can diff programs.

Region identity convention: two region keys are either equal or refer
to disjoint memory.  Every builder keys regions on block/slot/chunk
indices that tile their buffer (the emitters' geometry resolvers keep
that contract), so the checker may detect conflicts by key equality
alone.

Token convention (the data-flow half of the proof): every write —
a DMA landing or a compute — stamps its destination region with a
token describing the value (``("x", b)`` = rank ``b``'s input block,
``("p", d, k, c)`` = the traveling partial for destination ``d`` with
``k`` contributions in chunk ``c``, ...).  Reads declare the token they
expect; the checker flags reads of unwritten regions and reads that
observe a different epoch's data even when no in-flight overlap exists
(the slot-reuse bug class the credits gate).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

__all__ = [
    "ME", "Var", "Bin", "mod", "xor", "ev",
    "Dma", "Start", "WaitSend", "WaitRecv", "WaitLocal", "Compute",
    "BufferSpec", "Schedule", "SCHEDULES", "build",
    "all_gather_schedule", "all_to_all_schedule",
    "reduce_scatter_schedule", "ag_matmul_schedule",
    "ag_matmul_rhs_schedule", "matmul_reducescatter_schedule",
    "a2a_offsets", "a2a_peer", "a2a_window", "mesh_subrings", "mesh_peer",
    "mesh_axis_size",
]


# ---------------------------------------------------------------------------
# tiny symbolic-expression language (symbolic only in the rank)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Var:
    """A symbolic variable (the rank, ``ME``)."""

    name: str

    def __add__(self, other):
        return Bin("add", self, other)

    def __sub__(self, other):
        return Bin("sub", self, other)

    def __mul__(self, other):
        return Bin("mul", self, other)


@dataclasses.dataclass(frozen=True)
class Bin:
    """A binary expression node; ``op`` in add/sub/mul/mod/xor."""

    op: str
    a: Any
    b: Any

    __add__ = Var.__add__
    __sub__ = Var.__sub__
    __mul__ = Var.__mul__


ME = Var("me")


def mod(e, n: int):
    """``e mod n`` (nonnegative); folds when ``e`` is concrete."""
    if isinstance(e, int):
        return e % n
    return Bin("mod", e, n)


def xor(e, k: int):
    """``e ^ k`` (bitwise); folds when ``e`` is concrete."""
    if isinstance(e, int):
        return e ^ k
    return Bin("xor", e, k)


def ev(x, env: dict):
    """Evaluate an expression/tuple against ``env``: needs ``env["me"]``
    and ``env["mod"]`` (a nonnegative-mod callable — ``%`` for concrete
    ints, the lax double-rem for traced values)."""
    if isinstance(x, Var):
        return env[x.name]
    if isinstance(x, Bin):
        a, b = ev(x.a, env), ev(x.b, env)
        if x.op == "add":
            return a + b
        if x.op == "sub":
            return a - b
        if x.op == "mul":
            return a * b
        if x.op == "mod":
            return env["mod"](a, b)
        if x.op == "xor":
            return a ^ b
        raise ValueError(f"unknown op {x.op!r}")
    if isinstance(x, tuple):
        return tuple(ev(e, env) for e in x)
    return x


# ---------------------------------------------------------------------------
# instructions
# ---------------------------------------------------------------------------

# A region is ``(buffer_name, key_tuple)``; key entries may be Exprs.
# A sem is ``(name, slot_index)``; slot 0 addresses scalar semaphores.


@dataclasses.dataclass(frozen=True)
class Dma:
    """One async copy descriptor.  ``peer is None`` means a local copy
    completing on ``sem``; otherwise a remote copy from my ``src`` into
    ``peer``'s ``dst``, signaling my ``send`` sem when the bytes have
    left and ``peer``'s ``recv`` sem when they have landed.

    ``token`` is the data version the landing writes into ``dst``;
    ``src_token`` (optional) is the version ``src`` must hold when the
    copy starts.  Wait instructions referencing a :class:`Dma` use it as
    a descriptor *template*: only its semaphore (and, for the emitter,
    its shape) matter — equal-sized transfers drain interchangeably.
    """

    src: tuple
    dst: tuple
    send: tuple | None = None
    recv: tuple | None = None
    peer: Any = None
    sem: tuple | None = None
    token: Any = None
    src_token: Any = None


@dataclasses.dataclass(frozen=True)
class Start:
    dma: Dma


@dataclasses.dataclass(frozen=True)
class WaitSend:
    dma: Dma


@dataclasses.dataclass(frozen=True)
class WaitRecv:
    dma: Dma


@dataclasses.dataclass(frozen=True)
class WaitLocal:
    dma: Dma


@dataclasses.dataclass(frozen=True)
class Compute:
    """A compute step: ``reads`` are ``(region, expected_token|None)``,
    ``writes`` are ``(region, token)``.  ``args`` carries the evaluated
    operands the emitter's kernel-specific compute fn needs."""

    tag: str
    reads: tuple = ()
    writes: tuple = ()
    args: tuple = ()


# ---------------------------------------------------------------------------
# schedule container
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BufferSpec:
    """``kind``: ``input`` (read-only), ``output``/``scratch``
    (writable), or ``credit`` (the 4-byte flow-control buffer — contents
    irrelevant, concurrent writes harmless, exempt from region checks).
    ``write_once`` buffers must see exactly one write per region."""

    kind: str
    write_once: bool = False


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One kernel's protocol: the per-rank program (symbolic in ``ME``)
    plus buffer/semaphore declarations and the expected final tokens."""

    name: str
    p: int
    params: tuple                 # ((name, value), ...) — e.g. chunk depth
    buffers: tuple                # ((name, BufferSpec), ...)
    sems: tuple                   # ((name, slots), ...); slots 0 = scalar
    program: tuple                # instruction sequence
    final: tuple                  # ((region, expected_token), ...)

    def buffer_specs(self) -> dict:
        return dict(self.buffers)

    def sem_slots(self) -> dict:
        return dict(self.sems)


def _credit(peer) -> Dma:
    return Dma(src=("cbuf", ()), dst=("cbuf", ()), send=("csend", 0),
               recv=("crecv", 0), peer=peer)


def _grant(prog: list, to) -> None:
    """Grant one credit: 4-byte RDMA to ``to``, drained immediately."""
    d = _credit(to)
    prog += [Start(d), WaitSend(d)]


def _take(prog: list, frm) -> None:
    """Take one credit: block until a grant from ``frm`` has landed."""
    prog.append(WaitRecv(_credit(frm)))


_CREDIT_BUFS = (("cbuf", BufferSpec("credit")),)
_CREDIT_SEMS = (("csend", 0), ("crecv", 0))


# ---------------------------------------------------------------------------
# ring all-gather (forward-from-output, zero staging)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def all_gather_schedule(p: int) -> Schedule:
    """Rank ``r`` copies its block to ``out[r]``, then forwards the block
    it most recently received to the right for ``p-1`` steps; send sems
    revolve through 2 slots, receives are waited in-step so the next
    step may forward the landed block."""
    prog: list = []
    right = mod(ME + 1, p)
    loc = Dma(src=("x", ()), dst=("out", (ME,)), sem=("copy", 0),
              token=("x", ME))
    prog += [Start(loc), WaitLocal(loc)]
    for t in range(p - 1):
        src = mod(ME - t, p)
        s = t % 2
        fwd = Dma(src=("out", (src,)), dst=("out", (src,)),
                  send=("send", s), recv=("recv", s), peer=right,
                  token=("x", src), src_token=("x", src))
        if t >= 2:
            # consume the step t-2 send on this sem slot before reuse
            prog.append(WaitSend(fwd))
        prog.append(Start(fwd))
        inc = mod(ME - t - 1, p)
        prog.append(WaitRecv(Dma(
            src=("out", (inc,)), dst=("out", (inc,)),
            send=("send", s), recv=("recv", s), peer=right)))
    for t in range(max(p - 3, 0), p - 1):
        prog.append(WaitSend(Dma(
            src=("out", (ME,)), dst=("out", (ME,)),
            send=("send", t % 2), recv=("recv", t % 2), peer=right)))
    final = tuple((("out", (b,)), ("x", b)) for b in range(p))
    return Schedule(
        "ring_all_gather", p, (),
        (("x", BufferSpec("input")),
         ("out", BufferSpec("output", write_once=True))),
        (("send", 2), ("recv", 2), ("copy", 0)),
        tuple(prog), final)


# ---------------------------------------------------------------------------
# chunked all-to-all (direct scatter, zero staging, every destination
# in flight)
# ---------------------------------------------------------------------------


def a2a_offsets(p: int) -> list:
    """Destination distances in the order a chunk's round of starts
    names them: interleaved by ring direction (+1, -1, +2, -2, ...).
    What puts more than one link to work is :func:`all_to_all_schedule`'s
    chunk-major issue, which keeps a transfer to every one of these in
    flight at once; the order inside a round still counts on the chip
    (on the 2x2, ``[1, 3, 2]`` with :func:`a2a_peer`'s pairing is X
    neighbour, diagonal, Y neighbour, the fastest of the six orders
    measured: ``PERF.md`` section 6, PR 32)."""
    offs = []
    for s in range(1, p // 2 + 1):
        offs.append(s)
        if s != p - s:
            offs.append(p - s)
    return offs


def a2a_peer(off: int, p: int):
    """The rank my piece at distance ``off`` goes to.

    Where ``p`` is a power of two the ranks pair up, ``me ^ off``
    (pairwise exchange), so a round of starts names the same kind of
    route on every rank: on a 2x2 with row-major ids ``^1`` is every
    chip's X neighbour, ``^2`` its Y neighbour and ``^3`` the diagonal,
    where ``+1`` is the X neighbour of the even chips and the diagonal
    of the odd ones.  Elsewhere it is ``me + off`` round the ring.
    Either way the distances ``1..p-1`` reach every other rank once."""
    return xor(ME, off) if p & (p - 1) == 0 else mod(ME + off, p)


def a2a_window(p: int, nc: int) -> tuple:
    """``(destinations in flight, depth per destination)`` of the
    all-to-all's send window: every one of the ``p-1`` destinations has
    a semaphore slot pair of its own (one slot when there is one chunk),
    so up to ``(p-1) * depth`` remote DMAs are in flight."""
    return p - 1, min(nc, 2)


@functools.lru_cache(maxsize=None)
def all_to_all_schedule(p: int, nc: int) -> Schedule:
    """Every piece is DMA'd directly into its final offset of the
    destination rank's output (write-once).  The remote DMAs are issued
    chunk-major and destination-minor: chunk ``c`` goes out to all
    ``p-1`` destinations before chunk ``c+1`` to any, and each
    destination revolves through a send-semaphore window of its own
    (:func:`a2a_window`), so from the first start to the last every
    destination that still has pieces to send has one in flight, and a
    slow stream holds the others back by at most the piece they have in
    flight.  The local block's copy is started first and waited after
    the sends have drained, so it runs under the wire.  The single
    receive sem accumulates the ``(p-1)*nc`` equal-sized landings and is
    drained at the end.  Remote ``out`` regions are keyed by (sender,
    chunk) — each is written exactly once by exactly one peer."""
    offs = a2a_offsets(p)
    _, depth = a2a_window(p, nc)
    prog: list = []
    loc = Dma(src=("x", (ME, "all")), dst=("out", (ME, "all")),
              sem=("copy", 0), token=("piece", ME, ME, "all"))
    prog.append(Start(loc))

    def piece(j, c):
        dst = a2a_peer(offs[j], p)
        return Dma(src=("x", (dst, c)), dst=("out", (ME, c)),
                   send=("send", j * depth + c % depth), recv=("recv", 0),
                   peer=dst, token=("piece", ME, dst, c))

    for c in range(nc):
        for j in range(len(offs)):
            d = piece(j, c)
            if c >= depth:
                prog.append(WaitSend(d))       # free this destination's slot
            prog.append(Start(d))
    for c in range(max(nc - depth, 0), nc):
        for j in range(len(offs)):
            prog.append(WaitSend(piece(j, c)))
    prog.append(WaitLocal(loc))
    landing = Dma(src=("x", (ME, 0)), dst=("out", (ME, 0)),
                  send=("send", 0), recv=("recv", 0), peer=ME)
    for _ in range((p - 1) * nc):
        prog.append(WaitRecv(landing))
    final = [(("out", (ME, "all")), ("piece", ME, ME, "all"))]
    for off in offs:
        # every other rank lands its pieces for me: as sources too the
        # peers are all of them, each once
        src_rank = a2a_peer(off, p)
        for c in range(nc):
            final.append(((("out", (src_rank, c))),
                          ("piece", src_rank, ME, c)))
    return Schedule(
        "ring_all_to_all", p, (("nc", nc),),
        (("x", BufferSpec("input")),
         ("out", BufferSpec("output", write_once=True))),
        (("send", (p - 1) * depth), ("recv", 0), ("copy", 0)),
        tuple(prog), tuple(final))


# ---------------------------------------------------------------------------
# ring reduce-scatter (traveling partials, credit-gated chunk reuse)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def reduce_scatter_schedule(p: int, nc: int) -> Schedule:
    """Per chunk: a ``p-1``-step ring of traveling partials.  The
    partial for destination ``d`` seeds at rank ``d+1`` and accumulates
    one local contribution per hop; per-step receive slots are
    write-once within a chunk; chunk-to-chunk slot reuse is gated by one
    credit from the consuming right neighbor.  Token ``("p", d, k, c)``
    = partial for destination ``d`` holding ``k`` contributions."""
    prog: list = []
    right, left = mod(ME + 1, p), mod(ME - 1, p)
    for c in range(nc):
        if c >= 1:
            # right must have consumed its chunk c-1 receive slots
            _take(prog, right)
        seed_b = mod(ME - 1, p)
        seed = Dma(src=("x", (seed_b, c)), dst=("acc", (0,)),
                   sem=("copy", 0), token=("p", seed_b, 1, c))
        prog += [Start(seed), WaitLocal(seed)]
        a = 0
        for t in range(p - 1):
            tok = ("p", mod(ME - 1 - t, p), t + 1, c)
            d = Dma(src=("acc", (a,)), dst=("recv", (t,)),
                    send=("send", a), recv=("recv", t), peer=right,
                    token=tok, src_token=tok)
            prog.append(Start(d))
            nb = mod(ME - t - 2, p)
            cp = Dma(src=("x", (nb, c)), dst=("tmp", (a,)),
                     sem=("tmp", a), token=("x", nb, c))
            prog.append(Start(cp))
            prog += [WaitSend(d), WaitRecv(d), WaitLocal(cp)]
            prog.append(Compute(
                "accum",
                reads=((("recv", (t,)), ("p", mod(ME - 2 - t, p), t + 1, c)),
                       (("tmp", (a,)), ("x", nb, c))),
                writes=((("acc", (1 - a,)),
                         ("p", mod(ME - 2 - t, p), t + 2, c)),),
                args=(("t", t), ("a", a))))
            a = 1 - a
        if c < nc - 1:
            _grant(prog, left)                 # chunk consumed
        out = Dma(src=("acc", (a,)), dst=("out", (c,)), sem=("copy", 0),
                  token=("p", ME, p, c), src_token=("p", ME, p, c))
        prog += [Start(out), WaitLocal(out)]
    final = tuple((("out", (c,)), ("p", ME, p, c)) for c in range(nc))
    return Schedule(
        "ring_reduce_scatter", p, (("nc", nc),),
        (("x", BufferSpec("input")),
         ("out", BufferSpec("output", write_once=True)),
         ("recv", BufferSpec("scratch")),
         ("acc", BufferSpec("scratch")),
         ("tmp", BufferSpec("scratch"))) + _CREDIT_BUFS,
        (("send", 2), ("recv", p - 1), ("copy", 0),
         ("tmp", 2)) + _CREDIT_SEMS,
        tuple(prog), final)


# ---------------------------------------------------------------------------
# fused ring GEMMs
# ---------------------------------------------------------------------------


def _ag_gemm_prog(p: int, compute_step) -> list:
    """The shared fused all-gather GEMM skeleton: the traveling operand
    forwards LEFT (so block ``me+t`` is resident at step ``t``, matching
    the lax path's pshift(-1) schedule) while the resident chunk's dot
    runs; slot reuse at the receiver is credit-gated.

    The credit window arms at ``t == 1``: the step-``t`` forward writes
    the slot the left neighbor's step-``t-1`` dot (and forward source)
    reads, and the neighbor may lag a full step — the model checker
    found that the original ``t >= 2`` window left the ``t == 1`` write
    unprotected (the one-step-skew overwrite the credits exist for), so
    every forward after the first now takes a credit granted right after
    the peer's matching consume.  Takes (``t`` in 1..p-2) and grants
    (``t`` in 0..p-3) still balance exactly, so the credit semaphores
    drain to zero."""
    prog: list = []
    left, right = mod(ME - 1, p), mod(ME + 1, p)
    loc = Dma(src=("xin", ()), dst=("buf", (0,)), sem=("copy", 0),
              token=("blk", ME))
    prog += [Start(loc), WaitLocal(loc)]
    for t in range(p):
        s = t % 2
        src = mod(ME + t, p)
        fwd = None
        if t < p - 1:
            if t >= 1:
                _take(prog, left)              # left freed the slot we hit
            fwd = Dma(src=("buf", (s,)), dst=("buf", (1 - s,)),
                      send=("send", s), recv=("recv", 1 - s), peer=left,
                      token=("blk", src), src_token=("blk", src))
            prog.append(Start(fwd))
        prog.append(compute_step(t, s, src))
        if t < p - 1:
            prog += [WaitSend(fwd), WaitRecv(fwd)]
            if t <= p - 3:
                _grant(prog, right)            # balance against the takes
    return prog


@functools.lru_cache(maxsize=None)
def ag_matmul_schedule(p: int) -> Schedule:
    """``ring_allgather_matmul``: traveling x chunks, stationary w, each
    resident chunk's dot writes its own output block (write-once)."""
    def step(t, s, src):
        return Compute(
            "dot",
            reads=((("buf", (s,)), ("blk", src)), (("w", ()), None)),
            writes=((("o", (src,)), ("o", src)),),
            args=(("src", src), ("s", s)))
    prog = _ag_gemm_prog(p, step)
    final = tuple((("o", (b,)), ("o", b)) for b in range(p))
    return Schedule(
        "ring_allgather_matmul", p, (),
        (("xin", BufferSpec("input")), ("w", BufferSpec("input")),
         ("o", BufferSpec("output", write_once=True)),
         ("buf", BufferSpec("scratch"))) + _CREDIT_BUFS,
        (("send", 2), ("recv", 2), ("copy", 0)) + _CREDIT_SEMS,
        tuple(prog), final)


@functools.lru_cache(maxsize=None)
def ag_matmul_rhs_schedule(p: int) -> Schedule:
    """``ring_allgather_matmul_rhs``: traveling b chunks contract against
    the resident a column slice, accumulating into the single output."""
    def step(t, s, src):
        reads = [(("buf", (s,)), ("blk", src)), (("w", ()), None)]
        if t > 0:
            reads.append((("o", ()), ("acc", t - 1)))
        return Compute(
            "accum_rhs", reads=tuple(reads),
            writes=((("o", ()), ("acc", t)),),
            args=(("src", src), ("s", s), ("t", t)))
    prog = _ag_gemm_prog(p, step)
    final = ((("o", ()), ("acc", p - 1)),)
    return Schedule(
        "ring_allgather_matmul_rhs", p, (),
        (("xin", BufferSpec("input")), ("w", BufferSpec("input")),
         ("o", BufferSpec("output")),
         ("buf", BufferSpec("scratch"))) + _CREDIT_BUFS,
        (("send", 2), ("recv", 2), ("copy", 0)) + _CREDIT_SEMS,
        tuple(prog), final)


@functools.lru_cache(maxsize=None)
def matmul_reducescatter_schedule(p: int) -> Schedule:
    """``ring_matmul_reducescatter``: traveling partials forward RIGHT;
    each destination block's GEMM runs while the partial's RDMA is in
    flight; the revolving receive slots are credit-gated.  The final
    partial ``("p", me, p)`` is copied out on the csend sem (the
    kernel's actual scratch economy)."""
    prog: list = []
    left, right = mod(ME - 1, p), mod(ME + 1, p)
    d0 = mod(ME - 1, p)
    prog.append(Compute(
        "gemm", reads=((("x", (d0,)), None), (("w", ()), None)),
        writes=((("acc", (0,)), ("p", d0, 1)),),
        args=(("d", d0), ("acc_slot", 0))))
    a = 0
    for t in range(1, p):
        s = t % 2
        tok = ("p", mod(ME - t, p), t)
        d = Dma(src=("acc", (a,)), dst=("recv", (s,)),
                send=("send", a), recv=("recv", s), peer=right,
                token=tok, src_token=tok)
        if t >= 3:
            _take(prog, right)                 # right freed recv slot s
        prog.append(Start(d))
        dt = mod(ME - 1 - t, p)
        # the next destination block's GEMM runs while the partial rides
        prog.append(Compute(
            "gemm", reads=((("x", (dt,)), None), (("w", ()), None)),
            writes=((("g", ()), ("g", t)),),
            args=(("d", dt), ("acc_slot", None))))
        prog += [WaitSend(d), WaitRecv(d)]
        prog.append(Compute(
            "accum",
            reads=((("recv", (s,)), ("p", dt, t)), (("g", ()), ("g", t))),
            writes=((("acc", (1 - a,)), ("p", dt, t + 1)),),
            args=(("s", s), ("a", a))))
        a = 1 - a
        if 1 <= t <= p - 3:
            _grant(prog, left)                 # balance against the takes
    out = Dma(src=("acc", (a,)), dst=("o", ()), sem=("csend", 0),
              token=("p", ME, p), src_token=("p", ME, p))
    prog += [Start(out), WaitLocal(out)]
    final = ((("o", ()), ("p", ME, p)),)
    return Schedule(
        "ring_matmul_reducescatter", p, (),
        (("x", BufferSpec("input")), ("w", BufferSpec("input")),
         ("o", BufferSpec("output", write_once=True)),
         ("acc", BufferSpec("scratch")), ("recv", BufferSpec("scratch")),
         ("g", BufferSpec("scratch"))) + _CREDIT_BUFS,
        (("send", 2), ("recv", 2)) + _CREDIT_SEMS,
        tuple(prog), final)


# ---------------------------------------------------------------------------
# mesh-axis sub-ring geometry
# ---------------------------------------------------------------------------
#
# A ring kernel armed along ONE axis of an N-D mesh runs an independent
# ring per combination of the other axes' coordinates (a "sub-ring").
# Schedules stay symbolic in the ring POSITION (``ME``) — nothing above
# changes — and these helpers are the single source of truth for how
# positions map to global ranks under the row-major flattening
# ``layout.mesh_for`` uses.  Both consumers share this geometry: the
# Pallas emitter builds its ``DeviceIdType.MESH`` ids from the same
# (position, other-axis coordinates) decomposition, and the protocol
# checker's mesh concretization uses ``mesh_subrings`` to prove the
# armed program partitions into disjoint rank-renamed 1-D rings.


def mesh_axis_size(mesh_shape: tuple, axis: int) -> int:
    """Ring width ``p`` of ``axis`` (negative axes index from the end)."""
    return mesh_shape[axis % len(mesh_shape)]


def mesh_subrings(mesh_shape: tuple, axis: int) -> tuple:
    """Sub-rings along ``axis``: a tuple of rank-tuples, each listing the
    global (row-major-flattened) ranks of one sub-ring in ring-position
    order.  Every rank appears in exactly one sub-ring."""
    ndim = len(mesh_shape)
    axis = axis % ndim
    p = mesh_shape[axis]
    stride = 1
    for d in mesh_shape[axis + 1:]:
        stride *= d
    outer = 1
    for d in mesh_shape[:axis]:
        outer *= d
    rings = []
    for o in range(outer):
        for i in range(stride):
            base = o * p * stride + i
            rings.append(tuple(base + q * stride for q in range(p)))
    return tuple(rings)


def mesh_peer(mesh_shape: tuple, axis: int, rank: int, pos: int) -> int:
    """Global rank sitting at ring position ``pos`` of ``rank``'s
    sub-ring — the scalar twin of the emitter's MESH device id (all
    coordinates of ``rank`` kept, the ``axis`` coordinate replaced by
    ``pos``)."""
    ndim = len(mesh_shape)
    axis = axis % ndim
    p = mesh_shape[axis]
    stride = 1
    for d in mesh_shape[axis + 1:]:
        stride *= d
    my_pos = (rank // stride) % p
    return rank + (pos - my_pos) * stride


# the checker's registry: name -> builder(p, nc); chunkless kernels
# ignore nc
SCHEDULES = {
    "ring_all_gather": lambda p, nc=1: all_gather_schedule(p),
    "ring_all_to_all": lambda p, nc=1: all_to_all_schedule(p, nc),
    "ring_reduce_scatter": lambda p, nc=1: reduce_scatter_schedule(p, nc),
    "ring_allgather_matmul": lambda p, nc=1: ag_matmul_schedule(p),
    "ring_allgather_matmul_rhs": lambda p, nc=1: ag_matmul_rhs_schedule(p),
    "ring_matmul_reducescatter":
        lambda p, nc=1: matmul_reducescatter_schedule(p),
}


def build(name: str, p: int, nc: int = 1) -> Schedule:
    """Build the named kernel's schedule (chunkless kernels ignore nc)."""
    return SCHEDULES[name](p, nc)
