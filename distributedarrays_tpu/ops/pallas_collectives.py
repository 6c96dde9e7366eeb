"""Hand-rolled Pallas TPU RDMA ring collectives: overlap DMA with compute.

Every inter-chip exchange in the framework used to be an XLA collective
(``lax.all_gather`` / ``all_to_all`` / ``ppermute`` via the
``parallel.collectives`` helpers).  XLA's collectives are asynchronous,
but their *schedule* is XLA's: within one program the compiler sequences
the wire time of a ring step against the MXU work of the same step more
often than not.  This module owns the schedule explicitly, following the
Pallas TPU distributed recipe (SNIPPETS.md [1]/[2], the
``make_async_remote_copy`` send/recv-semaphore pattern) and the chunk
decomposition of "Memory-efficient array redistribution through portable
collective communication" (arXiv:2112.01075):

- :func:`ring_all_gather` — forward-from-output ring: each rank DMAs the
  block it most recently received straight out of its own output buffer
  into its right neighbor's output buffer, so the concat IS the transfer
  (zero staging) and the next incoming block rides the wire while the
  previous forward drains (send semaphores double-buffered).
- :func:`ring_reduce_scatter` — chunked traveling-partial ring: each
  chunk runs a p-1-step ring whose per-step receive slots are
  write-once (no reuse race by construction); the next local block's
  HBM→VMEM copy overlaps the partial's RDMA hop; chunk-to-chunk slot
  reuse is gated by a credit DMA from the consuming neighbor.
- :func:`ring_all_to_all` — chunked all-to-all: every piece is DMA'd
  directly into its final offset of the destination rank's output
  (write-once, zero staging).  Chunk ``c`` goes out to every peer before
  chunk ``c+1`` to any, each peer behind a send window of its own, so a
  transfer to every peer is in flight at once and every link that leads
  to one carries traffic; the local block's copy runs under the wire.
- :func:`ring_allgather_matmul` / :func:`ring_allgather_matmul_rhs` /
  :func:`ring_matmul_reducescatter` — the fused collective GEMMs: the
  next chunk's RDMA is STARTED before the resident chunk's ``jnp.dot``
  and WAITED after it, inside one kernel, so the MXU covers the wire
  time (the overlap ``ops/collective_matmul`` can only hint to XLA).

Semaphore protocol (shared by every kernel; docs/pallas_collectives.md
has the worked schedule diagrams):

- every remote copy carries a local *send* semaphore (signaled when the
  source bytes have left) and a remote *receive* semaphore (signaled on
  the destination chip when the bytes have landed);
- buffers a peer writes into are either write-once for the kernel's
  lifetime (all_gather, all_to_all, reduce-scatter recv slots within a
  chunk) or revolve under an explicit **credit**: a 4-byte RDMA from
  the consumer back to the producer that grants one more in-flight
  transfer, because a DMA-semaphore wait alone only keeps neighbors
  within one step of each other — one step is exactly the distance at
  which a 2-slot buffer is overwritten mid-read;
- all transfers of one kind are equal-sized, so a single receive
  semaphore can accumulate several landings and be drained with one
  descriptor wait per landing, in any order.

Every kernel body is **emitted from a declarative schedule**
(``ops/ring_schedules.py``): the per-step DMA starts, semaphore waits,
credit grants/takes, and compute steps are data, interpreted at trace
time by :func:`_emit` (regions → ref slices, sems → DMA-semaphore
scratch) and exhaustively model-checked by ``analysis.protocol`` — the
emitter and the checker share one source of truth, so the semaphore
protocol documented in docs/pallas_collectives.md is machine-verified,
not hand-argued (``python -m distributedarrays_tpu.analysis
verify-protocols``).

Dispatch: the RDMA kernels run compiled on real TPUs and in interpreter
mode when forced (tests, ``DA_TPU_RDMA=interpret``); every other platform
takes the bit-equivalent ``lax`` collective, counted via
``fallback.hits`` and warned once when RDMA was explicitly requested
(``DA_TPU_RDMA=1``) or the caller demanded the compiled kernel
(``interpret=False``) and a platform or eligibility gate refused it.
``DA_TPU_RDMA=0`` is
the kill switch.  ``DA_TPU_RDMA_CHUNKS`` pins the ring chunk depth;
unset, it is derived from ``DA_TPU_RESHARD_CHUNK_MB`` (one chunk stages
at most one reshard chunk target) with an ``"rdma_chunks"`` autotune
registry entry taking precedence, the ``pallas_gemm`` pattern.

Mesh addressing.  On a 1-D mesh the kernels use LOGICAL device ids
(ring position = device id).  Armed along one axis of a 2-D/3-D mesh —
pass ``mesh_axes`` (the mesh's full axis-name tuple, in mesh order) —
they switch to ``DeviceIdType.MESH``: the peer's device id keeps every
other axis' own coordinate (``lax.axis_index``) and replaces only the
armed axis' coordinate with the ring position, so each combination of
the other axes' coordinates runs an independent sub-ring
(``ring_schedules.mesh_subrings`` is the shared geometry and
``analysis.protocol.check_mesh_schedule`` proves the variants).  The
schedules stay symbolic in the ring position — nothing about the
protocol changes per axis.  One platform gate: Pallas *interpret* mode
only discharges DMAs on 1-D meshes (``dma_start_p``), so multi-axis
arming is compiled-TPU-only and every other platform takes the
bit-equivalent ``lax`` collective fallback (counted as usual).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .. import telemetry as _tm
from ..parallel.collectives import (axis_size as _axis_size, pall_to_all,
                                    pgather)
from . import ring_schedules as _rs
from .pallas_gemm import _on_tpu

__all__ = ["rdma_mode", "resolve_chunks", "ring_all_gather",
           "ring_reduce_scatter", "ring_all_to_all",
           "ring_allgather_matmul", "ring_allgather_matmul_rhs",
           "ring_matmul_reducescatter", "gemm_ring_eligible"]


RDMA_ENV = "DA_TPU_RDMA"
CHUNKS_ENV = "DA_TPU_RDMA_CHUNKS"

# scoped-VMEM budget for the fused GEMM rings — same silicon-measured
# limit as pallas_gemm's tile sets
_VMEM_LIMIT = int(15.5 * 2**20)


def _lax_instead(site: str, interpret: bool | None, why: str) -> None:
    """The dispatch decision ``None`` (take the ``lax`` path) for a call a
    platform or eligibility gate refuses.  Where the caller demanded the
    compiled kernel (``interpret=False``) that is a degradation like every
    other: counted (``fallback.hits``) and warned once."""
    if interpret is False:
        from ..utils.debug import warn_once
        warn_once(f"pallas_collectives:{site}:{why}",
                  f"{site}: compiled RDMA kernel demanded "
                  f"(interpret=False) but refused ({why}); taking the lax "
                  f"path")
    return None


def rdma_mode(interpret: bool | None = None) -> str | None:
    """The dispatch decision for one RDMA call site: ``"compiled"`` (real
    TPU), ``"interpret"`` (forced — tests / ``DA_TPU_RDMA=interpret``),
    or ``None`` (take the ``lax`` fallback).

    ``DA_TPU_RDMA=0`` kills the RDMA path everywhere.  An explicit
    ``DA_TPU_RDMA=1`` on a platform that cannot serve it warns once and
    counts every hit (``fallback.hits``); the unset default stays quiet
    off-TPU (nothing was promised)."""
    env = os.environ.get(RDMA_ENV)
    val = (env or "1").strip().lower()
    if val in ("0", "off", "false"):
        return None
    if interpret or val == "interpret":
        return "interpret"
    if _on_tpu():
        return "compiled"
    if interpret is False:
        # caller demands the compiled kernel or nothing
        return _lax_instead("rdma_mode", interpret, "platform not tpu")
    if env is not None:
        # RDMA was explicitly requested and cannot be served here
        from ..utils.debug import warn_once
        warn_once("pallas_collectives:platform not tpu",
                  "DA_TPU_RDMA requested but unavailable (platform not "
                  "tpu); falling back to XLA collectives")
    return None


def _chunk_target_bytes() -> int:
    # late import: parallel.reshard imports this module for its kernels
    from ..parallel.reshard import _chunk_target_bytes as ct
    return ct()


def resolve_chunks(local_bytes: int, *key_parts) -> tuple[int, str]:
    """The ring chunk depth for a transfer of ``local_bytes`` per device:
    ``DA_TPU_RDMA_CHUNKS`` wins, else a valid ``"rdma_chunks"`` autotune
    entry for this shape/platform, else derived so one chunk stays under
    the ``DA_TPU_RESHARD_CHUNK_MB`` target.  Returns ``(chunks, source)``
    — the source is stamped on the dispatch span."""
    env = os.environ.get(CHUNKS_ENV)
    if env:
        try:
            return max(int(env), 1), "env"
        except ValueError:
            pass
    from ..utils import autotune
    vals = autotune.valid_ints(
        autotune.get("rdma_chunks", autotune.device_key_for(*key_parts)),
        (1,))
    if vals is not None:
        return vals[0], "autotune"
    derived = -(-int(local_bytes) // _chunk_target_bytes())   # ceil
    return min(max(derived, 1), 64), "derived"


def _record_dispatch(op: str, path: str, x, axis: str, p: int = 0,
                     **labels) -> None:
    """Trace-time dispatch telemetry: a labeled counter plus, on the
    RDMA path, a comm-byte record mirroring
    ``parallel.collectives._rec`` (these helpers run inside shard_map
    tracing — once per compilation, flagged traced).  The ``xla`` path
    only counts the dispatch: its ``lax`` lowering records its own
    bytes, and two records for one transfer would double-count.

    ``p`` (the ring size) adds a ``bytes_ici`` provenance stamp to the
    comm record — PER-DEVICE ring volume, matching this record's
    per-rank-block byte convention (``collectives._rec``)."""
    # ``inflight`` (the all-to-all's send window) is also a label of the
    # counter, so a report says what engaged without the journal
    window = {"inflight": labels["inflight"]} if "inflight" in labels else {}
    _tm.count("pallas_collectives.dispatch", op=op, path=path, **window)
    if path == "rdma" and _tm.enabled():
        if p and p > 1:
            # every ring kernel forwards each resident/received piece
            # p-1 hops: per-device ICI volume = (p-1) x the local payload
            labels = {**labels,
                      "bytes_ici": (p - 1) * _tm.nbytes_of(x)}
        _tm.record_comm(op, _tm.nbytes_of(x), axis=axis, traced=True,
                        dispatch=path,
                        once_key=f"pallas_collectives:{op}:{path}:{axis}:"
                                 f"{labels}", **labels)


def _ds_at(ref, dim: int, start, size: int, ndim: int):
    """``ref.at[..., pl.ds(start, size), ...]`` with the slice on ``dim``."""
    idx = tuple(pl.ds(start, size) if d == dim else slice(None)
                for d in range(ndim))
    return ref.at[idx]


def _mod(a, n: int):
    """Nonnegative ``a % n`` for possibly-negative traced ``a``."""
    return lax.rem(lax.rem(a, n) + n, n)


def _copy(src, dst, sem):
    c = pltpu.make_async_copy(src, dst, sem)
    c.start()
    c.wait()


class _Credit:
    """The 4-byte flow-control grant: ``grant(to)`` DMAs one credit to a
    neighbor; ``take(frm)`` blocks until one credit has landed here.
    Contents are irrelevant (only the receive semaphore's count matters);
    concurrent grants into the same buffer are harmless.  The six ring
    kernels get their credits from the declarative schedules; this
    helper remains for the fused ring-attention kernel
    (``models/ring_attention``), whose blockwise-softmax compute is not
    schedule-emitted yet."""

    def __init__(self, buf_ref, send_sem, recv_sem):
        self.buf, self.ssem, self.rsem = buf_ref, send_sem, recv_sem

    def _desc(self, peer):
        return pltpu.make_async_remote_copy(
            src_ref=self.buf, dst_ref=self.buf,
            send_sem=self.ssem, recv_sem=self.rsem,
            device_id=peer, device_id_type=pltpu.DeviceIdType.LOGICAL)

    def grant(self, to):
        d = self._desc(to)
        d.start()
        d.wait_send()

    def take(self, frm):
        self._desc(frm).wait_recv()


def _credit_scratch():
    return [pltpu.VMEM((1, 1), jnp.int32),
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]


def _mesh_device_id(mesh_axes: tuple, axis: str):
    """MESH device-id builder for a ring armed along ``axis`` of a
    multi-axis mesh: peer position replaces the armed axis' coordinate,
    every other coordinate stays mine — the emitter-side twin of
    ``ring_schedules.mesh_peer`` (the checker refutes any other
    choice)."""
    if axis not in mesh_axes:
        raise ValueError(f"armed axis {axis!r} not in mesh axes "
                         f"{mesh_axes!r}")

    def device_id(pos):
        return tuple(pos if a == axis else lax.axis_index(a)
                     for a in mesh_axes)
    return device_id


def _emit(sched, me, regions, sems, computes=None, device_id=None):
    """Replay a :class:`ring_schedules.Schedule` as Pallas DMA ops.

    ``regions`` maps buffer name → ``fn(key) -> ref slice`` (the
    kernel's geometry — keys arrive with rank expressions already
    evaluated to traced values); ``sems`` maps sem name → scratch ref;
    ``computes`` maps compute tag → ``fn(args dict)``.  Wait
    instructions rebuild an equal-shaped descriptor from their template
    DMA, the same same-size-drains-one semantics the hand-rolled
    kernels used.  Credit grants/takes arrive as ordinary
    start/wait-send/wait-recv instructions over the ``cbuf`` buffer.

    ``device_id`` (from :func:`_mesh_device_id`) maps an evaluated ring
    position to a MESH-coordinate tuple for multi-axis meshes; None
    keeps the 1-D LOGICAL addressing (position = device id)."""
    env = {"me": me, "mod": _mod}
    slots = sched.sem_slots()

    def reg(r):
        buf, key = r
        return regions[buf](_rs.ev(key, env))

    def sref(sm):
        name, idx = sm
        ref = sems[name]
        return ref.at[idx] if slots[name] else ref

    def desc(d):
        if d.peer is None:
            return pltpu.make_async_copy(reg(d.src), reg(d.dst),
                                         sref(d.sem))
        pos = _rs.ev(d.peer, env)
        if device_id is None:
            did, idt = pos, pltpu.DeviceIdType.LOGICAL
        else:
            did, idt = device_id(pos), pltpu.DeviceIdType.MESH
        return pltpu.make_async_remote_copy(
            src_ref=reg(d.src), dst_ref=reg(d.dst),
            send_sem=sref(d.send), recv_sem=sref(d.recv),
            device_id=did, device_id_type=idt)

    for ins in sched.program:
        if isinstance(ins, _rs.Start):
            desc(ins.dma).start()
        elif isinstance(ins, _rs.WaitSend):
            desc(ins.dma).wait_send()
        elif isinstance(ins, _rs.WaitRecv):
            desc(ins.dma).wait_recv()
        elif isinstance(ins, _rs.WaitLocal):
            desc(ins.dma).wait()
        else:
            computes[ins.tag]({k: _rs.ev(v, env) for k, v in ins.args})


def _arm_mesh(mode: str | None, axis: str, mesh_axes) -> tuple:
    """Normalize a kernel's ``(mode, mesh_axes)`` for the armed axis.
    A 1-D (or omitted) mesh keeps LOGICAL addressing (``None``); a
    multi-axis mesh keeps the axis tuple for MESH addressing but
    demotes *interpret* mode to the lax fallback — Pallas interpret
    mode only discharges DMAs on 1-D meshes (``dma_start_p``)."""
    if mesh_axes is None or len(mesh_axes) <= 1:
        return mode, None
    mesh_axes = tuple(mesh_axes)
    if axis not in mesh_axes:
        raise ValueError(f"armed axis {axis!r} not in mesh axes "
                         f"{mesh_axes!r}")
    if mode == "interpret":
        return None, None
    return mode, mesh_axes


# ---------------------------------------------------------------------------
# ring all-gather
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _ag_call(axis: str, p: int, shape: tuple, dtype_str: str, dim: int,
             interpret: bool, mesh_axes: tuple | None = None):
    dtype = jnp.dtype(dtype_str)
    blk = shape[dim]
    ndim = len(shape)
    out_shape = tuple(blk * p if d == dim else s
                      for d, s in enumerate(shape))

    sched = _rs.all_gather_schedule(p)
    did = _mesh_device_id(mesh_axes, axis) if mesh_axes else None

    def kernel(x_ref, o_ref, send_sem, recv_sem, copy_sem):
        _emit(sched, lax.axis_index(axis), regions={
            "x": lambda k: x_ref,
            "out": lambda k: _ds_at(o_ref, dim, k[0] * blk, blk, ndim),
        }, sems={"send": send_sem, "recv": recv_sem, "copy": copy_sem},
            device_id=did)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA],
        name="ring_all_gather",
        interpret=interpret,
    )


def ring_all_gather(x, axis: str, *, dim: int = 0,
                    interpret: bool | None = None,
                    mesh_axes: tuple | None = None):
    """``lax.all_gather(x, axis, axis=dim, tiled=True)`` as a Pallas RDMA
    ring (bit-identical: pure data movement).  Falls back to ``pgather``
    off-TPU.  ``mesh_axes`` (the full axis tuple of a multi-axis mesh)
    arms per-axis sub-rings with MESH device ids — compiled TPU only."""
    p = _axis_size(axis)
    if p == 1:
        return x
    mode, mesh_axes = _arm_mesh(rdma_mode(interpret), axis, mesh_axes)
    if mode is None:
        _record_dispatch("ring_all_gather", "xla", x, axis)
        return pgather(x, axis, tiled=True, dim=dim)
    _record_dispatch("ring_all_gather", "rdma", x, axis, p=p, mode=mode)
    shape = tuple(int(s) for s in x.shape)
    return _ag_call(axis, p, shape, str(x.dtype), dim,
                    mode == "interpret", mesh_axes)(x)


# ---------------------------------------------------------------------------
# ring all-to-all
# ---------------------------------------------------------------------------


def _chunk_fit(extent: int, want: int) -> int:
    """Largest divisor of ``extent`` that is <= ``want`` (>= 1)."""
    want = max(min(want, extent), 1)
    for c in range(want, 0, -1):
        if extent % c == 0:
            return c
    return 1


@functools.lru_cache(maxsize=256)
def _a2a_call(axis: str, p: int, shape: tuple, dtype_str: str,
              split_dim: int, concat_dim: int, nchunks: int,
              interpret: bool, mesh_axes: tuple | None = None):
    dtype = jnp.dtype(dtype_str)
    ndim = len(shape)
    sblk = shape[split_dim] // p
    out_shape = tuple(sblk if d == split_dim else
                      (s * p if d == concat_dim else s)
                      for d, s in enumerate(shape))
    cext = shape[concat_dim]
    nc = _chunk_fit(cext, nchunks)
    piece = cext // nc
    sched = _rs.all_to_all_schedule(p, nc)
    did = _mesh_device_id(mesh_axes, axis) if mesh_axes else None

    def kernel(x_ref, o_ref, send_sem, recv_sem, copy_sem):
        def x_reg(k):
            # (dst, c) piece, or (me, "all") — the whole resident block
            if k[1] == "all":
                return _ds_at(x_ref, split_dim, k[0] * sblk, sblk, ndim)
            r = _ds_at(x_ref, split_dim, k[0] * sblk, sblk, ndim)
            return _ds_at(r, concat_dim, k[1] * piece, piece, ndim)

        def o_reg(k):
            # keyed by the SENDER's rank: its piece lands at its own
            # concat offset of the destination's output
            if k[1] == "all":
                return _ds_at(o_ref, concat_dim, k[0] * cext, cext, ndim)
            return _ds_at(o_ref, concat_dim, k[0] * cext + k[1] * piece,
                          piece, ndim)

        _emit(sched, lax.axis_index(axis),
              regions={"x": x_reg, "out": o_reg},
              sems={"send": send_sem, "recv": recv_sem, "copy": copy_sem},
              device_id=did)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((sched.sem_slots()["send"],)),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA],
        name="ring_all_to_all",
        interpret=interpret,
    )


def a2a_chunks_for(local_shape, dtype_str: str, p: int,
                   concat_dim: int | None = None) -> tuple[int, str]:
    """The chunk depth :func:`ring_all_to_all` will use for a local
    shard of ``local_shape`` — shared with the reshard planner so the
    ``reshard`` span labels the depth the kernel actually runs.  With
    ``concat_dim`` given, the resolved depth is clamped to a divisor of
    that extent exactly like the kernel clamps it (span, bench row, and
    kernel must agree)."""
    nbytes = math.prod(local_shape) * jnp.dtype(dtype_str).itemsize
    nc, src = resolve_chunks(nbytes // max(p, 1), "a2a", *local_shape,
                             dtype_str, p)
    if concat_dim is not None:
        nc = _chunk_fit(int(local_shape[concat_dim]), nc)
    return nc, src


def a2a_inflight(p: int, nc: int) -> str:
    """The send window :func:`ring_all_to_all` runs at ring size ``p``
    and chunk depth ``nc``, as ``"<destinations in flight>x<depth>"`` —
    the ``inflight`` label of the dispatch counter and record, and the
    ``reshard`` span's ``rdma_inflight``."""
    return "{}x{}".format(*_rs.a2a_window(p, nc))


def ring_all_to_all(x, axis: str, *, split_dim: int, concat_dim: int,
                    chunks: int | None = None,
                    interpret: bool | None = None,
                    mesh_axes: tuple | None = None):
    """``lax.all_to_all(x, axis, split_dim, concat_dim, tiled=True)`` as
    chunked direct RDMA (bit-identical: pure data movement; every piece
    lands at its final output offset, zero staging).  A transfer to each
    of the ``p-1`` peers is in flight at once (``inflight`` on the
    dispatch record says how many, and how deep), and the local block is
    copied meanwhile.  ``split_dim == concat_dim`` keeps the ``lax``
    path.  ``mesh_axes`` arms per-axis sub-rings with MESH device ids —
    compiled TPU only."""
    p = _axis_size(axis)
    if p == 1:
        return x
    shape = tuple(int(s) for s in x.shape)
    # split extent must divide evenly (the lax path raises properly;
    # silent truncation would be wrong data)
    mode = rdma_mode(interpret)
    if mode is not None and (split_dim == concat_dim
                             or shape[split_dim] % p):
        mode = _lax_instead("ring_all_to_all", interpret,
                            "split_dim == concat_dim or uneven split")
    mode, mesh_axes = _arm_mesh(mode, axis, mesh_axes)
    if mode is None:
        _record_dispatch("ring_all_to_all", "xla", x, axis)
        return pall_to_all(x, axis, split_dim=split_dim,
                           concat_dim=concat_dim)
    # a caller's depth is clamped as the kernel clamps it (the derived one
    # already is), so the stamp names the depth and the window that run
    nc, src = (_chunk_fit(shape[concat_dim], chunks), "arg") if chunks \
        else a2a_chunks_for(shape, str(x.dtype), p, concat_dim)
    _record_dispatch("ring_all_to_all", "rdma", x, axis, p=p, mode=mode,
                     chunks=nc, chunks_source=src,
                     inflight=a2a_inflight(p, nc))
    return _a2a_call(axis, p, shape, str(x.dtype), split_dim, concat_dim,
                     nc, mode == "interpret", mesh_axes)(x)


# ---------------------------------------------------------------------------
# ring reduce-scatter
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _rs_call(axis: str, p: int, shape: tuple, dtype_str: str, dim: int,
             nchunks: int, interpret: bool,
             mesh_axes: tuple | None = None):
    dtype = jnp.dtype(dtype_str)
    ndim = len(shape)
    oblk = shape[dim] // p
    out_shape = tuple(oblk if d == dim else s for d, s in enumerate(shape))
    # chunk along the largest axis of the OUTPUT block so the per-chunk
    # staging (p-1 write-once receive slots + 2 revolving partials + 2
    # prefetch slots, all VMEM) stays bounded; prefer an axis other than
    # the scattered dim so the block and chunk slices stay on distinct
    # axes
    cands = sorted(range(ndim), key=lambda d: (d != dim, out_shape[d]))
    cax = cands[-1]
    nc = _chunk_fit(out_shape[cax], nchunks)
    piece = tuple(s // nc if d == cax else s
                  for d, s in enumerate(out_shape))

    sched = _rs.reduce_scatter_schedule(p, nc)
    did = _mesh_device_id(mesh_axes, axis) if mesh_axes else None

    def kernel(x_ref, o_ref, recv, acc, tmp, send_sem, recv_sem, copy_sem,
               tmp_sem, cbuf, csend, crecv):
        def x_piece(k):
            b, c = k
            r = _ds_at(x_ref, dim, b * oblk, oblk, ndim)
            # nc == 1 keeps the block slice whole (also avoids chaining
            # two slices on the same axis when ndim == 1 forces cax==dim)
            return r if nc == 1 else _ds_at(r, cax, c * piece[cax],
                                            piece[cax], ndim)

        def accum(a):
            acc[1 - a["a"]] = recv[a["t"]] + tmp[a["a"]]

        _emit(sched, lax.axis_index(axis), regions={
            "x": x_piece,
            "acc": lambda k: acc.at[k[0]],
            "recv": lambda k: recv.at[k[0]],
            "tmp": lambda k: tmp.at[k[0]],
            "out": lambda k: _ds_at(o_ref, cax, k[0] * piece[cax],
                                    piece[cax], ndim),
            "cbuf": lambda k: cbuf,
        }, sems={"send": send_sem, "recv": recv_sem, "copy": copy_sem,
                 "tmp": tmp_sem, "csend": csend, "crecv": crecv},
            computes={"accum": accum}, device_id=did)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((p - 1,) + piece, dtype),
                        pltpu.VMEM((2,) + piece, dtype),
                        pltpu.VMEM((2,) + piece, dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((p - 1,)),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA((2,))] + _credit_scratch(),
        name="ring_reduce_scatter",
        interpret=interpret,
    )


def _rs_vmem_bytes(shape, itemsize, p, nc, dim):
    oblk_shape = [s // p if d == dim else s for d, s in enumerate(shape)]
    cands = sorted(range(len(shape)),
                   key=lambda d: (d != dim, oblk_shape[d]))
    cax = cands[-1]
    nc = _chunk_fit(oblk_shape[cax], nc)     # the depth the kernel fits
    piece = math.prod(s // nc if d == cax else s
                      for d, s in enumerate(oblk_shape))
    return (p + 3) * piece * itemsize


def ring_reduce_scatter(x, axis: str, *, dim: int = 0,
                        chunks: int | None = None,
                        interpret: bool | None = None,
                        mesh_axes: tuple | None = None):
    """``lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)``
    as a chunked Pallas RDMA traveling-partial ring.  Summation order is
    the ring arrival order (exact for integer-valued data; float results
    differ from XLA's reduction order by rounding only).  Needs the
    scattered dim divisible by the axis size; falls back otherwise.
    ``mesh_axes`` arms per-axis sub-rings — compiled TPU only."""
    p = _axis_size(axis)
    if p == 1:
        return x
    mode, mesh_axes = _arm_mesh(rdma_mode(interpret), axis, mesh_axes)
    shape = tuple(int(s) for s in x.shape)
    itemsize = jnp.dtype(x.dtype).itemsize
    nc = src = None
    if mode is not None and shape[dim] % p == 0:
        blk_bytes = math.prod(shape) * itemsize // p
        # the p-1 receive slots multiply the staged piece: derive with
        # that factor so staging stays under the chunk target
        nc, src = (chunks, "arg") if chunks else resolve_chunks(
            blk_bytes * (p - 1), "rs", *shape, str(x.dtype), p)
        if mode == "compiled" and \
                _rs_vmem_bytes(shape, itemsize, p, nc, dim) > _VMEM_LIMIT:
            mode = _lax_instead("ring_reduce_scatter", interpret,
                                "receive slots exceed scoped VMEM")
    elif mode is not None:
        mode = _lax_instead("ring_reduce_scatter", interpret,
                            "uneven scatter dim")
    if mode is None:
        _record_dispatch("ring_reduce_scatter", "xla", x, axis)
        return lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)
    _record_dispatch("ring_reduce_scatter", "rdma", x, axis, p=p, mode=mode,
                     chunks=nc, chunks_source=src)
    return _rs_call(axis, p, shape, str(x.dtype), dim, nc,
                    mode == "interpret", mesh_axes)(x)


# ---------------------------------------------------------------------------
# fused ring GEMMs: DMA started before the resident chunk's dot, waited
# after it — the compute/comm overlap the XLA ring can only hint at
# ---------------------------------------------------------------------------


_GEMM_RING_WHY = "operands exceed scoped VMEM, or mixed dtypes"


def gemm_ring_eligible(kind: str, x_shape, w_shape, p: int, itemsize: int,
                       out_itemsize: int = 4) -> bool:
    """VMEM-budget gate for the fused ring GEMMs: the revolving operand
    slots, the resident stationary operand, and the output/accumulator
    must fit the scoped-VMEM budget together."""
    xb = math.prod(x_shape) * itemsize
    wb = math.prod(w_shape) * itemsize
    if kind == "ag":        # out (p*m_loc, n) + 2 slots of x + w
        ob = x_shape[0] * p * w_shape[1] * out_itemsize
        need = 2 * xb + wb + ob
    elif kind == "ag_rhs":  # 2 slots of traveling b (x_shape) + resident
        # a (w_shape = (m_loc, k)) + the (m_loc, n) accumulator
        ob = w_shape[0] * x_shape[1] * out_itemsize
        need = 2 * xb + wb + ob
    else:                   # rs: 2 acc + 2 recv of (m/p, n) + w + x
        ob = (x_shape[0] // p) * w_shape[1] * out_itemsize
        need = 4 * ob + wb + xb
    return need <= _VMEM_LIMIT


@functools.lru_cache(maxsize=128)
def _ag_mm_call(axis: str, p: int, xs: tuple, ws: tuple, dtype_str: str,
                out_dtype_str: str, interpret: bool,
                mesh_axes: tuple | None = None):
    m_loc, k = xs
    n = ws[1]
    dtype = jnp.dtype(dtype_str)
    out_dtype = jnp.dtype(out_dtype_str)

    sched = _rs.ag_matmul_schedule(p)
    did = _mesh_device_id(mesh_axes, axis) if mesh_axes else None

    def kernel(x_ref, w_ref, o_ref, buf, send_sem, recv_sem, copy_sem,
               cbuf, csend, crecv):
        def dot(a):
            # resident chunk multiplies while the forward is in flight;
            # resident chunk originated at rank me + t (the lax path's
            # pshift(-1) = fetch-from-the-right schedule)
            o_ref[pl.ds(a["src"] * m_loc, m_loc)] = jnp.dot(
                buf[a["s"]], w_ref[...],
                preferred_element_type=jnp.float32).astype(out_dtype)

        _emit(sched, lax.axis_index(axis), regions={
            "xin": lambda k: x_ref,
            "buf": lambda k: buf.at[k[0]],
            "cbuf": lambda k: cbuf,
        }, sems={"send": send_sem, "recv": recv_sem, "copy": copy_sem,
                 "csend": csend, "crecv": crecv},
            computes={"dot": dot}, device_id=did)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((p * m_loc, n), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, m_loc, k), dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA] + _credit_scratch(),
        name="matmul_ring_ag",
        interpret=interpret,
    )


def ring_allgather_matmul(x, w, axis: str, *,
                          interpret: bool | None = None,
                          mesh_axes: tuple | None = None):
    """``allgather_matmul``'s contract as one fused Pallas kernel: the
    next chunk's RDMA is started before the resident chunk's dot and
    waited after it.  Forward-only (no VJP); callers arm it on any
    single mesh axis for inference paths (``mesh_axes`` for multi-axis
    meshes — compiled TPU only)."""
    p = _axis_size(axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    mode, mesh_axes = _arm_mesh(rdma_mode(interpret), axis, mesh_axes)
    if p > 1 and mode == "compiled" and not (
            x.dtype == w.dtype and gemm_ring_eligible(
                "ag", x.shape, w.shape, p, jnp.dtype(x.dtype).itemsize,
                jnp.dtype(out_dtype).itemsize)):
        mode = _lax_instead("ring_allgather_matmul", interpret,
                            _GEMM_RING_WHY)
    if p == 1 or mode is None or x.dtype != w.dtype:
        return None                          # caller takes the lax path
    _record_dispatch("ring_allgather_matmul", "rdma", x, axis, p=p, mode=mode)
    return _ag_mm_call(axis, p, tuple(map(int, x.shape)),
                       tuple(map(int, w.shape)), str(x.dtype),
                       str(out_dtype), mode == "interpret",
                       mesh_axes)(x, w)


@functools.lru_cache(maxsize=128)
def _ag_mm_rhs_call(axis: str, p: int, as_: tuple, bs: tuple,
                    dtype_str: str, out_dtype_str: str, interpret: bool,
                    mesh_axes: tuple | None = None):
    m_loc, _k = as_
    k_loc, n = bs
    dtype = jnp.dtype(dtype_str)
    out_dtype = jnp.dtype(out_dtype_str)

    sched = _rs.ag_matmul_rhs_schedule(p)
    did = _mesh_device_id(mesh_axes, axis) if mesh_axes else None

    def kernel(a_ref, b_ref, o_ref, buf, send_sem, recv_sem, copy_sem,
               cbuf, csend, crecv):
        def accum_rhs(a):
            # resident chunk contracts against its column slice of a —
            # cast per step like the lax path's ``part``
            part = jnp.dot(a_ref[:, pl.ds(a["src"] * k_loc, k_loc)],
                           buf[a["s"]],
                           preferred_element_type=jnp.float32
                           ).astype(out_dtype)
            if a["t"] == 0:
                o_ref[...] = part
            else:
                o_ref[...] = o_ref[...] + part

        _emit(sched, lax.axis_index(axis), regions={
            "xin": lambda k: b_ref,
            "buf": lambda k: buf.at[k[0]],
            "cbuf": lambda k: cbuf,
        }, sems={"send": send_sem, "recv": recv_sem, "copy": copy_sem,
                 "csend": csend, "crecv": crecv},
            computes={"accum_rhs": accum_rhs}, device_id=did)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m_loc, n), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, k_loc, n), dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA] + _credit_scratch(),
        name="matmul_ring_ag_rhs",
        interpret=interpret,
    )


def ring_allgather_matmul_rhs(a, b, axis: str, *,
                              interpret: bool | None = None,
                              mesh_axes: tuple | None = None):
    """``allgather_matmul_rhs``'s contract fused: the traveling B chunk's
    forward RDMA overlaps the resident chunk's contraction."""
    p = _axis_size(axis)
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    mode, mesh_axes = _arm_mesh(rdma_mode(interpret), axis, mesh_axes)
    if p > 1 and mode == "compiled" and not (
            a.dtype == b.dtype and gemm_ring_eligible(
                "ag_rhs", b.shape, a.shape, p, jnp.dtype(b.dtype).itemsize,
                jnp.dtype(out_dtype).itemsize)):
        mode = _lax_instead("ring_allgather_matmul_rhs", interpret,
                            _GEMM_RING_WHY)
    if p == 1 or mode is None or a.dtype != b.dtype:
        return None
    _record_dispatch("ring_allgather_matmul_rhs", "rdma", b, axis, p=p,
                     mode=mode)
    return _ag_mm_rhs_call(axis, p, tuple(map(int, a.shape)),
                           tuple(map(int, b.shape)), str(a.dtype),
                           str(out_dtype), mode == "interpret",
                           mesh_axes)(a, b)


@functools.lru_cache(maxsize=128)
def _mm_rs_call(axis: str, p: int, xs: tuple, ws: tuple, dtype_str: str,
                interpret: bool, mesh_axes: tuple | None = None):
    m, k_loc = xs
    n = ws[1]
    m_loc = m // p
    dtype = jnp.dtype(dtype_str)

    sched = _rs.matmul_reducescatter_schedule(p)
    did = _mesh_device_id(mesh_axes, axis) if mesh_axes else None

    def kernel(x_ref, w_ref, o_ref, acc, recv, send_sem, recv_sem,
               cbuf, csend, crecv):
        # the lax path: acc seeds with destination (me - 1), forwards to
        # the RIGHT, and accumulates block (me - 1 - t) at step t; the
        # in-flight-hop GEMM parks in ``tmp`` until the wait completes
        tmp = {}

        def block(d):
            return jnp.dot(x_ref[pl.ds(d * m_loc, m_loc)], w_ref[...],
                           preferred_element_type=jnp.float32
                           ).astype(dtype)

        def gemm(a):
            if a["acc_slot"] is None:
                tmp["g"] = block(a["d"])
            else:
                acc[a["acc_slot"]] = block(a["d"])

        def accum(a):
            acc[1 - a["a"]] = recv[a["s"]] + tmp["g"]

        _emit(sched, lax.axis_index(axis), regions={
            "acc": lambda k: acc.at[k[0]],
            "recv": lambda k: recv.at[k[0]],
            "o": lambda k: o_ref,
            "cbuf": lambda k: cbuf,
        }, sems={"send": send_sem, "recv": recv_sem, "csend": csend,
                 "crecv": crecv},
            computes={"gemm": gemm, "accum": accum}, device_id=did)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m_loc, n), dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((2, m_loc, n), dtype),
                        pltpu.VMEM((2, m_loc, n), dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))] + _credit_scratch(),
        name="matmul_ring_rs",
        interpret=interpret,
    )


def ring_matmul_reducescatter(x, w, axis: str, *,
                              interpret: bool | None = None,
                              mesh_axes: tuple | None = None):
    """``matmul_reducescatter``'s contract fused: each destination
    block's GEMM runs while the traveling partial's RDMA is in flight."""
    p = _axis_size(axis)
    mode, mesh_axes = _arm_mesh(rdma_mode(interpret), axis, mesh_axes)
    if p > 1 and mode == "compiled" and not (
            x.dtype == w.dtype and x.shape[0] % p == 0
            and gemm_ring_eligible(
                "rs", x.shape, w.shape, p, jnp.dtype(x.dtype).itemsize,
                jnp.dtype(jnp.result_type(x.dtype, w.dtype)).itemsize)):
        mode = _lax_instead("ring_matmul_reducescatter", interpret,
                            _GEMM_RING_WHY)
    if p == 1 or mode is None or x.dtype != w.dtype or x.shape[0] % p:
        return None
    _record_dispatch("ring_matmul_reducescatter", "rdma", x, axis, p=p,
                     mode=mode)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    return _mm_rs_call(axis, p, tuple(map(int, x.shape)),
                       tuple(map(int, w.shape)), str(out_dtype),
                       mode == "interpret",
                       mesh_axes)(x.astype(out_dtype),
                                  w.astype(out_dtype))
