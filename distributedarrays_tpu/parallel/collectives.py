"""Traced SPMD collectives: the static-pattern half of the SPMD layer.

Where the reference builds ring shifts, halo exchanges, and reductions out
of eager ``sendto``/``recvfrom`` over TCP channels (spmd.jl:145-231; ring
program test/spmd.jl:90-101; stencil docs/src/index.md:160-181), the
TPU-native design compiles the *pattern* once: programs written against
these helpers run under ``jax.shard_map`` over a device mesh, and every
communication lowers to an XLA collective on ICI:

- ``pshift``           — ring neighbor shift       → ``lax.ppermute``
- ``halo_exchange``    — stencil boundary exchange → two ``lax.ppermute``
- ``pbarrier``         — sync point                → ``lax.psum`` of 1
- ``pbcast``           — root broadcast            → masked ``lax.psum``
- ``pgather``          — concat over ranks         → ``lax.all_gather``
- ``preduce``          — all-reduce                → ``lax.psum``/``pmax``…
- ``pall_to_all``      — repartition               → ``lax.all_to_all``

This is exactly the substrate of ring attention / context parallelism
(SURVEY.md §5: "long-context"): a sequence-sharded array ring-shifting
blocks while accumulating is ``pshift`` in a ``lax.fori_loop``.

``run_spmd`` wraps a function into a jitted shard_map program over a mesh —
the compiled analog of the reference's ``spmd(f, ...)`` driver.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import layout as L
from .. import telemetry as _tm

__all__ = [
    "spmd_mesh", "run_spmd", "shard_map_compat", "pshift", "halo_exchange",
    "pbarrier", "pbcast", "pgather", "preduce", "pall_to_all", "axis_rank",
    "axis_size",
]


def shard_map_compat(f: Callable, mesh: Mesh, in_specs, out_specs,
                     check: bool | None = None):
    """``jax.shard_map`` with the package's ``check`` spelling.  Every
    shard_map construction in the package goes through here.
    ``check=None`` keeps the library's own default (the replication/VMA
    check stays ON for call sites that never opted out of it)."""
    kw = {} if check is None else {"check_vma": check}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def _rec(kind: str, x, axis: str, **fields) -> None:
    """Trace-time communication accounting for the compiled collectives.

    These helpers execute inside ``shard_map`` tracing, so the recording
    happens ONCE PER TRACE (compilation), not per device step — flagged
    ``traced=True`` in the journal.  ``x`` is the per-rank block; its
    static shape/dtype give the per-rank payload estimate."""
    if _tm.enabled():
        _tm.record_comm(kind, _tm.nbytes_of(x), axis=axis, traced=True,
                        once_key=f"collective:{kind}:{axis}:{fields}",
                        **fields)


def spmd_mesh(n: int | None = None, axis: str = "p") -> Mesh:
    """A 1-D mesh over the first ``n`` device ranks (default: all)."""
    n = L.nranks() if n is None else int(n)
    return L.mesh_for(list(range(n)), (n,)) if axis == "d0" else \
        Mesh(np.asarray(jax.devices()[:n], dtype=object).reshape(n), (axis,))


def run_spmd(f: Callable, mesh: Mesh, in_specs, out_specs,
             check_vma: bool = False):
    """Compile ``f`` as one SPMD program over ``mesh`` (jit ∘ shard_map).

    The traced analog of the reference's ``spmd(f, args...)`` driver
    (spmd.jl:233-254): every rank runs the same ``f`` on its shard; inside,
    collectives from this module communicate over the mesh axes.
    """
    _tm.count("op.run_spmd")
    # cold path: program construction, not the per-step execution
    _tm.event("jit", "build", fn="run_spmd",  # dalint: disable=DAL003
              once_key=f"run_spmd:{getattr(f, '__name__', f)!s}:"
                       f"{tuple(mesh.shape.items())}")
    return jax.jit(shard_map_compat(f, mesh, in_specs, out_specs,
                                    check=check_vma))


def axis_rank(axis: str):
    """This rank's index along a mesh axis (reference myid() analog)."""
    return lax.axis_index(axis)


def axis_size(axis: str):
    """Static size of a mesh axis from inside a traced program."""
    return lax.axis_size(axis)


def pshift(x, axis: str, shift: int = 1, wrap: bool = True):
    """Ring/neighbor shift along a mesh axis: rank i receives rank
    ``i - shift``'s block (reference: the sendto/recvfrom ring,
    test/spmd.jl:90-101 → one ``lax.ppermute`` over ICI).

    With ``wrap=False`` ranks at the boundary receive zeros.
    """
    n = axis_size(axis)
    if wrap:
        perm = [(i, (i + shift) % n) for i in range(n)]
    else:
        perm = [(i, i + shift) for i in range(n) if 0 <= i + shift < n]
    _rec("ppermute", x, axis, op="pshift", shift=shift)
    return lax.ppermute(x, axis, perm)


def halo_exchange(x, axis: str, halo: int = 1, dim: int = 0,
                  wrap: bool = False):
    """Exchange ``halo``-wide boundary slabs with both mesh-axis neighbors.

    Returns ``(lo, hi)``: the slab arriving from the previous rank (to
    prepend) and from the next rank (to append) along local dim ``dim``.
    This is the 5-point-stencil / Game-of-Life pattern the reference builds
    with eager sends (docs/src/index.md:160-181) — here two ppermutes that
    ride ICI, fused into the surrounding jitted program.
    """
    idx_lo = [slice(None)] * x.ndim
    idx_lo[dim] = slice(0, halo)
    idx_hi = [slice(None)] * x.ndim
    idx_hi[dim] = slice(x.shape[dim] - halo, x.shape[dim])
    # my top slab goes to my previous neighbor (arrives as their `hi`);
    # my bottom slab goes to my next neighbor (arrives as their `lo`)
    hi = pshift(x[tuple(idx_lo)], axis, shift=-1, wrap=wrap)
    lo = pshift(x[tuple(idx_hi)], axis, shift=+1, wrap=wrap)
    return lo, hi


def halo_exchange_2d(x, axes: tuple[str, str], halo: int = 1,
                     wrap: bool = False):
    """Full 2-D halo exchange including corners.

    ``x`` is this rank's (m, n) block on a 2-D mesh ``axes = (row_axis,
    col_axis)``.  Returns the (m + 2h, n + 2h) block padded with the
    neighbors' boundary data (zeros at the global edge when ``wrap`` is
    False).  Corners arrive correctly because the column exchange runs on
    the already row-extended block — the standard two-phase scheme, four
    ``ppermute``s total.
    """
    row_axis, col_axis = axes
    # phase 1: exchange rows along the row axis
    lo, hi = halo_exchange(x, row_axis, halo=halo, dim=0, wrap=wrap)
    xr = jnp.concatenate([lo, x, hi], axis=0)          # (m + 2h, n)
    # phase 2: exchange columns of the extended block along the col axis
    lo2, hi2 = halo_exchange(xr, col_axis, halo=halo, dim=1, wrap=wrap)
    return jnp.concatenate([lo2, xr, hi2], axis=1)     # (m + 2h, n + 2h)


def pbarrier(axis: str):
    """Synchronization point: all ranks must reach it before any proceeds
    (reference barrier, spmd.jl:159-184).  In a compiled SPMD program this
    is a collective dependency — a psum of 1."""
    _rec("psum", jnp.ones((), jnp.int32), axis, op="pbarrier")
    return lax.psum(jnp.ones((), jnp.int32), axis)


def pbcast(x, axis: str, root: int = 0):
    """Every rank gets root's block (reference bcast, spmd.jl:186-196):
    mask + all-reduce, which XLA lowers to an ICI broadcast."""
    me = lax.axis_index(axis)
    masked = jnp.where(me == root, x, jnp.zeros_like(x))
    _rec("psum", x, axis, op="pbcast", root=root)
    return lax.psum(masked, axis)


def pgather(x, axis: str, tiled: bool = False, dim: int = 0):
    """Concatenate every rank's block, pid-ordered (reference gather,
    spmd.jl:214-231) → ``lax.all_gather``.  ``dim`` picks the local axis
    the blocks stack along (the reshard planner gathers along the
    previously-sharded dim, not always dim 0)."""
    _rec("all_gather", x, axis, op="pgather")
    return lax.all_gather(x, axis, axis=dim, tiled=tiled)


_PREDUCERS = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin,
              "mean": lax.pmean}


def preduce(x, axis: str, op: str = "sum"):
    """All-reduce over a mesh axis (two-phase mapreduce analog,
    mapreduce.jl:29-35, but over ICI)."""
    _rec("psum" if op in ("sum", "mean") else f"p{op}", x, axis,
         op="preduce")
    return _PREDUCERS[op](x, axis)


def pall_to_all(x, axis: str, split_dim: int, concat_dim: int,
                tiled: bool = True):
    """All-to-all repartition (the scatter phase of the reference's sample
    sort, sort.jl:24-55) → ``lax.all_to_all``."""
    _rec("all_to_all", x, axis, op="pall_to_all")
    return lax.all_to_all(x, axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=tiled)
