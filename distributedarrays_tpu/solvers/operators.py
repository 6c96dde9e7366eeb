"""Distributed matrix-free operators for the iterative solver suite.

A :class:`LinearOperator` is the solver-facing contract: ``apply(x:
DArray) -> DArray`` maps a row-sharded vector to a row-sharded vector.
The reference solves ``A \\ b`` by shipping whole blocks between workers;
here the operator IS the communication schedule, and each concrete
operator picks the cheapest one the layout allows:

- :class:`DenseOperator` — one sharded GEMV through ``ops.linalg.matmul``
  (XLA/GSPMD inserts the all-gather of ``x`` over ICI).
- :class:`SparseOperator` — row-sharded BCOO SpMV built on
  ``ops.sparse.ddata_bcoo``: each rank's block splits into a local
  *diagonal* part (columns it already owns) and a *halo* part (columns
  within ``h`` rows of its range).  ``apply`` dispatches the diagonal
  SpMV first — JAX's async dispatch overlaps it with the halo
  ``ppermute`` program that ships the needed remote vector slices — then
  finishes with the halo SpMV over the extended slab.  Only ``2*h``
  vector elements per neighbor cross ICI; the matrix never moves.
- :class:`StencilOperator` — the 2-D Poisson (5-point) operator as one
  ``models.stencil`` halo-exchange program; "vectors" are the 2-D grids
  themselves.

Every operator re-derives its partition from the live device set on
``prepare(live_ranks)`` so a mid-solve ``elastic.shrink()`` (device loss)
leaves the solver with a working operator on the survivors.
"""

from __future__ import annotations

import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import layout as L
from .. import telemetry as _tm
from ..darray import DArray, _wrap_global, dzeros
from ..ops.mapreduce import samedist
from ..ops.sparse import ddata_bcoo, jsparse
from ..parallel.collectives import halo_exchange, shard_map_compat

__all__ = ["LinearOperator", "DenseOperator", "SparseOperator",
           "StencilOperator", "POISSON_WEIGHTS", "poisson2d_dense"]


POISSON_WEIGHTS = ((0.0, -1.0, 0.0), (-1.0, 4.0, -1.0), (0.0, -1.0, 0.0))


def poisson2d_dense(nx: int, ny: int, scale: float = 1.0) -> np.ndarray:
    """Dense (nx*ny, nx*ny) matrix of the 5-point Poisson operator with
    zero Dirichlet boundary — the oracle for :class:`StencilOperator`
    (``A = scale * (kron(Tx, I) + kron(I, Ty))``)."""
    def trid(n):
        return (2.0 * np.eye(n) - np.eye(n, k=1) - np.eye(n, k=-1))
    A = np.kron(trid(nx), np.eye(ny)) + np.kron(np.eye(nx), trid(ny))
    return (scale * A).astype(np.float32)


class LinearOperator:
    """Protocol for distributed matrix-free operators.

    ``shape``/``dtype`` describe the square system; ``vector_layout()``
    is the row layout ``apply`` expects its operand on (solvers re-seat
    their persistent vectors there after an elastic shrink); ``prepare``
    re-derives internal structure for a new live rank set.
    """

    shape: tuple[int, ...]
    dtype = jnp.float32

    def apply(self, x: DArray) -> DArray:
        raise NotImplementedError

    def prepare(self, live_ranks: list[int]) -> None:  # noqa: ARG002
        """Adapt to the live device set (default: nothing to rebuild)."""

    def vector_layout(self) -> tuple[list[int], tuple[int, ...]]:
        raise NotImplementedError

    def new_vector(self) -> DArray:
        """A zeroed solution/workspace vector on the preferred layout."""
        procs, dist = self.vector_layout()
        return dzeros(self.shape[:1] if len(self.shape) == 1 else
                      self._vector_dims(), dtype=self.dtype, procs=procs,
                      dist=list(dist))

    def _vector_dims(self) -> tuple[int, ...]:
        return (self.shape[0],)

    def align(self, x: DArray) -> DArray:
        """A copy of ``x`` on the operator's preferred layout (the input
        is left untouched); aligned inputs come back via the free
        shared-buffer samedist path."""
        like = self.new_vector()
        try:
            return samedist(x, like)
        finally:
            like.close()


# ---------------------------------------------------------------------------
# dense: one sharded GEMV
# ---------------------------------------------------------------------------


class DenseOperator(LinearOperator):
    """Row-sharded dense operator: ``apply`` is ``ops.linalg.matmul``'s
    matvec path (result row-sharded like ``A``).  ``A`` may be a host
    array (distributed here) or an existing DArray (borrowed — the
    caller keeps ownership)."""

    def __init__(self, A, *, procs=None):
        from ..darray import distribute
        if isinstance(A, DArray):
            self._A, self._owned = A, False
        else:
            A = np.asarray(A, dtype=np.float32)
            n = len(procs) if procs is not None else L.nranks()
            p = _largest_divisor(A.shape[0], n)
            use = list(procs)[:p] if procs is not None else L.all_ranks()[:p]
            self._A = distribute(A, procs=use, dist=[p, 1])
            self._owned = True
        if self._A.ndim != 2 or self._A.dims[0] != self._A.dims[1]:
            raise ValueError(f"square operator required, got {self._A.dims}")
        self.shape = self._A.dims
        self.dtype = self._A.dtype

    def apply(self, x: DArray) -> DArray:
        from ..ops.linalg import matmul
        return matmul(self._A, x)

    def vector_layout(self):
        procs = [int(p) for p in self._A.pids.flat]
        return procs, (self._A.pids.shape[0],)

    def close(self):
        if self._owned:
            self._A.close()


# ---------------------------------------------------------------------------
# sparse: BCOO SpMV with halo exchange of remote vector slices
# ---------------------------------------------------------------------------


def _largest_divisor(n: int, cap: int) -> int:
    for p in range(min(n, cap), 0, -1):
        if n % p == 0:
            return p
    return 1


@functools.lru_cache(maxsize=32)
def _halo_ext_jit(mesh, halo: int):
    """Compiled halo program: each rank's vector block comes back extended
    to ``[lo | block | hi]`` — two ``ppermute``s over ICI, zero slabs at
    the open ends (which is exactly the zero-Dirichlet/out-of-range
    contract the halo column blocks are built against)."""
    ax = mesh.axis_names[0]

    def prog(xb):
        lo, hi = halo_exchange(xb, ax, halo=halo, dim=0, wrap=False)
        return jnp.concatenate([lo, xb, hi], axis=0)

    return jax.jit(shard_map_compat(prog, mesh=mesh, in_specs=P(ax),
                                    out_specs=P(ax), check=False))


@functools.lru_cache(maxsize=None)
def _spmv_pair_jit():
    # one compiled kernel for every rank: nse is padded uniform across
    # ranks so the (diag, halo) matvec traces once per partition shape
    return jax.jit(lambda d, h, x, e: d @ x + h @ e)


@functools.lru_cache(maxsize=None)
def _spmv_diag_jit():
    return jax.jit(lambda d, x: d @ x)


def _to_coo(A):
    """Normalize dense/scipy/BCOO-DData input to host COO triples."""
    try:
        import scipy.sparse as sps
    except Exception:  # pragma: no cover - scipy is baked into the image
        sps = None
    if sps is not None and sps.issparse(A):
        coo = A.tocoo()
        return (np.asarray(coo.row), np.asarray(coo.col),
                np.asarray(coo.data, dtype=np.float32), A.shape)
    A = np.asarray(A, dtype=np.float32)
    r, c = np.nonzero(A)
    return r, c, A[r, c], A.shape


class SparseOperator(LinearOperator):
    """Row-sharded BCOO SpMV.  Construction routes a DArray operand
    through ``ops.sparse.ddata_bcoo`` (per-rank BCOO parts), then splits
    each rank's block into the local-diagonal part and the halo part in
    *extended* coordinates; host COO triples are kept so ``prepare`` can
    re-partition onto survivors after an elastic shrink.

    Columns must reach at most one neighbor block away (banded systems;
    bandwidth ≤ rows-per-rank) — the halo program exchanges with adjacent
    mesh ranks only.  A wider reach raises at partition time.
    """

    def __init__(self, A, *, procs=None):
        if jsparse is None:  # pragma: no cover - jsparse ships with jax
            raise ImportError("jax.experimental.sparse is unavailable")
        if isinstance(A, DArray):
            parts = ddata_bcoo(A)
            try:
                # each part is one chunk of A's (possibly 2-D) grid with
                # chunk-local indices; the chunk's cuts give the offsets
                rows, cols, vals = [], [], []
                for gidx in np.ndindex(*A.pids.shape):
                    part = parts.localpart(int(A.pids[gidx]))
                    idx = np.asarray(part.indices)
                    r0 = int(A.cuts[0][gidx[0]])
                    c0 = int(A.cuts[1][gidx[1]]) if A.pids.ndim > 1 else 0
                    rows.append(idx[:, 0] + r0)
                    cols.append(idx[:, 1] + c0)
                    vals.append(np.asarray(part.data, dtype=np.float32))
                self._coo = (np.concatenate(rows), np.concatenate(cols),
                             np.concatenate(vals), A.dims)
            finally:
                parts.close()
        else:
            self._coo = _to_coo(A)
        r, c, v, shp = self._coo
        if len(shp) != 2 or shp[0] != shp[1]:
            raise ValueError(f"square operator required, got {shp}")
        keep = v != 0
        self._coo = (r[keep], c[keep], v[keep], shp)
        self.shape = tuple(int(s) for s in shp)
        self.dtype = jnp.float32
        self.nnz = int(keep.sum())
        self._procs_hint = list(procs) if procs is not None else None
        self._lock = threading.Lock()
        self._ranks: tuple[int, ...] | None = None
        self._partition(self._procs_hint or L.all_ranks())

    # -- partitioning ------------------------------------------------------

    def _partition(self, ranks: list[int]) -> None:
        n = self.shape[0]
        rows, cols, vals, _ = self._coo
        reach = int(np.max(np.abs(rows - cols))) if len(rows) else 0
        p = _largest_divisor(n, len(ranks))
        m = n // p
        while p > 1 and reach > m:
            # bandwidth wider than a block: coarsen the partition until
            # each halo reaches at most the adjacent block
            p = _largest_divisor(n, p - 1)
            m = n // p
        if reach > m:
            raise ValueError(
                f"bandwidth {reach} exceeds rows-per-rank {m}: halo SpMV "
                "exchanges with adjacent ranks only")
        self._p, self._m, self._h = p, m, max(reach, 0)
        self._pids = [int(x) for x in ranks[:p]]
        devs = np.asarray(jax.devices(), dtype=object)[self._pids]
        order = np.argsort(rows, kind="stable")
        rows, cols, vals = rows[order], cols[order], vals[order]
        bounds = np.searchsorted(rows, np.arange(0, n + 1, m))
        diag, halo = [], []
        for k in range(p):
            lo, hi = bounds[k], bounds[k + 1]
            rr = rows[lo:hi] - k * m
            cc = cols[lo:hi]
            vv = vals[lo:hi]
            local = (cc >= k * m) & (cc < (k + 1) * m)
            diag.append((rr[local], cc[local] - k * m, vv[local]))
            # halo part in extended coordinates [0, m + 2h): the slab
            # arriving from the previous rank occupies [0, h)
            halo.append((rr[~local], cc[~local] - k * m + self._h,
                         vv[~local]))
        self._diag = [_pad_bcoo(d, (m, m), _max_nse(diag), devs[k])
                      for k, d in enumerate(diag)]
        self._halo = [_pad_bcoo(hp, (m, m + 2 * self._h), _max_nse(halo),
                                devs[k])
                      for k, hp in enumerate(halo)]
        self._mesh = L.mesh_for(self._pids, (p,))
        self._ranks = tuple(ranks)

    def prepare(self, live_ranks: list[int]) -> None:
        with self._lock:
            live = [int(r) for r in live_ranks]
            if self._procs_hint is not None:
                live = [r for r in self._procs_hint if r in live] or live
            if tuple(live) != self._ranks:
                self._partition(live)

    def vector_layout(self):
        return list(self._pids), (self._p,)

    # -- apply -------------------------------------------------------------

    def apply(self, x: DArray) -> DArray:
        n, p, h = self.shape[0], self._p, self._h
        owned = None
        if [int(q) for q in x.pids.flat] != self._pids or x.pids.size != p:
            owned = x = self.align(x)
        try:
            with _tm.span("solver.spmv", op="bcoo", n=n, ranks=p):
                shards = {s.device: s.data
                          for s in x.garray.addressable_shards}
                xs = [shards[d] for d in self._mesh.devices.flat]
                if h == 0 or p == 1:
                    ys = [_spmv_diag_jit()(self._diag[k], xs[k])
                          for k in range(p)]
                    if p == 1 and self._halo[0].nse:
                        # single-rank extended part degenerates to local
                        ext = jnp.pad(xs[0], (h, h))
                        ys[0] = ys[0] + self._halo[0] @ ext
                else:
                    # local-diagonal SpMV dispatches first; JAX's async
                    # dispatch overlaps it with the halo ppermute program
                    y_diag = [_spmv_diag_jit()(self._diag[k], xs[k])
                              for k in range(p)]
                    ext = _halo_ext_jit(self._mesh, h)(x.garray)
                    eshards = {s.device: s.data
                               for s in ext.addressable_shards}
                    es = [eshards[d] for d in self._mesh.devices.flat]
                    ys = [y_diag[k] + self._halo[k] @ es[k]
                          for k in range(p)]
                sharding = L.sharding_for(self._pids, (p,), (n,))
                ys = [jax.device_put(y, d)
                      for y, d in zip(ys, self._mesh.devices.flat)]
                garr = jax.make_array_from_single_device_arrays(
                    (n,), sharding, ys)
                return _wrap_global(garr, procs=self._pids, dist=[p])
        finally:
            if owned is not None:
                owned.close()


def _max_nse(triples) -> int:
    return max(1, max(len(t[2]) for t in triples))


def _pad_bcoo(triple, shape, nse, device):
    """Build a rank's BCOO block padded to the partition-wide ``nse`` so
    every rank shares one compiled matvec (pad entries are explicit
    zeros at (0, 0); BCOO sums duplicates)."""
    rr, cc, vv = triple
    pad = nse - len(vv)
    idx = np.zeros((nse, 2), dtype=np.int32)
    dat = np.zeros((nse,), dtype=np.float32)
    idx[:len(vv), 0] = rr
    idx[:len(vv), 1] = cc
    dat[:len(vv)] = vv
    mat = jsparse.BCOO((jnp.asarray(dat), jnp.asarray(idx)), shape=shape)
    del pad
    return jax.device_put(mat, device)


# ---------------------------------------------------------------------------
# stencil: 2-D Poisson through the models.stencil halo program
# ---------------------------------------------------------------------------


class StencilOperator(LinearOperator):
    """5-point Poisson operator ``A·x = scale * (4x - Σ neighbors)`` with
    zero Dirichlet boundary, applied as ONE ``models.stencil`` program
    (interior update fused around two halo ``ppermute``s).  Vectors are
    the row-sharded 2-D grids themselves; the dense oracle is
    :func:`poisson2d_dense` on the flattened grid."""

    def __init__(self, grid: tuple[int, int], *, scale: float = 1.0,
                 procs=None):
        nx, ny = int(grid[0]), int(grid[1])
        self.grid = (nx, ny)
        self.shape = (nx * ny, nx * ny)
        self.scale = float(scale)
        self.dtype = jnp.float32
        self._procs_hint = list(procs) if procs is not None else None
        self._pids: list[int] = []
        self.prepare(self._procs_hint or L.all_ranks())

    @property
    def weights(self):
        s = self.scale
        return tuple(tuple(s * w for w in row) for row in POISSON_WEIGHTS)

    def prepare(self, live_ranks: list[int]) -> None:
        live = [int(r) for r in live_ranks]
        if self._procs_hint is not None:
            live = [r for r in self._procs_hint if r in live] or live
        p = _largest_divisor(self.grid[0], len(live))
        self._pids = live[:p]

    def vector_layout(self):
        return list(self._pids), (len(self._pids), 1)

    def _vector_dims(self):
        return self.grid

    def apply(self, x: DArray) -> DArray:
        from ..models.stencil import stencil3x3
        owned = None
        if ([int(q) for q in x.pids.flat] != self._pids
                or tuple(x.dims) != self.grid):
            owned = x = self.align(x)
        try:
            nx, ny = self.grid
            with _tm.span("solver.spmv", op="stencil", n=nx * ny,
                          ranks=len(self._pids)):
                return stencil3x3(x, self.weights, iters=1)
        finally:
            if owned is not None:
                owned.close()
