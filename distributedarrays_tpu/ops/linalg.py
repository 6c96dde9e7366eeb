"""Distributed dense linear algebra over DArrays.

TPU-native re-design of /root/reference/src/linalg.jl (311 LoC).  The
reference hand-schedules a SUMMA-like block GEMM: the caller slices B tiles
and ships them inside remotecall closures to A-tile owners, partial products
travel as Futures, and accumulation is serialized per C tile with an `add!`
loop (linalg.jl:189-253) — the caller is a scalability bottleneck.

On TPU the entire GEMM is ONE jitted ``jnp.matmul`` over sharded operands:
operands are laid out on the result's 2-D mesh (rows of A on axis ``i``,
columns of B on axis ``k``), and XLA/GSPMD inserts the all-gathers /
reduce-scatters over ICI that the hand-written tile loop emulated over TCP.
The MXU sees large contiguous tiles; nothing round-trips the host.

API parity: ``axpy_`` (linalg.jl:24-34), ``ddot`` (36-45), ``dnorm``
(47-52), ``rmul_``/``lmul_`` incl. Diagonal scaling (54-59, 169-187),
``matmul``/``mul_into`` for matvec (78-122) and matmat (189-311) with the
reference's cuts-compatibility errors, ``dtranspose``/``dadjoint`` (1-17).
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import layout as L
from .. import telemetry as _tm
from ..darray import DArray, SubDArray, _wrap_global, distribute
from .broadcast import _unwrap, elementwise
from ..parallel import reshard as _rs
from ..parallel.collectives import shard_map_compat

__all__ = [
    "axpy_", "ddot", "dnorm", "rmul_", "lmul_", "lmul_diag", "rmul_diag",
    "matmul", "mul_into", "dtranspose", "dadjoint", "tune_matmul_impl",
    "tune_matmul_impl_dist", "tune_matmul_impl_summa", "dmatmul_int8",
]


# ---------------------------------------------------------------------------
# BLAS-1
# ---------------------------------------------------------------------------


def _axpy_fn(a, x, y):
    return a * x + y


def axpy_(a, x, y: DArray) -> DArray:
    """y ← a*x + y in place (reference axpy!, linalg.jl:24-34).

    The scalar rides as a traced argument so the jit cache is keyed on the
    stable ``_axpy_fn`` — no per-call recompiles."""
    if np.shape(_unwrap(x)) != tuple(y.dims):
        # reference throws DimensionMismatch (linalg.jl:26-28)
        raise ValueError(f"axpy_: x dims {np.shape(_unwrap(x))} != y dims {y.dims}")
    return elementwise(_axpy_fn, jnp.asarray(a, y.dtype), x, y, out=y)


@functools.lru_cache(maxsize=None)
def _dot_jit():
    return jax.jit(lambda a, b: jnp.vdot(a, b))


def ddot(x, y):
    """Distributed dot product (reference dot, linalg.jl:36-45): per-device
    partial dots + psum, emitted by XLA from one jnp.vdot."""
    xv, yv = _unwrap(x), _unwrap(y)
    if np.shape(xv) != np.shape(yv):
        raise ValueError(f"ddot: dims {np.shape(xv)} != {np.shape(yv)}")
    return _dot_jit()(xv, yv)


@functools.lru_cache(maxsize=64)
def _norm_jit(p):
    return jax.jit(lambda a: jnp.linalg.norm(jnp.ravel(a), ord=p))


def dnorm(x, p=2):
    """Vector p-norm of the flattened array (reference norm, linalg.jl:47-52:
    norm of per-worker norms)."""
    return _norm_jit(p)(_unwrap(x))


def rmul_(d: DArray, s) -> DArray:
    """d ← d * s in place (reference rmul!, linalg.jl:54-59)."""
    return elementwise(jnp.multiply, d, s, out=d)


def lmul_(s, d: DArray) -> DArray:
    """d ← s * d in place (reference lmul!)."""
    return elementwise(jnp.multiply, s, d, out=d)


def lmul_diag(diag, d: DArray) -> DArray:
    """d ← Diagonal(diag) * d in place: scale row i by diag[i] (reference
    lmul!(D::Diagonal, DA), linalg.jl:169-177 — the diag slice scatter via
    DestinationSerializer becomes sharding propagation)."""
    v = _unwrap(diag)
    if np.shape(v) != (d.dims[0],):
        raise ValueError(f"diag length {np.shape(v)} != rows {d.dims[0]}")
    return elementwise(jnp.multiply, jnp.reshape(v, (-1, 1)), d, out=d)


def rmul_diag(d: DArray, diag) -> DArray:
    """d ← d * Diagonal(diag) in place: scale column j by diag[j] (reference
    rmul!(DA, D::Diagonal), linalg.jl:179-187)."""
    v = _unwrap(diag)
    if np.shape(v) != (d.dims[-1],):
        raise ValueError(f"diag length {np.shape(v)} != cols {d.dims[-1]}")
    return elementwise(jnp.multiply, d, jnp.reshape(v, (1, -1)), out=d)


# ---------------------------------------------------------------------------
# transpose / adjoint (reference linalg.jl:1-17)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _transpose_jit(conj):
    if conj:
        return jax.jit(lambda a: jnp.conj(jnp.swapaxes(a, -1, -2)))
    return jax.jit(lambda a: jnp.swapaxes(a, -1, -2))


def _transposed_layout(d: DArray):
    procs = [int(p) for p in d.pids.T.flat]
    dist = list(reversed(d.pids.shape))
    return procs, dist


def dtranspose(d: DArray) -> DArray:
    """Materialized transpose with the reversed layout (reference
    copy(::Transpose{T,DMatrix}), linalg.jl:10-17: each worker pulls its
    transposed global slice — here one XLA transpose + resharding)."""
    if d.ndim != 2:
        raise ValueError("dtranspose expects a 2-D DArray")
    procs, dist = _transposed_layout(d)
    return _wrap_global(_transpose_jit(False)(d.garray), procs=procs, dist=dist)


def dadjoint(d: DArray) -> DArray:
    """Materialized conjugate transpose (reference copy(::Adjoint),
    linalg.jl:1-8)."""
    if d.ndim != 2:
        raise ValueError("dadjoint expects a 2-D DArray")
    procs, dist = _transposed_layout(d)
    return _wrap_global(_transpose_jit(True)(d.garray), procs=procs, dist=dist)


DArray.T = property(dtranspose)


# ---------------------------------------------------------------------------
# GEMM / matvec
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _matmul_jit(out_sharding, mode: str):
    if mode == "ab":            # alpha*A@B + beta*C
        def fn(a, b, c, alpha, beta):
            return alpha * jnp.matmul(a, b) + beta * c
    elif mode == "alpha":       # fused alpha*A@B (no extra HBM pass)
        def fn(a, b, alpha):
            return alpha * jnp.matmul(a, b)
    else:
        def fn(a, b):
            return jnp.matmul(a, b)
    return jax.jit(fn, out_shardings=out_sharding)


def _gemm_layout(A: DArray, B):
    """Result layout for C = A*B: C's row chunking follows A's row grid and
    its column chunking follows B's column grid, clipped to the available
    ranks (reference `*` allocation, linalg.jl:261-311)."""
    ra = A.pids.shape[0]
    cb = B.pids.shape[1] if isinstance(B, DArray) and B.pids.ndim == 2 else 1
    procs = [int(p) for p in A.pids.flat]
    extra = [p for p in L.all_ranks() if p not in procs]
    procs = procs + extra
    while ra * cb > len(procs) and cb > 1:
        cb -= 1
    while ra * cb > len(procs) and ra > 1:
        ra -= 1
    return procs, (ra, cb)


def _impl_key(*parts):
    """Registry key for GEMM implementation choices: shape/dtype parts
    PLUS the backend and device kind — a winner measured on one platform
    (CPU dev box, v4, v5e...) must never drive dispatch on another, even
    through a shared persisted cache."""
    from ..utils import autotune
    return autotune.device_key_for(*parts)


def _impl_choice(m, n, k, a_dtype, b_dtype):
    """Consult the autotune registry for the GEMM implementation to use
    for this shape: ``"pallas"`` (hand-owned Pallas schedule) or ``"jnp"``
    (XLA).  Default is ``"jnp"`` — the owned schedules are promoted only
    by a measured win banked by ``tune_matmul_impl``, never by
    assumption (VERDICT round-3 item 4)."""
    from ..utils import autotune
    return autotune.get(
        "matmul_impl", _impl_key(m, n, k, a_dtype, b_dtype)) or "jnp"


def _try_pallas_gemm(av, bv, out_dtype):
    """Single-device Pallas GEMM attempt; returns None when ineligible
    (the caller falls back to the jnp path).  Eligibility: both operands
    resident on ONE device (the autotuned kernel owns the whole GEMM — no
    GSPMD partitioning to fight), float dtypes, an MXU-aligned tiling."""
    if len(av.sharding.device_set) != 1 or len(bv.sharding.device_set) != 1:
        return None
    if not (jnp.issubdtype(av.dtype, jnp.floating)
            and jnp.issubdtype(bv.dtype, jnp.floating)):
        return None
    from .pallas_gemm import pallas_matmul
    try:
        res = pallas_matmul(av, bv)
    except ValueError:      # no aligned tiling for these shapes
        return None
    return res.astype(out_dtype)


def _ring_ag_eligible(A: DArray, B, procs, dist):
    """The 1-D TP shape the overlapped ring serves: A row-chunked on a
    (p,1) grid, B contraction(row)-chunked on the SAME (p,1) rank list,
    result row-chunked like A (which is both `_gemm_layout`'s allocation
    and the mul_into cuts contract).  Plain GSPMD all-gathers B then
    multiplies; `allgather_matmul_rhs` pipelines the gather into the
    per-chunk matmuls over ICI."""
    if not isinstance(B, DArray):
        return False
    p = A.pids.shape[0] if A.pids.ndim == 2 else 0
    if p < 2 or A.pids.shape != (p, 1) or B.pids.shape != (p, 1):
        return False
    aprocs = [int(q) for q in A.pids.flat]
    if [int(q) for q in B.pids.flat] != aprocs:
        return False
    if list(dist) != [p, 1] or [int(q) for q in procs[:p]] != aprocs:
        return False
    # `_ring_ag_gemm` repositions operands with eager device_put, which
    # cannot move bytes between hosts — a persisted ring_ag promotion
    # (the autotune key matches across single- and multi-controller runs
    # of the same shapes) must not strand a process-spanning matmul
    # (ADVICE round-4); GSPMD handles that case.
    if not (A.garray.is_fully_addressable and B.garray.is_fully_addressable):
        return False
    # even chunking everywhere the ring assumes it
    m, k = A.dims
    return m % p == 0 and k % p == 0 and not (A._padded or B._padded)


@functools.lru_cache(maxsize=None)
def _ring_ag_jit(procs, p, out_dtype_str, rdma=None):
    """One shard_map program for the contraction-sharded-B GEMM: ring
    all-gather of B pipelined into the per-chunk matmuls.  The mesh here
    is the canonical 1-D mesh and this is a forward-only inference path,
    so the fused Pallas RDMA ring is armed (``rdma`` carries the
    ``rdma_mode()`` decision into the cache key; ineligible shapes keep
    the ``lax`` ring via the kernel's own dispatch gate)."""
    from .collective_matmul import allgather_matmul_rhs
    mesh = L.mesh_for(procs, (p,))
    ax = mesh.axis_names[0]

    def prog(a, b):
        return allgather_matmul_rhs(
            a, b, ax, rdma=bool(rdma),
            interpret=(rdma == "interpret") if rdma else None,
        ).astype(out_dtype_str)

    # pallas_call has no shard_map replication rule: the RDMA variant
    # must opt out of the check (the XLA variant keeps the default)
    shm = shard_map_compat(prog, mesh=mesh,
                        in_specs=(P(ax, None), P(ax, None)),
                        out_specs=P(ax, None),
                        check=False if rdma else None)
    return mesh, ax, jax.jit(shm)


def _ring_ag_gemm(A: DArray, B: DArray, out_dtype):
    """Run the eligible TP GEMM as the overlapped ring program; returns
    the (p,1)-row-sharded result array."""
    p = A.pids.shape[0]
    procs = tuple(int(q) for q in A.pids.flat)
    from . import pallas_collectives as _pc
    rdma = _pc.rdma_mode()
    m, k = (int(d) for d in A.dims)
    n = int(B.dims[1])
    isz = np.dtype(A.dtype).itemsize
    osz = np.dtype(out_dtype).itemsize
    if rdma == "compiled" and not (
            A.dtype == B.dtype and _pc.gemm_ring_eligible(
                "ag_rhs", (k // p, n), (m // p, k), p, isz, osz)):
        # decided HERE from the shapes, and said on the span: demanding
        # the compiled fused kernel for operands its scoped-VMEM gate
        # refuses would only be counted as a degradation further down
        rdma = None
    with _tm.span("matmul.ring_ag", ranks=p,
                  dispatch="rdma" if rdma else "xla",
                  shape=[m, k, n], dtype=str(A.dtype)):
        mesh, ax, fn = _ring_ag_jit(procs, p, str(jnp.dtype(out_dtype)),
                                    rdma)
        with _tm.span("matmul.ring_ag.place", _journal=False):
            sh_in = NamedSharding(mesh, P(ax, None))
            a = _rs.reshard(A.garray, sh_in, op="matmul_place")
            b = _rs.reshard(B.garray, sh_in, op="matmul_place")
        with _tm.span("matmul.ring_ag.compute", _journal=False):
            if not rdma:
                return fn(a, b)
            try:
                return fn(a, b)
            except Exception as e:
                # the RDMA arm must never cost correctness: rebuild the
                # lax ring, loudly once per failure signature
                from ..utils.debug import warn_once
                warn_once(f"ring_ag:rdma:{type(e).__name__}",
                          f"ring_ag RDMA path failed "
                          f"({type(e).__name__}: {e}); falling back to "
                          f"the XLA ppermute ring")
                _, _, fn = _ring_ag_jit(procs, p,
                                        str(jnp.dtype(out_dtype)), None)
                return fn(a, b)


def _dist_impl_choice(m, n, k, p, a_dtype, b_dtype):
    """Registry choice for the distributed GEMM: ``"ring_ag"`` (overlapped
    ring) or ``"jnp"`` (GSPMD).  Default ``"jnp"`` — same promotion-by-
    measurement policy as `_impl_choice` (XLA's own SPMD pass can overlap
    too, so the ring must earn its place on the target topology); banked
    by ``tune_matmul_impl_dist``."""
    from ..utils import autotune
    return autotune.get(
        "matmul_impl_dist", _impl_key(m, n, k, p, a_dtype, b_dtype)) or "jnp"


def _grid2d_ok(A: DArray, B):
    """Shared 2-D-grid eligibility core for the owned tile schedules
    (``matmul``'s summa/cannon dispatch AND ``dmatmul_int8``'s grid
    branch — one owner, so the rules cannot diverge): both operands
    DArrays on the SAME ``(r, c)`` rank grid (identical flat rank
    order), unpadded (⇒ even chunks on every axis), fully addressable
    (eager device_put cannot move bytes between hosts — same guard as
    ``_ring_ag_eligible``; ADVICE round-4).  Returns ``(r, c)`` with
    ``r * c >= 2`` ranks, or ``None``."""
    if not isinstance(B, DArray):
        return None
    if A.pids.ndim != 2 or B.pids.ndim != 2:
        return None
    r, c = A.pids.shape
    if r * c < 2 or B.pids.shape != (r, c):
        return None
    if [int(q) for q in B.pids.flat] != [int(q) for q in A.pids.flat]:
        return None
    if A._padded or B._padded:
        return None
    if not (A.garray.is_fully_addressable and B.garray.is_fully_addressable):
        return None
    return r, c


def _square_grid_ok(A: DArray, B):
    """``_grid2d_ok`` restricted to square ``(g, g)`` grids with
    ``g >= 2`` — the Cannon-ring shapes.  Returns ``g`` or ``None``."""
    rc = _grid2d_ok(A, B)
    if rc is None or rc[0] != rc[1] or rc[0] < 2:
        return None
    return rc[0]


def _summa_eligible(A: DArray, B, procs, dist):
    """The 2-D-grid shape the owned tile schedules serve: A and B on the
    SAME ``(r, c)`` rank grid, result on that grid too — the reference's
    tile-grid ``mul!`` (linalg.jl:189-253) and BASELINE config 3 (16384²
    on 2×2).  Square grids run the Cannon double ring; rectangular ones
    the masked-psum SUMMA panel schedule.  Plain GSPMD SUMMAs this
    itself; the owned schedules must earn their place by measurement
    (``_summa_impl_choice``).  Returns ``(r, c)`` or ``None``."""
    rc = _grid2d_ok(A, B)
    if rc is None:
        return None
    r, c = rc
    # degenerate 1-D grids belong to the ring-AG/GSPMD tiers
    if r < 2 or c < 2:
        return None
    aprocs = [int(q) for q in A.pids.flat]
    if list(dist) != [r, c] or [int(q) for q in procs[:r * c]] != aprocs:
        return None
    # even chunking everywhere the schedules assume it: m by r, n by c,
    # k by lcm(r, c) (A splits k over columns, B over rows; the SUMMA
    # panel width is k/lcm — for square grids lcm == g)
    m, k = A.dims
    n = B.dims[1]
    if m % r or n % c or k % math.lcm(r, c):
        return None
    return rc


def _summa_impl_choice(m, n, k, r, c, a_dtype, b_dtype):
    """Registry choice for the 2-D-grid GEMM: ``"summa"`` (the owned
    tile schedule — Cannon double ring on square grids, masked-psum
    SUMMA panels on rectangular ones) or ``"jnp"`` (GSPMD).  Shares the
    ``matmul_impl_dist`` registry with the 1-D ring, fenced by an
    ``rxc`` grid tag in the key so a (p,1) promotion never fires the
    2-D schedule or vice versa."""
    from ..utils import autotune
    return autotune.get(
        "matmul_impl_dist",
        _impl_key(m, n, k, f"{r}x{c}", a_dtype, b_dtype)) or "jnp"


@functools.lru_cache(maxsize=None)
def _summa_jit(procs, r, c, out_dtype_str):
    """One shard_map program for the 2-D-grid GEMM: Cannon pre-skew +
    overlapped double panel ring on square grids (``cannon_matmul``),
    masked-psum SUMMA panels on rectangular ones (``summa_matmul``)."""
    from .collective_matmul import cannon_matmul, summa_matmul
    mesh = L.mesh_for(procs, (r, c))
    ax_r, ax_c = mesh.axis_names

    if r == c:
        def prog(a, b):
            return cannon_matmul(a, b, ax_r, ax_c).astype(out_dtype_str)
    else:
        def prog(a, b):
            return summa_matmul(a, b, ax_r, ax_c).astype(out_dtype_str)

    shm = shard_map_compat(prog, mesh=mesh,
                        in_specs=(P(ax_r, ax_c), P(ax_r, ax_c)),
                        out_specs=P(ax_r, ax_c))
    return mesh, (ax_r, ax_c), jax.jit(shm)


def _summa_gemm(A: DArray, B: DArray, out_dtype):
    """Run the eligible 2-D-grid GEMM as the owned tile program; returns
    the (r,c)-block-sharded result array."""
    r, c = A.pids.shape
    procs = tuple(int(q) for q in A.pids.flat)
    with _tm.span("matmul.summa", grid=f"{r}x{c}", ranks=r * c):
        mesh, (ax_r, ax_c), fn = _summa_jit(procs, r, c,
                                            str(jnp.dtype(out_dtype)))
        sh = NamedSharding(mesh, P(ax_r, ax_c))
        with _tm.span("matmul.summa.place", _journal=False):
            a = _rs.reshard(A.garray, sh, op="matmul_place")
            b = _rs.reshard(B.garray, sh, op="matmul_place")
        with _tm.span("matmul.summa.compute", _journal=False):
            return fn(a, b)


def _default_impl_timer(op, a, b):
    """Best-of-3 wall clock with a scalar-fetch sync (block_until_ready
    does not synchronize through every transport)."""
    import time as _time
    op(a, b)                                  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = _time.perf_counter()
        float(jnp.sum(op(a, b)))              # scalar fetch = real sync
        best = min(best, _time.perf_counter() - t0)
    return best


def _tune_impls(kernel, key, candidates, a, b, timer, persist):
    """Shared promotion flow for the GEMM implementation tuners: time
    every candidate (an impl whose timer raises scores inf — an invalid
    tiling is an expected outcome), record the winner under ``kernel`` /
    ``key``, optionally persist the registry.  ONE owner of the
    record/persist contract for API and bench alike."""
    from ..utils import autotune
    results = {}
    for name, op in candidates.items():
        try:
            results[name] = timer(op, a, b)
        except Exception:
            results[name] = float("inf")
    winner = min(results, key=results.get)
    autotune.record(kernel, key, winner)
    if persist:
        autotune.save_default()
    return winner, results


@functools.lru_cache(maxsize=None)
def _int8_cannon_jit(procs, g, out_dtype_str):
    """One shard_map program: Cannon double ring with int8 panels +
    per-panel scales riding the hops (``cannon_matmul_int8``)."""
    from .collective_matmul import cannon_matmul_int8
    mesh = L.mesh_for(procs, (g, g))
    ax_r, ax_c = mesh.axis_names

    def prog(a, b):
        return cannon_matmul_int8(a, b, ax_r, ax_c,
                                  out_dtype=out_dtype_str)

    shm = shard_map_compat(prog, mesh=mesh,
                        in_specs=(P(ax_r, ax_c), P(ax_r, ax_c)),
                        out_specs=P(ax_r, ax_c), check=False)
    return mesh, (ax_r, ax_c), jax.jit(shm)


@functools.lru_cache(maxsize=None)
def _int8_shm_jit(procs, p, out_dtype_str):
    """One shard_map program: per-rank dynamic-quantized int8 GEMM of the
    resident row block against the replicated right operand."""
    from .pallas_gemm import quantized_matmul
    mesh = L.mesh_for(procs, (p,))
    ax = mesh.axis_names[0]

    def prog(a, b):
        return quantized_matmul(a, b, out_dtype=out_dtype_str)

    # check=False: pallas_call out_shapes carry no varying-mesh-axes
    # metadata (same setting as parallel.collectives.run_spmd)
    shm = shard_map_compat(prog, mesh=mesh,
                        in_specs=(P(ax, None), P(None, None)),
                        out_specs=P(ax, None), check=False)
    return mesh, ax, jax.jit(shm)


def dmatmul_int8(A, B, out_dtype=jnp.float32):
    """Distributed dynamic-quantization GEMM: float DArrays in, float out,
    int8 on the MXU — the DArray entry to ``quantized_matmul`` (no
    reference analog; targets the e-class MXU's 2x int8 rate).

    Per-row (A) / per-column (B) symmetric int8 quantization with exact
    int32 accumulation and fused dequant; relative error ~1e-2 on
    Gaussian data (see ``ops.pallas_gemm.quantized_matmul``).  Supported
    layouts: A on one device; A row-chunked on an even ``(p, 1)`` grid
    with B resident/replicated (each rank quantizes its own rows —
    row-wise scales are local by construction); or A and B both on the
    SAME even square ``(g, g)`` grid (the BLAS-3 tile shape — int8
    panels + per-panel scales ride the Cannon double ring,
    ``cannon_matmul_int8``).  Anything else raises: this is an opt-in
    performance API, not a silently-degrading one.
    """
    if isinstance(A, (SubDArray,)):
        A = A.materialize()      # route through the supported-layout pick
    if not isinstance(A, DArray):
        # host/raw arrays go straight onto a SUPPORTED layout (the
        # default prime-factorized grid may be 2-D and would fail the
        # check below): row-chunked when the rows divide the device
        # count, single-device otherwise
        av = jnp.asarray(A)
        ndev = len(L.all_ranks())
        if av.ndim == 2 and ndev > 1 and av.shape[0] % ndev == 0:
            A = distribute(av, procs=range(ndev), dist=(ndev, 1))
        else:
            A = distribute(av, procs=[0],
                           dist=(1,) * max(av.ndim, 1))
    bv = _unwrap(B)
    if A.ndim != 2 or np.ndim(bv) != 2:
        raise ValueError(f"dmatmul_int8 expects 2-D operands, got "
                         f"{A.dims} @ {np.shape(bv)}")
    m, k = A.dims
    if np.shape(bv)[0] != k:
        raise ValueError(f"dim mismatch: {A.dims} @ {np.shape(bv)}")
    n = np.shape(bv)[1]
    procs = [int(q) for q in A.pids.flat]
    p = len(procs)
    from .pallas_gemm import quantized_matmul
    if p == 1:
        res = quantized_matmul(A.garray, bv, out_dtype=out_dtype)
        return _wrap_global(res, procs=procs, dist=[1, 1])
    gq = _square_grid_ok(A, B) if isinstance(B, DArray) else None
    if gq is not None:
        mesh, axes, fn = _int8_cannon_jit(tuple(procs), gq,
                                          str(jnp.dtype(out_dtype)))
        sh = NamedSharding(mesh, P(*axes))
        a = _rs.reshard(A.garray, sh, op="matmul_place")
        b = _rs.reshard(B.garray, sh, op="matmul_place")
        return _wrap_global(fn(a, b), procs=procs, dist=[gq, gq])
    if A.pids.shape != (p, 1) or A._padded or m % p:
        raise ValueError(
            "dmatmul_int8 needs A on one device, A row-chunked on an even "
            "(p, 1) grid with B resident/replicated, or A and B both on "
            "the SAME even square (g, g) grid (matching rank order, no "
            f"padding); got grid {A.pids.shape}, dims {A.dims}")
    if isinstance(B, DArray) and B._padded:
        raise ValueError("dmatmul_int8 needs an even (or resident) B")
    mesh, ax, fn = _int8_shm_jit(tuple(procs), p, str(jnp.dtype(out_dtype)))
    a = _rs.reshard(A.garray, NamedSharding(mesh, P(ax, None)),
                    op="matmul_place")
    b = jax.device_put(jnp.asarray(bv),  # dalint: disable=DAL007 — fresh uncommitted host vector, no source layout to plan from
                       NamedSharding(mesh, P(None, None)))
    return _wrap_global(fn(a, b), procs=procs, dist=[p, 1])


def tune_matmul_impl(m, n, k, dtype=jnp.float32, timer=None, persist=True):
    """Measure ``jnp.matmul`` vs the Pallas schedule on THIS process's
    default device for an (m,k)x(k,n) GEMM and bank the winner in the
    autotune registry under ``matmul_impl`` (consulted by ``matmul`` /
    ``DArray @ DArray``; the key includes the device kind, so a winner
    from one platform never drives another).  ``timer(op, a, b) ->
    seconds`` is injectable (tests pass deterministic stubs).  Returns
    ``(winner, {impl: seconds})``."""
    from .pallas_gemm import pallas_matmul
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k),
                          jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n),
                          jnp.float32).astype(dtype)
    jfn = jax.jit(jnp.matmul)
    return _tune_impls(
        "matmul_impl", _impl_key(m, n, k, a.dtype, b.dtype),
        {"jnp": jfn, "pallas": pallas_matmul}, a, b,
        timer or _default_impl_timer, persist)


def tune_matmul_impl_dist(m, n, k, p=None, dtype=jnp.float32, timer=None,
                          persist=True):
    """Measure GSPMD vs the overlapped ring (`allgather_matmul_rhs`) for
    the 1-D TP GEMM — A row-chunked, B contraction-chunked over ``p``
    devices — and bank the winner under ``matmul_impl_dist`` (consulted
    by ``matmul`` for eligible (p,1)x(p,1) DArray operands).  ``p``
    defaults to every local device; requires ``m % p == k % p == 0``."""
    p = len(jax.devices()) if p is None else p
    if p < 2:
        raise ValueError("tune_matmul_impl_dist needs >= 2 devices")
    if m % p or k % p:
        raise ValueError(
            f"m ({m}) and k ({k}) must be divisible by p ({p})")
    procs = tuple(range(p))
    from .pallas_collectives import rdma_mode
    mesh, ax, ring = _ring_ag_jit(procs, p, str(jnp.dtype(dtype)),
                                  rdma_mode())
    sh = NamedSharding(mesh, P(ax, None))
    a = jax.device_put(jax.random.normal(  # dalint: disable=DAL007 — autotune staging of a fresh uncommitted array, nothing to plan
        jax.random.PRNGKey(0), (m, k), jnp.float32).astype(dtype), sh)
    b = jax.device_put(jax.random.normal(  # dalint: disable=DAL007 — autotune staging of a fresh uncommitted array, nothing to plan
        jax.random.PRNGKey(1), (k, n), jnp.float32).astype(dtype), sh)
    gspmd = jax.jit(jnp.matmul, out_shardings=sh)
    return _tune_impls(
        "matmul_impl_dist", _impl_key(m, n, k, p, a.dtype, b.dtype),
        {"jnp": gspmd, "ring_ag": ring}, a, b,
        timer or _default_impl_timer, persist)


def tune_matmul_impl_summa(m, n, k, g=None, dtype=jnp.float32, timer=None,
                           persist=True):
    """Measure GSPMD vs the owned 2-D tile schedule — the Cannon double
    ring (`cannon_matmul`) on square grids, the masked-psum SUMMA panels
    (`summa_matmul`) on rectangular ones — for A and B block-distributed
    over an ``(r, c)`` device grid (BASELINE config 3's 2×2 shape), and
    bank the winner under ``matmul_impl_dist`` with an ``rxc`` grid tag
    (consulted by ``matmul`` for eligible same-grid DArray operands).
    ``g``: an int (square ``(g, g)`` grid) or an ``(r, c)`` tuple;
    defaults to the largest square grid the local devices support.
    Requires ``m % r == n % c == k % lcm(r, c) == 0``."""
    if g is None:
        g = int(math.isqrt(len(jax.devices())))
    r, c = (g, g) if isinstance(g, int) else (int(g[0]), int(g[1]))
    if r < 2 or c < 2:
        raise ValueError("tune_matmul_impl_summa needs a >= 2x2 grid "
                         "(>= 4 devices for the default square)")
    if m % r or n % c or k % math.lcm(r, c):
        raise ValueError(
            f"m ({m}), n ({n}), k ({k}) must be divisible by r ({r}), "
            f"c ({c}), lcm(r, c) ({math.lcm(r, c)}) respectively")
    procs = tuple(range(r * c))
    mesh, (ax_r, ax_c), owned = _summa_jit(procs, r, c,
                                           str(jnp.dtype(dtype)))
    sh = NamedSharding(mesh, P(ax_r, ax_c))
    a = jax.device_put(jax.random.normal(  # dalint: disable=DAL007 — autotune staging of a fresh uncommitted array, nothing to plan
        jax.random.PRNGKey(0), (m, k), jnp.float32).astype(dtype), sh)
    b = jax.device_put(jax.random.normal(  # dalint: disable=DAL007 — autotune staging of a fresh uncommitted array, nothing to plan
        jax.random.PRNGKey(1), (k, n), jnp.float32).astype(dtype), sh)
    gspmd = jax.jit(jnp.matmul, out_shardings=sh)
    return _tune_impls(
        "matmul_impl_dist", _impl_key(m, n, k, f"{r}x{c}", a.dtype, b.dtype),
        {"jnp": gspmd, "summa": owned}, a, b,
        timer or _default_impl_timer, persist)


@_tm.traced(name="matmul")
def matmul(A, B, out: DArray | None = None, alpha=1.0, beta=0.0):
    """C = alpha*A*B [+ beta*C] — distributed GEMM / matvec.

    Out-of-place: allocates C with the layout of `_gemm_layout` (reference
    linalg.jl:261-311).  In-place (``out``): validates the reference's
    cuts-compatibility contract (linalg.jl:84,201 — C's row cuts must equal
    A's row cuts) and rebinds ``out``.

    One jitted matmul over sharded operands replaces the reference's
    caller-driven tile shipping (linalg.jl:211-251); XLA emits the ICI
    collectives.
    """
    if isinstance(A, (SubDArray,)):
        A = A.copy()
    if not isinstance(A, DArray):
        A = distribute(jnp.asarray(A))
    bv = _unwrap(B)
    av_shape, bv_shape = np.shape(A.garray), np.shape(bv)
    if len(av_shape) != 2 or len(bv_shape) not in (1, 2):
        raise ValueError(f"matmul expects 2-D A and 1/2-D B, got {av_shape} @ {bv_shape}")
    if av_shape[1] != bv_shape[0]:
        raise ValueError(f"matmul dim mismatch: {av_shape} @ {bv_shape}")
    vec = len(bv_shape) == 1
    m, k = av_shape
    n = 1 if vec else bv_shape[1]

    if out is not None:
        want = (m,) if vec else (m, n)
        if tuple(out.dims) != want:
            raise ValueError(f"out dims {out.dims} != result dims {want}")
        # reference layout contract: C's first-dim cuts == A's first-dim cuts
        # (linalg.jl:201 `C.cuts[1] == A.cuts[Ad1] || throw`)
        if out.cuts[0] != A.cuts[0]:
            raise ValueError(
                "mul_into: out's row cuts must equal A's row cuts "
                "(reference linalg.jl:201)")
        C = out
        out_dtype = C.dtype
        sharding = C.sharding
        procs = [int(p) for p in C.pids.flat]
        dist = list(C.pids.shape)
    else:
        # no zero-fill allocation: derive the result layout/sharding and
        # wrap the matmul output directly
        C = None
        out_dtype = np.result_type(A.dtype, bv.dtype)
        if vec:
            procs = [int(p) for p in A.pids.flat]
            dist = [A.pids.shape[0]]
        else:
            procs, dist = _gemm_layout(A, B)
            dist = list(dist)
        sharding = L.sharding_for(procs, dist, (m,) if vec else (m, n))

    use_ab = not (alpha == 1.0 and beta == 0.0)
    if beta != 0.0 and C is None:
        raise ValueError("beta accumulation requires out=")
    if _tm.enabled():
        # estimated cross-chip volume of the block GEMM on an (r, c) result
        # grid: every device assembles its A row panel and B column panel,
        # so the total receive volume is ~bytes(A)*(c-1) + bytes(B)*(r-1)
        # (0 on a single device) — the SUMMA communication volume both the
        # ring and GSPMD paths approximate.  An estimate, not a wire count.
        r = int(dist[0]) if dist else 1
        c = int(dist[1]) if len(dist) > 1 else 1
        a_bytes = int(np.prod(av_shape)) * np.dtype(A.dtype).itemsize
        b_bytes = _tm.nbytes_of(bv)
        _tm.count("op.matmul")
        ici_est = a_bytes * (c - 1) + b_bytes * (r - 1)
        _tm.annotate(grid=f"{r}x{c}")
        _tm.record_comm("collective", ici_est,
                        op="matmul", grid=f"{r}x{c}",
                        shape=[m, k, n])
    # plain-mode dispatch to the hand-owned schedules (VERDICT round-3
    # item 4), each behind the autotune registry with jnp.matmul + GSPMD
    # as the unconditional fallback: the overlapped ring for the 1-D TP
    # shape, the Pallas kernel for single-device operands
    if (not use_ab and not vec
            and _ring_ag_eligible(A, B, procs, dist)
            and _dist_impl_choice(m, n, k, A.pids.shape[0],
                                  A.dtype, B.dtype) == "ring_ag"):
        res = _ring_ag_gemm(A, B, out_dtype)
        res = _rs.reshard(res, sharding, op="matmul_out")
        if C is not None:
            C._rebind(res)
            return C
        return _wrap_global(res, procs=procs, dist=dist)
    if (not use_ab and not vec
            and (_rc := _summa_eligible(A, B, procs, dist)) is not None
            and _summa_impl_choice(m, n, k, _rc[0], _rc[1],
                                   A.dtype, B.dtype) == "summa"):
        res = _summa_gemm(A, B, out_dtype)
        res = _rs.reshard(res, sharding, op="matmul_out")
        if C is not None:
            C._rebind(res)
            return C
        return _wrap_global(res, procs=procs, dist=dist)
    from .broadcast import _align_devices
    av, bv = _align_devices([A.garray, bv], sharding)
    if use_ab and C is not None:
        res = _matmul_jit(sharding, "ab")(
            av, bv, C.garray,
            jnp.asarray(alpha, out_dtype), jnp.asarray(beta, out_dtype))
    elif alpha != 1.0:
        res = _matmul_jit(sharding, "alpha")(
            av, bv, jnp.asarray(alpha, out_dtype))
    else:
        res = None
        if not vec and _impl_choice(m, n, k, av.dtype, bv.dtype) == "pallas":
            res = _try_pallas_gemm(av, bv, out_dtype)
        if res is None:
            res = _matmul_jit(sharding, "plain")(av, bv)
    if res.dtype != out_dtype:
        res = res.astype(out_dtype)
    if C is not None:
        C._rebind(res)
        return C
    return _wrap_global(res, procs=procs, dist=dist)


def mul_into(C: DArray, A, B, alpha=1.0, beta=0.0) -> DArray:
    """In-place mul! (reference linalg.jl:78-122,189-257)."""
    return matmul(A, B, out=C, alpha=alpha, beta=beta)


def _darray_matmul(self, other):
    if isinstance(other, (DArray, SubDArray, np.ndarray, jax.Array)):
        return matmul(self, other)
    return NotImplemented


def _darray_rmatmul(self, other):
    if isinstance(other, (np.ndarray, jax.Array)):
        return matmul(distribute(jnp.asarray(other)), self)
    return NotImplemented


DArray.__matmul__ = _darray_matmul
DArray.__rmatmul__ = _darray_rmatmul
