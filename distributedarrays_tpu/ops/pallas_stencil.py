"""Hand-written Pallas TPU kernel for the 5-point stencil hot loop.

BASELINE config 4 (the reference's SPMD halo-exchange stencil,
/root/reference/src/spmd.jl:145-184 + docs/src/index.md:160-181) is
bandwidth-bound: one Laplacian step reads and writes the grid once, so the
roofline is ~(HBM BW)/(8 bytes/cell).  The jnp formulation in
models/stencil.py (concat halo + four shifted adds) costs XLA several HBM
round-trips per step; this kernel streams each row-block through VMEM once
— one block read, one block write, plus two single-row neighbor arrays —
so a step approaches the 2-pass roofline.

Layout trick: instead of overlapping block windows (inexpressible with
block-granular BlockSpec index maps), the rows that cross block boundaries
are precomputed OUTSIDE the kernel as two tiny (nblocks, n) arrays:

    top_rows[i] = the row just above block i   (device halo ``lo`` for i=0)
    bot_rows[i] = the row just below block i   (device halo ``hi`` for last)

built with stride-``bm`` slices (negligible traffic), so the kernel's
index maps are the identity and every boundary case vanishes from the
kernel body.  The column neighbors are in-register shifts of the resident
block.

Interpreter mode runs the same kernel off-TPU for the CPU-mesh suite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_gemm import _on_tpu, _pow2_divisor

__all__ = ["stencil5_block", "stencil5_multistep", "stencil3x3_block",
           "stencil3x3_multistep", "supports", "LAPLACIAN_3X3"]

_VMEM_TARGET = 2 * 1024 * 1024  # ~per-buffer VMEM budget for (bm, n) tiles

# the 5-point Laplacian as a 3x3 stencil: out[i,j] = sum_ab w[a][b] *
# x[i-1+a, j-1+b] with zero boundary
LAPLACIAN_3X3 = ((0.0, 1.0, 0.0), (1.0, -4.0, 1.0), (0.0, 1.0, 0.0))


def _canon_weights(weights) -> tuple:
    """Validate + canonicalize a 3x3 weight stencil to a hashable tuple
    of floats (the kernels bake weights in as compile-time constants)."""
    import numpy as _np
    w = _np.asarray(weights, dtype=_np.float64)
    if w.shape != (3, 3):
        raise ValueError(f"stencil weights must be 3x3; got {w.shape}")
    return tuple(tuple(float(v) for v in row) for row in w)


def _apply3x3(ext, w):
    """One weighted-stencil step on row-extended ``ext`` ((r + 2, n): one
    neighbor row above and below the r output rows); zero column boundary.
    Zero weights cost nothing (static) and unit weights skip the multiply."""
    bands = (ext[:-2], ext[1:-1], ext[2:])              # rows i-1, i, i+1
    acc = None
    for bi in range(3):
        band = bands[bi]
        zc = jnp.zeros_like(band[:, :1])
        for ci, wv in enumerate(w[bi]):
            if wv == 0.0:
                continue
            if ci == 0:      # contribution of column j-1
                t = jnp.concatenate([zc, band[:, :-1]], axis=1)
            elif ci == 2:    # contribution of column j+1
                t = jnp.concatenate([band[:, 1:], zc], axis=1)
            else:
                t = band
            term = t if wv == 1.0 else ext.dtype.type(wv) * t
            acc = term if acc is None else acc + term
    if acc is None:          # all-zero stencil
        acc = jnp.zeros_like(ext[1:-1])
    return acc


def _plan(m: int, n: int, itemsize: int, block_rows: int | None,
          k: int = 0):
    """Resolve the row-block size, or None when no TPU-valid tiling
    exists.  Power-of-two blocks >= 8 satisfy the (8, 128)-or-equal block
    rule; the one escape is a single whole-array block (== array dims),
    which must itself fit the VMEM budget.  ``k`` > 0 budgets for the
    temporal kernel's (bm + 2k, n) ghost-extended buffers."""
    if block_rows is None:
        block_rows = max(8, _VMEM_TARGET // (n * itemsize) - 2 * k)
    bm = _pow2_divisor(m, min(block_rows, m))
    if bm >= 8:
        # the floor of 8 rows can still blow the budget once the 2k ghost
        # rows are added (wide n, deep k) — refuse rather than overshoot
        if k and (bm + 2 * k) * n * itemsize > _VMEM_TARGET:
            return None
        return bm
    if (m + 2 * k) * n * itemsize <= _VMEM_TARGET:
        return m
    return None


def supports(m: int, n: int, dtype, k: int = 0) -> bool:
    """Whether ``stencil5_block`` (``k`` = 0) / ``stencil5_multistep``
    (``k`` = temporal depth) can tile an (m, n) block on TPU — the single
    source of truth for routers choosing between these kernels and the
    jnp formulation (models/stencil.py)."""
    import jax.numpy as jnp
    return _plan(m, n, jnp.dtype(dtype).itemsize, None, k) is not None


def _kernel(mid_ref, top_ref, bot_ref, o_ref, *, w):
    c = mid_ref[...]                                    # (bm, n)
    ext = jnp.concatenate([top_ref[0], c, bot_ref[0]], axis=0)
    o_ref[...] = _apply3x3(ext, w)


@functools.lru_cache(maxsize=64)
def _build(m, n, bm, dtype_str, interpret, w):
    nb = m // bm
    call = pl.pallas_call(
        functools.partial(_kernel, w=w),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),    # resident block
            # boundary rows carry a unit middle axis — (nb, 1, n) blocked
            # (1, 1, n) — because a (1, n) block over an (nb, n) array
            # violates the TPU (8, 128)-or-equal block-shape rule
            pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0)),  # row above i
            pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0)),  # row below i
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(dtype_str)),
        name="stencil_3x3",
        interpret=interpret,
    )
    return call


def stencil3x3_block(block, lo, hi, weights=LAPLACIAN_3X3,
                     block_rows: int | None = None,
                     interpret: bool | None = None):
    """One weighted 3x3 stencil step on a local (m, n) block:
    ``out[i,j] = sum_ab w[a][b] * x[i-1+a, j-1+b]`` with zero column
    boundary.  Weights are compile-time constants (zero entries cost
    nothing), so the 5-point Laplacian, diffusion steps, blurs, and
    sharpen filters all stream through the same kernel.

    ``lo``/``hi``: the (1, n) halo rows from the neighboring ranks (zeros
    at the outer boundary) — exactly what ``halo_exchange`` returns.
    Diagonal taps read column-shifts of those same full-width rows, so no
    corner exchange is needed on a row-sharded layout.

    ``block_rows`` defaults to whatever keeps one (bm, n) buffer around
    2 MB — the kernel body materializes several such temporaries plus the
    double-buffered in/out blocks, and a full-width 8192² f32 block at 512
    rows blows the 16 MB VMEM scoped limit.
    """
    w = _canon_weights(weights)
    m, n = block.shape
    if lo.shape != (1, n) or hi.shape != (1, n):
        raise ValueError(f"halo rows must be (1, {n}); got {lo.shape}, "
                         f"{hi.shape}")
    bm = _plan(m, n, block.dtype.itemsize, block_rows)
    if bm is None:
        raise ValueError(
            f"stencil3x3_block has no TPU-valid tiling for ({m}, {n}) "
            f"{block.dtype}: needs a power-of-two row divisor >= 8 within "
            "the VMEM budget, or a whole block small enough to process in "
            "one step; use the jnp path (use_pallas=False) for this layout")
    if interpret is None:
        interpret = not _on_tpu()
    nb = m // bm
    # top_rows[i] = last row of block i-1 (halo lo for i=0); bot_rows[i] =
    # first row of block i+1 (halo hi for the last block).  Stride-bm row
    # slices: tiny traffic, identity index maps in the kernel.
    if nb > 1:
        top_rows = jnp.concatenate([lo, block[bm - 1::bm][:-1]], axis=0)
        bot_rows = jnp.concatenate([block[bm::bm], hi], axis=0)
    else:
        top_rows, bot_rows = lo, hi
    return _build(m, n, bm, str(block.dtype), bool(interpret), w)(
        block, top_rows[:, None, :], bot_rows[:, None, :])


def stencil5_block(block, lo, hi, block_rows: int | None = None,
                   interpret: bool | None = None):
    """One 5-point Laplacian step (``stencil3x3_block`` with the
    Laplacian weights; semantics match models/stencil.py's jnp step)."""
    return stencil3x3_block(block, lo, hi, LAPLACIAN_3X3, block_rows,
                            interpret)


# ---------------------------------------------------------------------------
# Temporal blocking: k Laplacian steps per launch (trapezoid / ghost-zone
# scheme).  One launch reads the grid ~(1 + 2k/bm) times and writes it once,
# so HBM traffic per step drops to ~(2 + 2k/bm)/k passes instead of 2 —
# the only way past the single-step read+write roofline the streaming
# kernel above already sits on.
#
# Correctness: each block's buffer carries k ghost rows on both sides,
# seeded with step-0 values of the neighboring block (or the k-deep rank
# halo from ``halo_exchange(halo=k)``).  Stencil steps corrupt the ghost
# zone inward one row per step (its outermost rows lack neighbors), so
# after k steps exactly the middle ``bm`` rows are correct — the classic
# trapezoid argument.  The one case ghost evolution cannot express is the
# global Dirichlet edge (the zero boundary is zero at EVERY step, not just
# step 0); the kernel re-zeroes the ghost zone of the first/last block
# after each step when the rank-level edge flags say this rank sits on the
# global boundary.
# ---------------------------------------------------------------------------


def _kernel_multi(buf_ref, topf_ref, botf_ref, o_ref, *, k, bm, m, w):
    x = buf_ref[0]                                      # (bm + 2k, n)
    i = pl.program_id(0)
    top_d = topf_ref[0, 0] != 0
    bot_d = botf_ref[0, 0] != 0
    # outside-domain rows in GLOBAL extended coordinates (buffer row r is
    # extended row i*bm + r; rows < k / >= m + k lie beyond the domain) —
    # block-local gating would miss ghost rows spilling into the second /
    # penultimate block's window when k >= bm + 2
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm + 2 * k, 1), 0)
    ghost = ((rows < k) & top_d) | ((rows >= m + k) & bot_d)
    keep = jnp.where(ghost, 0, 1).astype(x.dtype)       # (bm + 2k, 1)
    for _ in range(k):
        zr = jnp.zeros_like(x[:1])
        ext = jnp.concatenate([zr, x, zr], axis=0)
        x = _apply3x3(ext, w) * keep
    o_ref[...] = x[k:k + bm]


@functools.lru_cache(maxsize=64)
def _build_multi(m, n, bm, k, dtype_str, interpret, w):
    nb = m // bm
    return pl.pallas_call(
        functools.partial(_kernel_multi, k=k, bm=bm, m=m, w=w),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, bm + 2 * k, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),     # top Dirichlet flag
            pl.BlockSpec((1, 1), lambda i: (0, 0)),     # bottom flag
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(dtype_str)),
        name="stencil_3x3_multistep",
        interpret=interpret,
    )


def stencil3x3_multistep(block, lo, hi, k: int, top_dirichlet,
                         bot_dirichlet, weights=LAPLACIAN_3X3,
                         block_rows: int | None = None,
                         interpret: bool | None = None):
    """``k`` weighted 3x3 stencil steps on a local (m, n) block in ONE
    kernel launch (temporal blocking — see the scheme note above; the
    trapezoid/ghost-shrink argument is weight-agnostic).

    ``lo``/``hi``: the (k, n) step-0 halo slabs from the neighboring ranks
    (``halo_exchange(..., halo=k)``; zeros at the global edge).
    ``top_dirichlet``/``bot_dirichlet``: scalars (python or traced bools),
    true when this rank's top/bottom edge is the global zero boundary —
    inside ``shard_map`` pass ``axis_index == 0`` / ``== nranks - 1``.
    """
    w = _canon_weights(weights)
    m, n = block.shape
    k = int(k)
    if k < 1:
        raise ValueError(f"k must be >= 1; got {k}")
    if lo.shape != (k, n) or hi.shape != (k, n):
        raise ValueError(f"halo slabs must be ({k}, {n}); got {lo.shape}, "
                         f"{hi.shape}")
    bm = _plan(m, n, block.dtype.itemsize, block_rows, k)
    if bm is None:
        raise ValueError(
            f"stencil3x3_multistep has no TPU-valid tiling for ({m}, {n}) "
            f"{block.dtype} at k={k}; use the jnp path (use_pallas=False) "
            "for this layout")
    if interpret is None:
        interpret = not _on_tpu()
    nb = m // bm
    extended = jnp.concatenate([lo, block, hi], axis=0)  # (m + 2k, n)
    # per-block ghost-extended buffers: overlapping (bm + 2k)-row windows at
    # stride bm — a full-row gather, (1 + 2k/bm)x input traffic
    row_idx = (jnp.arange(nb) * bm)[:, None] + jnp.arange(bm + 2 * k)[None, :]
    buf = jnp.take(extended, row_idx, axis=0)            # (nb, bm+2k, n)
    flag = lambda v: jnp.asarray(v).reshape(1, 1).astype(block.dtype)
    return _build_multi(m, n, bm, k, str(block.dtype), bool(interpret), w)(
        buf, flag(top_dirichlet), flag(bot_dirichlet))


def stencil5_multistep(block, lo, hi, k: int, top_dirichlet, bot_dirichlet,
                       block_rows: int | None = None,
                       interpret: bool | None = None):
    """``k`` 5-point Laplacian steps in one launch (the Laplacian special
    case of ``stencil3x3_multistep``; semantics match ``k`` applications
    of models/stencil.py's jnp step)."""
    return stencil3x3_multistep(block, lo, hi, k, top_dirichlet,
                                bot_dirichlet, LAPLACIAN_3X3, block_rows,
                                interpret)
