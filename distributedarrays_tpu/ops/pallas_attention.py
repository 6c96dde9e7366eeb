"""Hand-written Pallas TPU kernel: flash attention (blockwise, online
softmax).

This is the hot-op companion of ``models/ring_attention.py``: ring
attention moves K/V blocks between chips with ``ppermute`` while each rank
computes *local* blockwise attention — exactly the computation this kernel
owns.  On TPU it keeps the running-max/normalizer/accumulator resident in
VMEM while K/V blocks stream HBM→VMEM, so the S×S score matrix never
materializes (pallas_guide.md: grid/BlockSpec streaming, scratch
persistence across the innermost sequential grid axis).

Layout: the kernels read q, k, v and write their results in the caller's
token-major layout, (B, S, H, 64) viewed as (B, S, H x 64) with no copy,
where heads are 64 wide: a grid step's block is a 128-lane column block,
two heads side by side, and the batch is part of the grid's first axis
(``_Heads``, ``_lane_heads``).  Other widths go through head-major
(B x H, S, D) copies.  Grid ``(B x heads / heads a step, S/bq, S/bk)``
with the K axis innermost; scratch ``m (bq,128)``, ``l (bq,128)``
(lane-replicated) and ``acc (bq, lanes)`` a head persist across the K
sweep for each (heads, q-block) and flush to the output (and the per-row
logsumexp, written as a row) on the final K step.

Which blocks of the causal score matrix a kernel visits, and what it does
per score there:

- A grid step holds ``bq`` rows of q and ``bk`` rows of k, v (by default
  up to 1024, so S = 1024 is one step a head) and sweeps them in
  ``_TILE`` x ``_TILE`` sub-tiles.  A tile wholly above the diagonal is
  never computed; one wholly under it takes the plain body; only a tile
  the diagonal crosses pays a compare and a select, against one position
  difference built once a grid step.  The computed area is
  S^2/2 + S*tile/2 whatever the block.  Adjacent unmasked tiles of a
  sweep known at trace time go as one wider body (``_SPAN``): the same
  scores, one rescale of the online softmax for all of them, and fewer
  bodies in the kernel: what a kernel costs to trace and lower is paid
  by every process that builds a step program, compile cache or not.
- A grid step wholly above the diagonal ("dead") runs no body, and with
  static offsets its ``index_map`` repeats the last live block, so no DMA
  is issued for it.  The ring hop's offsets are traced scalars in SMEM:
  it shares the bodies, and its dead steps still fetch.
- ``scale`` is folded into q once a q block, in VMEM (see the note above
  the backward kernels for where each factor goes); masked scores take a
  large finite negative, so no ``isfinite`` guard runs per score: every
  row of a causal sweep from column 0 has a live key in its first tile.

Differentiable end to end with FlashAttention-2-style BACKWARD KERNELS
(custom_vjp): the forward saves only O(S) logsumexp rows; the backward
recomputes P tile by tile, as TRANSPOSED scores (k.q^T with lse and D as
rows) so that dV += P^T.dO and dK += dS^T.q are plain products.  Where a
head's dQ (S x D float32) fits VMEM it is ONE sweep, the kernel named
``flash_bwd_dkv``, that also produces dQ: under Mosaic's default scoped
limit where the resident dQ takes at most half of it, and past that under
a ``vmem_limit_bytes`` the kernel reckons from its own buffers, up to half
of the chip's VMEM (``_fused_backward``).  Otherwise, and on the ring hop,
a second K-sweep kernel ``flash_bwd_dq`` produces dQ.  Training memory
stays O(S.d).  Gradients match the dense formulation to ~1e-6 in float32
(tested).  The chosen blocks and the tiles a head computes by kind are
published as the gauge ``pallas.flash_attention.plan`` when a program is
built (docs/telemetry.md).

Interpreter mode runs the same kernels off-TPU for the CPU-mesh test suite.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .pallas_gemm import _on_tpu
from .. import telemetry as _tm

__all__ = ["flash_attention", "flash_block_size", "tuned_flash_config",
           "flash_attention_hop",
           "flash_attention_hop_bwd", "flash_carry_init",
           "flash_carry_finalize"]

# Per-row softmax stats (running max / normalizer / logsumexp) are stored
# broadcast across one 128-wide lane register: TPU lowering requires the
# last two dims of every block shape to be (divisible by 8, divisible by
# 128) or equal to the array dims, so an (h, s) array cannot be blocked
# (1, bq).  Same layout as jax's reference TPU flash kernel
# (pallas/ops/tpu/flash_attention.py MIN_BLOCK_SIZE).
_LANE = 128


def flash_block_size(S: int, cap: int = 512) -> int:
    """Largest power-of-two divisor of ``S``, capped — a always-valid block
    size for ``flash_attention`` (use when S is not a multiple of 128)."""
    from .pallas_gemm import _pow2_divisor
    return _pow2_divisor(S, cap)


def _fit_block(b: int, extent: int) -> int:
    """Clip a requested block size to the extent, then halve until it
    divides — every sequence length keeps working when defaults grow."""
    b = min(b, extent)
    while extent % b:
        b //= 2
    return max(b, 1)


# Masked scores take a large finite negative, not -inf: exp(_MASK - m) is
# exactly 0 for any finite m, (-inf) - (-inf) never arises, and so no
# isfinite / select guards run per score.  0.7 x max leaves room to
# subtract a row maximum without overflow (the value jax's reference TPU
# flash kernel uses).
_MASK = -0.7 * float(np.finfo(np.float32).max)

_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b

# Sub-tile edge inside a grid step.  The computed causal area is
# S^2/2 + S*tile/2, so the tile (not the resident block) sets how close a
# sweep gets to the triangle; 256 keeps the MXU's row streams long and the
# masked tiles under half of the visited ones from S = 1024 up.
_TILE = 256
# Static sweeps of at most this many tiles are unrolled at trace time
# (static slices, and the scheduler may overlap a tile's vector work with
# the next tile's products); longer or traced ones are one scf.for.
_UNROLL = 4
# Adjacent unmasked tiles of an unrolled sweep are computed as one body of
# up to this many tiles (``_loop``).
_SPAN = 4
# The fused backward keeps the whole dQ of a grid step's heads in VMEM
# beside its blocks: S x D float32 of scratch and the output block in two
# buffers.  Up to this many bytes (half of the 16 MiB a kernel may use
# under Mosaic's default scoped limit; the q, k, v, dO blocks, dK and dV
# take the rest) the fused kernel is built as it stands, no limit named.
_FUSED_DQ_BYTES = 8 * 1024 * 1024
# Past that the fused kernel names its own ``vmem_limit_bytes``, reckoned
# from its buffers (``_fused_vmem_bytes``), as long as that stays within
# this cap: half of the 128 MiB of VMEM a v5e core has, the rest left to
# the fusions XLA runs beside the kernel.  A head's dQ at (8192, 256) in
# bf16 asks for 38 MiB and twice that dQ for 58; past the cap, two passes.
_FUSED_VMEM_CAP = 64 * 1024 * 1024


def _when(cond):
    """``pl.when`` that also takes a trace-time bool (a one-step grid axis
    makes its conditions static)."""
    if isinstance(cond, (bool, np.bool_)):
        return (lambda f: f()) if cond else (lambda f: None)
    return pl.when(cond)


def _clip(x, lo, hi):
    if isinstance(x, (int, np.integer)):
        return max(lo, min(hi, int(x)))
    return jnp.clip(x, lo, hi)


def _mult(x, m):
    return x if isinstance(x, (int, np.integer)) else pl.multiple_of(x, m)


def _k_bounds(row0, tq, col0, tk, n):
    """For q rows [row0, row0+tq) sweeping k tiles t = 0..n-1 (tile t holds
    columns col0 + t*tk ...): tiles [0, n_full) lie wholly under the
    diagonal (no mask), [n_full, n_live) cross it (mask), the rest hold no
    live score.  Works on ints (trace time) and traced scalars alike;
    ``col0`` None means no mask at all: every tile is whole."""
    if col0 is None:
        return n, n
    n_full = _clip((row0 - col0 + 1) // tk, 0, n)
    n_live = _clip((row0 + tq - 1 - col0) // tk + 1, 0, n)
    return n_full, n_live


def _q_bounds(col0, tk, row0, tq, n):
    """For k columns [col0, col0+tk) sweeping q tiles t = 0..n-1 (tile t
    holds rows row0 + t*tq ...): tiles [0, t_live) see none of the
    columns, [t_live, t_full) cross the diagonal (mask), [t_full, n) lie
    wholly under it (no mask); ``col0`` None: all of them."""
    if col0 is None:
        return 0, 0
    t_live = _clip((col0 - row0) // tq, 0, n)
    t_full = _clip((col0 + tk - 1 - row0 + tq - 1) // tq, 0, n)
    return t_live, t_full


def _band(delta, tq: int, tk: int, window: int):
    """A (tq, tk) tile of a WINDOWED causal sweep whose first column minus
    first row is the int ``delta``: a score is live iff
    ``delta <= (row - column inside the tile) < delta + window``.  Returns
    (any score live, the diagonal crosses the tile, the window's edge
    crosses it): the two edges a tile may have to mask."""
    live = tq - 1 >= delta and 1 - tk < delta + window
    return live, 1 - tk < delta, tq - 1 >= delta + window


def _band_loop(n: int, delta_of, tq: int, tk: int, window: int, body,
               span: int = 1):
    """The windowed sweep over tiles 0..n-1, all at trace time (a windowed
    step's offsets are always ints): tiles outside the band are not
    visited, a tile an edge crosses goes alone as ``body(t, 1, (diagonal,
    edge))``, adjacent whole ones ``span`` at a time as ``body(t, w,
    False)``."""
    t = 0
    while t < n:
        live, diag, edge = _band(delta_of(t), tq, tk, window)
        if not live:
            t += 1
        elif diag or edge:
            body(t, 1, (diag, edge))
            t += 1
        else:
            w = 1
            while (w < span and t + w < n and _band(
                    delta_of(t + w), tq, tk, window) == (True, False, False)):
                w += 1
            body(t, w, False)
            t += w


def _live(rel, delta, masked, window):
    """The live scores of a tile: ``masked`` True is the causal diagonal
    alone (one compare); a pair (diagonal, edge) names which of the two
    edges of a windowed band cross the tile."""
    if masked is True:
        return rel >= delta
    diag, edge = masked
    live = (rel >= delta) if diag else None
    if edge:
        inside = rel < delta + window
        live = inside if live is None else live & inside
    return live


def _loop(lo, hi, body, span: int = 1):
    """Run ``body(t, w)`` over the tiles [lo, hi), ``w`` adjacent tiles at
    a time.  Static bounds of few tiles are unrolled at trace time, and
    unmasked tiles go ``span`` at a time as ONE wider body: the computed
    scores are the same, the online softmax rescales once for all of them,
    and the kernel has fewer bodies to trace and lower each time a process
    builds its step program.  Longer or traced sweeps are one
    ``fori_loop`` of single tiles."""
    if isinstance(lo, (int, np.integer)) and isinstance(hi, (int, np.integer)):
        if hi - lo <= _UNROLL:
            t = lo
            while t < hi:
                w = min(span, hi - t)
                body(t, w)
                t += w
            return
    jax.lax.fori_loop(lo, hi, lambda t, c: (body(t, 1), c)[1], 0)


def _each_window_kind(shift, b: int, n_blocks: int, window: int, sweep):
    """``_each_kind`` for a windowed causal program (static offsets, square
    blocks of ``b``): a step's k block lies ``m = -shift / b`` blocks
    behind its q block.  The steps whose every score is live share one
    body, ``sweep(None)``; each distance at which the diagonal or the
    window's edge crosses the block gets a body of its own with the int
    shift, so that its sweep is laid out at trace time; every other step
    is dead."""
    if isinstance(shift, (int, np.integer)):
        return sweep(int(shift))
    far = min(n_blocks - 1, -(-(window - 1) // b))      # last live distance
    if 2 * b - 1 < window:
        pl.when((shift + b - 1 <= 0) & (b - 1 - shift < window))(
            lambda: sweep(None))
    for m in range(far + 1):
        if m == 0 or (m + 1) * b - 1 >= window:         # an edge crosses
            pl.when(shift == -m * b)(lambda m=m: sweep(-m * b))


def _each_kind(shift, bq: int, bk: int, causal: bool, static: bool, sweep):
    """Run ``sweep(s)`` once for each kind of live grid step, under that
    kind's condition.  ``shift`` is the k block's first column minus the q
    block's first row, the one number the causal pattern of a step depends
    on.  ``s`` None: no score of the step is masked.  ``s`` an int: the
    diagonal crosses at a position known at trace time, so the sweep's
    bounds are ints and it unrolls.  ``s`` traced: anywhere (traced loop
    bounds).  With ``static`` offsets and bq == bk a step is whole, on the
    diagonal (shift 0) or dead, and the traced body is not built."""
    if not causal:
        return sweep(None)
    if isinstance(shift, (int, np.integer)):
        return sweep(int(shift))
    whole = shift + bk - 1 <= 0
    pl.when(whole)(lambda: sweep(None))
    if bq == bk:
        pl.when(shift == 0)(lambda: sweep(0))
    if not (static and bq == bk):
        crossing = (shift <= bq - 1) & jnp.logical_not(whole)
        if bq == bk:
            crossing = crossing & (shift != 0)
        pl.when(crossing)(lambda: sweep(shift))


def _lanes(x, n: int):
    """A lane-replicated ``(rows, _LANE)`` statistic at width ``n``: whole
    registers are repeated or cut, so no (rows, 1) -> lanes broadcast runs
    per tile."""
    if n == _LANE:
        return x
    if n % _LANE == 0:
        return jnp.tile(x, (1, n // _LANE))
    if n < _LANE:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _rel(shape, q_axis: int):
    """q position minus k position inside a tile, built once a grid step:
    a score is live iff ``rel >= (tile's first column) - (tile's first
    row)``, one compare and one select in the tiles the diagonal crosses."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))


def _store_row(ref, x, at, t: int):
    """Store a lane-replicated ``(n, _LANE)`` column statistic as rows:
    chunk ``c`` of up to 128 rows (``w`` of them, never across a multiple
    of ``t``) becomes the (1, w) row ``ref[at(c, w)]``; the diagonal of
    the square is summed down the sublanes (exact: the rest is zeros).
    Runs once a q block."""
    n = x.shape[0]
    eye = _rel((_LANE, _LANE), 0) == 0
    for c in (c for base in range(0, n, t)
              for c in range(base, base + t, _LANE)):
        w = min(_LANE, t - c % t)
        cut = x[c:c + w, :w]
        ref[at(c, w)] = jnp.sum(
            jax.lax.select(eye[:w, :w], cut, jnp.zeros_like(cut)), axis=0,
            keepdims=True)


class _Heads(NamedTuple):
    """Where the heads of a grid step lie in its blocks (static: part of a
    program's cache key).  A block is (``fold``, rows, ``lanes`` x
    ``width``): ``fold`` heads along its leading axis (the head-major
    form's head fold, 1 in place), ``lanes`` heads of ``width`` side by
    side in its lanes: 2 where two heads of 64 share one register, read
    in place from the caller's token-major (B, S, heads x width) arrays,
    ``nj`` column blocks a row; 1 where the arrays are head-major (B x
    heads, S, width).  ``gk`` and ``gv`` query heads share a head of k
    and of v.  ``packed``: q, k and v are one (B, S, 3 x heads x width)
    array, the result of one projection (q's columns, k's, v's), and the
    backward writes their gradients into one such array."""
    fold: int = 1
    lanes: int = 1
    width: int = _LANE
    nj: int = 1
    gk: int = 1
    gv: int = 1
    packed: bool = False

    @property
    def inplace(self) -> bool:
        return self.lanes > 1

    def halves(self):
        """(k's lanes, v's lanes): where in a k and in a v block the head
        lies that this grid step's query heads share; None where a group
        is one head (query head ``hh`` reads lanes ``hh``).  Read once at
        the top of a kernel."""
        if self.lanes == 1:
            return None, None
        j = jax.lax.rem(pl.program_id(0), self.nj)
        half = lambda g: None if g == 1 else jax.lax.rem(
            jax.lax.div(j * self.lanes, g), self.lanes)
        return half(self.gk), half(self.gv)

    def _lane(self, x):
        return jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1),
                           self.width)

    def _keep(self, x, hh):
        """``x`` with every lane but head ``hh``'s zeroed."""
        return jax.lax.select(self._lane(x) == hh, x, jnp.zeros_like(x))

    def _move(self, x, src, dst):
        """``x``'s lanes ``src`` at lanes ``dst``: of two heads side by
        side, a rotation by one head's width swaps them; ``src`` None:
        they are the same."""
        if src is None:
            return x
        # Mosaic rotates 32-bit words only: a bf16 tile goes through f32
        # and back, exactly
        turned = pltpu.roll(x.astype(jnp.float32), self.width, 1)
        return jax.lax.select(jnp.broadcast_to(src == dst, x.shape), x,
                              turned.astype(x.dtype))

    def query(self, x, hh, src):
        """Query head ``hh`` of a (rows, lanes) q or dO tile, the other
        head's lanes zeroed, at the lanes ``src`` of the k or v head it
        reads (``halves``): contracted over all lanes it meets that head
        alone."""
        if self.lanes == 1:
            return x
        x = self._keep(x, hh)
        return x if src is None else self._move(x, hh, src)

    def key(self, x, hh, src):
        """The k or v head that query head ``hh`` reads, from a (rows,
        lanes) tile, the other lanes zeroed, at lanes ``hh``."""
        if self.lanes == 1:
            return x
        if src is None:
            return self._keep(x, hh)
        return self._move(self._keep(x, src), src, hh)

    def stack(self, parts, t: int):
        """The heads' (rows, lanes) parts as ONE operand of ``lanes`` x
        rows: strip ``s`` (``t`` rows) of head ``hh`` at rows (lanes s +
        hh) t.  A kernel body then takes both heads of a strip as one
        (lanes t)-row tile: twice the rows, not twice the bodies."""
        if len(parts) == 1:
            return parts[0]
        n = parts[0].shape[0] // t
        return jnp.concatenate([p[s * t:(s + 1) * t] for s in range(n)
                                for p in parts], axis=0)

    def unstack(self, x, t: int, src=None):
        """``stack``'s inverse, merged into one (rows, lanes) block: head
        ``hh``'s lanes from its own rows (``merge``)."""
        n_l = self.lanes
        if n_l == 1:
            return x
        part = lambda s, hh: x[(n_l * s + hh) * t:(n_l * s + hh + 1) * t]
        return jnp.concatenate([
            self.merge([part(s, hh) for hh in range(n_l)], src)
            for s in range(x.shape[0] // (n_l * t))], axis=0)

    def rows_at(self, i: int, t: int):
        """``_store_row``'s ``at`` for a stacked statistic of leading
        block ``i``: each chunk to its own head's row of a (fold x lanes,
        1, bq) block, at its positions."""
        n_l = self.lanes
        return lambda c, w: (i * n_l + (c // t) % n_l, slice(None),
                             slice(c // t // n_l * t + c % t,
                                   c // t // n_l * t + c % t + w))

    def merge(self, parts, src=None):
        """One (rows, lanes) block from a (rows, lanes) part a head: head
        ``hh``'s lanes from ``parts[hh]``, where it computed them at its
        own lanes, or at the lanes ``src`` of its shared k or v head."""
        if self.lanes == 1:
            return parts[0]
        lane, out = self._lane(parts[0]), None
        for hh in range(self.lanes - 1, -1, -1):
            p = self._move(parts[hh], src, hh)
            out = p if out is None else jax.lax.select(lane == hh, p, out)
        return out


def _stacked(rel, n: int):
    """A tile's position differences for ``n`` heads' rows stacked."""
    return rel if n == 1 else jnp.concatenate([rel] * n, axis=0)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs_s, m_s, l_s, acc_s, *,
            scale: float, causal: bool, bq: int, bk: int, tq: int, tk: int,
            nq: int, nk: int, heads: _Heads, window: int | None = None):
    """Forward.  A grid step holds a (bq, lanes) block of q and a (bk,
    lanes) block of k and v; q takes the softmax scale once a q block, in
    VMEM (exact where the scale is a power of two, as 1/sqrt(64) is);
    inside the step each (tq)-row strip of q sweeps the k tiles that hold
    a live score, the ones under the diagonal with the plain body and only
    the ones the diagonal crosses with the masked one.  Two heads side by
    side are one strip of twice the rows (``_Heads.stack``): each head's
    q with the other's lanes zeroed, so the 128-deep contraction meets its
    own lanes alone, at the MXU's cost of a 64-deep one, and P V is taken
    over all lanes and only the head's kept at the flush.  m and l are
    kept lane-replicated (rows, 128), as in jax's reference kernel."""
    qi = pl.program_id(1) if nq > 1 else 0
    ki = pl.program_id(2) if nk > 1 else 0
    rk, rv = heads.halves()
    n_l = heads.lanes
    d = v_ref.shape[-1]          # the value block's lanes: acc's and o's
    n = bk // tk

    @_when(ki == 0)
    def _init():
        for i in range(heads.fold):
            q = (q_ref[i] * scale).astype(qs_s.dtype)
            qs_s[i] = heads.stack(
                [heads.query(q, hh, rk) for hh in range(n_l)], tq)
        m_s[:] = jnp.full_like(m_s, _MASK)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    rel = _stacked(_rel((tq, tk), 0), n_l) if causal else None

    def strip(i, qs, shift):
        rows = pl.ds(qs * n_l * tq, n_l * tq)
        # matmuls run at the INPUT dtype with f32 accumulation
        # (preferred_element_type): bf16 inputs take the fast MXU passes
        q = qs_s[i, rows, :]                                # (lanes tq, lanes)

        def tile(t, w, masked):
            cols = pl.ds(_mult(t * tk, tk), w * tk)
            k = k_ref[i, cols, :]                           # (w * tk, lanes)
            v = v_ref[i, cols, :]
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(_live(rel, shift + t * tk - qs * tq, masked,
                                    window), s, _MASK)
            m_prev = m_s[i, rows, :]                        # (rows, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, w * tk))
            l_s[i, rows, :] = (alpha * l_s[i, rows, :]
                               + jnp.sum(p, axis=1, keepdims=True))
            acc_s[i, rows, :] = (
                acc_s[i, rows, :] * _lanes(alpha, d)
                + jax.lax.dot_general(p.astype(v.dtype), v,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32))
            m_s[i, rows, :] = m_new

        # every row's first live tile holds its column 0 or its own
        # diagonal, so m is finite from the first tile on and a masked
        # score's exp is an exact 0 with no guard
        if window is None or shift is None:
            n_full, n_live = _k_bounds(qs * tq, tq, shift, tk, n)
            _loop(0, n_full, lambda t, w: tile(t, w, False), _SPAN)
            _loop(n_full, n_live, lambda t, w: tile(t, w, True))
        else:
            # a row whose scores in the window's edge tile are all masked
            # leaves m at _MASK there and sums weights of 1; the first
            # live score that follows (its own diagonal at the latest)
            # rescales that sum by exp(_MASK - m) = 0 exactly
            _band_loop(n, lambda t: shift + t * tk - qs * tq, tq, tk,
                       window, tile, _SPAN)

    def sweep(shift):
        for i in range(heads.fold):
            for qs in range(bq // tq):
                strip(i, qs, shift)

    # a k block wholly above the q block's rows is dead: no kind claims
    # its step, and the index map repeats the last live block so that no
    # DMA is issued for it either
    if window is None:
        _each_kind(ki * bk - qi * bq, bq, bk, causal, True, sweep)
    else:
        _each_window_kind(ki * bk - qi * bq, bq, nq, window, sweep)

    @_when(ki == nk - 1)
    def _flush():
        for i in range(heads.fold):
            l = l_s[i]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[i] = heads.unstack(acc_s[i] / _lanes(l, d), tq,
                                     rv).astype(o_ref.dtype)
            # per-row logsumexp as ROWS (1, bq) a head: what the
            # backward's transposed scores subtract without any relayout
            _store_row(lse_ref, m_s[i] + jnp.log(l), heads.rows_at(i, tq),
                       tq)


def _tiles(bq: int, bk: int):
    """Sub-tile edges for resident blocks (bq, bk): the largest divisors
    within ``_TILE`` (found by halving); a block with no lane-aligned
    divisor (S = 1000 unpadded: interpret mode only, the chip wants
    aligned blocks) is one tile rather than hundreds of slivers."""
    def one(b):
        t = _fit_block(_TILE, b)
        return b if t % _LANE and b <= 4 * _TILE else t
    return one(bq), one(bk)


def _count_steps(s: int, bq: int, bk: int, tq: int, tk: int, causal: bool,
                 sweep: str, window: int | None = None):
    """What a static-offset program does for one head: tiles computed
    without a mask, tiles computed with one, and grid steps visited but
    skipped.  ``sweep`` is "k" (forward, dQ: q strips sweep k tiles) or
    "q" (dK/dV and the fused backward: k strips sweep q tiles).  With a
    ``window`` both sweeps visit the same tiles (``_band``)."""
    out = {"unmasked": 0, "masked": 0, "dead": 0}
    for qi in range(s // bq):
        for ki in range(s // bk):
            r0, c0 = qi * bq, ki * bk
            if window is not None:
                kinds = [_band(c0 + t * tk - r0 - qs * tq, tq, tk, window)
                         for qs in range(bq // tq) for t in range(bk // tk)]
                live = [k for k in kinds if k[0]]
                out["dead"] += not live
                out["masked"] += sum(k[1] or k[2] for k in live)
                out["unmasked"] += sum(not (k[1] or k[2]) for k in live)
            elif not causal:
                out["unmasked"] += (bq // tq) * (bk // tk)
            elif c0 > r0 + bq - 1:
                out["dead"] += 1
            elif sweep == "k":
                for qs in range(bq // tq):
                    full, live = _k_bounds(r0 + qs * tq, tq, c0, tk, bk // tk)
                    out["unmasked"] += full
                    out["masked"] += live - full
            else:
                for ks in range(bk // tk):
                    live, full = _q_bounds(c0 + ks * tk, tk, r0, tq, bq // tq)
                    out["unmasked"] += bq // tq - full
                    out["masked"] += full - live
    return out


def _record_plan(kernel: str, s: int, d: int, causal: bool, sweep: str,
                 bq: int, bk: int, tq: int, tk: int, heads: _Heads,
                 window: int | None = None, vmem_limit: int | None = None):
    """The mechanism's gauge (docs/telemetry.md): when a static-offset
    program is built (trace time, never a step), what was chosen for
    (kernel, S, D, causal) and what its grid then does a head, one value
    for each ``what``: bq, bk, tq, tk, fold, ``lane_heads`` (the heads a
    block of the caller's token-major arrays holds, read in place: 2; 0
    where the call goes through head-major copies), and the
    ``_count_steps`` kinds.  A windowed program's gauge carries its
    ``window`` as one more label; a fused backward built under a raised
    VMEM limit one more ``what``, ``vmem_limit`` (bytes)."""
    plan = dict(bq=bq, bk=bk, tq=tq, tk=tk, fold=heads.fold,
                lane_heads=heads.lanes if heads.inplace else 0,
                **_count_steps(s, bq, bk, tq, tk, causal, sweep, window))
    if vmem_limit is not None:
        plan["vmem_limit"] = vmem_limit
    more = {} if window is None else {"window": window}
    for what, n in plan.items():
        _tm.set_gauge("pallas.flash_attention.plan", n, kernel=kernel, s=s,
                      d=d, causal=causal, what=what, **more)


def _cols(heads: _Heads, group: int = 1, part: int = 0):
    """Grid step -> (leading, column) block of an array whose heads serve
    ``group`` query heads each (no copy of a k or v head exists per query
    head in HBM): in place the step's column block of the batch row, or
    the one that holds the head its query heads share, ``part`` (q 0, k 1,
    v 2) blocks of a row further on in a packed array; head-major the
    head itself."""
    nj = heads.nj
    if heads.inplace:
        skip = part * nj if heads.packed else 0
        return lambda g: (g // nj, g % nj // group + skip)
    return lambda g: (g // group, 0)


def _n_heads(shape, d: int) -> int:
    """Query heads in all of a (leading, S, width) q array."""
    return shape[0] * shape[2] // d


@functools.lru_cache(maxsize=64)
def _build(qshape, d, dv, bq, bk, dtype_str, scale, causal, interpret,
           heads: _Heads, window: int | None = None):
    """The forward program: ``call(q, k, v) -> (out, lse)`` on arrays laid
    out as ``heads`` says, q's of ``qshape``; ``out`` is q's array with
    the value head's width ``dv``, ``lse`` (heads, 1, S) float32, rows.
    ``d`` is a head's width in q and k; ``window`` keeps the scores of
    the last ``window`` positions only."""
    s = qshape[1]
    ht, fl = _n_heads(qshape, d), heads.fold * heads.lanes
    nq, nk = s // bq, s // bk
    tq, tk = _tiles(bq, bk)
    kern = functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                             bk=bk, tq=tq, tk=tk, nq=nq, nk=nk, heads=heads,
                             window=window)
    _record_plan("flash_fwd", s, d, causal, "k", bq, bk, tq, tk, heads,
                 window)
    qc, kc, vc = (_cols(heads), _cols(heads, heads.gk, 1),
                  _cols(heads, heads.gv, 2))
    wq, wv = heads.lanes * d, heads.lanes * dv

    def k_of(qi, ki):
        if causal:
            # dead steps re-name the last live k block: no new DMA
            ki = jnp.minimum(ki, ((qi + 1) * bq - 1) // bk)
        if window is not None:
            ki = jnp.maximum(ki, jnp.maximum(qi * bq - window + 1, 0) // bk)
        return ki

    def spec(rows, w, cols, axis):
        def index(g, qi, ki):
            lead, col = cols(g)
            return lead, (qi if axis == "q" else k_of(qi, ki)), col
        return pl.BlockSpec((heads.fold, rows, w), index)

    dtype = jnp.dtype(dtype_str)
    call = pl.pallas_call(
        kern,
        grid=(ht // fl, nq, nk),
        in_specs=[spec(bq, wq, qc, "q"), spec(bk, wq, kc, "k"),
                  spec(bk, wv, vc, "k")],
        out_specs=(
            spec(bq, wv, qc, "q"),
            pl.BlockSpec((fl, 1, bq), lambda g, qi, ki: (g, 0, qi)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(
                (qshape[0], s, qshape[2] // d * dv), dtype),
            jax.ShapeDtypeStruct((ht, 1, s), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((heads.fold, heads.lanes * bq, wq), dtype),
            pltpu.VMEM((heads.fold, heads.lanes * bq, _LANE), jnp.float32),
            pltpu.VMEM((heads.fold, heads.lanes * bq, _LANE), jnp.float32),
            pltpu.VMEM((heads.fold, heads.lanes * bq, wv), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )
    return jax.jit(call)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 style): given saved per-row logsumexp
# L and D = rowsum(dO * O) (summed by the kernels in place, by XLA for
# head-major arrays), recompute P blockwise — O(S·d) memory end to end, no
# S×S materialization in the backward either.  Two heads side by side take
# their k and v (the dK/dV sweep) or their q and dO (the dQ pass) with the
# other head's lanes zeroed, a head, once a block.
#
# Where the factors of ``scale`` went.  q takes scale once a q block in
# VMEM (qs), so s = qs·k needs none; dS = P∘(dP − D) is kept WITHOUT scale; then
# dK = dSᵀ·qs already holds it (no second multiply), dV = Pᵀ·dO never had
# it, and dQ = scale · dS·k takes it once a q block, on (bq, d), at the
# flush.
#
# ``_bwd_dkv_kernel`` computes the TRANSPOSED scores k·qᵀ with lse and D as
# rows, so dV += Pᵀ·dO and dK += dSᵀ·qs are plain products (no (bq, bk)
# transpose); with ``with_dq`` it is the whole backward in one sweep: dQ of
# the head stays in VMEM (S x D float32) and takes dSᵀ transposed once.
# ``_bwd_dq_kernel`` is the second pass where that dQ does not fit, and for
# the ring hop; it computes the plain scores, whose dQ += dS·k is plain.
# ---------------------------------------------------------------------------


def _recompute(a, b, c, e, lse, dd, live):
    """P and dS of one tile from operands in either orientation:
    s = a·bᵀ, dP = c·eᵀ (plain: a, c = q, dO and b, e = k, v with lse, D
    as lane-replicated columns; transposed: a, c = k, v and b, e = q, dO
    with lse, D as rows, or with heads stacked along a's rows as
    (heads, 1, columns) rows, a head's to its own rows).  ``live`` masks
    the tile, or is None."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if live is not None:
        s = jnp.where(live, s, _MASK)
    dp = jax.lax.dot_general(c, e, _NT, preferred_element_type=jnp.float32)
    if lse.ndim == 3:
        s, dp = (x.reshape(lse.shape[0], -1, x.shape[1]) for x in (s, dp))
    p = jnp.exp(s - lse)               # exact probabilities; masked -> 0
    ds = p * (dp - dd)
    if lse.ndim == 3:
        p, ds = (x.reshape(-1, x.shape[2]) for x in (p, ds))
    return p, ds


def _offsets(refs, traced: bool):
    """Global offsets arrive as SMEM scalars on the ring hop (a rank's
    block sits at an axis_index-dependent position) and are zero at trace
    time otherwise; only their difference matters."""
    if traced:
        return refs[1][0] - refs[0][0], refs[2:]
    return 0, refs


def _dd_column(heads, do, o, hh):
    """D = rowsum(dO ∘ O) of head ``hh``'s rows, over its own lanes of a
    (rows, lanes) tile of dO and of O, float32, lane-replicated."""
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if heads.lanes > 1:
        prod = heads._keep(prod, hh)
    col = jnp.sum(prod, axis=1, keepdims=True)
    return jnp.broadcast_to(col, (col.shape[0], _LANE))


def _bwd_dq_kernel(*refs, scale, causal, bq, bk, tq, tk, nq, nk, traced,
                   heads, window=None):
    # ``x_ref``: D as columns, or in place O, whose rows the kernel sums;
    # two heads side by side: q, dO, lse and D stacked once a q block
    off, refs = _offsets(refs, traced)
    q_ref, k_ref, v_ref, do_ref, lse_ref, x_ref, dq_ref, qs_s, acc_s = refs[:9]
    n_l = heads.lanes
    do_s, lse_s, dd_s = refs[9:] if n_l > 1 else (do_ref, lse_ref, x_ref)
    qi = pl.program_id(1) if nq > 1 else 0
    ki = pl.program_id(2) if nk > 1 else 0
    rk, rv = heads.halves()
    n = bk // tk

    @_when(ki == 0)
    def _init():
        for i in range(heads.fold):
            q = (q_ref[i] * scale).astype(qs_s.dtype)
            qs_s[i] = heads.stack(
                [heads.query(q, hh, rk) for hh in range(n_l)], tq)
        acc_s[:] = jnp.zeros_like(acc_s)
        if n_l > 1:
            do = do_ref[0]
            do_s[0] = heads.stack(
                [heads.query(do, hh, rv) for hh in range(n_l)], tq)
            lse_s[0] = heads.stack([lse_ref[hh] for hh in range(n_l)], tq)
            dd_s[0] = heads.stack([_dd_column(heads, do, x_ref[0], hh)
                                   for hh in range(n_l)], tq)

    rel = _stacked(_rel((tq, tk), 0), n_l) if causal else None

    def strip(i, qs, shift):
        rows = pl.ds(qs * n_l * tq, n_l * tq)
        q = qs_s[i, rows, :]                                # scaled
        do = do_s[i, rows, :]
        lse = lse_s[i, rows, :]                             # (rows, 128)
        dd = dd_s[i, rows, :]

        def tile(t, w, masked):
            cols = pl.ds(_mult(t * tk, tk), w * tk)
            k = k_ref[i, cols, :]
            v = v_ref[i, cols, :]
            live = _live(rel, shift + t * tk - qs * tq, masked,
                         window) if masked else None
            _, ds = _recompute(q, k, do, v, _lanes(lse, w * tk),
                               _lanes(dd, w * tk), live)
            acc_s[i, rows, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if window is None or shift is None:
            n_full, n_live = _k_bounds(qs * tq, tq, shift, tk, n)
            _loop(0, n_full, lambda t, w: tile(t, w, False), _SPAN)
            _loop(n_full, n_live, lambda t, w: tile(t, w, True))
        else:
            _band_loop(n, lambda t: shift + t * tk - qs * tq, tq, tk,
                       window, tile, _SPAN)

    def sweep(shift):
        for i in range(heads.fold):
            for qs in range(bq // tq):
                strip(i, qs, shift)

    if window is None:
        _each_kind(ki * bk + off - qi * bq, bq, bk, causal, not traced,
                   sweep)
    else:
        _each_window_kind(ki * bk - qi * bq, bq, nq, window, sweep)

    @_when(ki == nk - 1)
    def _flush():
        for i in range(heads.fold):
            dq_ref[i] = (heads.unstack(acc_s[i], tq, rk)
                         * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, tq, tk, nq, nk, traced,
                    heads, with_dq, window=None):
    # ``x_ref``: D as rows, or in place O, whose rows the kernel sums into
    # the rows ``dd_s`` once a step; two heads side by side: their k and v
    # stacked once a k block (``kh_s``, ``vh_s``), and dK, dV with them
    off, refs = _offsets(refs, traced)
    q_ref, k_ref, v_ref, do_ref, lse_ref, x_ref = refs[:6]
    n_out = 1 if heads.packed else 2 + with_dq
    outs, scratch = refs[6:6 + n_out], list(refs[6 + n_out:])
    qs_s = scratch.pop(0)
    dq_s = scratch.pop(0) if with_dq else None
    dk_s, dv_s = scratch.pop(0), scratch.pop(0)
    n_l = heads.lanes
    dd_s = scratch.pop(0) if heads.inplace else x_ref
    kh_s, vh_s = ((scratch.pop(0), scratch.pop(0)) if n_l > 1
                  else (k_ref, v_ref))
    if heads.packed:
        grad = _packed_writer(outs[0], *scratch, heads)
        dq_ref = dk_ref = dv_ref = None
    else:
        dq_ref, dk_ref, dv_ref = outs if with_dq else (None,) + outs
    ki = pl.program_id(1) if nk > 1 else 0
    qi = pl.program_id(2) if nq > 1 else 0
    rk, rv = heads.halves()
    n = bq // tq
    first = (ki == 0) if nq == 1 else ((ki == 0) & (qi == 0))
    last = (ki == nk - 1) if nq == 1 else ((ki == nk - 1) & (qi == nq - 1))

    @_when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)
        if n_l > 1:
            k, v = k_ref[0], v_ref[0]
            kh_s[0] = heads.stack(
                [heads.key(k, hh, rk) for hh in range(n_l)], tk)
            vh_s[0] = heads.stack(
                [heads.key(v, hh, rv) for hh in range(n_l)], tk)

    if with_dq:
        @_when(first)
        def _init_dq():
            dq_s[:] = jnp.zeros_like(dq_s)

    # rows of a transposed tile are k positions, lanes are q positions
    rel = _stacked(_rel((tk, tq), 1), n_l) if causal else None

    def strip(i, ks, shift):
        # each head's k and v at its own lanes, the rest zeroed: k q^T and
        # v dO^T meet its lanes of q and dO alone, and dS^T k lands there
        krows = pl.ds(ks * n_l * tk, n_l * tk)
        k = kh_s[i, krows, :]                               # (rows, lanes)
        v = vh_s[i, krows, :]

        def rows_of(ref, t, w):
            # the (1, w * tq) row of a statistic over q tiles t .. t+w-1;
            # heads side by side: (heads, 1, w * tq), a row each
            at = i if n_l == 1 else pl.ds(i * n_l, n_l)
            if w == 1:
                return ref[at, t]
            return jnp.concatenate([ref[at, t + j] for j in range(w)],
                                   axis=-1)

        def tile(t, w, masked):
            qrows = pl.ds(_mult(t * tq, tq), w * tq)
            q = qs_s[i, qrows, :]                   # (w * tq, lanes), scaled
            do = do_ref[i, qrows, :]
            live = _live(rel, shift + ks * tk - t * tq, masked,
                         window) if masked else None
            p, ds = _recompute(k, q, v, do, rows_of(lse_ref, t, w),
                               rows_of(dd_s, t, w), live)   # (rows, w * tq)
            dv_s[i, krows, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = ds.astype(q.dtype)
            dk_s[i, krows, :] += jax.lax.dot_general(
                ds, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if with_dq:
                grows = pl.ds(_mult(qi * bq + t * tq, tq), w * tq)
                dq_s[i, grows, :] += jax.lax.dot_general(
                    ds, k, _TN, preferred_element_type=jnp.float32)

        if window is None or shift is None:
            t_live, t_full = _q_bounds(
                None if shift is None else shift + ks * tk, tk, 0, tq, n)
            _loop(t_live, t_full, lambda t, w: tile(t, w, True))
            _loop(t_full, n, lambda t, w: tile(t, w, False), _SPAN)
        else:
            _band_loop(n, lambda t: shift + ks * tk - t * tq, tq, tk,
                       window, tile, _SPAN)

    def sweep(shift):
        for i in range(heads.fold):
            for ks in range(bk // tk):
                strip(i, ks, shift)

    # once a step that holds a live score: q takes the softmax scale, and
    # in place D = rowsum(dO ∘ O) of the q block is summed a head
    live = True
    if causal and not traced:
        lo = ki * bk - qi * bq
        live = lo <= bq - 1
        if window is not None:
            live = live & (lo + bk - 1 > -window)

    @_when(live)
    def _prep():
        qs_s[:] = (q_ref[:] * scale).astype(qs_s.dtype)
        if heads.inplace:
            do, o = do_ref[0], x_ref[0]
            for hh in range(n_l):
                _store_row(dd_s, _dd_column(heads, do, o, hh),
                           lambda c, w, hh=hh: (hh, c // tq, slice(None),
                                                slice(c % tq, c % tq + w)),
                           tq)

    if window is None:
        _each_kind(ki * bk + off - qi * bq, bq, bk, causal, not traced,
                   sweep)
    else:
        _each_window_kind(ki * bk - qi * bq, bq, nq, window, sweep)

    @_when(qi == nq - 1)
    def _flush():
        if heads.packed:
            krows = pl.ds(_mult(ki * bk, bk), bk)
            grad(1, krows, heads.unstack(dk_s[0], tk))
            grad(2, krows, heads.unstack(dv_s[0], tk))
            return
        for i in range(heads.fold):
            dk_ref[i] = heads.unstack(dk_s[i], tk).astype(dk_ref.dtype)
            dv_ref[i] = heads.unstack(dv_s[i], tk).astype(dv_ref.dtype)

    if with_dq:
        @_when(last)
        def _flush_dq():
            if heads.packed:
                grad(0, pl.ds(0, dq_s.shape[1]), dq_s[0] * scale)
            else:
                dq_ref[:] = (dq_s[:] * scale).astype(dq_ref.dtype)


def _packed_writer(out_ref, buf_q, buf_k, buf_v, sems, heads: _Heads):
    """``grad(part, rows, value)``: ``value``, the (rows, 128) gradient of
    this grid step's two heads of q (``part`` 0), k (1) or v (2), copied
    from VMEM to its place in the packed (B, S, 3 x H x D) gradient in
    HBM, the columns ``part`` x H x D on: one array for what the packed
    projection's backward reads, no concatenation after the kernel."""
    g = pl.program_id(0)
    b, j = jax.lax.div(g, heads.nj), jax.lax.rem(g, heads.nj)

    def grad(part, rows, value):
        buf = (buf_q, buf_k, buf_v)[part]
        buf[...] = value.astype(buf.dtype)
        col = pl.multiple_of((part * heads.nj + j) * _LANE, _LANE)
        copy = pltpu.make_async_copy(
            buf, out_ref.at[b, rows, pl.ds(col, _LANE)], sems.at[part])
        copy.start()
        copy.wait()
    return grad


def _whole_lanes(w: int) -> int:
    """A width as VMEM holds it: rows are whole 128-lane registers."""
    return -(-w // _LANE) * _LANE


def _fused_vmem_bytes(s: int, d: int, dv: int, bq: int, bk: int, dtype,
                      out_dtype, kv_dtype, hfold: int, lanes: int = 1) -> int:
    """What the fused backward holds in VMEM a grid step, from its specs:
    the resident dQ (float32 scratch and the output block), the q, dO, k,
    v blocks and q scaled, lse and D rows (a (1, tq) row takes 8
    sublanes), and where ``lanes`` heads lie side by side in place O's
    block, k and v a head and D as one scratch; dK and dV (float32
    scratch a head and their blocks), every block in two buffers; and a
    body's float32 tiles (s, p, dP, dS and the copies the products take,
    six of ``_SPAN`` tiles).  ``d`` and ``dv`` are a block's lanes."""
    size = lambda t: jnp.dtype(t).itemsize
    tq, tk = _tiles(bq, bk)
    side = lanes > 1
    wide = _whole_lanes(d) + _whole_lanes(dv)
    dq = s * _whole_lanes(d) * (4 + 2 * size(out_dtype))
    blocks = (2 * (bq + bk) * wide + side * 2 * bq * _whole_lanes(dv)
              + bq * _whole_lanes(d) + side * lanes * bk * wide) * size(dtype)
    rows = lanes * (4 - side) * (bq // tq) * 8 * _whole_lanes(tq) * 4
    dkv = bk * wide * (4 * lanes + 2 * size(kv_dtype))
    tiles = 6 * tk * min(_SPAN * tq, bq) * 4
    return hfold * (dq + blocks + rows + dkv) + tiles


def _fused_backward(s: int, d: int, out_dtype, hfold: int, traced: bool,
                    dv: int | None = None, bq: int = 1024, bk: int = 1024,
                    dtype=None, kv_dtype=None, lanes: int = 1):
    """The backward's form, from the shapes alone, as ``(fused,
    vmem_limit)``.  One sweep (dK, dV and dQ) with no limit named where
    the offsets are static and the resident dQ fits beside the blocks
    under the default limit (``_FUSED_DQ_BYTES``); one sweep under a
    ``vmem_limit`` of the kernel's own buffers and a quarter more for
    Mosaic's internal scratch, in whole MiB, where that stays within
    ``_FUSED_VMEM_CAP``; two passes otherwise, and on the ring hop.  Two
    heads side by side (``lanes``) keep a float32 dK and dV each, and the
    second head's count against the default limit's room beside dQ."""
    if traced:
        return False, None
    dv = d if dv is None else dv
    resident = hfold * s * _whole_lanes(d) * (
        4 + 2 * jnp.dtype(out_dtype).itemsize) + (lanes - 1) * bk * (
            _whole_lanes(d) + _whole_lanes(dv)) * 4
    if resident <= _FUSED_DQ_BYTES:
        return True, None
    need = _fused_vmem_bytes(s, d, dv, bq, bk, dtype or out_dtype, out_dtype,
                             kv_dtype or out_dtype, hfold, lanes)
    mib = 1024 * 1024
    limit = -(-(need + need // 4) // mib) * mib
    return (True, limit) if limit <= _FUSED_VMEM_CAP else (False, None)


@functools.lru_cache(maxsize=64)
def _build_bwd(qshape, d, dv, bq, bk, dtype_str, scale, causal, interpret,
               heads: _Heads, out_dtype_str=None, traced: bool = False,
               window: int | None = None, kv_dtype_str=None):
    """The backward programs, ``(dq_call, dkv_call)``.

    Operands of both: ``[qoff, koff,] q, k, v, dO, lse, D``, the arrays
    laid out as ``heads`` says (q's of ``qshape``, dO as the forward's
    output), the two int32[1] offsets only when ``traced``; in place O
    (as dO) takes D's place and the kernels sum D = rowsum(dO ∘ O)
    themselves.  ``dkv_call`` takes lse
    (and D) as rows cut to the q tile, (heads, S/tq, 1, tq)
    (``_stat_rows``), ``dq_call`` as lane-replicated columns (heads, S,
    128).  Where ``_fused_backward`` says one sweep ``dq_call`` is None
    and ``dkv_call`` returns (dq, dk, dv), built under the VMEM limit it
    names if it names one; else it returns (dk, dv).  ``d``, ``dv`` and
    ``window`` as in ``_build``; dK and dV come back one a QUERY head, in
    q's array and in the output's, in ``kv_dtype_str`` (float32 where the
    caller adds them up over each group; dQ keeps the output type, which
    is what decides whether it fits VMEM).
    """
    out_dtype = jnp.dtype(out_dtype_str or dtype_str)
    kv_dtype = jnp.dtype(kv_dtype_str or out_dtype)
    s = qshape[1]
    ht, fold, fl = _n_heads(qshape, d), heads.fold, heads.fold * heads.lanes
    wq, wv = heads.lanes * d, heads.lanes * dv
    nq, nk = s // bq, s // bk
    tq, tk = _tiles(bq, bk)
    fused, vmem_limit = _fused_backward(s, wq, out_dtype, fold, traced, wv,
                                        bq, bk, dtype_str, kv_dtype,
                                        heads.lanes)
    common = dict(scale=scale, causal=causal, bq=bq, bk=bk, tq=tq, tk=tk,
                  nq=nq, nk=nk, traced=traced, heads=heads, window=window)
    clamp = causal and not traced
    offs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2 if traced else []
    if not traced:
        plan = (bq, bk, tq, tk, heads, window)
        _record_plan("flash_bwd_dkv", s, d, causal, "q", *plan, vmem_limit)
        if not fused:
            _record_plan("flash_bwd_dq", s, d, causal, "k", *plan)
    qc, kc, vc = (_cols(heads), _cols(heads, heads.gk, 1),
                  _cols(heads, heads.gv, 2))
    per_q = [jax.ShapeDtypeStruct(qshape, kv_dtype),
             jax.ShapeDtypeStruct((qshape[0], s, qshape[2] // d * dv),
                                  kv_dtype)]

    def spec(rows, w, cols, at):
        def index(g, a, b):
            lead, col = cols(g)
            return lead, at(a, b), col
        return pl.BlockSpec((fold, rows, w), index)

    # --- dK/dV (and dQ when fused): k blocks outer, q blocks swept -------
    def q_of(ki, qi):
        if clamp:
            # q blocks wholly above the k block are dead steps: re-name
            # the first live one, so no DMA is issued for them
            qi = jnp.maximum(qi, (ki * bk) // bq)
        if window is not None:
            # and so do those wholly past the window of its last column
            qi = jnp.minimum(qi, ((ki + 1) * bk + window - 2) // bq)
        return qi

    k_at = lambda ki, qi: ki
    rowspec = pl.BlockSpec((fl, bq // tq, 1, tq),
                           lambda g, ki, qi: (g, q_of(ki, qi), 0, 0))
    out_specs = [spec(bk, wq, qc, k_at), spec(bk, wv, qc, k_at)]
    out_shape = per_q
    dtype = jnp.dtype(dtype_str)
    n_l = heads.lanes
    scratch = [pltpu.VMEM((fold, bq, wq), dtype),
               pltpu.VMEM((fold, n_l * bk, wq), jnp.float32),
               pltpu.VMEM((fold, n_l * bk, wv), jnp.float32)]
    if heads.inplace:
        scratch += [pltpu.VMEM((fl, bq // tq, 1, tq), jnp.float32),
                    pltpu.VMEM((fold, n_l * bk, wq), dtype),
                    pltpu.VMEM((fold, n_l * bk, wv), dtype)]
    if fused:
        out_shape = [jax.ShapeDtypeStruct(qshape, out_dtype)] + per_q
        out_specs.insert(0, spec(s, wq, qc, lambda ki, qi: 0))
        scratch.insert(1, pltpu.VMEM((fold, s, wq), jnp.float32))
    if heads.packed:
        # dQ, dK and dV go into one (B, S, 3 x H x D) gradient, written by
        # the kernel's own copies from VMEM at its flushes
        out_shape = [jax.ShapeDtypeStruct(
            (qshape[0], s, 3 * qshape[2]), out_dtype)]
        out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((s, wq), out_dtype),
                    pltpu.VMEM((bk, wq), out_dtype),
                    pltpu.VMEM((bk, wv), out_dtype),
                    pltpu.SemaphoreType.DMA((3,))]
    # a program that fits the default limit is built as before, to the
    # letter: no compiler_params
    more = {} if vmem_limit is None else dict(
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit))
    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, with_dq=fused, **common),
        grid=(ht // fl, nk, nq),
        in_specs=offs + [spec(bq, wq, qc, q_of), spec(bk, wq, kc, k_at),
                         spec(bk, wv, vc, k_at), spec(bq, wv, qc, q_of),
                         rowspec,
                         spec(bq, wv, qc, q_of) if heads.inplace else rowspec],
        out_specs=tuple(out_specs) if len(out_specs) > 1 else out_specs[0],
        out_shape=tuple(out_shape) if len(out_shape) > 1 else out_shape[0],
        scratch_shapes=scratch,
        name="flash_bwd_dkv",
        interpret=interpret,
        **more,
    )
    if fused:
        return None, jax.jit(dkv_call)

    # --- dQ: q blocks outer, k blocks swept -------------------------------
    def k_of(qi, ki):
        if clamp:
            ki = jnp.minimum(ki, ((qi + 1) * bq - 1) // bk)
        if window is not None:
            ki = jnp.maximum(ki, jnp.maximum(qi * bq - window + 1, 0) // bk)
        return ki

    q_at = lambda qi, ki: qi
    colspec = pl.BlockSpec((fl, bq, _LANE), lambda g, qi, ki: (g, qi, 0))
    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(ht // fl, nq, nk),
        in_specs=offs + [spec(bq, wq, qc, q_at), spec(bk, wq, kc, k_of),
                         spec(bk, wv, vc, k_of), spec(bq, wv, qc, q_at),
                         colspec,
                         spec(bq, wv, qc, q_at) if heads.inplace else colspec],
        out_specs=spec(bq, wq, qc, q_at),
        out_shape=jax.ShapeDtypeStruct(qshape, out_dtype),
        scratch_shapes=[pltpu.VMEM((fold, n_l * bq, wq), dtype),
                        pltpu.VMEM((fold, n_l * bq, wq), jnp.float32)] + (
            [pltpu.VMEM((fold, n_l * bq, wv), dtype),
             pltpu.VMEM((fold, n_l * bq, _LANE), jnp.float32),
             pltpu.VMEM((fold, n_l * bq, _LANE), jnp.float32)]
            if n_l > 1 else []),
        name="flash_bwd_dq",
        interpret=interpret,
    )
    return jax.jit(dq_call), jax.jit(dkv_call)


def _stat_rows(x, bq: int):
    """(H, S) per-row statistic -> (H, S/tq, 1, tq): the row each q tile
    of the transposed backward reads, picked by a leading index."""
    tq = _tiles(bq, bq)[0]
    return x.reshape(x.shape[0], x.shape[1] // tq, 1, tq)


def _stat_cols(x):
    """(H, S) per-row statistic -> (H, S, 128) lane-replicated columns
    for the plain-score dQ pass (TPU blocks want a 128-wide minor dim)."""
    return jnp.broadcast_to(x[:, :, None], (*x.shape, _LANE))


# ---------------------------------------------------------------------------
# carry-in/carry-out flash kernel: one ring-attention hop.  The online-
# softmax state (m, l, acc) enters and leaves as HBM arrays so it can flow
# around the ppermute ring; global q/k offsets arrive as scalars because a
# rank's blocks sit at traced (axis_index-dependent) global positions.
# ---------------------------------------------------------------------------


def _carry_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, m_in_ref,
                  l_in_ref, acc_in_ref, m_out_ref, l_out_ref, acc_out_ref,
                  m_s, l_s, acc_s, *, scale, causal, bq, bk, k_steps,
                  hfold):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[:] = m_in_ref[:, :, :1]
        l_s[:] = l_in_ref[:, :, :1]
        acc_s[:] = acc_in_ref[:]

    if causal:
        # skip k blocks wholly after this q block's last row: on the hops
        # where the whole incoming K/V block is in the masked future the
        # kernel degenerates to a copy-through
        live = (koff_ref[0] + ki * bk
                <= qoff_ref[0] + qi * bq + bq - 1)
    else:
        live = ki == ki

    @pl.when(live)
    def _accumulate():
        # native-dtype MXU passes with f32 accumulation; ``hfold`` heads
        # ride each grid step as a batched dot (see _kernel)
        q = q_ref[:]                                      # (hfold, bq, d)
        k = k_ref[:]                                      # (hfold, bk, d)
        v = v_ref[:]                                      # (hfold, bk, d)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (hfold, bq, bk)
        if causal:
            qpos = qoff_ref[0] + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (hfold, bq, bk), 1)
            kpos = koff_ref[0] + ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (hfold, bq, bk), 2)
            s = jnp.where(kpos <= qpos, s, -jnp.inf)

        m_prev = m_s[:]
        blk_max = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_s[:] = l_s[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new

    @pl.when(ki == k_steps - 1)
    def _flush():
        m_out_ref[:] = jnp.broadcast_to(m_s[:], (hfold, bq, _LANE))
        l_out_ref[:] = jnp.broadcast_to(l_s[:], (hfold, bq, _LANE))
        acc_out_ref[:] = acc_s[:]


@functools.lru_cache(maxsize=64)
def _build_carry(h, b, d, bq, bk, dtype_str, scale, causal, interpret,
                 hfold: int = 1):
    k_steps = b // bk
    kern = functools.partial(_carry_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, k_steps=k_steps, hfold=hfold)
    call = pl.pallas_call(
        kern,
        grid=(h // hfold, b // bq, k_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # qoff
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # koff
            pl.BlockSpec((hfold, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((hfold, bk, d), lambda hh, qi, ki: (hh, ki, 0)),
            pl.BlockSpec((hfold, bk, d), lambda hh, qi, ki: (hh, ki, 0)),
            pl.BlockSpec((hfold, bq, _LANE),
                         lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((hfold, bq, _LANE),
                         lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((hfold, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((hfold, bq, _LANE),
                         lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((hfold, bq, _LANE),
                         lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((hfold, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((h, b, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((h, b, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((h, b, d), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((hfold, bq, 1), jnp.float32),
            pltpu.VMEM((hfold, bq, 1), jnp.float32),
            pltpu.VMEM((hfold, bq, d), jnp.float32),
        ],
        name="flash_carry",
        interpret=interpret,
    )
    return call


def flash_attention_hop(q, k, v, m, l, acc, qoff, koff,
                        causal: bool = False, scale: float | None = None,
                        block_q: int = 512, block_k: int = 512,
                        head_fold: int = 1,
                        interpret: bool | None = None):
    """One ring hop of flash attention with explicit online-softmax carry.

    q/k/v: ``(H, B, D)`` blocks (B = per-rank sequence block); m/l/acc:
    the running max/normalizer/accumulator from previous hops (build the
    initial carry with ``flash_carry_init`` — m and l are lane-broadcast
    ``(H, B, _LANE)`` f32 arrays); qoff/koff: global sequence offsets of
    the q and k blocks (traced scalars — a rank's position in the ring is
    ``lax.axis_index``-dependent).  Returns updated (m, l, acc).
    Finalize with ``acc / l[..., :1]`` after the last hop.
    """
    H, B, D = q.shape
    bq, bk = _fit_block(block_q, B), _fit_block(block_k, B)
    hfold = _fit_block(max(int(head_fold), 1), H)
    if interpret is None:
        interpret = not _on_tpu()
    sc = float(1.0 / np.sqrt(D) if scale is None else scale)
    call = _build_carry(H, B, D, bq, bk, str(q.dtype), sc, bool(causal),
                        bool(interpret), hfold)
    qo = jnp.asarray(qoff, jnp.int32).reshape(1)
    ko = jnp.asarray(koff, jnp.int32).reshape(1)
    return call(qo, ko, q, k, v, m, l, acc)


def flash_carry_init(h: int, b: int, d: int):
    """Initial (m, l, acc) carry for ``flash_attention_hop``."""
    return (jnp.full((h, b, _LANE), -jnp.inf, jnp.float32),
            jnp.zeros((h, b, _LANE), jnp.float32),
            jnp.zeros((h, b, d), jnp.float32))


def flash_carry_finalize(m, l, acc, dtype):
    """Turn a final ``flash_attention_hop`` carry into (out, lse):
    ``out = acc / l`` in ``dtype`` (h, b, d) and the per-row logsumexp
    (h, b) f32 the FA2 backward consumes.  All-masked rows (l == 0)
    produce out = 0, lse = 0 — with causal ring layouts every row attends
    at least its own diagonal, so this case never carries gradients."""
    ln = l[:, :, :1]
    ln_safe = jnp.where(ln == 0.0, 1.0, ln)
    out = (acc / ln_safe).astype(dtype)
    m1, l1 = m[:, :, 0], l[:, :, 0]
    m_fin = jnp.where(jnp.isfinite(m1), m1, 0.0)
    lse = m_fin + jnp.log(jnp.where(l1 == 0.0, 1.0, l1))
    return out, lse


def flash_attention_hop_bwd(q, k, v, do, lse, dd, qoff, koff,
                            causal: bool = False, scale: float | None = None,
                            block_q: int = 512, block_k: int = 512,
                            interpret: bool | None = None):
    """Backward of ONE ring hop: the FA2 recompute pass restricted to the
    (local q block) x (resident k/v block) tile pair.

    Because ``p = exp(s - lse)`` is exact given the FINAL logsumexp, each
    hop's gradient contribution is independent and additive: the ring
    backward sums dq contributions locally and circulates dk/dv
    accumulators around the ``ppermute`` ring with their k/v blocks.

    q/k/v/do: ``(H, B, D)``; lse/dd: lane-broadcast ``(H, B, _LANE)`` f32
    (final logsumexp rows and ``D_i = rowsum(dO * O)``); qoff/koff: global
    sequence offsets (traced scalars).  Returns f32 ``(dq, dk, dv)``
    CONTRIBUTIONS for this tile pair — callers accumulate.
    """
    H, B, D = q.shape
    bq, bk = _fit_block(block_q, B), _fit_block(block_k, B)
    if interpret is None:
        interpret = not _on_tpu()
    sc = float(1.0 / np.sqrt(D) if scale is None else scale)
    dq_call, dkv_call = _build_bwd(q.shape, D, D, bq, bk, str(q.dtype), sc,
                                   bool(causal), bool(interpret),
                                   _Heads(width=D), out_dtype_str="float32",
                                   traced=True)
    qo = jnp.asarray(qoff, jnp.int32).reshape(1)
    ko = jnp.asarray(koff, jnp.int32).reshape(1)
    dq = dq_call(qo, ko, q, k, v, do, lse, dd)
    dk, dv = dkv_call(qo, ko, q, k, v, do, _stat_rows(lse[:, :, 0], bq),
                      _stat_rows(dd[:, :, 0], bq))
    return dq, dk, dv


def _dense_attention_shd(q, k, v, causal: bool, scale: float):
    """Dense jnp attention with EXACTLY the kernel's semantics (f32 softmax,
    (S, H, D) layout) — used as the differentiation rule for the kernel."""
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        S = q.shape[0]
        qi = jnp.arange(S)[:, None]
        ki = jnp.arange(S)[None, :]
        s = jnp.where((ki <= qi)[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _lane_heads(H: int, D: int, dv: int, gk: int, gv: int, fold: int) -> int:
    """The heads one column block of the caller's token-major (B, S,
    heads x D) arrays holds, read in place through the index maps: 2
    where two heads of 64 share a 128-lane register (queries, keys and
    values alike, an even count, and a group of k or v heads, if any,
    spanning whole pairs); 0 where the call goes through head-major
    copies.  A head of 128 or more lanes is not read in place: the
    callers that have one build q and k a head at a time (a concatenation
    of columns per head, in ``models/mla_moe.py``), so their arrays lie
    tiled as (B, S, H, D), whose view as (B, S, H x D) is itself a copy;
    the head-major copy is the one XLA fuses into the concatenation."""
    if (fold <= 2 and 2 * D == 2 * dv == _LANE and H % 2 == 0
            and all(g == 1 or g % 2 == 0 for g in (gk, gv))):
        return 2
    return 0


def _heads(q, k, v, fold: int) -> _Heads:
    """How a call on (B, S, heads, width) arrays reaches the kernels."""
    H, D = q.shape[2:]
    gk, gv = H // k.shape[2], H // v.shape[2]
    lanes = _lane_heads(H, D, v.shape[3], gk, gv, fold)
    if lanes:
        return _Heads(1, lanes, D, H // lanes, gk, gv)
    return _Heads(fold, 1, D, 1, gk, gv)


def _into(x, heads: _Heads):
    """(B, S, heads, W) -> the kernels' array: in place the same bytes
    viewed as (B, S, heads x W); head-major (B x heads, S, W), a copy."""
    B, S, H, W = x.shape
    if heads.inplace:
        return x.reshape(B, S, H * W)
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, S, W)


def _out_of(x, B: int, heads: _Heads):
    """The kernels' array of values a query head -> (B, S, heads, W)."""
    if heads.inplace:
        return x.reshape(B, x.shape[1], heads.nj * heads.lanes, -1)
    return jnp.transpose(x.reshape(B, -1, *x.shape[1:]), (0, 2, 1, 3))


def _forward(q, k, v, causal, scale, bq, bk, interpret, hfold, window=None):
    heads = _heads(q, k, v, hfold)
    qx = _into(q, heads)
    out, lse = _build(qx.shape, q.shape[3], v.shape[3], bq, bk, str(q.dtype),
                      scale, causal, interpret, heads, window)(
                          qx, _into(k, heads), _into(v, heads))
    return _out_of(out, q.shape[0], heads), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, causal, scale, bq, bk, interpret, hfold=1,
                window=None):
    return _forward(q, k, v, causal, scale, bq, bk, interpret, hfold,
                    window)[0]


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret, hfold=1,
               window=None):
    o, lse = _forward(q, k, v, causal, scale, bq, bk, interpret, hfold,
                      window)
    # the residual lse is one float a row, (B x H, S): the kernel wrote rows
    return o, (q, k, v, o, lse[:, 0, :])


def _group_sum(t, heads: int, dtype):
    """(B, S, H, W) gradients, one a query head, added up over the query
    heads that share each of the ``heads`` heads."""
    B, S, H, W = t.shape
    if H != heads:
        t = jnp.sum(t.reshape(B, S, heads, H // heads, W), axis=3)
    return t.astype(dtype)


def _flash_bwd(causal, scale, bq, bk, interpret, hfold, window, res, g):
    # FlashAttention-2-style backward: recompute P blockwise from the saved
    # per-row logsumexp — O(S·d) memory, no S×S materialization.  One
    # sweep where a head's dQ fits VMEM, two passes otherwise
    # (_fused_backward)
    q, k, v, o, lse = res
    B, D = q.shape[0], q.shape[3]
    heads = _heads(q, k, v, hfold)
    # a group's dK, dV are summed from its query heads' in float32
    grouped = heads.gk > 1 or heads.gv > 1
    qx, kx, vx, dox = (_into(x.astype(q.dtype), heads) for x in (q, k, v, g))
    if heads.inplace:
        # D = rowsum(dO ∘ O) a row: the kernels sum it from the blocks of
        # dO and O they read (a sum over 64 of a row's 128 lanes in XLA
        # would lay the product out anew first)
        rows = cols = _into(o, heads)
    else:
        dd = jnp.einsum("bshd,bshd->bhs", g.astype(jnp.float32),
                        o.astype(jnp.float32)).reshape(-1, o.shape[1])
        rows, cols = _stat_rows(dd, bq), dd
    dq_call, dkv_call = _build_bwd(
        qx.shape, D, v.shape[3], bq, bk, str(q.dtype), scale, causal,
        interpret, heads, window=window,
        kv_dtype_str="float32" if grouped else None)
    if dq_call is None:
        dq, dk, dv = dkv_call(qx, kx, vx, dox, _stat_rows(lse, bq), rows)
    else:
        dq = dq_call(qx, kx, vx, dox, _stat_cols(lse),
                     cols if heads.inplace else _stat_cols(cols))
        dk, dv = dkv_call(qx, kx, vx, dox, _stat_rows(lse, bq), rows)
    return (_out_of(dq, B, heads).astype(q.dtype),
            _group_sum(_out_of(dk, B, heads), k.shape[2], k.dtype),
            _group_sum(_out_of(dv, B, heads), v.shape[2], v.dtype))


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _packed_heads(qkv) -> _Heads:
    H, D = qkv.shape[3:]
    return _Heads(1, 2, D, H // 2, packed=True)


def _packed_forward(qkv, causal, scale, bq, bk, interpret, window):
    B, S, _, H, D = qkv.shape
    x = qkv.reshape(B, S, 3 * H * D)
    out, lse = _build((B, S, H * D), D, D, bq, bk, str(qkv.dtype), scale,
                      causal, interpret, _packed_heads(qkv), window)(x, x, x)
    return out.reshape(B, S, H, D), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash_packed(qkv, causal, scale, bq, bk, interpret, window):
    """Attention over one (B, S, 3, H, 64) array of q, k and v, the
    result of one projection, read in place; its gradient comes back as
    one such array, so neither direction slices or concatenates."""
    return _packed_forward(qkv, causal, scale, bq, bk, interpret, window)[0]


def _flash_packed_fwd(qkv, causal, scale, bq, bk, interpret, window):
    o, lse = _packed_forward(qkv, causal, scale, bq, bk, interpret, window)
    return o, (qkv, o, lse[:, 0, :])


def _flash_packed_bwd(causal, scale, bq, bk, interpret, window, res, g):
    qkv, o, lse = res
    B, S, _, H, D = qkv.shape
    x, heads = qkv.reshape(B, S, 3 * H * D), _packed_heads(qkv)
    _, dkv_call = _build_bwd((B, S, H * D), D, D, bq, bk, str(qkv.dtype),
                             scale, causal, interpret, heads, window=window)
    return (dkv_call(x, x, x, _into(g.astype(qkv.dtype), heads),
                     _stat_rows(lse, bq), _into(o, heads)).reshape(qkv.shape),)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def _default_config(S: int, D: int):
    """(block, head_fold) of a call that names neither and has no registry
    entry: the rows of q (and of k, v) resident a grid step, and the heads
    a step takes.  1024 rows make the cell's S = 1024 one step a head (no
    dead step), and leave a longer sequence few enough blocks that the
    clamped dead steps are noise; how close the sweep gets to the triangle
    is the sub-tile's business (``_TILE``), not the block's.  One head a
    step: at (1024, 128, 64) two heads a step were 3% faster in the
    kernels (64 grid steps fewer) and cost every process that builds the
    training step 1 to 1.5 s more of tracing and lowering, unrolled or
    looped, which the compile cache does not save; the explicit
    ``head_fold`` is there for a caller who wants the trade."""
    return 1024, 1


def tuned_flash_config(S, H, D, dtype, causal: bool,
                       block_q=None, block_k=None, head_fold=None,
                       default: int | None = None):
    """Resolve (block_q, block_k, head_fold) for a flash call: explicit
    values win; ``None`` consults the autotune registry's entry for
    (S, H, D, dtype, causal) — a 2- or 3-tuple — falling back to
    ``default``²/1, or with no ``default`` to the choice
    ``_default_config`` makes from the shapes.  The tuned head_fold was
    measured WITH the tuned blocks, so it is grafted only when BOTH blocks
    also come from the registry.  A malformed cache entry degrades to the
    defaults, never breaks dispatch.  Callers that cache jitted programs
    must call this OUTSIDE the cache and key on the resolved values (see
    models/ulysses.py) or a later-banked tune would be silently
    ignored."""
    if block_q is not None and block_k is not None and head_fold is not None:
        return block_q, block_k, head_fold
    from ..utils import autotune
    vals = autotune.valid_ints(
        autotune.get("flash_attention",
                     autotune.device_key_for(S, H, D, dtype, bool(causal))),
        (2, 3))
    if default is None:
        default, dfold = _default_config(S, D)
    else:
        dfold = 1
    tq, tk = (vals[0], vals[1]) if vals else (default, default)
    tf = vals[2] if vals and len(vals) == 3 else (1 if vals else dfold)
    use_tuned_fold = block_q is None and block_k is None
    block_q = tq if block_q is None else block_q
    block_k = tk if block_k is None else block_k
    if head_fold is None:
        head_fold = tf if use_tuned_fold else 1
    return block_q, block_k, head_fold


@_tm.traced(name="pallas.flash_attention")
def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    head_fold: int | None = None,
                    interpret: bool | None = None,
                    window: int | None = None):
    """Exact attention over (batch, seq, heads, head_dim) arrays, or
    (seq, heads, head_dim) ones (one row of a batch), without
    materializing the S×S score matrix.

    With heads of 64 (an even count, values 64 wide too, groups of one
    head or of whole pairs) the kernels read q, k, v and write the result
    in the caller's token-major layout: (B, S, H, D) is (B, S, H·D) with
    no copy, and a grid step's block is a column block of it, two heads
    side by side in 128 lanes.  Every other shape goes through head-major
    (B·H, S, D) copies, heads of 128 or more lanes among them (``_lane_heads``:
    the callers that have them build q and k a head at a time, so the
    token-major view would itself be a copy), as do an odd count of
    64-wide heads, values of another width than 64-wide keys, and a
    ``head_fold`` past 2; the gauge
    ``pallas.flash_attention.plan{what=lane_heads}`` says which (2 or 0).
    Causality, the window and the softmax run per row of the batch.
    ``q`` may also carry k and v, with ``k`` and ``v`` None: the
    (B, S, 3·H·D) result of one projection (q's columns, then k's, then
    v's) viewed as ([B,] S, 3, H, D).  With heads of 64 the kernels read
    all three from it in place and the backward writes their gradient as
    one such array, so neither direction slices or concatenates; other
    shapes are split into q, k, v.

    ``k`` and ``v`` may have fewer heads than ``q`` (grouped heads: each
    serves ``H / heads`` consecutive query heads, read in place through the
    kernels' index maps, never repeated in HBM), each its own number, and
    ``v`` a head width of its own, which is then the result's.  ``window``
    (with ``causal``) keeps for every row the scores of its last
    ``window`` positions, itself included; one that covers the sequence
    is the plain causal call.

    ``block_q`` / ``block_k`` are the rows of q and of k, v resident a
    grid step, ``head_fold`` the heads a step takes.  Unnamed,
    they come from the autotune registry's entry for
    (S, B·H, D, dtype, causal) — a (bq, bk) or (bq, bk, hfold) tuple,
    populated by ``utils.autotune`` sweeps — and with no entry from the
    shapes (``_default_config``).  Either way blocks are fitted to the
    sequence length (clipped, then halved until they divide S) and
    ``head_fold`` is clipped to a divisor of B·H.  Inside a step the sweep
    goes tile by tile (``_TILE``), skips the tiles above the causal
    diagonal and masks only those it crosses; the backward is one fused
    sweep where the resident dQ fits VMEM: under the default scoped limit,
    or under a larger one that the kernel names from its buffers, within
    half of the chip's VMEM (``_fused_backward``); past that, two passes.
    Use as the per-rank compute inside ring attention, or standalone
    single-chip.
    """
    packed = None
    if k is None and v is None:
        packed = jnp.asarray(q)
        row = packed.ndim == 4
        packed = packed[None] if row else packed
        if packed.ndim != 5 or packed.shape[2] != 3:
            raise ValueError(f"packed q/k/v must be ([B,] S, 3, H, D); got "
                             f"{packed.shape[row:]}")
        q, k, v = (packed[:, :, n] for n in range(3))
    else:
        q, k, v = (jnp.asarray(x) for x in (q, k, v))
        row = q.ndim == 3
        if row and k.ndim == v.ndim == 3:
            q, k, v = q[None], k[None], v[None]
    if (q.ndim != 4 or k.ndim != 4 or v.ndim != 4
            or k.shape[:2] != q.shape[:2] or k.shape[3] != q.shape[3]
            or v.shape[:2] != q.shape[:2]
            or q.shape[2] % k.shape[2] or q.shape[2] % v.shape[2]):
        raise ValueError(f"q/k/v must share B and S, k's width be q's and "
                         f"their heads divide q's: q ([B,] S, H, D), k ([B,] "
                         f"S, H/gk, D), v ([B,] S, H/gv, Dv); got "
                         f"{q.shape[row:]}, {k.shape[row:]}, "
                         f"{v.shape[row:]}")
    B, S, H, D = q.shape
    if window is not None:
        if not causal or window < 1:
            raise ValueError("window needs causal=True and window >= 1")
        window = None if window >= S else int(window)
    block_q, block_k, head_fold = tuned_flash_config(
        S, B * H, D, q.dtype, bool(causal), block_q, block_k, head_fold)
    bq, bk = _fit_block(block_q, S), _fit_block(block_k, S)
    hfold = _fit_block(max(int(head_fold), 1), B * H)
    if k.shape[2] != H or v.shape[2] != H:
        hfold = 1             # a step's heads would straddle the groups
    if interpret is None:
        interpret = not _on_tpu()
    sc = float(1.0 / np.sqrt(D) if scale is None else scale)
    if window is not None:
        # a windowed program's grid steps are told apart by their distance
        # from the diagonal in whole blocks: square blocks
        bq = bk = min(bq, bk)
    causal = window is not None or bool(causal)
    if packed is not None and _lane_heads(H, D, D, 1, 1, hfold) and (
            _fused_backward(S, _LANE, q.dtype, 1, False, _LANE, bq, bk,
                            q.dtype, q.dtype, 2)[0]):
        out = _flash_packed(packed, causal, sc, bq, bk, bool(interpret),
                            window)
    else:
        out = _flash_core(q, k, v, causal, sc, bq, bk, bool(interpret),
                          hfold, window)
    return out[0] if row else out
