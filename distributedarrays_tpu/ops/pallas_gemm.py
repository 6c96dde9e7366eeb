"""Hand-written Pallas TPU kernel for the block GEMM hot path.

The reference's hottest op is the tile-grid GEMM (linalg.jl:189-253); the
framework's default path is one jitted ``jnp.matmul`` (XLA's MXU pipeline,
ops/linalg.py).  This module adds the Pallas alternative for when the
schedule should be owned explicitly — fused epilogues, nonstandard tiling,
mixed precision — following /opt/skills/guides/pallas_guide.md:

- grid ``(M/bm, N/bn, K/bk)`` with the K axis innermost (sequential),
- A/B tiles streamed HBM→VMEM by BlockSpec index maps,
- one float32 VMEM scratch accumulator per (i, j) tile,
- ``preferred_element_type=float32`` so bf16/f32 inputs accumulate in f32
  on the MXU,
- optional fused epilogue applied in-register before the tile is written
  back (saves one full HBM round-trip vs a separate elementwise kernel).

``pallas_matmul`` falls back to interpreter mode off-TPU so the kernel is
unit-testable on the CPU mesh (same discipline as the rest of the suite).
"""

from __future__ import annotations

import functools
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .. import telemetry as _tm

__all__ = ["pallas_matmul", "pallas_matmul_int8", "quantized_matmul",
           "quantize_rows", "entry_valid_for_seed"]


# Scoped-VMEM budget for a GEMM tile set: v5e enforces a 16 MiB limit on
# the Pallas stack allocation (measured on silicon: a 17.38M int8 tile set
# was rejected with ~0.4M of Mosaic overhead on top of the raw block
# bytes), so tiles are validated against 15.5 MiB regardless of where the
# block came from (cache entry, explicit block=, heuristic).
_VMEM_LIMIT = int(15.5 * 2**20)


def _vmem_parts_matmul(tm, tn, tk, ab, bb, ob):
    """Scoped-VMEM estimate for a float GEMM tile set, by component.
    The Pallas pipeline DOUBLE-BUFFERS only the REVOLVING blocks — the
    A/B inputs, whose index maps depend on the innermost (sequential) K
    grid axis, so the next tile streams in while the current one computes
    (the ``_x2`` entries).  The output block's index map is ``(i, j)``:
    constant across the K steps of one tile, so it is carried once, like
    the f32 accumulator scratch (ADVICE round-5: counting it double
    rejected legitimate tilings near the budget).  ``ab``/``bb``/``ob``
    are the operand/output itemsizes."""
    return {
        "a_blocks_x2": 2 * tm * tk * ab,
        "b_blocks_x2": 2 * tk * tn * bb,
        "out_block": tm * tn * ob,
        "acc_scratch_f32": tm * tn * 4,
    }


def _vmem_parts_int8(tm, tn, tk, ob):
    """Scoped-VMEM estimate for the int8 GEMM tile set, by component:
    the revolving int8 input blocks double-buffered; the f32 scale
    carriers — lane/sublane-aligned to (bm, 128) and (8, bn), index maps
    ``(i, 0)``/``(0, j)`` constant along the innermost K axis — counted
    once, like the K-constant output block and the int32 accumulator
    scratch (ADVICE round-5)."""
    return {
        "a_blocks_x2": 2 * tm * tk,
        "b_blocks_x2": 2 * tk * tn,
        "scale_carriers": tm * 128 * 4 + 8 * tn * 4,
        "out_block": tm * tn * ob,
        "acc_scratch_i32": tm * tn * 4,
    }


def _resolve_block(m, n, k, block, interpret, *, kernel, dtype_key,
                   caps, m_align, vmem_parts=None):
    """Shared block-resolution path for the GEMM kernels: explicit
    ``block`` > valid autotune-cache entry > auto heuristic (whole dim
    when under the cap, else largest power-of-two divisor).  A
    stale/hand-edited/malformed cache entry must degrade to the auto
    heuristic, never break dispatch — validation includes the Mosaic
    alignment rules (last dim % 128, second-to-last % ``m_align``, or
    equal to the array dim) and, when the caller supplies a
    ``vmem_parts(bm, bn, bk) -> {component: bytes}`` estimator, the
    scoped-VMEM budget; only real TPUs enforce either, interpret mode
    runs any tiling."""
    def aligned(tm, tn, tk):
        return ((tm % m_align == 0 or tm == m)
                and (tn % 128 == 0 or tn == n)
                and (tk % 128 == 0 or tk == k))

    def vmem_ok(tm, tn, tk):
        return (interpret or vmem_parts is None
                or sum(vmem_parts(tm, tn, tk).values()) <= _VMEM_LIMIT)

    if block is None:
        from ..utils import autotune
        vals = autotune.valid_ints(
            autotune.get(kernel, autotune.device_key_for(m, n, k, *dtype_key)),
            (3,))
        if vals is not None:
            tm, tn, tk = vals
            if (m % tm == 0 and n % tn == 0 and k % tk == 0
                    and (interpret or aligned(tm, tn, tk))
                    and vmem_ok(tm, tn, tk)):
                block = (tm, tn, tk)
    if block is None:
        bm0, bn0, bk0 = caps

        def fit(dim, cap):
            return dim if dim <= cap else _pow2_divisor(dim, cap)

        bm, bn, bk = fit(m, bm0), fit(n, bn0), fit(k, bk0)
        if not interpret and not aligned(bm, bn, bk):
            raise ValueError(
                f"shapes ({m},{k})x({k},{n}) have no MXU-aligned "
                "power-of-two tiling; pad the operands or pass block=")
    else:
        bm, bn, bk = block
        bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
        if not vmem_ok(bm, bn, bk):
            # fail at dispatch with the budget AND the per-component
            # breakdown, not deep in Mosaic with a scoped-vmem stack OOM
            # (the silicon failure mode this guards).  A legitimate
            # near-budget tiling rejection must be diagnosable: the
            # estimate double-buffers the revolving input blocks (the
            # _x2 components) while K-grid-constant output blocks and
            # scale carriers count once — easy to forget when sizing
            # blocks by raw tile bytes.
            parts = vmem_parts(bm, bn, bk)
            total = sum(parts.values())
            breakdown = ", ".join(f"{c}={v}" for c, v in parts.items())
            raise ValueError(
                f"block {(bm, bn, bk)} needs ~{total} bytes of scoped "
                f"VMEM, over the {_VMEM_LIMIT} budget (headroom "
                f"{total - _VMEM_LIMIT} over). Estimate components — "
                f"revolving input blocks double-buffered (the _x2 "
                f"entries), K-constant output/scale blocks once: "
                f"{breakdown}. Pass a smaller block=.")
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"shapes ({m},{k})x({k},{n}) must divide block {(bm, bn, bk)}")
    return bm, bn, bk


def entry_valid_for_seed(kernel: str, key: str, entry):
    """Validity predicate for promoting an autotune-cache GEMM winner into
    the tracked seed registry (tools/seed_refresh.py): the SAME checks
    ``_resolve_block`` applies at dispatch — well-formed 3-tuple, shape
    divisibility, Mosaic alignment (last dim % 128, M block % m_align),
    and the per-kernel scoped-VMEM estimate — so a winner measured before
    a VMEM-estimator fix can never ship as a dead entry that every later
    dispatch silently rejects (ADVICE round-5).

    Returns ``None`` for kernels this module does not own (no opinion),
    else ``True``/``False``.  ``key`` is ``m|n|k|<dtypes...>|platform|
    device_kind`` as built by ``autotune.device_key_for``.
    """
    if kernel not in ("pallas_matmul", "pallas_matmul_int8"):
        return None
    segs = str(key).split("|")
    # device_key_for produces exactly this arity per kernel (m, n, k,
    # dtype segs, platform, kind); anything else cannot match a lookup
    # and must not ship
    if len(segs) != (7 if kernel == "pallas_matmul" else 6):
        return False
    try:
        m, n, k = (int(x) for x in segs[:3])
    except ValueError:
        return False
    from ..utils.autotune import valid_ints
    vals = valid_ints(entry, (3,))
    if vals is None:
        return False
    bm, bn, bk = vals
    if m % bm or n % bn or k % bk:
        return False
    if kernel == "pallas_matmul_int8":
        m_align = 32
        # dispatch-default f32 output — the layout quantized_matmul uses
        parts = _vmem_parts_int8(bm, bn, bk, 4)
    else:
        m_align = 8
        try:
            ab = jnp.dtype(segs[3]).itemsize
            bb = jnp.dtype(segs[4]).itemsize
            ob = jnp.dtype(jnp.result_type(jnp.dtype(segs[3]),
                                           jnp.dtype(segs[4]))).itemsize
        except TypeError:
            return False
        parts = _vmem_parts_matmul(bm, bn, bk, ab, bb, ob)
    aligned = ((bm % m_align == 0 or bm == m)
               and (bn % 128 == 0 or bn == n)
               and (bk % 128 == 0 or bk == k))
    return aligned and sum(parts.values()) <= _VMEM_LIMIT


def _pow2_divisor(dim: int, cap: int) -> int:
    """Largest power-of-two divisor of ``dim`` that is <= ``cap`` — the
    shared block-fitting primitive (also used by pallas_stencil and
    flash_block_size)."""
    b = 1
    while b * 2 <= cap and dim % (b * 2) == 0:
        b *= 2
    return b


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
            epilogue: Callable | None):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        out = acc_ref[:]
        if epilogue is not None:
            out = epilogue(out)
        o_ref[:] = out.astype(o_ref.dtype)


def _on_tpu() -> bool:
    """True iff the default device is a TPU.  The ONE platform test of the
    kernel modules (``interpret = not _on_tpu()``); a failed device query
    propagates — it must not turn every kernel to interpret mode in
    silence."""
    return jax.devices()[0].platform == "tpu"


@functools.lru_cache(maxsize=64)
def _build(m, n, k, bm, bn, bk, dtype_str, epilogue, interpret):
    dtype = jnp.dtype(dtype_str)
    k_steps = k // bk
    kern = functools.partial(_kernel, k_steps=k_steps, epilogue=epilogue)
    call = pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="pallas_matmul",
        interpret=interpret,
    )
    return jax.jit(call)


@_tm.traced(name="pallas.matmul")
def pallas_matmul(a, b, block: tuple[int, int, int] | None = None,
                  epilogue: Callable | None = None,
                  interpret: bool | None = None):
    """C = epilogue(A @ B) as a Pallas TPU kernel.

    Shapes must divide by ``block`` (pad beforehand otherwise); bf16/f32
    inputs accumulate in f32.  ``block=None`` picks the largest tiling
    that fits VMEM on v5e, measured on hardware: (1024, 1024, 512) for
    2-byte dtypes (151.9 TFLOPS on a 4096^2 bf16 GEMM vs 78.2 at the old
    256^3 default), (512, 512, 512) for f32.  ``epilogue`` (e.g.
    ``jax.nn.gelu``) fuses into the tile flush.  ``interpret`` defaults
    to auto (True off-TPU).

    The kernel cache is keyed on the ``epilogue`` callable's identity —
    pass a module-level function (not a fresh lambda per call) or the
    kernel recompiles on every invocation.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    m, ka = a.shape
    kb, n = b.shape
    if ka != kb:
        raise ValueError(f"matmul dim mismatch {a.shape} @ {b.shape}")
    if interpret is None:
        interpret = not _on_tpu()
    two_byte = max(jnp.dtype(a.dtype).itemsize,
                   jnp.dtype(b.dtype).itemsize) <= 2
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    ab, bb = jnp.dtype(a.dtype).itemsize, jnp.dtype(b.dtype).itemsize
    ob = jnp.dtype(out_dtype).itemsize
    if _tm.enabled():
        # on the @traced dispatch span (shapes were unknown when it opened)
        _tm.annotate(shape=[m, ka, n],
                     dtype=[str(a.dtype), str(b.dtype)])

    bm, bn, bk = _resolve_block(
        m, n, ka, block, interpret, kernel="pallas_matmul",
        dtype_key=(a.dtype, b.dtype),
        caps=(1024, 1024, 512) if two_byte else (512, 512, 512), m_align=8,
        vmem_parts=lambda tm, tn, tk: _vmem_parts_matmul(
            tm, tn, tk, ab, bb, ob))
    fn = _build(m, n, ka, bm, bn, bk, str(out_dtype), epilogue, interpret)
    return fn(a, b)


# ---------------------------------------------------------------------------
# int8 quantized GEMM — the MXU runs int8 x int8 -> int32 at 2x the bf16
# rate on the "e"-class chips (v5e ~394 TOPS vs ~197 TFLOPS bf16), so a
# quantization-tolerant GEMM can BEAT the chip's bf16 peak.  No reference
# analog (linalg.jl:189-253 is Float only) — this is a TPU-native extra.
# ---------------------------------------------------------------------------


def _int8_kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref, *,
                 k_steps: int):
    """Int8 tiles accumulate in an int32 VMEM scratch; the flush dequantizes
    in-register with the per-row/per-column scales (one fused epilogue, no
    extra HBM pass): C = (Qa @ Qb) * (sa sb^T)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:],
                          preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        # scale refs arrive lane/sublane-aligned — (bm,128) and (8,bn),
        # value replicated across the padding dims (Mosaic requires the
        # minor block dim % 128, like the attention stats broadcast in
        # pallas_attention) — slice one row/col back out for the outer
        # product
        scale = sa_ref[:, 0:1] * sb_ref[0:1, :]  # (bm,1)*(1,bn) -> (bm,bn)
        o_ref[:] = (acc_ref[:].astype(jnp.float32) * scale
                    ).astype(o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _build_int8(m, n, k, bm, bn, bk, out_dtype_str, interpret):
    k_steps = k // bk
    kern = functools.partial(_int8_kernel, k_steps=k_steps)
    call = pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((bm, 128), lambda i, j, s: (i, 0)),
            pl.BlockSpec((8, bn), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype_str)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        name="pallas_matmul_int8",
        interpret=interpret,
    )
    return jax.jit(call)


@_tm.traced(name="pallas.matmul_int8")
def pallas_matmul_int8(qa, qb, a_scale, b_scale,
                       block: tuple[int, int, int] | None = None,
                       out_dtype=jnp.float32, interpret: bool | None = None):
    """C = (Qa @ Qb) * (a_scale b_scale^T) with int8 operands on the MXU.

    ``qa`` (m,k) int8, ``qb`` (k,n) int8; ``a_scale`` (m,) per-row and
    ``b_scale`` (n,) per-column dequant scales (float32).  Accumulates in
    int32 (no rounding inside the K loop — exact whenever the running sum
    fits int32, guaranteed for K <= ~133k even with fully saturated
    operands; a warning fires above that) and dequantizes in the tile
    flush.  Shapes must divide ``block``; int8 native MXU tiling wants
    the K block % 128 and the M block % 32.
    """
    qa = jnp.asarray(qa)
    qb = jnp.asarray(qb)
    if qa.dtype != jnp.int8 or qb.dtype != jnp.int8:
        raise ValueError(
            f"operands must be int8, got {qa.dtype} x {qb.dtype} "
            "(use quantized_matmul for float inputs)")
    m, ka = qa.shape
    kb, n = qb.shape
    if ka != kb:
        raise ValueError(f"matmul dim mismatch {qa.shape} @ {qb.shape}")
    if interpret is None:
        interpret = not _on_tpu()
    _tm.annotate(shape=[m, ka, n])
    safe_k = (2**31 - 1) // (127 * 127)
    if ka > safe_k:
        # worst-case saturated operands overflow the int32 accumulator
        # above this K; real data rarely saturates, so warn, don't refuse.
        # Keyed on K so each risky contraction length surfaces once
        # (a single process-wide key would hide later, larger K's).
        from ..utils.debug import warn_once
        warn_once(f"pallas_matmul_int8_overflow:{ka}",
                  f"pallas_matmul_int8: K={ka} exceeds the worst-case "
                  f"int32-exact bound (K <= {safe_k}); saturated operands "
                  "may wrap. Split the contraction if inputs can saturate.")
    # int8 tiles are half the bytes of bf16, so the K cap doubles; int8
    # native MXU tiling wants the M block % 32.  The M cap stays at 512:
    # a 1024^3 tile set was rejected by v5e's 16 MB scoped-VMEM check on
    # silicon (round 5, Mosaic-reported ~17.4 MB stack), so the heuristic
    # never proposes it even though the tightened estimator (K-constant
    # out/scale blocks counted once, ADVICE r5) now prices it at
    # ~12.5 MB — 512x1024x1024 is ~7.5 MB with the same K-step
    # arithmetic intensity.  An explicit block=/cached entry near the
    # budget that Mosaic's own (less favorable) accounting still rejects
    # fails loudly at compile with Mosaic's scoped-vmem error — the
    # dispatch estimate deliberately errs toward admitting, per ADVICE:
    # a conservative guard that rejects legitimate tilings is worse
    ob8 = jnp.dtype(out_dtype).itemsize

    bm, bn, bk = _resolve_block(
        m, n, ka, block, interpret, kernel="pallas_matmul_int8",
        dtype_key=("int8",), caps=(512, 1024, 1024), m_align=32,
        vmem_parts=lambda tm, tn, tk: _vmem_parts_int8(tm, tn, tk, ob8))
    # lane/sublane-aligned scale carriers (see _int8_kernel flush): the
    # replication costs m*512 + n*32 bytes of HBM — noise next to the
    # int8 operands — and keeps every VMEM block Mosaic-legal
    sa = jnp.broadcast_to(jnp.asarray(a_scale, jnp.float32).reshape(m, 1),
                          (m, 128))
    sb = jnp.broadcast_to(jnp.asarray(b_scale, jnp.float32).reshape(1, n),
                          (8, n))
    fn = _build_int8(m, n, ka, bm, bn, bk, str(jnp.dtype(out_dtype)),
                     interpret)
    return fn(qa, qb, sa, sb)


def quantize_rows(x, axis: int):
    """Symmetric per-slice int8 quantization along ``axis`` (the contraction
    axis): returns (q_int8, scale_f32) with x ≈ q * scale broadcast over
    ``axis``.  All-zero slices get scale 0 (q = 0), not NaN."""
    x = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = amax / 127.0
    q = jnp.where(scale > 0, jnp.round(x / jnp.where(scale > 0, scale, 1.0)),
                  0.0)
    return q.astype(jnp.int8), jnp.squeeze(scale, axis)


def quantized_matmul(a, b, block: tuple[int, int, int] | None = None,
                     out_dtype=jnp.float32, interpret: bool | None = None):
    """Dynamic-quantization GEMM: float in, float out, int8 on the MXU.

    Per-row (A) / per-column (B) symmetric int8 quantization, exact int32
    accumulation, fused dequant.  Relative error is bounded by the two
    quantization steps (~1/127 per operand worst case, typically ~1e-2
    on Gaussian data) — the trade for ~2x bf16 throughput on e-class
    chips.  For repeated use with a static weight matrix, pre-quantize
    once with ``quantize_rows`` and call ``pallas_matmul_int8`` directly.
    """
    qa, sa = quantize_rows(a, 1)
    qb, sb = quantize_rows(b, 0)
    return pallas_matmul_int8(qa, qb, sa, sb, block=block,
                              out_dtype=out_dtype, interpret=interpret)
