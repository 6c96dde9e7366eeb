"""Hand-written Pallas TPU kernel: flash attention (blockwise, online
softmax).

This is the hot-op companion of ``models/ring_attention.py``: ring
attention moves K/V blocks between chips with ``ppermute`` while each rank
computes *local* blockwise attention — exactly the computation this kernel
owns.  On TPU it keeps the running-max/normalizer/accumulator resident in
VMEM while K/V blocks stream HBM→VMEM, so the S×S score matrix never
materializes (pallas_guide.md: grid/BlockSpec streaming, scratch
persistence across the innermost sequential grid axis).

Layout: grid ``(heads, S/bq, S/bk)`` with the K axis innermost; scratch
``m (bq,1)``, ``l (bq,1)``, ``acc (bq,d)`` persist across the K sweep for
each (head, q-block) and flush to the output (and the per-row logsumexp)
on the final K step.  Causal masking compares global q/k positions derived
from the grid ids.

Differentiable end to end with FlashAttention-2-style BACKWARD KERNELS
(custom_vjp): the forward saves only O(S) logsumexp rows; the backward
recomputes P blockwise and runs two Pallas passes — a K-sweep accumulating
dQ and a Q-sweep accumulating dK/dV — so training memory stays O(S·d).
Gradients match the dense formulation to ~1e-5 (tested).

Interpreter mode runs the same kernels off-TPU for the CPU-mesh test suite.
"""

from __future__ import annotations

import functools

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


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, k_steps: int,
            hfold: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: a k block strictly below the q block's diagonal band is fully
    # masked — skip its matmuls entirely (the DMA still streams, but it
    # pipelines under the unmasked blocks' compute)
    live = (ki * bk <= qi * bq + bq - 1) if causal else (ki == ki)

    @pl.when(live)
    def _accumulate():
        # matmuls run at the INPUT dtype with f32 accumulation
        # (preferred_element_type): bf16 inputs take the fast MXU passes;
        # an astype(f32) here would silently force 4x-slower f32 passes.
        # ``hfold`` heads ride each grid step as a batched dot — at small
        # head_dim (64) this fills the 128-wide lanes the per-head layout
        # leaves half-idle (VERDICT round-3 item 3's tuning lever).
        q = q_ref[:]                                      # (hfold, bq, d)
        k = k_ref[:]                                      # (hfold, bk, d)
        v = v_ref[:]                                      # (hfold, bk, d)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (hfold, bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (hfold, bq, bk), 1)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (hfold, bq, bk), 2)
            s = jnp.where(kpos <= qpos, s, -jnp.inf)

        m_prev = m_ref[:]                                 # (hfold, bq, 1)
        blk_max = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(ki == k_steps - 1)
    def _flush():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        # per-row logsumexp, consumed by the backward kernels
        m_fin = jnp.where(jnp.isfinite(m_ref[:]), m_ref[:], 0.0)
        lse_ref[:] = jnp.broadcast_to(m_fin + jnp.log(l),
                                      (hfold, bq, _LANE))


@functools.lru_cache(maxsize=64)
def _build(h, s, d, bq, bk, dtype_str, scale, causal, interpret,
           hfold: int = 1):
    k_steps = s // bk
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, k_steps=k_steps, hfold=hfold)
    call = pl.pallas_call(
        kern,
        grid=(h // hfold, s // bq, k_steps),
        in_specs=[
            pl.BlockSpec((hfold, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((hfold, bk, d), lambda hh, qi, ki: (hh, ki, 0)),
            pl.BlockSpec((hfold, bk, d), lambda hh, qi, ki: (hh, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((hfold, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((hfold, bq, _LANE),
                         lambda hh, qi, ki: (hh, qi, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((h, s, d), jnp.dtype(dtype_str)),
            jax.ShapeDtypeStruct((h, s, _LANE), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((hfold, bq, 1), jnp.float32),
            pltpu.VMEM((hfold, bq, 1), jnp.float32),
            pltpu.VMEM((hfold, bq, d), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )
    return jax.jit(call)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 style): given saved per-row logsumexp
# L and the precomputed D = rowsum(dO * O), recompute P blockwise and
# accumulate dQ (sweep over K blocks) and dK/dV (sweep over Q blocks) —
# O(S·d) memory end to end, no S×S materialization in the backward either.
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   dd_ref, dq_ref, acc_ref, *, scale, causal, bq, bk, k_steps):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # global offsets arrive as SMEM scalars (0 single-chip; the block's ring
    # position per hop), so causality is judged in GLOBAL sequence positions
    if causal:
        live = (koff_ref[0] + ki * bk <= qoff_ref[0] + qi * bq + bq - 1)
    else:
        live = ki == ki

    @pl.when(live)
    def _accumulate():
        # native-dtype MXU passes with f32 accumulation (see _kernel)
        q = q_ref[0]                                       # (bq, d)
        k = k_ref[0]                                       # (bk, d)
        v = v_ref[0]                                       # (bk, d)
        do = do_ref[0]                                     # (bq, d)
        lse = lse_ref[0][:, :1]                            # (bq, 1)
        dd = dd_ref[0][:, :1]                              # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qoff_ref[0] + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = koff_ref[0] + ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, -jnp.inf)
        p = jnp.exp(s - lse)                               # exact probs
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == k_steps - 1)
    def _flush():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    dd_ref, dk_ref, dv_ref, acck_ref, accv_ref, *,
                    scale, causal, bq, bk, q_steps):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        acck_ref[:] = jnp.zeros_like(acck_ref)
        accv_ref[:] = jnp.zeros_like(accv_ref)

    # causal: a q block strictly above the k block (in GLOBAL positions —
    # see _bwd_dq_kernel on the SMEM offsets) sees none of it
    if causal:
        live = (qoff_ref[0] + qi * bq + bq - 1 >= koff_ref[0] + ki * bk)
    else:
        live = qi == qi

    @pl.when(live)
    def _accumulate():
        # native-dtype MXU passes with f32 accumulation (see _kernel)
        q = q_ref[0]                                       # (bq, d)
        k = k_ref[0]                                       # (bk, d)
        v = v_ref[0]                                       # (bk, d)
        do = do_ref[0]                                     # (bq, d)
        lse = lse_ref[0][:, :1]                            # (bq, 1)
        dd = dd_ref[0][:, :1]                              # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qoff_ref[0] + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = koff_ref[0] + ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, -jnp.inf)
        p = jnp.exp(s - lse)
        p = jnp.where(jnp.isfinite(s), p, 0.0)             # (bq, bk)
        # dV += P^T @ dO
        accv_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd) * scale                         # (bq, bk)
        # dK += dS^T @ Q
        acck_ref[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == q_steps - 1)
    def _flush():
        dk_ref[0] = acck_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = accv_ref[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=64)
def _build_bwd(h, s, d, bq, bk, dtype_str, scale, causal, interpret,
               out_dtype_str=None):
    out_dtype = jnp.dtype(out_dtype_str or dtype_str)
    k_steps, q_steps = s // bk, s // bq

    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, k_steps=k_steps),
        grid=(h, q_steps, k_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # qoff
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # koff
            pl.BlockSpec((1, bq, d), lambda hh, qi, ki: (hh, qi, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda hh, qi, ki: (hh, ki, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda hh, qi, ki: (hh, ki, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda hh, qi, ki: (hh, qi, 0)),  # dO
            pl.BlockSpec((1, bq, _LANE), lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((1, bq, _LANE), lambda hh, qi, ki: (hh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, s, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )

    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, q_steps=q_steps),
        grid=(h, k_steps, q_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # qoff
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # koff
            pl.BlockSpec((1, bq, d), lambda hh, ki, qi: (hh, qi, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda hh, ki, qi: (hh, ki, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda hh, ki, qi: (hh, ki, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda hh, ki, qi: (hh, qi, 0)),  # dO
            pl.BlockSpec((1, bq, _LANE), lambda hh, ki, qi: (hh, qi, 0)),
            pl.BlockSpec((1, bq, _LANE), lambda hh, ki, qi: (hh, qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, d), lambda hh, ki, qi: (hh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda hh, ki, qi: (hh, ki, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((h, s, d), out_dtype),
            jax.ShapeDtypeStruct((h, s, d), out_dtype),
        ),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=interpret,
    )
    return jax.jit(dq_call), jax.jit(dkv_call)


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
    dq_call, dkv_call = _build_bwd(H, B, D, bq, bk, str(q.dtype), sc,
                                   bool(causal), bool(interpret),
                                   out_dtype_str="float32")
    qo = jnp.asarray(qoff, jnp.int32).reshape(1)
    ko = jnp.asarray(koff, jnp.int32).reshape(1)
    dq = dq_call(qo, ko, q, k, v, do, lse, dd)
    dk, dv = dkv_call(qo, ko, q, k, v, do, lse, dd)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, causal, scale, bq, bk, interpret, hfold=1):
    S, H, D = q.shape
    qh, kh, vh = (jnp.transpose(x, (1, 0, 2)) for x in (q, k, v))
    out, _ = _build(H, S, D, bq, bk, str(q.dtype), scale, causal,
                    interpret, hfold)(qh, kh, vh)
    return jnp.transpose(out, (1, 0, 2))


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret, hfold=1):
    S, H, D = q.shape
    qh, kh, vh = (jnp.transpose(x, (1, 0, 2)) for x in (q, k, v))
    out, lse = _build(H, S, D, bq, bk, str(q.dtype), scale, causal,
                      interpret, hfold)(qh, kh, vh)
    o = jnp.transpose(out, (1, 0, 2))
    # keep only one lane of the lane-broadcast lse in the residuals —
    # (H, S) instead of (H, S, 128); rebroadcast in the backward like dd
    return o, (q, k, v, o, lse[:, :, 0])


def _flash_bwd(causal, scale, bq, bk, interpret, hfold, res, g):
    # FlashAttention-2-style backward: recompute P blockwise from the saved
    # per-row logsumexp, sweep K blocks for dQ and Q blocks for dK/dV —
    # O(S·d) memory, no S×S materialization
    q, k, v, o, lse = res
    S, H, D = q.shape
    qh, kh, vh, doh = (jnp.transpose(x, (1, 0, 2)).astype(q.dtype)
                       for x in (q, k, v, g))
    # D_i = rowsum(dO ∘ O), per (head, row); lane-broadcast both stats for
    # the kernels' (1, bq, _LANE) block layout
    dd = jnp.einsum("shd,shd->hs", g.astype(jnp.float32),
                    o.astype(jnp.float32))
    dd = jnp.broadcast_to(dd[:, :, None], (H, S, _LANE))
    lse = jnp.broadcast_to(lse[:, :, None], (H, S, _LANE))
    dq_call, dkv_call = _build_bwd(H, S, D, bq, bk, str(q.dtype), scale,
                                   causal, interpret)
    zero = jnp.zeros((1,), jnp.int32)                 # single-chip: offsets 0
    dq = dq_call(zero, zero, qh, kh, vh, doh, lse, dd)
    dk, dv = dkv_call(zero, zero, qh, kh, vh, doh, lse, dd)
    back = lambda t: jnp.transpose(t, (1, 0, 2)).astype(q.dtype)
    return back(dq), back(dk), back(dv)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def tuned_flash_config(S, H, D, dtype, causal: bool,
                       block_q=None, block_k=None, head_fold=None,
                       default: int = 512):
    """Resolve (block_q, block_k, head_fold) for a flash call: explicit
    values win; ``None`` consults the autotune registry's entry for
    (S, H, D, dtype, causal) — a 2- or 3-tuple — falling back to
    ``default``²/1.  The tuned head_fold was measured WITH the tuned
    blocks, so it is grafted only when BOTH blocks also come from the
    registry.  A malformed cache entry degrades to the defaults, never
    breaks dispatch.  Callers that cache jitted programs must call this
    OUTSIDE the cache and key on the resolved values (see
    models/ulysses.py) or a later-banked tune would be silently
    ignored."""
    if block_q is not None and block_k is not None and head_fold is not None:
        return block_q, block_k, head_fold
    from ..utils import autotune
    vals = autotune.valid_ints(
        autotune.get("flash_attention",
                     autotune.device_key_for(S, H, D, dtype, bool(causal))),
        (2, 3))
    tq, tk = (vals[0], vals[1]) if vals else (default, default)
    tf = vals[2] if vals and len(vals) == 3 else 1
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
                    interpret: bool | None = None):
    """Exact attention over (seq, heads, head_dim) arrays without
    materializing the S×S score matrix.

    Block sizes (and the forward's ``head_fold`` — how many heads ride
    each grid step as a batched dot, the lane-occupancy lever for small
    head_dim) default to the autotune registry's tuned value for this
    (S, H, D, dtype, causal) — populated by ``utils.autotune`` sweeps
    (bench.py runs one on hardware) — falling back to 512²/1.  A 2- or
    3-tuple cache entry is accepted ((bq, bk) or (bq, bk, hfold)).
    Either way blocks are fitted to the sequence length (clipped, then
    halved until they divide S); ``head_fold`` is clipped to a divisor
    of H.  Use as the per-rank compute inside ring attention, or
    standalone single-chip.
    """
    q, k, v = (jnp.asarray(x) for x in (q, k, v))
    if q.shape != k.shape or q.shape != v.shape or q.ndim != 3:
        raise ValueError(f"q/k/v must share (S, H, D), got {q.shape}, "
                         f"{k.shape}, {v.shape}")
    S, H, D = q.shape
    block_q, block_k, head_fold = tuned_flash_config(
        S, H, D, q.dtype, bool(causal), block_q, block_k, head_fold)
    bq, bk = _fit_block(block_q, S), _fit_block(block_k, S)
    hfold = _fit_block(max(int(head_fold), 1), H)
    if interpret is None:
        interpret = not _on_tpu()
    sc = float(1.0 / np.sqrt(D) if scale is None else scale)
    return _flash_core(q, k, v, bool(causal), sc, bq, bk, bool(interpret),
                       hfold)
