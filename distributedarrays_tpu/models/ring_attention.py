"""Ring attention: sequence-parallel exact attention over the device mesh.

The long-context flagship built on the framework's collective substrate.
The reference's SPMD layer contains the *mechanism* — neighbor ring
send/recv (test/spmd.jl:90-101, docs/src/index.md:356-369) — without the
application; SURVEY.md §5 pins ring attention / context parallelism as the
TPU-native deliverable riding that substrate.

Design (Liu et al., "Ring Attention with Blockwise Transformers", 2023 —
re-derived here for shard_map):

- Q, K, V are sequence-sharded over a 1-D mesh axis: each rank holds a
  ``(seq/P, d)`` block per head.
- P steps: each rank computes blockwise attention of its Q block against
  the K/V block currently resident, maintaining a *numerically stable
  online softmax* (running max ``m``, normalizer ``l``, weighted
  accumulator ``o``), then passes K/V to its ring neighbor via
  ``lax.ppermute`` over ICI; compute and the (tiny) boundary transfer
  overlap because XLA pipelines the permute with the matmuls.
- After P hops every Q block has attended to the full sequence exactly —
  no O(seq²) memory anywhere, communication O(seq·d) per rank.

``ring_attention`` takes/returns DArrays sequence-sharded on dim 0 of
shape (seq, heads, head_dim); ``ring_attention_kernel`` is the raw
shard_map program for embedding in larger jitted models (causal masking
supported via block-index comparison).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import layout as L
from .. import telemetry as _tm
from ..darray import DArray, _wrap_global
from ..parallel.collectives import (axis_size as _axis_size,
                                    shard_map_compat)

__all__ = ["ring_attention", "ring_attention_kernel",
           "ring_attention_prefill",
           "ring_attention_rdma_kernel",
           "ring_flash_attention", "ring_flash_attention_kernel",
           "zigzag_ring_attention", "zigzag_ring_attention_kernel",
           "zigzag_ring_flash_attention",
           "zigzag_ring_flash_attention_kernel",
           "zigzag_order", "zigzag_shard", "zigzag_unshard",
           "tuned_hop_blocks_for", "reference_attention"]


def _online_accumulate(m, l, o, qf, kc, vc, mask=None):
    """One online-softmax block accumulate (running max ``m``, normalizer
    ``l``, weighted sum ``o``, all (h, bq[, dh]) f32).  ``qf``: scaled f32
    (bq, h, d) query rows; ``kc``/``vc``: (bk, h, d) resident key/value
    rows; ``mask``: bool (bq, bk), True = attend (None = attend all).
    Fully-masked rows contribute nothing (the -inf/isfinite guards)."""
    s = jnp.einsum("qhd,khd->hqk", qf, kc.astype(jnp.float32))
    if mask is not None:
        s = jnp.where(mask[None], s, -jnp.inf)
    blk_max = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, blk_max)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[:, :, None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[:, :, None] + jnp.einsum(
        "hqk,khd->hqd", p, vc.astype(jnp.float32))
    return m_new, l_new, o_new


def ring_attention_kernel(q, k, v, axis: str, causal: bool = False,
                          scale: float | None = None):
    """Blockwise ring attention for one (local) block triple.

    q, k, v: ``(block, heads, d)`` — the calling rank's sequence block.
    Runs inside ``shard_map`` with ``axis`` a 1-D mesh axis.
    """
    nblk = _axis_size(axis)
    me = lax.axis_index(axis)
    b, h, dh = q.shape
    sc = jnp.asarray(1.0 / np.sqrt(dh) if scale is None else scale, q.dtype)

    qf = (q * sc).astype(jnp.float32)
    # accumulators: running max m, normalizer l, output o  (per head)
    m0 = jnp.full((h, b), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((h, b), jnp.float32)
    o0 = jnp.zeros((h, b, dh), jnp.float32)

    def accumulate(step, m, l, o, kc, vc):
        # kc/vc currently hold the block that started on rank (me - step)
        src = (me - step) % nblk
        mask = None
        if causal:
            qpos = me * b + jnp.arange(b)[:, None]          # global q index
            kpos = src * b + jnp.arange(b)[None, :]         # global k index
            mask = kpos <= qpos
        return _online_accumulate(m, l, o, qf, kc, vc, mask)

    perm = [(i, (i + 1) % nblk) for i in range(nblk)]

    def body(step, carry):
        m, l, o, kc, vc = carry
        m, l, o = accumulate(step, m, l, o, kc, vc)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return m, l, o, kc, vc

    # nblk-1 accumulate+shift hops, then a final accumulate with no shift
    # (the last rotation's result would be discarded)
    m, l, o, kc, vc = lax.fori_loop(0, nblk - 1, body, (m0, l0, o0, k, v))
    m, l, o = accumulate(nblk - 1, m, l, o, kc, vc)
    l = jnp.where(l == 0.0, 1.0, l)                          # all-masked rows
    out = (o / l[:, :, None]).astype(q.dtype)                # (h, b, dh)
    return jnp.transpose(out, (1, 0, 2))                     # (b, h, dh)


# ---------------------------------------------------------------------------
# RDMA ring attention: the K/V ring and the blockwise online softmax in
# ONE Pallas kernel — the next hop's K/V remote copy is STARTED before
# the resident block's accumulate and WAITED after it, so the einsum
# work covers the wire time (the overlap the XLA ``ppermute`` schedule
# can only hint at).  Semaphore/credit protocol shared with
# ``ops/pallas_collectives`` (see its module docstring).
# ---------------------------------------------------------------------------


def _attn_vmem_bytes(b, h, dh, itemsize, qblk):
    """Scoped-VMEM estimate for the fused kernel: the q input block
    (VMEM in_spec) and its f32 scaled copy, the two revolving K/V slot
    pairs, the (m, l, acc) carries, the per-block score/probability
    tiles (x3: s, p, and the masked intermediate), and the output
    block."""
    return (b * h * dh * itemsize + b * h * dh * 4
            + 4 * b * h * dh * itemsize + 2 * h * b * 4
            + h * b * dh * 4 + 3 * h * qblk * b * 4 + b * h * dh * itemsize)


@functools.lru_cache(maxsize=64)
def _rdma_attn_call(axis, p, b, h, dh, dtype_str, causal, scale, qblk,
                    interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..ops import pallas_collectives as _pc

    dtype = jnp.dtype(dtype_str)
    nq = b // qblk
    sc = float(1.0 / np.sqrt(dh) if scale is None else scale)

    def kernel(q_ref, k_ref, v_ref, o_ref, qf, kv, m_ref, l_ref, acc,
               send_sem, recv_sem, copy_sem, cbuf, csend, crecv):
        me = lax.axis_index(axis)
        left = _pc._mod(me - 1, p)
        right = _pc._mod(me + 1, p)
        credit = _pc._Credit(cbuf, csend, crecv)
        _pc._copy(k_ref, kv.at[0, 0], copy_sem)
        _pc._copy(v_ref, kv.at[0, 1], copy_sem)
        # mirror the lax path exactly: scale in the input dtype, then f32
        qf[...] = (q_ref[...] * jnp.asarray(sc, dtype)).astype(jnp.float32)
        m_ref[...] = jnp.full((h, b), -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros((h, b), jnp.float32)
        acc[...] = jnp.zeros((h, b, dh), jnp.float32)
        for t in range(p):
            s = t % 2
            src = _pc._mod(me - t, p)        # resident block's origin
            if t < p - 1:
                # credit window arms at t == 1, mirroring the
                # checker-proven _ag_gemm_prog window (ring_schedules):
                # the step-t forward writes the slot the lagging right
                # neighbor's step-(t-1) attention compute still reads,
                # so every forward after the first must take a credit
                if t >= 1:
                    credit.take(right)       # right freed the slot we hit
                fwd = pltpu.make_async_remote_copy(
                    src_ref=kv.at[s], dst_ref=kv.at[1 - s],
                    send_sem=send_sem.at[s], recv_sem=recv_sem.at[1 - s],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                fwd.start()
            # resident block accumulates while the K/V pair rides the
            # ring — blocked over query rows to bound the score tile
            kc = kv[s, 0].astype(jnp.float32)
            vc = kv[s, 1].astype(jnp.float32)
            for qb in range(nq):
                r0 = qb * qblk
                qx = qf[r0:r0 + qblk]
                s_ = jnp.einsum("qhd,khd->hqk", qx, kc)
                if causal:
                    qpos = me * b + r0 + lax.broadcasted_iota(
                        jnp.int32, (qblk, b), 0)
                    kpos = src * b + lax.broadcasted_iota(
                        jnp.int32, (qblk, b), 1)
                    s_ = jnp.where((kpos <= qpos)[None], s_, -jnp.inf)
                mm = m_ref[:, r0:r0 + qblk]
                blk_max = jnp.max(s_, axis=-1)
                m_new = jnp.maximum(mm, blk_max)
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                pr = jnp.exp(s_ - m_safe[:, :, None])
                pr = jnp.where(jnp.isfinite(s_), pr, 0.0)
                alpha = jnp.where(jnp.isfinite(mm), jnp.exp(mm - m_safe),
                                  0.0)
                l_ref[:, r0:r0 + qblk] = (l_ref[:, r0:r0 + qblk] * alpha
                                          + jnp.sum(pr, axis=-1))
                acc[:, r0:r0 + qblk] = (
                    acc[:, r0:r0 + qblk] * alpha[:, :, None]
                    + jnp.einsum("hqk,khd->hqd", pr, vc))
                m_ref[:, r0:r0 + qblk] = m_new
            if t < p - 1:
                fwd.wait()
                if t <= p - 3:               # balance against the takes
                    credit.grant(left)
        ll = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        out = (acc[...] / ll[:, :, None]).astype(dtype)
        o_ref[...] = jnp.transpose(out, (1, 0, 2))

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((b, h, dh), jnp.float32),
                        pltpu.VMEM((2, 2, b, h, dh), dtype),
                        pltpu.VMEM((h, b), jnp.float32),
                        pltpu.VMEM((h, b), jnp.float32),
                        pltpu.VMEM((h, b, dh), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA] + _pc._credit_scratch(),
        name="attn_ring_hop",
        interpret=interpret,
    )


def ring_attention_rdma_kernel(q, k, v, axis: str, causal: bool = False,
                               scale: float | None = None,
                               interpret: bool | None = None):
    """The fused Pallas RDMA path of :func:`ring_attention_kernel` —
    same contract, K/V ring hops as in-kernel remote DMAs overlapped
    with the online-softmax accumulates.  Falls back to the ``lax``
    kernel when RDMA is unavailable (platform, kill switch, K/V dtype
    mismatch, VMEM budget)."""
    from ..ops import pallas_collectives as _pc

    p = _axis_size(axis)
    b, h, dh = (int(s) for s in q.shape)
    mode = _pc.rdma_mode(interpret)
    qblk = b // _pc._chunk_fit(b, max(-(-b // 256), 1))
    if mode == "compiled" and _attn_vmem_bytes(
            b, h, dh, jnp.dtype(q.dtype).itemsize,
            qblk) > _pc._VMEM_LIMIT:
        mode = None
    if p == 1 or mode is None or k.dtype != q.dtype or v.dtype != q.dtype:
        return ring_attention_kernel(q, k, v, axis, causal=causal,
                                     scale=scale)
    _pc._record_dispatch("ring_attention", "rdma", k, axis, mode=mode)
    return _rdma_attn_call(axis, p, b, h, dh, str(q.dtype), bool(causal),
                           None if scale is None else float(scale), qblk,
                           mode == "interpret")(q, k, v)


@functools.lru_cache(maxsize=32)
def _ring_jit(mesh, causal: bool, rdma=None):
    axis = mesh.axis_names[0]
    spec = P(axis, None, None)

    def fn(q, k, v):
        if rdma:
            return ring_attention_rdma_kernel(
                q, k, v, axis, causal=causal,
                interpret=rdma == "interpret")
        return ring_attention_kernel(q, k, v, axis, causal=causal)

    return jax.jit(shard_map_compat(fn, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check=False))


@functools.lru_cache(maxsize=32)
def _ring_jit_1d(pids: tuple, causal: bool, rdma: str):
    # the RDMA kernels address ring neighbors by LOGICAL device id,
    # which Pallas only supports under a single named mesh axis — so the
    # armed program runs over the canonical 1-D mesh (same devices, same
    # order; inputs committed to the (n,1,1) mesh relabel for free)
    mesh = L.mesh_for(list(pids), (len(pids),))
    return _ring_jit(mesh, causal, rdma), mesh


def ring_attention(q: DArray, k: DArray, v: DArray,
                   causal: bool = False) -> DArray:
    """Exact attention over sequence-sharded (seq, heads, d) DArrays."""
    for name, a in (("q", q), ("k", k), ("v", v)):
        if a.ndim != 3:
            raise ValueError(f"{name} must be (seq, heads, head_dim), "
                             f"got {a.dims}")
        if a.dims != q.dims:
            raise ValueError("q, k, v dims must match")
    pids = [int(p) for p in q.pids.flat]
    n = len(pids)
    if q.pids.shape[0] != n or q.dims[0] % n != 0:
        raise ValueError(
            "ring attention needs the sequence dim sharded evenly over a "
            f"1-D grid; got grid {q.pids.shape} for dims {q.dims}")
    from ..ops import pallas_collectives as _pc
    rdma = _pc.rdma_mode()
    with _tm.span("ring_attention", ranks=n, causal=causal,
                  dispatch="rdma" if rdma else "xla"):
        out = None
        if rdma:
            fn, _ = _ring_jit_1d(tuple(pids), causal, rdma)
            try:
                out = fn(q.garray, k.garray, v.garray)
            except Exception as e:
                # the RDMA arm must never cost correctness: take the XLA
                # ring, loudly once per failure signature
                from ..utils.debug import warn_once
                warn_once(f"ring_attention:rdma:{type(e).__name__}",
                          f"ring_attention RDMA path failed "
                          f"({type(e).__name__}: {e}); falling back to "
                          f"the XLA ppermute ring")
        if out is None:
            out = _ring_jit(L.mesh_for(pids, (n, 1, 1)), causal)(
                q.garray, k.garray, v.garray)
        return _wrap_global(out, procs=pids, dist=[n, 1, 1])


def ring_attention_prefill(q, k, v, *, causal: bool = True,
                           procs: list[int] | None = None,
                           min_ring_tokens: int | None = None):
    """Cache-aware prefill entry for the decode service: exact causal
    attention over host/device ``(ntok, heads, head_dim)`` q/k/v rows,
    returning a host ``(ntok, heads, head_dim)`` output.

    Long prompts ride the sequence-sharded ring kernel (RDMA when
    armed): the rows are end-padded with zero rows to a multiple of the
    rank count — safe under causal masking, since every real query row
    sits *before* the padded key rows and never attends to them — then
    distributed, run through :func:`ring_attention`, gathered, and
    trimmed, with the scratch DArrays closed before returning (the
    caller's HBM ledger only keeps the KV pages it writes back).  Short
    prompts (below ``min_ring_tokens``, default ``2 * nranks``) take the
    dense :func:`reference_attention` oracle — sharding a handful of
    rows buys nothing and the grid would not divide."""
    q = np.asarray(q, np.float32)
    k = np.asarray(k, np.float32)
    v = np.asarray(v, np.float32)
    if q.ndim != 3:
        raise ValueError(f"q must be (ntok, heads, head_dim), "
                         f"got {q.shape}")
    ntok = q.shape[0]
    pids = [int(p) for p in (procs if procs is not None
                             else L.all_ranks())]
    n = max(1, len(pids))
    floor = 2 * n if min_ring_tokens is None else int(min_ring_tokens)
    if not causal or n < 2 or ntok < max(floor, n):
        return reference_attention(q, k, v, causal)
    from ..darray import distribute
    pad = (-ntok) % n
    if pad:
        z = np.zeros((pad,) + q.shape[1:], q.dtype)
        q, k, v = (np.concatenate([a, z]) for a in (q, k, v))
    dq = dk = dv = dout = None
    try:
        dq = distribute(q, procs=pids, dist=[n, 1, 1])
        dk = distribute(k, procs=pids, dist=[n, 1, 1])
        dv = distribute(v, procs=pids, dist=[n, 1, 1])
        dout = ring_attention(dq, dk, dv, causal=True)
        return np.asarray(dout.garray)[:ntok]
    finally:
        for d in (dq, dk, dv, dout):
            if d is not None:
                d.close()


def _ring_flash_fwd_loop(q, k, v, axis, causal, scale, block_q, block_k,
                         interpret, hfold=1):
    """Shared fused-ring forward.  Returns ``(out (b,h,d), oh (h,b,d),
    lse (h,b))`` — the latter two are the FA2 backward's residuals."""
    from ..ops.pallas_attention import (flash_attention_hop,
                                       flash_carry_finalize,
                                       flash_carry_init)

    nblk = _axis_size(axis)
    me = lax.axis_index(axis)
    b, h, dh = q.shape
    sc = float(1.0 / np.sqrt(dh) if scale is None else scale)

    # kernel layout is (heads, block, d); transpose once, ring-permute the
    # transposed buffers
    qh = jnp.transpose(q, (1, 0, 2))
    kh = jnp.transpose(k, (1, 0, 2))
    vh = jnp.transpose(v, (1, 0, 2))
    m0, l0, a0 = flash_carry_init(h, b, dh)
    perm = [(i, (i + 1) % nblk) for i in range(nblk)]
    qoff = me * b

    def hop(step, m, l, a, kc, vc):
        koff = ((me - step) % nblk) * b
        return flash_attention_hop(qh, kc, vc, m, l, a, qoff, koff,
                                   causal=causal, scale=sc,
                                   block_q=block_q, block_k=block_k,
                                   head_fold=hfold, interpret=interpret)

    def body(step, carry):
        m, l, a, kc, vc = carry
        m, l, a = hop(step, m, l, a, kc, vc)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return m, l, a, kc, vc

    m, l, a, kc, vc = lax.fori_loop(0, nblk - 1, body, (m0, l0, a0, kh, vh))
    m, l, a = hop(nblk - 1, m, l, a, kc, vc)
    oh, lse = flash_carry_finalize(m, l, a, q.dtype)
    return jnp.transpose(oh, (1, 0, 2)), oh, lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_flash_core(q, k, v, axis, causal, scale, block_q, block_k,
                     interpret, hfold=1):
    out, _, _ = _ring_flash_fwd_loop(q, k, v, axis, causal, scale,
                                     block_q, block_k, interpret, hfold)
    return out


def _ring_flash_core_fwd(q, k, v, axis, causal, scale, block_q, block_k,
                         interpret, hfold=1):
    out, oh, lse = _ring_flash_fwd_loop(q, k, v, axis, causal, scale,
                                        block_q, block_k, interpret, hfold)
    return out, (q, k, v, oh, lse)


def _ring_flash_core_bwd(axis, causal, scale, block_q, block_k, interpret,
                         hfold, res, g):
    # FA2 ring backward: p = exp(s - lse) is exact given the FINAL lse, so
    # every (q block, k/v block) pair's gradient contribution is
    # independent and additive.  Mirror the forward's ring schedule: dq
    # accumulates locally; dk/dv accumulators TRAVEL with their k/v blocks
    # through the same ppermute, and one extra rotation after the last hop
    # returns each block's gradient to its home rank.
    from ..ops.pallas_attention import _LANE, flash_attention_hop_bwd

    q, k, v, oh, lse = res
    nblk = _axis_size(axis)
    me = lax.axis_index(axis)
    b, h, dh = q.shape
    sc = float(1.0 / np.sqrt(dh) if scale is None else scale)

    qh = jnp.transpose(q, (1, 0, 2))
    kh = jnp.transpose(k, (1, 0, 2))
    vh = jnp.transpose(v, (1, 0, 2))
    gf = jnp.transpose(g, (1, 0, 2)).astype(jnp.float32)   # (h, b, dh)
    # dd from the FULL-precision cotangent (matches _flash_bwd); only the
    # kernel operand gh is downcast to the MXU input dtype
    dd = jnp.einsum("hbd,hbd->hb", gf, oh.astype(jnp.float32))
    gh = gf.astype(q.dtype)
    ddb = jnp.broadcast_to(dd[:, :, None], (h, b, _LANE))
    lseb = jnp.broadcast_to(lse[:, :, None], (h, b, _LANE))
    perm = [(i, (i + 1) % nblk) for i in range(nblk)]
    qoff = me * b
    zeros = lambda: jnp.zeros((h, b, dh), jnp.float32)

    def hop_bwd(step, dqa, dka, dva, kc, vc):
        koff = ((me - step) % nblk) * b
        dqc, dkc, dvc = flash_attention_hop_bwd(
            qh, kc, vc, gh, lseb, ddb, qoff, koff, causal=causal, scale=sc,
            block_q=block_q, block_k=block_k, interpret=interpret)
        return dqa + dqc, dka + dkc, dva + dvc

    def body(step, carry):
        dqa, dka, dva, kc, vc = carry
        dqa, dka, dva = hop_bwd(step, dqa, dka, dva, kc, vc)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        dka = lax.ppermute(dka, axis, perm)
        dva = lax.ppermute(dva, axis, perm)
        return dqa, dka, dva, kc, vc

    dqa, dka, dva, kc, vc = lax.fori_loop(
        0, nblk - 1, body, (zeros(), zeros(), zeros(), kh, vh))
    dqa, dka, dva = hop_bwd(nblk - 1, dqa, dka, dva, kc, vc)
    # block r's dk/dv sits one rank behind home after nblk-1 rotations
    dka = lax.ppermute(dka, axis, perm)
    dva = lax.ppermute(dva, axis, perm)
    back = lambda t: jnp.transpose(t, (1, 0, 2)).astype(q.dtype)
    return back(dqa), back(dka), back(dva)


_ring_flash_core.defvjp(_ring_flash_core_fwd, _ring_flash_core_bwd)


def ring_flash_attention_kernel(q, k, v, axis: str, causal: bool = False,
                                scale: float | None = None,
                                block_q: int | None = None,
                                block_k: int | None = None,
                                head_fold: int | None = None,
                                interpret: bool | None = None):
    """Fused ring attention: each hop's blockwise accumulate is ONE Pallas
    flash program (VMEM-resident online softmax, no (h, b, b) score
    materialization in HBM) and the online-softmax carry (m, l, acc) flows
    around the ``ppermute`` ring.  XLA schedules the next hop's K/V
    permute concurrently with the current hop's kernel, overlapping ICI
    with MXU compute (VERDICT round-2 item 7 / design.md round-2 item 5).

    q, k, v: ``(block, heads, d)`` — the calling rank's sequence block,
    inside ``shard_map``.  DIFFERENTIABLE end to end: the FA2-style ring
    backward (custom_vjp) saves only O(B) logsumexp rows per rank and
    re-runs the ring with Pallas recompute kernels, circulating dk/dv
    accumulators with their blocks — sequence-parallel training runs at
    Pallas speed (VERDICT round-3 item 3).
    """
    block_q, block_k, hfold = _tuned_hop_blocks(
        q, bool(causal), block_q, block_k)
    if head_fold is not None:
        hfold = head_fold
    sc = None if scale is None else float(scale)
    return _ring_flash_core(q, k, v, axis, bool(causal), sc,
                            int(block_q), int(block_k), interpret,
                            int(hfold))


def _tuned_hop_blocks(q, causal: bool, block_q, block_k):
    """Per-hop block sizes for an actual (local block, heads, d) array —
    see ``tuned_hop_blocks_for``."""
    return tuned_hop_blocks_for(q.shape, q.dtype, causal, block_q, block_k)


def tuned_hop_blocks_for(shape, dtype, causal: bool, block_q, block_k):
    """Per-hop block sizes: explicit values win; ``None`` consults the
    ``"ring_flash"`` autotune entry for this (local block, heads, d,
    dtype, causal), falling back to 512².  Shared by the contiguous and
    zigzag fused kernels (the hop programs fit blocks to their half/full extents anyway;
    both thread a 3-tuple entry's head fold through
    ``flash_attention_hop``).  Callers that cache jitted programs must
    resolve through here OUTSIDE the cache and key on the resolved
    values (see models/sp_transformer._resolve_cfg) — resolving at trace
    time inside a cached program silently pins the registry's state at
    first trace."""
    if block_q is not None and block_k is not None:
        return block_q, block_k, 1
    from ..utils import autotune
    vals = autotune.valid_ints(
        autotune.get("ring_flash",
                     autotune.device_key_for(shape[0], shape[1],
                                             shape[2], dtype, causal)),
        (2, 3))
    tq, tk = (vals[0], vals[1]) if vals else (512, 512)
    # the tuned fold was measured WITH the tuned blocks (same policy as
    # tuned_flash_config)
    hf = vals[2] if (vals and len(vals) == 3
                     and block_q is None and block_k is None) else 1
    return (tq if block_q is None else block_q,
            tk if block_k is None else block_k, hf)


@functools.lru_cache(maxsize=32)
def _ring_flash_jit(mesh, causal: bool, block_q: int, block_k: int,
                    head_fold: int = 1):
    axis = mesh.axis_names[0]
    spec = P(axis, None, None)

    def fn(q, k, v):
        return ring_flash_attention_kernel(q, k, v, axis, causal=causal,
                                           block_q=block_q, block_k=block_k,
                                           head_fold=head_fold)

    return jax.jit(shard_map_compat(fn, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check=False))


def ring_flash_attention(q: DArray, k: DArray, v: DArray,
                         causal: bool = False, block_q: int | None = None,
                         block_k: int | None = None) -> DArray:
    """Fused (Pallas per-hop) exact attention over sequence-sharded
    (seq, heads, d) DArrays — the performance path of ``ring_attention``."""
    for name, a in (("q", q), ("k", k), ("v", v)):
        if a.ndim != 3:
            raise ValueError(f"{name} must be (seq, heads, head_dim), "
                             f"got {a.dims}")
        if a.dims != q.dims:
            raise ValueError("q, k, v dims must match")
    pids = [int(p) for p in q.pids.flat]
    n = len(pids)
    if q.pids.shape[0] != n or q.dims[0] % n != 0:
        raise ValueError(
            "ring attention needs the sequence dim sharded evenly over a "
            f"1-D grid; got grid {q.pids.shape} for dims {q.dims}")
    blk = q.dims[0] // n
    lq = jax.ShapeDtypeStruct((blk, q.dims[1], q.dims[2]), q.dtype)
    block_q, block_k, hf = _tuned_hop_blocks(lq, bool(causal), block_q,
                                             block_k)
    bq = min(block_q, blk)
    bk = min(block_k, blk)
    while blk % bq:
        bq //= 2
    while blk % bk:
        bk //= 2
    mesh = L.mesh_for(pids, (n, 1, 1))
    out = _ring_flash_jit(mesh, causal, bq, bk, hf)(
        q.garray, k.garray, v.garray)
    return _wrap_global(out, procs=pids, dist=[n, 1, 1])


# ---------------------------------------------------------------------------
# Zigzag (load-balanced) causal ring attention.
#
# With the contiguous layout above, causal masking makes the USEFUL work
# per rank proportional to its position (rank P-1's block attends to the
# whole prefix, rank 0's almost nothing), and the dense per-hop einsum
# spends full FLOPs either way.  The zigzag layout (as popularized by the
# zigzag/"striped" ring-attention schemes in the long-context literature)
# splits the sequence into 2P chunks and gives rank i the PAIR
# (chunk i, chunk 2P-1-i).  Chunk-level causal structure then becomes
# static per quadrant:
#
#   local (q1, q2) = chunks (me, 2P-1-me); visiting (k1, k2) from src:
#     q1 x k2 : ALWAYS fully masked  -> never computed
#     q2 x k1 : ALWAYS fully unmasked -> computed maskless
#     q1 x k1 : unmasked iff src < me, diagonal iff src == me
#     q2 x k2 : unmasked iff src > me, diagonal iff src == me
#
# so each rank computes ~2 of 4 quadrants every hop — half the dense
# FLOPs, evenly balanced — selected with lax.switch on sign(src - me).
# ---------------------------------------------------------------------------


def zigzag_order(S: int, nranks: int) -> np.ndarray:
    """Permutation taking a natural-order sequence to zigzag-shard order:
    rank i's rows are [chunk i, chunk 2P-1-i] of 2P equal chunks."""
    if S % (2 * nranks):
        raise ValueError(f"sequence length {S} must divide 2*nranks "
                         f"({2 * nranks})")
    half = S // (2 * nranks)
    chunks = np.arange(S).reshape(2 * nranks, half)
    order = [c for i in range(nranks)
             for c in (chunks[i], chunks[2 * nranks - 1 - i])]
    return np.concatenate(order)


def zigzag_shard(x, nranks: int):
    """Reorder dim 0 of ``x`` (length S, natural order) into zigzag-shard
    order.  Apply before distributing over the ring."""
    return jnp.asarray(x)[jnp.asarray(zigzag_order(x.shape[0], nranks))]


def zigzag_unshard(x, nranks: int):
    """Inverse of ``zigzag_shard``."""
    inv = np.argsort(zigzag_order(x.shape[0], nranks))
    return jnp.asarray(x)[jnp.asarray(inv)]


def zigzag_ring_attention_kernel(q, k, v, axis: str,
                                 scale: float | None = None):
    """Causal blockwise ring attention on zigzag-ordered blocks.

    q, k, v: ``(block, heads, d)`` — the calling rank's zigzag PAIR
    (chunk me, chunk 2P-1-me concatenated), inside ``shard_map``.
    Exact; computes only the ~2 useful quadrants per hop (see the scheme
    note above).  Causal only — for non-causal use the plain ring (the
    mask is the whole point of the layout).
    """
    nblk = _axis_size(axis)
    me = lax.axis_index(axis)
    b, h, dh = q.shape
    if b % 2:
        raise ValueError(f"zigzag needs an even local block; got {b}")
    half = b // 2
    sc = jnp.asarray(1.0 / np.sqrt(dh), q.dtype) if scale is None \
        else jnp.asarray(scale, q.dtype)

    qf = (q * sc).astype(jnp.float32)
    q1, q2 = qf[:half], qf[half:]

    def acc_half(m, l, o, qh_, kc, vc, mask=None):
        # one (half x half) quadrant through the shared accumulate
        return _online_accumulate(m, l, o, qh_, kc, vc, mask)

    diag = jnp.tril(jnp.ones((half, half), bool))   # intra-chunk causal

    init = (jnp.full((h, half), -jnp.inf, jnp.float32),
            jnp.zeros((h, half), jnp.float32),
            jnp.zeros((h, half, dh), jnp.float32))

    def accumulate(step, c1, c2, kc, vc):
        src = (me - step) % nblk
        k1, v1 = kc[:half], vc[:half]
        k2, v2 = kc[half:], vc[half:]
        # q2 x k1: always fully unmasked
        c2 = acc_half(*c2, q2, k1, v1)

        def lt(ops):                       # src < me: q1 attends all of k1
            c1, c2, k1, v1, k2, v2 = ops
            return acc_half(*c1, q1, k1, v1), c2

        def eq(ops):                       # src == me: both diagonals
            c1, c2, k1, v1, k2, v2 = ops
            return (acc_half(*c1, q1, k1, v1, diag),
                    acc_half(*c2, q2, k2, v2, diag))

        def gt(ops):                       # src > me: q2 attends all of k2
            c1, c2, k1, v1, k2, v2 = ops
            return c1, acc_half(*c2, q2, k2, v2)

        idx = jnp.clip(jnp.sign(src - me) + 1, 0, 2).astype(jnp.int32)
        c1, c2 = lax.switch(idx, (lt, eq, gt), (c1, c2, k1, v1, k2, v2))
        return c1, c2

    perm = [(i, (i + 1) % nblk) for i in range(nblk)]

    def body(step, carry):
        c1, c2, kc, vc = carry
        c1, c2 = accumulate(step, c1, c2, kc, vc)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return c1, c2, kc, vc

    c1, c2, kc, vc = lax.fori_loop(0, nblk - 1, body, (init, init, k, v))
    c1, c2 = accumulate(nblk - 1, c1, c2, kc, vc)

    outs = []
    for m, l, o in (c1, c2):
        l = jnp.where(l == 0.0, 1.0, l)
        outs.append((o / l[:, :, None]).astype(q.dtype))     # (h, half, dh)
    return jnp.transpose(jnp.concatenate(outs, axis=1), (1, 0, 2))


def _zigzag_flash_fwd_loop(q, k, v, axis, scale, block_q, block_k,
                           interpret, hfold=1):
    """Shared fused-zigzag forward.  Returns ``(out (b,h,d), oh (h,b,d),
    lse (h,b))`` with the two half-chunks concatenated on the row axis."""
    from ..ops.pallas_attention import (flash_attention_hop,
                                       flash_carry_finalize,
                                       flash_carry_init)

    nblk = _axis_size(axis)
    me = lax.axis_index(axis)
    b, h, dh = q.shape
    if b % 2:
        raise ValueError(f"zigzag needs an even local block; got {b}")
    half = b // 2
    sc = float(1.0 / np.sqrt(dh) if scale is None else scale)

    qh = jnp.transpose(q, (1, 0, 2))                     # (h, b, dh)
    kh = jnp.transpose(k, (1, 0, 2))
    vh = jnp.transpose(v, (1, 0, 2))
    q1, q2 = qh[:, :half], qh[:, half:]
    qoff1 = me * half
    qoff2 = (2 * nblk - 1 - me) * half

    def hop(causal_, qx, kx, vx, carry, qoff, koff):
        m, l, a = carry
        return flash_attention_hop(qx, kx, vx, m, l, a, qoff, koff,
                                   causal=causal_, scale=sc,
                                   block_q=block_q, block_k=block_k,
                                   head_fold=hfold, interpret=interpret)

    init = flash_carry_init(h, half, dh)

    def accumulate(step, c1, c2, kc, vc):
        src = (me - step) % nblk
        k1, v1 = kc[:, :half], vc[:, :half]
        k2, v2 = kc[:, half:], vc[:, half:]
        koff1 = src * half
        koff2 = (2 * nblk - 1 - src) * half
        # q2 x k1: always fully unmasked
        c2 = hop(False, q2, k1, v1, c2, qoff2, koff1)

        def lt(ops):
            c1, c2, k1, v1, k2, v2 = ops
            return hop(False, q1, k1, v1, c1, qoff1, koff1), c2

        def eq(ops):
            c1, c2, k1, v1, k2, v2 = ops
            return (hop(True, q1, k1, v1, c1, qoff1, koff1),
                    hop(True, q2, k2, v2, c2, qoff2, koff2))

        def gt(ops):
            c1, c2, k1, v1, k2, v2 = ops
            return c1, hop(False, q2, k2, v2, c2, qoff2, koff2)

        idx = jnp.clip(jnp.sign(src - me) + 1, 0, 2).astype(jnp.int32)
        c1, c2 = lax.switch(idx, (lt, eq, gt), (c1, c2, k1, v1, k2, v2))
        return c1, c2

    perm = [(i, (i + 1) % nblk) for i in range(nblk)]

    def body(step, carry):
        c1, c2, kc, vc = carry
        c1, c2 = accumulate(step, c1, c2, kc, vc)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return c1, c2, kc, vc

    c1, c2, kc, vc = lax.fori_loop(0, nblk - 1, body, (init, init, kh, vh))
    c1, c2 = accumulate(nblk - 1, c1, c2, kc, vc)

    oh1, lse1 = flash_carry_finalize(*c1, q.dtype)
    oh2, lse2 = flash_carry_finalize(*c2, q.dtype)
    oh = jnp.concatenate([oh1, oh2], axis=1)             # (h, b, dh)
    lse = jnp.concatenate([lse1, lse2], axis=1)          # (h, b)
    return jnp.transpose(oh, (1, 0, 2)), oh, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _zigzag_flash_core(q, k, v, axis, scale, block_q, block_k, interpret,
                       hfold=1):
    out, _, _ = _zigzag_flash_fwd_loop(q, k, v, axis, scale,
                                       block_q, block_k, interpret, hfold)
    return out


def _zigzag_flash_core_fwd(q, k, v, axis, scale, block_q, block_k,
                           interpret, hfold=1):
    out, oh, lse = _zigzag_flash_fwd_loop(q, k, v, axis, scale,
                                          block_q, block_k, interpret,
                                          hfold)
    return out, (q, k, v, oh, lse)


def _zigzag_flash_core_bwd(axis, scale, block_q, block_k, interpret, hfold,
                           res, g):
    # the ring FA2 backward (see _ring_flash_core_bwd) specialized to the
    # zigzag quadrant schedule: each hop re-runs exactly the quadrants the
    # forward computed (the same lax.switch on sign(src - me)), adding
    # each quadrant's (dq, dk, dv) contribution — dq into the local half
    # accumulators, dk/dv into the accumulators TRAVELING with the k/v
    # halves around the ring.
    from ..ops.pallas_attention import _LANE, flash_attention_hop_bwd

    q, k, v, oh, lse = res
    nblk = _axis_size(axis)
    me = lax.axis_index(axis)
    b, h, dh = q.shape
    half = b // 2
    sc = float(1.0 / np.sqrt(dh) if scale is None else scale)

    qh = jnp.transpose(q, (1, 0, 2))
    kh = jnp.transpose(k, (1, 0, 2))
    vh = jnp.transpose(v, (1, 0, 2))
    gf = jnp.transpose(g, (1, 0, 2)).astype(jnp.float32)   # (h, b, dh)
    # dd from the FULL-precision cotangent (matches _flash_bwd); only the
    # kernel operand gh is downcast to the MXU input dtype
    dd = jnp.einsum("hbd,hbd->hb", gf, oh.astype(jnp.float32))
    gh = gf.astype(q.dtype)
    ddb = jnp.broadcast_to(dd[:, :, None], (h, b, _LANE))
    lseb = jnp.broadcast_to(lse[:, :, None], (h, b, _LANE))
    q1, q2 = qh[:, :half], qh[:, half:]
    g1, g2 = gh[:, :half], gh[:, half:]
    dd1, dd2 = ddb[:, :half], ddb[:, half:]
    lse1, lse2 = lseb[:, :half], lseb[:, half:]
    qoff1 = me * half
    qoff2 = (2 * nblk - 1 - me) * half

    def hb(causal_, qx, gx, lsex, ddx, qoff, kx, vx, koff):
        return flash_attention_hop_bwd(
            qx, kx, vx, gx, lsex, ddx, qoff, koff, causal=causal_, scale=sc,
            block_q=block_q, block_k=block_k, interpret=interpret)

    def accumulate_bwd(step, dq1a, dq2a, dka, dva, kc, vc):
        src = (me - step) % nblk
        k1, v1 = kc[:, :half], vc[:, :half]
        k2, v2 = kc[:, half:], vc[:, half:]
        koff1 = src * half
        koff2 = (2 * nblk - 1 - src) * half
        # q2 x k1: always computed in the forward
        dqc, dkc, dvc = hb(False, q2, g2, lse2, dd2, qoff2, k1, v1, koff1)
        dq2a = dq2a + dqc
        dka = dka.at[:, :half].add(dkc)
        dva = dva.at[:, :half].add(dvc)

        def lt(ops):
            dq1a, dq2a, dka, dva = ops
            dqc, dkc, dvc = hb(False, q1, g1, lse1, dd1, qoff1,
                               k1, v1, koff1)
            return (dq1a + dqc, dq2a, dka.at[:, :half].add(dkc),
                    dva.at[:, :half].add(dvc))

        def eq(ops):
            dq1a, dq2a, dka, dva = ops
            dqc1, dkc1, dvc1 = hb(True, q1, g1, lse1, dd1, qoff1,
                                  k1, v1, koff1)
            dqc2, dkc2, dvc2 = hb(True, q2, g2, lse2, dd2, qoff2,
                                  k2, v2, koff2)
            return (dq1a + dqc1, dq2a + dqc2,
                    dka.at[:, :half].add(dkc1).at[:, half:].add(dkc2),
                    dva.at[:, :half].add(dvc1).at[:, half:].add(dvc2))

        def gt(ops):
            dq1a, dq2a, dka, dva = ops
            dqc, dkc, dvc = hb(False, q2, g2, lse2, dd2, qoff2,
                               k2, v2, koff2)
            return (dq1a, dq2a + dqc, dka.at[:, half:].add(dkc),
                    dva.at[:, half:].add(dvc))

        idx = jnp.clip(jnp.sign(src - me) + 1, 0, 2).astype(jnp.int32)
        return lax.switch(idx, (lt, eq, gt), (dq1a, dq2a, dka, dva))

    perm = [(i, (i + 1) % nblk) for i in range(nblk)]
    zh = lambda: jnp.zeros((h, half, dh), jnp.float32)
    zb = lambda: jnp.zeros((h, b, dh), jnp.float32)

    def body(step, carry):
        dq1a, dq2a, dka, dva, kc, vc = carry
        dq1a, dq2a, dka, dva = accumulate_bwd(step, dq1a, dq2a, dka, dva,
                                              kc, vc)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        dka = lax.ppermute(dka, axis, perm)
        dva = lax.ppermute(dva, axis, perm)
        return dq1a, dq2a, dka, dva, kc, vc

    dq1a, dq2a, dka, dva, kc, vc = lax.fori_loop(
        0, nblk - 1, body, (zh(), zh(), zb(), zb(), kh, vh))
    dq1a, dq2a, dka, dva = accumulate_bwd(nblk - 1, dq1a, dq2a, dka, dva,
                                          kc, vc)
    # block r's dk/dv sits one rank behind home after nblk-1 rotations
    dka = lax.ppermute(dka, axis, perm)
    dva = lax.ppermute(dva, axis, perm)
    dq = jnp.concatenate([dq1a, dq2a], axis=1)
    back = lambda t: jnp.transpose(t, (1, 0, 2)).astype(q.dtype)
    return back(dq), back(dka), back(dva)


_zigzag_flash_core.defvjp(_zigzag_flash_core_fwd, _zigzag_flash_core_bwd)


def zigzag_ring_flash_attention_kernel(q, k, v, axis: str,
                                       scale: float | None = None,
                                       block_q: int | None = None,
                                       block_k: int | None = None,
                                       head_fold: int | None = None,
                                       interpret: bool | None = None):
    """Fused zigzag ring attention: the quadrant schedule of
    ``zigzag_ring_attention_kernel`` with each computed quadrant running
    as ONE Pallas flash hop (``flash_attention_hop`` on half-blocks, the
    online-softmax carry flowing around the ring).  Cross quadrants use
    the maskless kernel; diagonal quadrants the causal kernel with global
    chunk offsets.  DIFFERENTIABLE end to end (custom_vjp): the backward
    re-runs the quadrant schedule with the FA2 recompute kernels, so
    load-balanced causal training also runs at Pallas speed.
    """
    block_q, block_k, hfold = _tuned_hop_blocks(q, True, block_q, block_k)
    if head_fold is not None:
        hfold = head_fold
    sc = None if scale is None else float(scale)
    return _zigzag_flash_core(q, k, v, axis, sc, int(block_q),
                              int(block_k), interpret, int(hfold))


@functools.lru_cache(maxsize=32)
def _zigzag_flash_jit(mesh, block_q: int, block_k: int,
                      head_fold: int = 1):
    axis = mesh.axis_names[0]
    spec = P(axis, None, None)

    def fn(q, k, v):
        return zigzag_ring_flash_attention_kernel(q, k, v, axis,
                                                  block_q=block_q,
                                                  block_k=block_k,
                                                  head_fold=head_fold)

    return jax.jit(shard_map_compat(fn, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check=False))


def zigzag_ring_flash_attention(q: DArray, k: DArray, v: DArray,
                                block_q: int | None = None,
                                block_k: int | None = None) -> DArray:
    """Fused (Pallas per-quadrant) zigzag causal ring attention over
    zigzag-ordered sequence-sharded DArrays — the performance path of
    ``zigzag_ring_attention``."""
    for name, a in (("q", q), ("k", k), ("v", v)):
        if a.ndim != 3:
            raise ValueError(f"{name} must be (seq, heads, head_dim), "
                             f"got {a.dims}")
        if a.dims != q.dims:
            raise ValueError("q, k, v dims must match")
    pids = [int(p) for p in q.pids.flat]
    n = len(pids)
    if q.pids.shape[0] != n or q.dims[0] % (2 * n) != 0:
        raise ValueError(
            "zigzag ring attention needs the sequence dim divisible by "
            f"2*nranks over a 1-D grid; got grid {q.pids.shape} for dims "
            f"{q.dims}")
    half = q.dims[0] // (2 * n)
    # None blocks: the registry default (keyed on the per-rank local
    # block the kernel will see) before fitting to the half extent
    lq = jax.ShapeDtypeStruct((q.dims[0] // n, q.dims[1], q.dims[2]),
                              q.dtype)
    block_q, block_k, zhf = _tuned_hop_blocks(lq, True, block_q, block_k)
    bq = min(block_q, half)
    bk = min(block_k, half)
    while half % bq:
        bq //= 2
    while half % bk:
        bk //= 2
    mesh = L.mesh_for(pids, (n, 1, 1))
    out = _zigzag_flash_jit(mesh, bq, bk, zhf)(
        q.garray, k.garray, v.garray)
    return _wrap_global(out, procs=pids, dist=[n, 1, 1])


@functools.lru_cache(maxsize=32)
def _zigzag_jit(mesh):
    axis = mesh.axis_names[0]
    spec = P(axis, None, None)

    def fn(q, k, v):
        return zigzag_ring_attention_kernel(q, k, v, axis)

    return jax.jit(shard_map_compat(fn, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check=False))


def zigzag_ring_attention(q: DArray, k: DArray, v: DArray) -> DArray:
    """Load-balanced causal ring attention over sequence-sharded
    (seq, heads, d) DArrays whose rows are already in zigzag order
    (``zigzag_shard``).  Returns zigzag-ordered output — ``zigzag_unshard``
    to recover natural order.  ~2x the useful-FLOP efficiency of
    ``ring_attention(causal=True)`` per rank, evenly balanced."""
    for name, a in (("q", q), ("k", k), ("v", v)):
        if a.ndim != 3:
            raise ValueError(f"{name} must be (seq, heads, head_dim), "
                             f"got {a.dims}")
        if a.dims != q.dims:
            raise ValueError("q, k, v dims must match")
    pids = [int(p) for p in q.pids.flat]
    n = len(pids)
    if q.pids.shape[0] != n or q.dims[0] % (2 * n) != 0:
        raise ValueError(
            "zigzag ring attention needs the sequence dim divisible by "
            f"2*nranks over a 1-D grid; got grid {q.pids.shape} for dims "
            f"{q.dims}")
    mesh = L.mesh_for(pids, (n, 1, 1))
    out = _zigzag_jit(mesh)(q.garray, k.garray, v.garray)
    return _wrap_global(out, procs=pids, dist=[n, 1, 1])


def reference_attention(q, k, v, causal: bool = False):
    """Dense O(seq²) oracle for tests."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = np.einsum("qhd,khd->hqk", q / np.sqrt(q.shape[-1]), k)
    if causal:
        qi = np.arange(q.shape[0])[:, None]
        ki = np.arange(k.shape[0])[None, :]
        s = np.where((ki <= qi)[None], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    o = np.einsum("hqk,khd->hqd", p, v)
    return np.transpose(o, (1, 0, 2))
