"""Hand-written Pallas TPU kernels: the gated delta rule of a linear-attention
layer (Gated DeltaNet, Yang et al., ICLR 2025) by the chunked WY/UT form of
Yang et al., NeurIPS 2024, forward and backward.

    S_t = a_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     S_{-1} = 0
    o_t = S_t q_t

for one sequence of ``H`` heads: ``q, k`` (L, H, dk), ``v`` (L, H, dv),
``beta`` (L, H), ``a_t = exp(g_t)`` with ``g`` (L, H) the log-gate; each head
has its own state in R^{dv x dk}.  ``beta`` may reach 2 (the transition's
eigenvalue ``1 - beta`` then reaches into (-1, 0)), which the solve below
takes exactly.  A batch folds into the heads.

Within a chunk of ``C`` positions, with ``b`` the chunk's running sum of
``g``, ``G[i, j] = exp(b_i - b_j)`` (i >= j, else 0) and ``S`` the state the
chunk starts from,

    A  = strict_lower(diag(beta) (K K^T * G))      T = (I + A)^-1 diag(beta)
    W  = T (K * exp(b))                             U = T V
    V' = U - W S^T
    O  = (Q * exp(b)) S^T + lower(Q K^T * G) V'
    S' = exp(b_C) S + V'^T (K * exp(b_C - b))

(``V'`` are the values the delta rule writes, net of what the state
already held).  ``(I + A)^-1`` of the unit lower-triangular ``I + A`` is
taken by doubling: with ``X`` the inverse of its diagonal blocks of ``s``
rows, the blocks of ``2 s`` rows have the inverse ``X - X E X``, ``E`` the
part of ``A`` below those blocks and inside the larger ones (exact, as
``(X E)^2 = 0``), six levels of products for a chunk of 64, float32 at the
MXU's full precision.  Every exponent is a difference ``b_i - b_j`` with
``i >= j`` or a running sum itself, never above 0, so nothing overflows.

Layout.  Heads lead: q, k, v and o are (H, L, width) and a grid step holds
``head_block`` heads; ``beta`` and the running sums come as (H, chunks, C)
and stay resident for a head block while its chunks go by.  The forward
is two kernels, both named ``gdn_fwd``.  The solve writes each chunk's
``X = (I + A)^-1`` (C x C float32 a chunk and head, consecutive chunks side
by side in 128-lane rows, a grid step a row), which depends on k, beta
and the gates alone: it carries no state, and ``_gdn_fwd`` names its
result ``"gdn_solve"`` so that a recomputation policy may keep it.  The
recurrence reads ``X``, walks the chunks first to last (grid (head blocks,
chunks), a block's states in a scratch between chunks), and writes ``o``
and the state each chunk starts from (H x chunks x dv x dk float32).  The
backward walks the chunks last to
first, reads a chunk's ``X`` and takes ``T = X diag(beta)`` from it,
computes what is cheap again (``K K^T``, ``G``, ``W``, ``V'`` from the
saved state, ``Q K^T``), carries the state's cotangent in VMEM, and gives
dq, dk, dv, dbeta and the cotangent of the running sums (the running sums
themselves, and their cotangent's sum back into ``g``, are XLA's, round
the kernels).  The products with a width take their operands in the
activations' type (bf16 in training) and accumulate in float32; the
solve, the states and every sum are float32.

``custom_vjp``: ``gated_delta`` is differentiable in all five arguments.
A caller without a recomputation runs the solve once and the recurrence
once; under ``jax.checkpoint`` its policy decides whether the recomputed
forward solves again.  The plan (chunk, chunks, heads a block, the VMEM
the three kernels ask for, the bytes of the saved states and of the
saved solves) is published as the gauge
``pallas.gated_delta.plan`` when a program is built (docs/telemetry.md).
Interpreter mode runs the same kernels off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_gemm import _on_tpu
from .. import telemetry as _tm

__all__ = ["gated_delta", "gated_delta_plan"]

_CHUNK = 64           # positions a grid step (the published kernels' 64)
# heads a grid step: at 30 heads of the benchmark's size five read 4%
# faster than two, one 7% slower (PERF.md, section 6)
_HEAD_BLOCK = 5
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))       # a @ b^T
_TN = (((0,), (0,)), ((), ()))       # a^T @ b


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot32(a, b, dims=_NN):
    """A product of two float32 operands at float32's precision."""
    return jax.lax.dot_general(a, b, dims, precision=(_HI, _HI),
                               preferred_element_type=_F32)


class _Masks:
    """The (C, C) index planes of a chunk and the masks built from them."""

    def __init__(self, c: int):
        self.c = c
        self.ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        self.jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.eye = self.ii == self.jj
        self.lower = self.jj <= self.ii
        self.strict = self.jj < self.ii
        self.last_col = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1


def _col(row, m: _Masks):
    """A row (1, C) as a column (C, 1), through the diagonal."""
    return jnp.sum(jnp.where(m.eye, row, 0.0), axis=1, keepdims=True)


def _row(col, m: _Masks):
    """A column (C, 1) as a row (1, C), through the diagonal."""
    return jnp.sum(jnp.where(m.eye, col, 0.0), axis=0, keepdims=True)


def _chunk_row(blk, c):
    """Row ``c`` (1, C) of a resident (chunks, C) block."""
    at = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == c
    return jnp.sum(jnp.where(at, blk, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(a, m: _Masks):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` by doubling the
    diagonal blocks whose inverse is known: ``X <- X - X E X``."""
    x = m.eye.astype(_F32)
    s = 1
    while s < m.c:
        big, small = -2 * s, -s
        e = jnp.where(((m.ii & big) == (m.jj & big))
                      & ((m.ii & small) != (m.jj & small)), a, 0.0)
        x = x - _dot32(_dot32(x, e), x)
        s *= 2
    return x


def _prelude(brow, m: _Masks):
    """A chunk's state-free (C, C) part that every kernel takes again: the
    running sums as a column, ``G`` and ``b_C`` as a row."""
    bcol = _col(brow, m)
    gam = jnp.exp(jnp.where(m.lower, bcol - brow, -jnp.inf))      # G
    bend = jnp.sum(jnp.where(m.jj == m.c - 1, brow, 0.0), axis=1,
                   keepdims=True)                                # b_C a row
    return dict(bcol=bcol, gam=gam, bend=bend)


def _solve(k, betarow, pre, m: _Masks):
    """The chunk's ``X = (I + A)^-1``: the solve kernel's alone, which
    writes it out for the recurrence and the backward."""
    a = jnp.where(m.strict, _col(betarow, m) * _dot(k, k, _NT) * pre["gam"],
                  0.0)
    return _unit_lower_inverse(a, m)


def _forward_parts(q, k, v, x, betarow, s, pre, m: _Masks):
    """What a chunk's forward computes from its prelude, its solve ``x``
    and the state ``s`` it starts from, for its output, its next state and
    the backward: ``pre`` with the chunk's further matrices."""
    cdt = q.dtype
    t = x * betarow                                              # T
    eb = jnp.exp(pre["bcol"])
    kf = k.astype(_F32)
    kg = kf * eb
    tc = t.astype(cdt)
    w = _dot(tc, kg.astype(cdt))
    vn = _dot(tc, v) - _dot(w.astype(cdt), s.astype(cdt), _NT)     # V'
    qk = _dot(q, k, _NT)
    p = qk * pre["gam"]
    kt = kf * jnp.exp(pre["bend"] - pre["bcol"])
    return dict(pre, t=t, eb=eb, kg=kg, w=w, vn=vn, qk=qk, p=p, kt=kt)


def _pack(chunk: int) -> int:
    """Chunks whose solves share a row of the saved ``X``: a (C, C) float32
    block pads to 128 lanes in HBM and VMEM, so chunks of a width that
    divides 128 lie side by side, consecutive chunks in one block."""
    return 128 // chunk if 128 % chunk == 0 else 1


def _get_solve(x_ref, i, c, w):
    """Chunk ``c``'s solve, (w, w), from head ``i``'s resident row."""
    pack = x_ref.shape[3] // w
    x = x_ref[i, 0, :, 0:w]
    for r in range(1, pack):
        x = jnp.where(c % pack == r, x_ref[i, 0, :, r * w:(r + 1) * w], x)
    return x


def _end_scale(bcol, like, m: _Masks):
    """``exp(b_C)`` as a row of ``like``'s width, by a masked sum down the
    sublanes (Mosaic does not broadcast a (1, 1) value over sublanes and
    lanes at once)."""
    return jnp.exp(jnp.sum(jnp.where(m.last_col, bcol, 0.0)
                           + jnp.zeros((1, like.shape[1]), _F32),
                           axis=0, keepdims=True))


def _solve_kernel(k_ref, beta_ref, b_ref, x_ref):
    """The solves of one row of the saved ``X`` for a head block: the row's
    ``pack`` consecutive chunks side by side in its lanes.  No state is
    carried, so every grid step stands alone.  The heads go by in a loop:
    unrolled, their solves overlap by some 5%, but every equation of a
    kernel's body is lowered again for each of its calls in a program, at
    set-up, which the unrolled body lengthened by more (PERF.md, section
    6)."""
    w = beta_ref.shape[2]
    pack = x_ref.shape[3] // w
    first = pl.program_id(1) * pack
    m = _Masks(w)

    def head(i, carry):
        for p in range(pack):
            c = first + p
            pre = _prelude(_chunk_row(b_ref[i], c), m)
            x_ref[i, 0, :, p * w:(p + 1) * w] = _solve(
                k_ref[i, p * w:(p + 1) * w], _chunk_row(beta_ref[i], c),
                pre, m)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], head, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, beta_ref, b_ref, x_ref, o_ref, ck_ref,
                s_scr, *, hb: int):
    """The recurrence: one chunk (the grid walks them first to last) of one
    head block, from the chunk's solve read from ``x_ref``; ``s_scr``
    carries each head's state from chunk to chunk."""
    c = pl.program_id(1)
    m = _Masks(q_ref.shape[1])

    @pl.when(c == 0)
    def _init():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    for i in range(hb):
        q, k, v = q_ref[i], k_ref[i], v_ref[i]
        cdt = q.dtype
        s = s_scr[i]                                             # (dv, dk)
        ck_ref[i, 0] = s
        f = _forward_parts(q, k, v, _get_solve(x_ref, i, c, m.c),
                           _chunk_row(beta_ref[i], c), s,
                           _prelude(_chunk_row(b_ref[i], c), m), m)
        qg = (q.astype(_F32) * f["eb"]).astype(cdt)
        o_ref[i] = (_dot(qg, s.astype(cdt), _NT)
                    + _dot(f["p"].astype(cdt), f["vn"].astype(cdt))
                    ).astype(o_ref.dtype)
        s_scr[i] = _end_scale(f["bcol"], s, m) * s \
            + _dot(f["vn"].astype(cdt), f["kt"].astype(cdt), _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, beta_ref, b_ref, ck_ref, x_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dbeta_ref, db_ref, ds_scr, *, hb: int,
                nc: int):
    """One chunk (the grid walks them last to first) of one head block.
    ``ds_scr`` holds each head's cotangent of the state the chunk ends
    with; the rows of dbeta and db are written into resident blocks.  The
    chunk's solve ``X`` is the forward's, read from ``x_ref``."""
    c = pl.program_id(1)
    at = nc - 1 - c
    m = _Masks(q_ref.shape[1])
    rsum = lambda t: jnp.sum(t, axis=1, keepdims=True)
    csum = lambda t: jnp.sum(t, axis=0, keepdims=True)

    @pl.when(c == 0)
    def _init():
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)

    for i in range(hb):
        q, k, v = q_ref[i], k_ref[i], v_ref[i]
        cdt = q.dtype
        s0 = ck_ref[i, 0]
        s0c = s0.astype(cdt)
        ds1 = ds_scr[i]
        ds1c = ds1.astype(cdt)
        doc = do_ref[i].astype(cdt)
        brow = _chunk_row(b_ref[i], at)
        betarow = _chunk_row(beta_ref[i], at)
        x = _get_solve(x_ref, i, at, m.c)
        f = _forward_parts(q, k, v, x, betarow, s0, _prelude(brow, m), m)
        eb, gam, t = f["eb"], f["gam"], f["t"]
        kk, betacol = _dot(k, k, _NT), _col(betarow, m)
        vnc = f["vn"].astype(cdt)
        tc = t.astype(cdt)
        qg = q.astype(_F32) * eb
        ebend = _end_scale(f["bcol"], s0, m)
        # S' = exp(b_C) S + V'^T Kt
        ds0 = ebend * ds1
        d_end = jnp.sum(rsum(ds1 * s0 * ebend), axis=0, keepdims=True)
        dvn = _dot(f["kt"].astype(cdt), ds1c, _NT)
        dkt = _dot(vnc, ds1c)
        r = rsum(dkt * f["kt"])                          # of b_C - b_i
        d_end = d_end + jnp.sum(r, axis=0, keepdims=True)
        dk = dkt * jnp.exp(f["bend"] - f["bcol"])
        db_col = -r
        # O = Qg S^T + P V'
        dqg = _dot(doc, s0c)
        ds0 = ds0 + _dot(doc, qg.astype(cdt), _TN)
        dp = _dot(doc, vnc, _NT)
        dvn = dvn + _dot(f["p"].astype(cdt), doc, _TN)
        dqk = (dp * gam).astype(cdt)
        dq = _dot(dqk, k) + dqg * eb
        dk = dk + _dot(dqk, q, _TN)
        dgam = dp * f["qk"]
        db_col = db_col + rsum(dqg * qg)
        # V' = U - W S^T,  W = T Kg,  U = T V
        dvnc = dvn.astype(cdt)
        dw = -_dot(dvnc, s0c)
        ds0 = ds0 - _dot(dvnc, f["w"].astype(cdt), _TN)
        dwc = dw.astype(cdt)
        dt = _dot(dwc, f["kg"].astype(cdt), _NT) + _dot(dvnc, v, _NT)
        dkg = _dot(tc, dwc, _TN)
        dv = _dot(tc, dvnc, _TN)
        dk = dk + dkg * eb
        db_col = db_col + rsum(dkg * f["kg"])
        # T = X diag(beta),  X = (I + A)^-1
        dbeta_row = csum(dt * x)
        da = -_dot32(_dot32(x, dt * betarow, _TN), x, _NT)
        da = jnp.where(m.strict, da, 0.0)
        # A = diag(beta) (K K^T * G), strictly lower
        dbeta_col = rsum(da * kk * gam)
        dkk = (betacol * da * gam).astype(cdt)
        dgam = dgam + betacol * da * kk
        dk = dk + _dot(dkk, k) + _dot(dkk, k, _TN)
        # G = exp(b_i - b_j)
        mm = dgam * gam
        db_col = db_col + rsum(mm) + jnp.where(m.last_col, d_end, 0.0)
        db_row = _row(db_col, m) - csum(mm)
        dq_ref[i] = dq.astype(dq_ref.dtype)
        dk_ref[i] = dk.astype(dk_ref.dtype)
        dv_ref[i] = dv.astype(dv_ref.dtype)
        rows = jax.lax.broadcasted_iota(jnp.int32, dbeta_ref.shape[1:], 0)
        dbeta_ref[i] = jnp.where(rows == at, dbeta_row + _row(dbeta_col, m),
                                 dbeta_ref[i])
        db_ref[i] = jnp.where(rows == at, db_row, db_ref[i])
        ds_scr[i] = ds0


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _vmem_bytes(c: int, hb: int, nc: int, dk: int, dv: int,
                itemsize: int) -> int:
    """What the backward (the largest of the three kernels) holds in VMEM
    a grid step, from its specs: the q, k, v, dq, dk, dv blocks and dO
    (float32), the checkpoint block, the solve's block, the resident rows
    of beta, b, dbeta and db, every block in two buffers; the states'
    cotangents (scratch); and a head's (C, C) and (C, width) float32
    temporaries, some thirty of each."""
    kw, vw = _lanes(dk), _lanes(dv)
    blocks = hb * c * (4 * kw * itemsize + 2 * vw * itemsize + vw * 4)
    blocks += hb * dv * kw * 4 + hb * c * _lanes(c) * 4
    blocks += 4 * hb * nc * _lanes(c) * 4
    temps = 30 * (c * _lanes(c) + c * vw) * 4
    return 2 * blocks + hb * dv * kw * 4 + temps


def gated_delta_plan(L: int, H: int, dk: int, dv: int,
                     chunk: int | None = None, itemsize: int = 2) -> dict:
    """What a call on these shapes is built with: the chunk and the padded
    length, the heads a grid step, the VMEM limit the kernels name, and
    the bytes the forward saves for the backward: the states each chunk
    starts from, and each chunk's solve."""
    chunk = int(chunk or _CHUNK)
    if chunk % 8:
        raise ValueError(f"chunk {chunk} is not a multiple of 8")
    hb = max(d for d in range(1, min(_HEAD_BLOCK, H) + 1) if H % d == 0)
    chunks, pack = -(-L // chunk), _pack(chunk)
    need = _vmem_bytes(chunk, hb, chunks, dk, dv, itemsize)
    return dict(chunk=chunk, chunks=chunks, padded=chunks * chunk,
                head_block=hb, vmem_bytes=-(-need * 5 // 4 // 2**20) * 2**20,
                checkpoint_bytes=chunks * H * dv * dk * 4,
                solve_bytes=H * -(-chunks // pack) * chunk * pack * chunk * 4)


@functools.lru_cache(maxsize=32)
def _build(Lp: int, H: int, dk: int, dv: int, chunk: int, cdt: str,
           interpret: bool):
    """(solve call, recurrence call, backward call) on padded operands: q,
    k (H, Lp, dk) and v (H, Lp, dv) in ``cdt``; beta and the running sums
    (H, chunks, chunk) float32.  The solve and the recurrence are both
    named ``gdn_fwd``: together they are the forward."""
    plan = gated_delta_plan(Lp, H, dk, dv, chunk, jnp.dtype(cdt).itemsize)
    hb, nc, pack = plan["head_block"], Lp // chunk, _pack(chunk)
    for what in ("chunk", "chunks", "head_block", "vmem_bytes",
                 "checkpoint_bytes", "solve_bytes"):
        _tm.set_gauge("pallas.gated_delta.plan", plan[what], L=Lp, H=H,
                      dk=dk, dv=dv, what=what)
    params = pltpu.CompilerParams(vmem_limit_bytes=plan["vmem_bytes"])

    def specs(cmap):
        keys = pl.BlockSpec((hb, chunk, dk), lambda j, c: (j, cmap(c), 0))
        vals = pl.BlockSpec((hb, chunk, dv), lambda j, c: (j, cmap(c), 0))
        rows = pl.BlockSpec((hb, nc, chunk), lambda j, c: (j, 0, 0))
        ck = pl.BlockSpec((hb, 1, dv, dk), lambda j, c: (j, cmap(c), 0, 0))
        xs = pl.BlockSpec((hb, 1, chunk, pack * chunk),
                          lambda j, c: (j, cmap(c) // pack, 0, 0))
        return keys, vals, rows, ck, xs

    keys, vals, rows, ck, xs = specs(lambda c: c)
    nr = -(-nc // pack)
    solve = pl.pallas_call(
        _solve_kernel,
        grid=(H // hb, nr),
        in_specs=[pl.BlockSpec((hb, pack * chunk, dk),
                               lambda j, r: (j, r, 0)), rows, rows],
        out_specs=pl.BlockSpec((hb, 1, chunk, pack * chunk),
                               lambda j, r: (j, r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, nr, chunk, pack * chunk), _F32),
        compiler_params=params,
        name="gdn_fwd",
        interpret=interpret,
    )
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb),
        grid=(H // hb, nc),
        in_specs=[keys, keys, vals, rows, rows, xs],
        out_specs=(vals, ck),
        out_shape=(jax.ShapeDtypeStruct((H, Lp, dv), _F32),
                   jax.ShapeDtypeStruct((H, nc, dv, dk), _F32)),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=params,
        name="gdn_fwd",
        interpret=interpret,
    )
    keys, vals, rows, ck, xs = specs(lambda c: nc - 1 - c)
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, nc=nc),
        grid=(H // hb, nc),
        in_specs=[keys, keys, vals, rows, rows, ck, xs, vals],
        out_specs=(keys, keys, vals, rows, rows),
        out_shape=(jax.ShapeDtypeStruct((H, Lp, dk), jnp.dtype(cdt)),
                   jax.ShapeDtypeStruct((H, Lp, dk), jnp.dtype(cdt)),
                   jax.ShapeDtypeStruct((H, Lp, dv), jnp.dtype(cdt)),
                   jax.ShapeDtypeStruct((H, nc, chunk), _F32),
                   jax.ShapeDtypeStruct((H, nc, chunk), _F32)),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=params,
        name="gdn_bwd",
        interpret=interpret,
    )
    return solve, fwd, bwd


def _calls(q, v, b, interpret):
    H, Lp, dk = q.shape
    return _build(Lp, H, dk, v.shape[2], b.shape[2], str(q.dtype), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdn_core(q, k, v, beta, b, interpret):
    return _gdn_fwd(q, k, v, beta, b, interpret)[0]


def _gdn_fwd(q, k, v, beta, b, interpret):
    solve, fwd, _ = _calls(q, v, b, interpret)
    # named, so that a caller's recomputation policy may keep the solves
    # (``models.olmo_hybrid._KEEP``): a recomputed forward then runs the
    # recurrence alone
    x = checkpoint_name(solve(k, beta, b), "gdn_solve")
    o, ck = fwd(q, k, v, beta, b, x)
    return o, (q, k, v, beta, b, ck, x)


def _gdn_bwd(interpret, res, do):
    q, k, v, beta, b, ck, x = res
    bwd = _calls(q, v, b, interpret)[2]
    return bwd(q, k, v, beta, b, ck, x, do)


_gdn_core.defvjp(_gdn_fwd, _gdn_bwd)


@_tm.traced(name="pallas.gated_delta")
def gated_delta(q, k, v, beta, g, chunk: int | None = None,
                interpret: bool | None = None):
    """``o`` (L, H, dv) float32 of the gated delta rule above for one
    sequence (or a batch folded into the heads).

    ``q, k``: (L, H, dk), ``q`` already scaled as the layer wants it;
    ``v``: (L, H, dv); ``beta``: (L, H), in [0, 2]; ``g``: (L, H), the
    log of the gate (at most 0).  ``q`` keeps its float type, which k and v
    take too and in which the large products run; ``beta``, ``g``, the
    states and the result are float32.  ``L`` is padded to a multiple of
    ``chunk`` (64 by default) with steps of ``beta = 0, g = 0``, which
    leave the state as it is.  Differentiable in all five arguments.
    """
    q = jnp.asarray(q)
    k, v = (jnp.asarray(t).astype(q.dtype) for t in (k, v))
    beta, g = (jnp.asarray(t).astype(_F32) for t in (beta, g))
    L, H, dk = q.shape
    dv = v.shape[2]
    if k.shape != q.shape or v.shape[:2] != (L, H) or beta.shape != (L, H) \
            or g.shape != (L, H):
        raise ValueError(f"gated_delta shapes: q {q.shape}, k {k.shape}, v "
                         f"{v.shape}, beta {beta.shape}, g {g.shape}")
    plan = gated_delta_plan(L, H, dk, dv, chunk, q.dtype.itemsize)
    if interpret is None:
        interpret = not _on_tpu()
    pad = plan["padded"] - L
    if pad:
        q, k, v, beta, g = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                            for t in (q, k, v, beta, g))
    heads = lambda t: jnp.transpose(t, (1, 0, 2))
    rows = lambda t: t.T.reshape(H, plan["chunks"], plan["chunk"])
    o = _gdn_core(heads(q), heads(k), heads(v), rows(beta),
                  jnp.cumsum(rows(g), axis=-1), bool(interpret))
    o = heads(o)
    return o[:L] if pad else o
