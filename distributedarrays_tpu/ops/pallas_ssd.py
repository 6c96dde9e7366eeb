"""Hand-written Pallas TPU kernels: the state-space step of a Mamba-2 layer
by the chunked state-space-duality algorithm (SSD, arXiv:2405.21060),
forward and backward.

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        h_0 = 0
    y_t = h_t C_t

for one sequence of ``H`` heads: ``x`` (L, H, P), ``dt`` (L, H), ``A`` (H,)
one scalar decay a head, ``B, C`` (L, G, N) shared by the ``H / G`` heads
of a group (head ``h`` reads group ``h // (H / G)``); each head has its own
state in R^{P x N}.  A batch folds into the heads (group ``g`` of row ``b``
is group ``b G + g``).

Within a chunk of ``Q`` positions the recurrence is a masked product.  With
``cum`` the chunk's running sum of ``dt A`` and ``S`` the state the chunk
starts from,

    L[i, j] = exp(cum_i - cum_j)  (i >= j, else 0)
    y       = (C B^T * L) (dt x) + exp(cum) C S^T
    S'      = exp(cum_Q) S + (exp(cum_Q - cum) dt x)^T B

so the work is products of size ``Q`` on the MXU and the only sequential
part is one state a head carried from chunk to chunk.  ``C B^T`` is
computed once a chunk for all heads of a group; the decay mask lives in
VMEM and is never written out.  The forward saves the state each chunk
starts from (chunks x H x P x N floats) and nothing else; the backward
walks the chunks from the last to the first with the state's cotangent
carried in VMEM, and gives dx, ddt, dA, dB and dC.

Layout.  Heads lead: x and y are (H, L, P) and a grid step holds a block
of ``head_block`` heads of one chunk; ``dt`` comes as rows (heads on the
sublanes, positions on the lanes); B and C as (G, L, N).  The grid is
(chunks, head blocks) with the head blocks innermost, so a chunk's B and C
are fetched once and ``C B^T`` is reused by every block of its group; the
states of all heads persist in one scratch between chunks.  The running
sums are products with a 0/1 triangle on the MXU, their operand split into
three bfloat16 parts so that the sum keeps float32's precision.  The large
products take their operands in the activations' type (bf16 in training)
and accumulate in float32; the states, the decays and every sum are
float32.

``custom_vjp``: ``ssd`` is differentiable in all five arguments.  The plan
(chunk, chunks, heads a block, VMEM asked for, checkpoint bytes) is
published as the gauge ``pallas.ssd.plan`` when a program is built
(docs/telemetry.md).  Interpreter mode runs the same kernels off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_gemm import _on_tpu
from .. import telemetry as _tm

__all__ = ["ssd", "ssd_plan"]

_LANE = 128
_CHUNK = 256          # the published mamba_chunk_size
_HEAD_BLOCK = 8       # heads a grid step: one sublane tile of dt's rows
_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))       # a @ b^T
_TN = (((0,), (0,)), ((), ()))       # a^T @ b


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _tri_sum(tri, v):
    """``tri @ v`` for a 0/1 matrix ``tri`` in bfloat16 and a float32 ``v``,
    to float32's precision: ``v`` in three bfloat16 parts, each product
    exact, accumulated in float32."""
    out = None
    for _ in range(3):
        part = v.astype(jnp.bfloat16)
        prod = _dot(tri, part)
        out = prod if out is None else out + prod
        v = v - part.astype(_F32)
    return out


def _iotas(q: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0),
            jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _end_row(v, like):
    """The last entry of a column ``v`` (Q, 1) as a row of ``like``'s
    width, by a masked sum down the sublanes (Mosaic does not broadcast a
    (1, 1) value over sublanes and lanes at once)."""
    at_end = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) == v.shape[0] - 1
    return jnp.sum(jnp.where(at_end, v, 0.0) + jnp.zeros((1, like.shape[1]),
                                                         _F32),
                   axis=0, keepdims=True)


def _running_sums(dt_ref, a_ref):
    """``(dt as columns, cum as columns, cum as rows, i >= j)`` of a head
    block: ``dt_ref`` (1, hb, Q) rows, ``a_ref`` (1, hb, 1)."""
    dt = dt_ref[0]                                          # (hb, Q)
    ii, jj = _iotas(dt.shape[1])
    lower = jj <= ii
    cum = _tri_sum(lower.astype(jnp.bfloat16), (dt * a_ref[0]).T)  # (Q, hb)
    return dt.T, cum, cum.T, lower


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, ck_ref, s_scr,
                cb_scr, *, hb: int, per_group: int):
    c, j = pl.program_id(0), pl.program_id(1)
    q = x_ref.shape[1]
    cdt = x_ref.dtype

    @pl.when(c == 0)
    def _init():
        s_scr[pl.ds(j * hb, hb)] = jnp.zeros((hb, *s_scr.shape[1:]), _F32)

    @pl.when(j % per_group == 0)
    def _scores():
        cb_scr[...] = _dot(c_ref[0], b_ref[0], _NT)

    dt_cols, cum_cols, cum_rows, lower = _running_sums(dt_ref, a_ref)
    cb, cm, bm = cb_scr[...], c_ref[0], b_ref[0]
    for i in range(hb):
        h = j * hb + i
        cc, cr = cum_cols[:, i:i + 1], cum_rows[i:i + 1, :]
        decay = jnp.exp(jnp.where(lower, cc - cr, -jnp.inf))   # (Q, Q)
        u = x_ref[i].astype(_F32) * dt_cols[:, i:i + 1]         # dt x
        s = s_scr[h]                                            # (P, N)
        ck_ref[0, i] = s
        y = _dot((cb * decay).astype(cdt), u.astype(cdt))
        y_ref[i] = y + jnp.exp(cc) * _dot(cm, s.astype(cdt), _NT)
        last = cc[q - 1:q, :]
        w = (u * jnp.exp(last - cc)).astype(cdt)
        s_scr[h] = _end_row(jnp.exp(cc), s) * s + _dot(w, bm, _TN)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, ck_ref, dy_ref, dx_ref,
                ddt_ref, dadt_ref, db_ref, dc_ref, ds_scr, cb_scr, *,
                hb: int, per_group: int):
    """One chunk (the grid walks them last to first) of one head block.
    ``ds_scr`` holds each head's cotangent of the state the chunk ends
    with; dB and dC sum over the head blocks of a group in their resident
    output blocks.  The cotangent of the running sum, ``dcum``, is kept as
    columns and rows and summed backwards (``da_k = sum_{i >= k} dcum_i``)
    by one triangular product for the block."""
    c, j = pl.program_id(0), pl.program_id(1)
    q = x_ref.shape[1]
    cdt = x_ref.dtype

    @pl.when(c == 0)
    def _init():
        ds_scr[pl.ds(j * hb, hb)] = jnp.zeros((hb, *ds_scr.shape[1:]), _F32)

    @pl.when(j % per_group == 0)
    def _scores():
        cb_scr[...] = _dot(c_ref[0], b_ref[0], _NT)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    dt_cols, cum_cols, cum_rows, lower = _running_sums(dt_ref, a_ref)
    cb, cm, bm = cb_scr[...], c_ref[0], b_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, hb), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (hb, q), 0)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    rsum = lambda t: jnp.sum(t, axis=1, keepdims=True)
    dcum_cols = jnp.zeros((q, hb), _F32)
    dcum_rows = jnp.zeros((hb, q), _F32)
    dux_cols = jnp.zeros((q, hb), _F32)
    d_b = jnp.zeros(db_ref.shape[1:], _F32)
    d_c = jnp.zeros(dc_ref.shape[1:], _F32)
    for i in range(hb):
        h = j * hb + i
        cc, cr = cum_cols[:, i:i + 1], cum_rows[i:i + 1, :]
        dtc = dt_cols[:, i:i + 1]
        decay = jnp.exp(jnp.where(lower, cc - cr, -jnp.inf))
        m = cb * decay
        xf = x_ref[i].astype(_F32)
        u = xf * dtc
        ub = u.astype(cdt)
        dy = dy_ref[i]
        dyb = dy.astype(cdt)
        s0 = ck_ref[0, i]
        s0b = s0.astype(cdt)
        ds = ds_scr[h]
        dsb = ds.astype(cdt)
        ecum = jnp.exp(cc)
        last = cc[q - 1:q, :]
        elast = jnp.exp(last)
        tail = jnp.exp(last - cc)                  # decay to the chunk's end
        # within the chunk: y = M u, M = C B^T * decay
        dm = _dot(dyb, ub, _NT)                    # dy_i . u_j
        w = dm * decay                             # cotangent of C B^T
        z = w * cb                                 # ... of cum_i - cum_j
        wb = w.astype(cdt)
        y_in = ecum * _dot(cm, s0b, _NT)           # the carried state's part
        g = tail * _dot(bm, dsb, _NT)              # B_j dS^T: u_j's from S'
        du = _dot(m.astype(cdt), dyb, _TN) + g
        d_c = d_c + _dot(wb, bm) + ecum * _dot(dyb, s0b)
        d_b = d_b + _dot(wb, cm, _TN) + tail * _dot(ub, dsb)
        t = rsum(u * g)                            # ... of -cum_j through S'
        ends = elast * jnp.sum(rsum(ds * s0), axis=0, keepdims=True) \
            + jnp.sum(t, axis=0, keepdims=True)
        col = rsum(z) + rsum(dy * y_in) - t + jnp.where(at_last, ends, 0.0)
        dcum_cols = jnp.where(lane == i, col, dcum_cols)
        dcum_rows = jnp.where(sub == i, -jnp.sum(z, axis=0, keepdims=True),
                              dcum_rows)
        dux_cols = jnp.where(lane == i, rsum(du * xf), dux_cols)
        dx_ref[i] = (du * dtc).astype(dx_ref.dtype)
        ds_scr[h] = _end_row(ecum, ds) * ds \
            + _dot((dy * ecum).astype(cdt), cm, _TN)
    db_ref[0] += d_b
    dc_ref[0] += d_c
    ii, jj = _iotas(q)
    da = _tri_sum((jj >= ii).astype(jnp.bfloat16),
                  dcum_cols + dcum_rows.T).T                 # (hb, Q)
    ddt_ref[0] = da * a_ref[0] + dux_cols.T
    dadt_ref[0] = da * dt_ref[0]


def _whole_lanes(n: int) -> int:
    return -(-n // _LANE) * _LANE


def _vmem_bytes(q: int, hb: int, h: int, p: int, n: int,
                itemsize: int) -> int:
    """What the backward (the larger kernel) holds in VMEM a grid step,
    from its specs: the x, dy, dx blocks (a head's (Q, P) tile takes whole
    lanes), the checkpoint block, B, C, dB, dC, the dt rows, every block in
    two buffers; the states' cotangents of all ``h`` heads and ``C B^T``
    (scratch); and a head's eight (Q, Q) float32 temporaries."""
    pw, nw = _whole_lanes(p), _whole_lanes(n)
    blocks = hb * q * pw * (2 * itemsize + 4) + hb * p * nw * 4
    blocks += 2 * q * n * itemsize + 2 * q * n * 4 + 3 * 8 * q * 4
    return 2 * blocks + h * p * nw * 4 + q * q * 4 + 8 * q * q * 4


def ssd_plan(L: int, H: int, P: int, G: int, N: int,
             chunk: int | None = None, itemsize: int = 2) -> dict:
    """What a call on these shapes is built with: the chunk and the padded
    length, the heads a grid step, the VMEM limit the kernels name, and
    the bytes of the states the forward saves for the backward."""
    chunk = int(chunk or _CHUNK)
    if chunk % 8:
        raise ValueError(f"chunk {chunk} is not a multiple of 8")
    if H % G:
        raise ValueError(f"{H} heads do not divide into {G} groups")
    per = H // G
    hb = max(d for d in range(1, min(_HEAD_BLOCK, per) + 1) if per % d == 0)
    chunks = -(-L // chunk)
    need = _vmem_bytes(chunk, hb, H, P, N, itemsize)
    return dict(chunk=chunk, chunks=chunks, padded=chunks * chunk,
                head_block=hb, vmem_bytes=-(-need * 5 // 4 // 2**20) * 2**20,
                checkpoint_bytes=chunks * H * P * N * 4)


@functools.lru_cache(maxsize=32)
def _build(Lp: int, H: int, P: int, G: int, N: int, chunk: int, cdt: str,
           interpret: bool):
    """(forward call, backward call) on padded operands: x (H, Lp, P) and
    B, C (G, Lp, N) in ``cdt``; dt (H / hb, hb, Lp) and A (H / hb, hb, 1)
    float32."""
    plan = ssd_plan(Lp, H, P, G, N, chunk, jnp.dtype(cdt).itemsize)
    hb, nc = plan["head_block"], Lp // chunk
    nb, per_group = H // hb, H // G // hb
    for what in ("chunk", "chunks", "head_block", "vmem_bytes",
                 "checkpoint_bytes"):
        _tm.set_gauge("pallas.ssd.plan", plan[what], L=Lp, H=H, P=P, G=G,
                      N=N, what=what)
    params = pltpu.CompilerParams(vmem_limit_bytes=plan["vmem_bytes"])
    kw = dict(hb=hb, per_group=per_group)

    def specs(cmap):
        heads = pl.BlockSpec((hb, chunk, P), lambda c, j: (j, cmap(c), 0))
        rows = pl.BlockSpec((1, hb, chunk), lambda c, j: (j, 0, cmap(c)))
        a = pl.BlockSpec((1, hb, 1), lambda c, j: (j, 0, 0))
        grp = pl.BlockSpec((1, chunk, N),
                           lambda c, j: (j // per_group, cmap(c), 0))
        ck = pl.BlockSpec((1, hb, P, N), lambda c, j: (cmap(c), j, 0, 0))
        return heads, rows, a, grp, ck

    heads, rows, a, grp, ck = specs(lambda c: c)
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, **kw),
        grid=(nc, nb),
        in_specs=[heads, rows, a, grp, grp],
        out_specs=(heads, ck),
        out_shape=(jax.ShapeDtypeStruct((H, Lp, P), _F32),
                   jax.ShapeDtypeStruct((nc, H, P, N), _F32)),
        scratch_shapes=[pltpu.VMEM((H, P, N), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=params,
        name="ssd_fwd",
        interpret=interpret,
    )
    heads, rows, a, grp, ck = specs(lambda c: nc - 1 - c)
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, **kw),
        grid=(nc, nb),
        in_specs=[heads, rows, a, grp, grp, ck, heads],
        out_specs=(heads, rows, rows, grp, grp),
        out_shape=(jax.ShapeDtypeStruct((H, Lp, P), jnp.dtype(cdt)),
                   jax.ShapeDtypeStruct((nb, hb, Lp), _F32),
                   jax.ShapeDtypeStruct((nb, hb, Lp), _F32),
                   jax.ShapeDtypeStruct((G, Lp, N), _F32),
                   jax.ShapeDtypeStruct((G, Lp, N), _F32)),
        scratch_shapes=[pltpu.VMEM((H, P, N), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=params,
        name="ssd_bwd",
        interpret=interpret,
    )
    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_core(x, dt, a, b, c, chunk, interpret):
    return _ssd_fwd(x, dt, a, b, c, chunk, interpret)[0]


def _calls(x, b, chunk, interpret):
    H, Lp, P = x.shape
    G, _, N = b.shape
    return _build(Lp, H, P, G, N, chunk, str(x.dtype), interpret)


def _ssd_fwd(x, dt, a, b, c, chunk, interpret):
    fwd, _ = _calls(x, b, chunk, interpret)
    y, ck = fwd(x, dt, a, b, c)
    return y, (x, dt, a, b, c, ck)


def _ssd_bwd(chunk, interpret, res, dy):
    x, dt, a, b, c, ck = res
    _, bwd = _calls(x, b, chunk, interpret)
    dx, ddt, dadt, db, dc = bwd(x, dt, a, b, c, ck, dy)
    return (dx, ddt, jnp.sum(dadt, axis=-1, keepdims=True),
            db.astype(b.dtype), dc.astype(c.dtype))


_ssd_core.defvjp(_ssd_fwd, _ssd_bwd)


@_tm.traced(name="pallas.ssd")
def ssd(x, dt, A, B, C, chunk: int | None = None,
        interpret: bool | None = None):
    """``y`` (L, H, P) float32 of the recurrence above for one sequence (or
    a batch folded into the heads).

    ``x``: (L, H, P); ``dt``: (L, H), already through its softplus; ``A``:
    (H,), negative for a decaying state; ``B, C``: (L, G, N), ``G``
    dividing ``H``.  ``x`` keeps its float type, which B and C take too and
    in which the large products run; ``dt``, ``A``, the states and the
    result are float32.  ``L`` is padded to a multiple of ``chunk`` (the
    published 256 by default) with steps of ``dt = 0``, which leave the
    state as it is.  Differentiable in all five arguments.
    """
    x = jnp.asarray(x)
    dt, A = (jnp.asarray(t).astype(_F32) for t in (dt, A))
    B, C = (jnp.asarray(t).astype(x.dtype) for t in (B, C))
    L, H, P = x.shape
    _, G, N = B.shape
    if dt.shape != (L, H) or A.shape != (H,) or B.shape != (L, G, N) \
            or C.shape != (L, G, N) or H % G:
        raise ValueError(f"ssd shapes: x {x.shape}, dt {dt.shape}, A "
                         f"{A.shape}, B {B.shape}, C {C.shape}")
    plan = ssd_plan(L, H, P, G, N, chunk, x.dtype.itemsize)
    if interpret is None:
        interpret = not _on_tpu()
    pad = plan["padded"] - L
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                       for t in (x, dt, B, C))
    hb, Lp = plan["head_block"], plan["padded"]
    y = _ssd_core(jnp.transpose(x, (1, 0, 2)),
                  dt.T.reshape(H // hb, hb, Lp), A.reshape(H // hb, hb, 1),
                  jnp.transpose(B, (1, 0, 2)), jnp.transpose(C, (1, 0, 2)),
                  plan["chunk"], bool(interpret))
    y = jnp.transpose(y, (1, 0, 2))
    return y[:L] if pad else y
