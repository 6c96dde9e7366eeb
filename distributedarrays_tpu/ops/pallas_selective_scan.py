"""Hand-written Pallas TPU kernels: the selective scan of a Mamba layer,
forward and backward, with the state kept in VMEM.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) (x) B_t        h_0 = 0
    y_t = h_t . C_t

for one sequence: ``x, dt`` (L, E), ``A`` (E, N), ``B, C`` (L, N), ``h`` in
R^{E x N}.  Materialised, the states are L x E x N floats (2.7 GB at
8192 x 5120 x 16), so neither direction ever writes them to HBM: the
forward keeps ``h`` resident and saves one checkpoint of it per chunk of
``chunk`` steps ((L/chunk) x N x E floats); the backward walks the chunks
from the last to the first, recomputes a chunk's states from its checkpoint
into VMEM scratch, and then runs the reverse recurrence over them.

Layout.  The state of a block of ``block_e`` channels is an (N, block_e)
tile: N on the sublanes, channels on the lanes, so every per-step product
is full-width vector work and the contraction with ``C_t`` is a sum down
the sublanes.  ``B_t`` and ``C_t`` are needed as columns; the wrapper hands
them over lane-replicated, (L, N, 128), and a step reads its (N, 128) tile
by a leading index.  The grid is (chunks, channel blocks) with the channel
blocks innermost, so a chunk's B and C tiles are fetched once for all of
them; the states of all channel blocks persist in one scratch between
chunks.  Eight steps are taken per loop iteration: one aligned (8,
block_e) load of ``x`` and ``dt`` and one aligned store of ``y``.

The reductions over channels that dB and dC need are left as per-lane
partial sums (L, N, 128), accumulated over the channel blocks in the
resident output block, and finished by the wrapper.  Everything crosses
the kernel boundary in float32; the skip term ``D * x``, the gate and the
``A = -exp(A_log)`` parametrisation are the caller's (plain XLA, and
differentiated by it).

``custom_vjp``: ``selective_scan`` is differentiable in all five
arguments.  The chosen chunk and what it costs are published as the gauge
``pallas.selective_scan.plan`` when a program is built
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

__all__ = ["selective_scan", "selective_scan_plan"]

_LANE = 128
_ROWS = 8            # steps per loop iteration: one sublane tile of x, dt, y
# Steps per chunk: the backward holds a chunk's states (chunk x N x
# block_e floats) and four (chunk, N, 128) tiles of B, C, dB, dC in VMEM.
_CHUNK = 64
_BLOCK_E = 512


def _tile(x, n: int):
    """A lane-replicated (N, 128) tile at width ``n``: whole registers
    repeated, no broadcast per step."""
    return x if n == _LANE else jnp.tile(x, (1, n // _LANE))


def _fold(x):
    """(N, block_e) -> (N, 128): the lane groups added up (the rest of the
    sum over channels is the wrapper's)."""
    out = x[:, :_LANE]
    for k in range(1, x.shape[1] // _LANE):
        out = out + x[:, k * _LANE:(k + 1) * _LANE]
    return out


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, ck_ref, h_s, *,
                chunk: int, eb: int):
    c, e = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_s[e] = jnp.zeros(h_s.shape[1:], h_s.dtype)

    a_t = a_ref[...]                                        # (N, eb)
    h0 = h_s[e]
    ck_ref[0] = h0                    # the state this chunk starts from

    def body(i, h):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        xs = x_ref[pl.ds(r, _ROWS), :]                      # (8, eb)
        ds = dt_ref[pl.ds(r, _ROWS), :]
        us = xs * ds
        ys = []
        for j in range(_ROWS):
            da = jnp.exp(ds[j:j + 1, :] * a_t)              # (N, eb)
            h = da * h + us[j:j + 1, :] * _tile(b_ref[r + j], eb)
            ys.append(jnp.sum(h * _tile(c_ref[r + j], eb), axis=0,
                              keepdims=True))
        y_ref[pl.ds(r, _ROWS), :] = jnp.concatenate(ys, axis=0)
        return h

    h_s[e] = jax.lax.fori_loop(0, chunk // _ROWS, body, h0)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, ck_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, hs_s, g_s, *,
                chunk: int, eb: int):
    """One chunk (the grid walks them last to first) of one channel block:
    the chunk's states again from its checkpoint, then the reverse
    recurrence.  ``g`` is what the later steps hand back to the state,
    ``a_{t+1} * G_{t+1}``; it and dA persist across the chunks."""
    c, e = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        g_s[e] = jnp.zeros(g_s.shape[1:], g_s.dtype)
        da_ref[e] = jnp.zeros(da_ref.shape[1:], da_ref.dtype)

    @pl.when(e == 0)
    def _init_bc():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    a_t = a_ref[...]                                        # (N, eb)
    hs_s[0] = ck_ref[0]

    def forward(i, h):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        ds = dt_ref[pl.ds(r, _ROWS), :]
        us = x_ref[pl.ds(r, _ROWS), :] * ds
        for j in range(_ROWS):
            da = jnp.exp(ds[j:j + 1, :] * a_t)
            h = da * h + us[j:j + 1, :] * _tile(b_ref[r + j], eb)
            hs_s[r + j + 1] = h
        return h

    jax.lax.fori_loop(0, chunk // _ROWS, forward, hs_s[0])

    def backward(ii, carry):
        g, d_a = carry
        r = pl.multiple_of((chunk // _ROWS - 1 - ii) * _ROWS, _ROWS)
        xs = x_ref[pl.ds(r, _ROWS), :]
        ds = dt_ref[pl.ds(r, _ROWS), :]
        dys = dy_ref[pl.ds(r, _ROWS), :]
        us = xs * ds
        dxs, dds = [None] * _ROWS, [None] * _ROWS
        for j in reversed(range(_ROWS)):
            t = r + j
            d, x, dy = ds[j:j + 1, :], xs[j:j + 1, :], dys[j:j + 1, :]
            da = jnp.exp(d * a_t)
            h_t, h_prev = hs_s[t + 1], hs_s[t]
            big_g = _tile(c_ref[t], eb) * dy + g            # dL/dh_t, whole
            dc_ref[t] += _fold(h_t * dy)
            db_ref[t] += _fold(big_g * us[j:j + 1, :])
            du = jnp.sum(big_g * _tile(b_ref[t], eb), axis=0, keepdims=True)
            t1 = big_g * h_prev * da                        # dL/da_t * a_t
            dds[j] = jnp.sum(t1 * a_t, axis=0, keepdims=True) + du * x
            dxs[j] = du * d
            d_a = d_a + t1 * d
            g = da * big_g
        dx_ref[pl.ds(r, _ROWS), :] = jnp.concatenate(dxs, axis=0)
        ddt_ref[pl.ds(r, _ROWS), :] = jnp.concatenate(dds, axis=0)
        return g, d_a

    g, d_a = jax.lax.fori_loop(0, chunk // _ROWS, backward,
                               (g_s[e], da_ref[e]))
    g_s[e] = g
    da_ref[e] = d_a


def selective_scan_plan(L: int, E: int, N: int, chunk: int | None = None,
                        block_e: int | None = None) -> dict:
    """What a call on these shapes is built with: the chunk length and the
    padded length, the channel block, and the bytes of the checkpoints the
    forward saves for the backward."""
    chunk = int(chunk or _CHUNK)
    if chunk % _ROWS:
        raise ValueError(f"chunk {chunk} is not a multiple of {_ROWS}")
    if E % _LANE or N % 8:
        raise ValueError(f"selective_scan wants E a multiple of {_LANE} and "
                         f"N a multiple of 8, got E={E}, N={N}")
    eb = int(block_e or _BLOCK_E)
    eb = max(_LANE, min(eb, E) // _LANE * _LANE)
    while E % eb:
        eb -= _LANE
    chunks = -(-L // chunk)
    return dict(chunk=chunk, chunks=chunks, padded=chunks * chunk,
                block_e=eb, checkpoint_bytes=chunks * N * E * 4)


@functools.lru_cache(maxsize=32)
def _build(Lp: int, E: int, N: int, chunk: int, eb: int, interpret: bool):
    """(forward call, backward call) on padded float32 operands: x, dt
    (Lp, E); A transposed (N, E); B, C lane-replicated (Lp, N, 128)."""
    nc, ne = Lp // chunk, E // eb
    for what, n in dict(chunk=chunk, chunks=nc, block_e=eb,
                        checkpoint_bytes=nc * N * E * 4).items():
        _tm.set_gauge("pallas.selective_scan.plan", n, L=Lp, E=E, N=N,
                      what=what)
    f32 = jnp.float32

    def specs(cmap):
        rows = pl.BlockSpec((chunk, eb), lambda c, e: (cmap(c), e))
        a = pl.BlockSpec((N, eb), lambda c, e: (0, e))
        cols = pl.BlockSpec((chunk, N, _LANE), lambda c, e: (cmap(c), 0, 0))
        ck = pl.BlockSpec((1, N, eb), lambda c, e: (cmap(c), 0, e))
        return rows, a, cols, ck

    rows, a, cols, ck = specs(lambda c: c)
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, eb=eb),
        grid=(nc, ne),
        in_specs=[rows, rows, a, cols, cols],
        out_specs=(rows, ck),
        out_shape=(jax.ShapeDtypeStruct((Lp, E), f32),
                   jax.ShapeDtypeStruct((nc, N, E), f32)),
        scratch_shapes=[pltpu.VMEM((ne, N, eb), f32)],
        name="selective_scan_fwd",
        interpret=interpret,
    )
    rows, a, cols, ck = specs(lambda c: nc - 1 - c)
    whole = pl.BlockSpec((ne, N, eb), lambda c, e: (0, 0, 0))
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, eb=eb),
        grid=(nc, ne),
        in_specs=[rows, rows, a, cols, cols, ck, rows],
        out_specs=(rows, rows, whole, cols, cols),
        out_shape=(jax.ShapeDtypeStruct((Lp, E), f32),
                   jax.ShapeDtypeStruct((Lp, E), f32),
                   jax.ShapeDtypeStruct((ne, N, eb), f32),
                   jax.ShapeDtypeStruct((Lp, N, _LANE), f32),
                   jax.ShapeDtypeStruct((Lp, N, _LANE), f32)),
        scratch_shapes=[pltpu.VMEM((chunk + 1, N, eb), f32),
                        pltpu.VMEM((ne, N, eb), f32)],
        name="selective_scan_bwd",
        interpret=interpret,
    )
    return fwd, bwd


def _cols(v):
    """(L, N) -> (L, N, 128): each value across one lane register."""
    return jnp.broadcast_to(v[:, :, None], (*v.shape, _LANE))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan_core(x, dt, a_t, b, c, chunk, eb, interpret):
    return _scan_fwd(x, dt, a_t, b, c, chunk, eb, interpret)[0]


def _scan_fwd(x, dt, a_t, b, c, chunk, eb, interpret):
    fwd, _ = _build(x.shape[0], x.shape[1], a_t.shape[0], chunk, eb,
                    interpret)
    y, ck = fwd(x, dt, a_t, _cols(b), _cols(c))
    return y, (x, dt, a_t, b, c, ck)


def _scan_bwd(chunk, eb, interpret, res, dy):
    x, dt, a_t, b, c, ck = res
    E, N = x.shape[1], a_t.shape[0]
    _, bwd = _build(x.shape[0], E, N, chunk, eb, interpret)
    dx, ddt, da, db, dc = bwd(x, dt, a_t, _cols(b), _cols(c), ck, dy)
    da_t = jnp.transpose(da, (1, 0, 2)).reshape(N, E)
    return dx, ddt, da_t, jnp.sum(db, axis=-1), jnp.sum(dc, axis=-1)


_scan_core.defvjp(_scan_fwd, _scan_bwd)


@_tm.traced(name="pallas.selective_scan")
def selective_scan(x, dt, A, B, C, chunk: int | None = None,
                   block_e: int | None = None,
                   interpret: bool | None = None):
    """``y`` (L, E) float32 of the recurrence above for one sequence.

    ``x, dt``: (L, E); ``A``: (E, N), negative for a decaying state; ``B,
    C``: (L, N).  Any float type comes in; the arithmetic and the result
    are float32.  ``L`` is padded to a multiple of ``chunk`` with steps of
    ``dt = 0``, which leave the state as it is; ``E`` has to be a multiple
    of 128 and ``N`` of 8.  Differentiable in all five arguments.
    """
    x, dt, A, B, C = (jnp.asarray(t).astype(jnp.float32)
                      for t in (x, dt, A, B, C))
    L, E = x.shape
    N = A.shape[1]
    if dt.shape != (L, E) or A.shape != (E, N) or B.shape != (L, N) \
            or C.shape != (L, N):
        raise ValueError(f"selective_scan shapes: x {x.shape}, dt {dt.shape},"
                         f" A {A.shape}, B {B.shape}, C {C.shape}")
    plan = selective_scan_plan(L, E, N, chunk, block_e)
    if interpret is None:
        interpret = not _on_tpu()
    pad = plan["padded"] - L
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, pad), (0, 0))) for t in (x, dt, B, C))
    y = _scan_core(x, dt, A.T, B, C, plan["chunk"], plan["block_e"],
                   bool(interpret))
    return y[:L] if pad else y
