#!/usr/bin/env python
"""chip_smoke.py — the DArray main path on the TPU, end to end, one process.

The quickest proof that the system still starts on the chip.  Drives the
public entry points (``import distributedarrays_tpu as dat``) at the sizes
``BASELINE.json`` states, compares every result with a plain
reference that shares no code with the path under test (numpy, or float32
``jax.numpy`` written in this file), and fails on anything that hides the
device: a ``RuntimeWarning``, a moved fallback counter, a kernel that was
not compiled through Mosaic.

    python chip_smoke.py              # one chip: arrays, kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: the path across chips only
    python chip_smoke.py --platform cpu --tiny [--chips 4]   # rehearsal

Each phase prints one JSON line (``phase``, ``seconds``,
``compile_seconds``, the checks made and their largest error).  The LAST
line of standard output is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it; any failure prints ``"ok": false`` there
and exits non-zero.  Without ``--platform cpu`` the script fails at once
unless JAX's first device is a TPU.  It starts no child process that
imports JAX (the only child it can cause is the ``g++`` build of
``native/chunkcopy.cpp``).  Seconds printed here are set-up facts, not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

ONE_CHIP_PHASES = ("arrays", "kernels", "train", "serve")
MULTI_PHASES = ("multichip",)
# counters that must not move inside a phase: every one of them records a
# path that quietly gave way to something else
_WATCHED = ("fallback.hits", "reshard.collective_fallbacks")
_DISPATCH = "pallas_collectives.dispatch"


# ---------------------------------------------------------------------------
# sizes: the real ones, and the tiny rehearsal ones (CPU, interpret mode)
# ---------------------------------------------------------------------------

REAL = dict(
    n_gemm=4096, n_chain=8192, n_vec=100_000_000, n_big=16384,
    n_stencil=8192, stencil_iters=3, stencil_k=8, check_rows=256,
    flash_s=8192, flash_bwd_s=2048, flash_heads={64: 8, 128: 4, 256: 2},
    moe=dict(tokens=4096, load=(700, 0, 4096, 300, 511, 512, 513, 60),
             d=2048, f=1536),
    model=dict(vocab=8192, dim=1024, heads=16, layers=8, ffn_mult=4,
               max_seq=2048),
    batch=4, train_steps=5, trainer_steps=3, lr=1.0,
    gen_batch=8, gen_prompt=16, gen_new=32,
    serve_n=1024, serve_requests=6, decode_prompt=96, decode_new=8,
    n_ring=8192, n_reshard=8192, ring_rows=4096,
)
TINY = dict(
    n_gemm=256, n_chain=256, n_vec=1 << 16, n_big=512,
    n_stencil=256, stencil_iters=3, stencil_k=2, check_rows=32,
    flash_s=256, flash_bwd_s=128, flash_heads={64: 2, 128: 1, 256: 1},
    moe=dict(tokens=40, load=(5, 0, 40, 8), d=32, f=24),
    model=dict(vocab=256, dim=128, heads=2, layers=2, ffn_mult=4,
               max_seq=64),
    batch=2, train_steps=5, trainer_steps=3, lr=1.0,
    gen_batch=2, gen_prompt=8, gen_new=8,
    serve_n=128, serve_requests=4, decode_prompt=40, decode_new=4,
    n_ring=256, n_reshard=256, ring_rows=128,
)


def _rdma_dispatches(tm, op):
    """How often ``op`` dispatched on the rdma path, over whatever further
    labels its counter carries (the all-to-all's ``inflight``)."""
    return sum(val for key, val in tm.report()["counters"].items()
               if key.startswith(_DISPATCH + "{") and f"op={op}," in key
               and "path=rdma" in key)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


class Ctx:
    """What every phase needs: sizes, the seed, and the check recorder.
    A check that does not hold is recorded and the phase goes on — one
    chip call should show every fault of a phase, not the first — and the
    phase fails at its end."""

    def __init__(self, args, sizes, device):
        self.args, self.sz, self.device = args, sizes, device
        self.on_tpu = device["platform"] == "tpu"
        self.seed = args.seed
        self.chips = args.chips
        # kernels are asked for COMPILED on the chip; the CPU rehearsal
        # runs them in interpret mode
        self.interpret = not self.on_tpu
        self.checks: list[dict] = []

    def check(self, what, err, tol, **extra):
        err = float(err)
        ok = bool(err <= tol)          # NaN compares false: fails
        self.checks.append({"what": what, "err": err, "tol": tol,
                            "ok": ok, **extra})

    def require(self, what, cond, **extra):
        self.checks.append({"what": what, "ok": bool(cond), **extra})


def _rel_err(got, want):
    """Largest absolute error relative to the reference's largest value."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise SmokeFailure(f"shape {got.shape} != reference {want.shape}")
    if not np.all(np.isfinite(got)):
        return float("nan")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _timed(fn):
    """(result, cold seconds, warm seconds): ``fn`` twice, each call ended
    by ``block_until_ready`` — JAX returns before the device finishes."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, cold, time.perf_counter() - t0


def _assert_compiled(ctx, what, fn, *args):
    """On the chip, the lowered program of ``fn(*args)`` must hold the
    Mosaic custom call — a kernel that silently ran interpreted, or gave
    way to its ``lax`` form, has none."""
    if not ctx.on_tpu:
        return
    import jax
    lower = fn.lower if hasattr(fn, "lower") else jax.jit(fn).lower
    txt = lower(*args).as_text()
    ctx.require(f"{what}: tpu_custom_call in lowered text",
                "tpu_custom_call" in txt)


# ---------------------------------------------------------------------------
# plain references (float32 jax.numpy / numpy; nothing from the package)
# ---------------------------------------------------------------------------


def ref_rmsnorm(x, scale):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    n = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return n * scale.astype(jnp.float32)


def ref_forward(params, tokens, heads):
    """Cache-free float32 forward of the learned-position GELU MHA decoder:
    dense causal softmax attention, HIGHEST-precision matmuls."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    f32 = lambda t: t.astype(jnp.float32)
    B, S = tokens.shape
    x = f32(params["embed"])[tokens] + f32(params["pos"])[:S][None]
    E = x.shape[-1]
    D = E // heads
    mask = jnp.tril(jnp.ones((S, S), bool))
    for blk in params["blocks"]:
        h = ref_rmsnorm(x, blk["ln1"])
        qkv = jnp.einsum("bse,ef->bsf", h, f32(blk["qkv"]), precision=hp)
        q, k, v = (t.reshape(B, S, heads, D)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hp) / (D ** 0.5)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hp)
        x = x + jnp.einsum("bse,ef->bsf", o.reshape(B, S, E),
                           f32(blk["proj"]), precision=hp)
        h = ref_rmsnorm(x, blk["ln2"])
        u = jax.nn.gelu(jnp.einsum("bse,ef->bsf", h, f32(blk["w1"]),
                                   precision=hp))
        x = x + jnp.einsum("bsf,fe->bse", u, f32(blk["w2"]), precision=hp)
    return jnp.einsum("bse,ev->bsv", ref_rmsnorm(x, params["ln_f"]),
                      f32(params["head"]), precision=hp)


def ref_loss(params, tokens, heads):
    """Mean next-token cross-entropy, one batch row at a time (the dense
    score matrix of a whole batch would crowd the device)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def row_nll(params, row):
        logits = ref_forward(params, row[None, :-1], heads)[0]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1)[:, 0]

    return float(jnp.mean(jnp.stack([row_nll(params, r) for r in tokens])))


def ref_attention(q, k, v, causal):
    """Dense float32 attention over (S, H, D), one head at a time."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    S, H, D = q.shape

    def head(qkv):
        qh, kh, vh = (t.astype(jnp.float32) for t in qkv)
        s = jnp.dot(qh, kh.T, precision=hp) / (D ** 0.5)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        return jnp.dot(jax.nn.softmax(s, axis=-1), vh, precision=hp)

    hm = lambda t: jnp.swapaxes(t, 0, 1)
    return hm(jax.lax.map(head, (hm(q), hm(k), hm(v))))


def ref_stencil5(x, iters):
    """``iters`` 5-point Laplacian steps, zero boundary, float64 numpy."""
    import numpy as np
    x = np.asarray(x, np.float64)
    for _ in range(iters):
        p = np.pad(x, 1)
        x = (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
             - 4.0 * x)
    return x


# ---------------------------------------------------------------------------
# phase: arrays
# ---------------------------------------------------------------------------


def phase_arrays(ctx):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import distributedarrays_tpu as dat
    from distributedarrays_tpu.models import stencil
    from distributedarrays_tpu.telemetry import memory as tmem

    sz = ctx.sz
    rng = np.random.default_rng(ctx.seed)
    dat.seed(ctx.seed)
    garr = lambda d: d.garray

    # -- the README's opening four lines ----------------------------------
    n = sz["n_chain"]
    d = dat.drand((n, n))
    r = dat.dmap(jnp.sin, d) + d * 2.0
    s = float(dat.dsum(r))
    C = d @ r.T
    jax.block_until_ready(garr(C))
    dh = np.asarray(d).astype(np.float64)
    rh = np.sin(dh) + dh * 2.0
    ctx.check("readme: dmap(sin, d) + d*2", _rel_err(np.asarray(r), rh), 1e-5)
    ctx.check("readme: dsum(r)", abs(s - rh.sum()) / rh.sum(), 1e-4)
    rows = np.sort(rng.choice(n, sz["check_rows"], replace=False))
    ctx.check("readme: d @ r.T (seeded rows)",
              _rel_err(np.asarray(garr(C)[rows]), dh[rows] @ rh.T), 2e-3,
              rows=len(rows))
    del dh, rh
    dat.d_closeall()

    # -- BASELINE config 0: 4096^2 f32 C = A @ B and sum(A.^2) ------------
    n = sz["n_gemm"]
    A = dat.drand((n, n), dtype=jnp.float32)
    B = dat.drand((n, n), dtype=jnp.float32)
    C, cold, warm = _timed(lambda: garr(A @ B))
    ah, bh = np.asarray(A), np.asarray(B)
    ctx.check("config0: C = A @ B", _rel_err(np.asarray(C), ah @ bh), 2e-3,
              n=n, cold_s=cold, seconds=warm)
    ss, cold, warm = _timed(lambda: dat.dmapreduce(jnp.square, "sum", A))
    want = float(np.sum(np.square(ah.astype(np.float64))))
    ctx.check("config0: sum(A.^2)", abs(float(ss) - want) / want, 1e-4,
              cold_s=cold, seconds=warm)
    # lifecycle: the scalar-index guard
    try:
        A[3, 4]
        guarded = False
    except RuntimeError:
        guarded = True
    ctx.require("lifecycle: scalar indexing raises outside allowscalar",
                guarded)
    with dat.allowscalar(True):
        ctx.check("lifecycle: A[3, 4] under allowscalar",
                  abs(float(A[3, 4]) - float(ah[3, 4])), 0.0)
    del ah, bh
    dat.d_closeall()

    # -- BASELINE config 1: sin(A) + B * C on 8192^2 through djit ---------
    n = sz["n_chain"]
    A, B, C = (dat.drand((n, n), dtype=jnp.float32) for _ in range(3))
    chain = dat.djit(lambda a, b, c: jnp.sin(a) + b * c)
    R, cold, warm = _timed(lambda: garr(chain(A, B, C)))
    want = (np.sin(np.asarray(A).astype(np.float64))
            + np.asarray(B).astype(np.float64) * np.asarray(C))
    ctx.check("config1: djit(sin(A) + B*C)", _rel_err(np.asarray(R), want),
              1e-5, n=n, cold_s=cold, seconds=warm)
    del want, R
    dat.d_closeall()

    # -- BASELINE config 2: mapreduce / mean / std over a 1e8 vector ------
    n = sz["n_vec"]
    V = dat.drand((n,), dtype=jnp.float32)
    vh = np.asarray(V).astype(np.float64)
    ss, cold, warm = _timed(lambda: dat.dmapreduce(jnp.square, "sum", V))
    want = float(np.sum(vh * vh))
    ctx.check("config2: dmapreduce(square, +)",
              abs(float(ss) - want) / want, 1e-4, n=n, cold_s=cold,
              seconds=warm)
    mean, cold, warm = _timed(lambda: dat.dmean(V))
    ctx.check("config2: dmean", abs(float(mean) - vh.mean()) / vh.mean(),
              1e-4, cold_s=cold, seconds=warm)
    std, cold, warm = _timed(lambda: dat.dstd(V))
    want = float(vh.std(ddof=1))
    ctx.check("config2: dstd", abs(float(std) - want) / want, 1e-3,
              cold_s=cold, seconds=warm)
    del vh
    dat.d_closeall()

    # -- BASELINE config 3 on one chip: 16384^2 f32 GEMM, 1x1 layout ------
    n = sz["n_big"]
    A = dat.drand((n, n), dtype=jnp.float32)
    B = dat.drand((n, n), dtype=jnp.float32)
    C, cold, warm = _timed(lambda: garr(dat.matmul(A, B)))
    rows = np.sort(rng.choice(n, sz["check_rows"], replace=False))
    want = (np.asarray(garr(A)[rows]).astype(np.float64)
            @ np.asarray(B).astype(np.float64))
    ctx.check("config3: 16384^2 GEMM (seeded rows)",
              _rel_err(np.asarray(C[rows]), want), 2e-3, n=n,
              rows=len(rows), cold_s=cold, seconds=warm)
    del want, C
    dat.d_closeall()

    # -- BASELINE config 4: 5-point stencil, Pallas and jnp step ----------
    n, iters = sz["n_stencil"], sz["stencil_iters"]
    G = dat.drand((n, n), dtype=jnp.float32,
                  dist=(len(jax.devices()), 1))
    want = ref_stencil5(np.asarray(G), iters)
    for use_pallas, name in ((True, "pallas"), (False, "jnp")):
        out, cold, warm = _timed(lambda: garr(stencil.stencil5(
            G, iters=iters, use_pallas=use_pallas)))
        ctx.check(f"config4: stencil5 {name} step x{iters}",
                  _rel_err(np.asarray(out), want), 1e-5, n=n, cold_s=cold,
                  seconds=warm)
    del want, out
    # lifecycle: everything closes, the HBM ledger drains
    dat.d_closeall()
    ctx.require("lifecycle: live_ids() == [] after d_closeall",
                dat.live_ids() == [])
    ctx.require("lifecycle: HBM ledger at 0", tmem.live_bytes() == 0,
                live_bytes=tmem.live_bytes())


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def phase_kernels(ctx):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu.ops.pallas_attention import flash_attention
    from distributedarrays_tpu.ops.pallas_gemm import (pallas_matmul,
                                                       pallas_matmul_int8)
    from distributedarrays_tpu.ops.pallas_stencil import (stencil5_block,
                                                          stencil5_multistep)

    sz, interp = ctx.sz, ctx.interpret
    hp = jax.lax.Precision.HIGHEST
    keys = iter(jax.random.split(jax.random.key(ctx.seed), 64))

    # -- block GEMM, bf16 and f32 ------------------------------------------
    n = sz["n_gemm"]
    # f32 operands take the MXU's default precision inside the kernel (bf16
    # passes, f32 accumulation), as XLA's own default f32 dot does: both
    # widths are held to the bf16-pass tolerance
    for dt, tol in ((jnp.bfloat16, 1e-2), (jnp.float32, 1e-2)):
        a = jax.random.normal(next(keys), (n, n), dt)
        b = jax.random.normal(next(keys), (n, n), dt)
        fn = lambda a, b: pallas_matmul(a, b, interpret=interp)
        _assert_compiled(ctx, f"pallas_matmul {jnp.dtype(dt).name}", fn,
                         a, b)
        out, cold, warm = _timed(lambda: fn(a, b))
        want = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                       precision=hp)
        ctx.check(f"pallas_matmul {n}^2 {jnp.dtype(dt).name}",
                  _rel_err(out, want), tol, cold_s=cold, seconds=warm)
        del a, b, out, want

    # -- int8 GEMM: exact int32 accumulation, fused dequant ---------------
    qa = jax.random.randint(next(keys), (n, n), -127, 128, jnp.int8)
    qb = jax.random.randint(next(keys), (n, n), -127, 128, jnp.int8)
    sa = jax.random.uniform(next(keys), (n,), jnp.float32, 0.5, 1.5)
    sb = jax.random.uniform(next(keys), (n,), jnp.float32, 0.5, 1.5)
    fn = lambda qa, qb, sa, sb: pallas_matmul_int8(qa, qb, sa, sb,
                                                   interpret=interp)
    _assert_compiled(ctx, "pallas_matmul_int8", fn, qa, qb, sa, sb)
    out, cold, warm = _timed(lambda: fn(qa, qb, sa, sb))
    acc = jax.lax.dot(qa, qb, preferred_element_type=jnp.int32)
    want = (np.asarray(acc).astype(np.float64)
            * np.asarray(sa, np.float64)[:, None]
            * np.asarray(sb, np.float64)[None, :])
    ctx.check(f"pallas_matmul_int8 {n}^2", _rel_err(out, want), 1e-6,
              cold_s=cold, seconds=warm)
    del qa, qb, out, acc, want

    # -- flash attention forward (full and causal) and backward -----------
    S, Sb = sz["flash_s"], sz["flash_bwd_s"]
    for D, H in sz["flash_heads"].items():
        q, k, v = (jax.random.normal(next(keys), (S, H, D), jnp.bfloat16)
                   for _ in range(3))
        for causal in (False, True):
            fn = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                                 interpret=interp)
            _assert_compiled(ctx, f"flash fwd d{D} causal={causal}", fn,
                             q, k, v)
            out, cold, warm = _timed(lambda: fn(q, k, v))
            ctx.check(f"flash fwd S={S} H={H} d{D} causal={causal}",
                      _rel_err(out, ref_attention(q, k, v, causal)), 2e-2,
                      cold_s=cold, seconds=warm)
        q, k, v, w = (jax.random.normal(next(keys), (Sb, 2 * H, D),
                                        jnp.bfloat16) for _ in range(4))
        w32 = w.astype(jnp.float32)
        loss = lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=interp).astype(jnp.float32)
            * w32)
        oracle = lambda q, k, v: jnp.sum(ref_attention(q, k, v, True) * w32)
        grad = jax.grad(loss, argnums=(0, 1, 2))
        _assert_compiled(ctx, f"flash bwd d{D}", grad, q, k, v)
        got, cold, warm = _timed(lambda: grad(q, k, v))
        want = jax.grad(oracle, argnums=(0, 1, 2))(
            *(t.astype(jnp.float32) for t in (q, k, v)))
        for name, g, wnt in zip(("dq", "dk", "dv"), got, want):
            ctx.check(f"flash bwd S={Sb} H={2 * H} d{D} {name}",
                      _rel_err(g, wnt), 3e-2, cold_s=cold, seconds=warm)
        del q, k, v, w, w32, got, want

    # -- an expert layer's grouped products: uneven and empty groups --------
    from distributedarrays_tpu.models.moe import held_experts_apply
    g = sz["moe"]
    T, kk, n_e = g["tokens"], 4, len(g["load"])
    idx = np.full((T, kk), n_e, np.int32) + np.arange(kk, dtype=np.int32)
    for e, load in enumerate(g["load"]):    # the first load[e] tokens choose e
        idx[:load, e % kk] = e
    idx = jnp.asarray(idx)
    u = jax.random.normal(next(keys), (T, g["d"]), jnp.bfloat16)
    wts = jax.random.uniform(next(keys), (T, kk), jnp.float32, 0.1, 1.0)
    w1 = (jax.random.normal(next(keys), (n_e, g["d"], 2 * g["f"]),
                            jnp.float32) / np.sqrt(g["d"])
          ).astype(jnp.bfloat16)
    w2 = (jax.random.normal(next(keys), (n_e, g["f"], g["d"]), jnp.float32)
          / np.sqrt(g["f"])).astype(jnp.bfloat16)
    ct = jax.random.normal(next(keys), (T, g["d"]), jnp.float32)
    loss = lambda u, w1, w2: jnp.sum(held_experts_apply(
        u, idx, wts, w1, w2, held=(0, n_e)).astype(jnp.float32) * ct)

    def oracle(u, w1, w2):       # every expert on every token, under a mask
        y = 0.0
        for e in range(n_e):
            w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=-1)
            gg, v = jnp.split(jnp.dot(u, w1[e], precision=hp), 2, axis=-1)
            y = y + w_e[:, None] * jnp.dot(jax.nn.silu(gg) * v, w2[e],
                                           precision=hp)
        return jnp.sum(y * ct)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    (val, got), cold, warm = _timed(lambda: grad(u, w1, w2))
    want_val, want = jax.value_and_grad(oracle, argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (u, w1, w2)))
    ctx.check(f"held_experts_apply {T} tokens, loads {list(g['load'])}: sum",
              abs(float(val) - float(want_val)) / abs(float(want_val)), 2e-2,
              cold_s=cold, seconds=warm)
    for name, a_, b_ in zip(("du", "dw1", "dw2"), got, want):
        ctx.check(f"held_experts_apply {name}", _rel_err(a_, b_), 3e-2,
                  cold_s=cold, seconds=warm)
    ctx.require("held_experts_apply: an empty group's dW is zeros",
                not bool(jnp.any(got[1][1])) and not bool(jnp.any(got[2][1])))
    del u, w1, w2, ct, got, want

    # -- stencil kernels: streaming single step, temporal k-step ----------
    n, kk = sz["n_stencil"], sz["stencil_k"]
    x = jax.random.normal(next(keys), (n, n), jnp.float32)
    xh = np.asarray(x)
    zeros = lambda r: jnp.zeros((r, n), jnp.float32)
    fn = lambda x: stencil5_block(x, zeros(1), zeros(1), interpret=interp)
    _assert_compiled(ctx, "stencil5_block", fn, x)
    out, cold, warm = _timed(lambda: fn(x))
    ctx.check(f"stencil5_block {n}^2", _rel_err(out, ref_stencil5(xh, 1)),
              1e-5, cold_s=cold, seconds=warm)
    fn = lambda x: stencil5_multistep(x, zeros(kk), zeros(kk), kk, True,
                                      True, interpret=interp)
    _assert_compiled(ctx, "stencil5_multistep", fn, x)
    out, cold, warm = _timed(lambda: fn(x))
    ctx.check(f"stencil5_multistep {n}^2 k={kk}",
              _rel_err(out, ref_stencil5(xh, kk)), 1e-5, cold_s=cold,
              seconds=warm)


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------


def phase_train(ctx):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import distributedarrays_tpu as dat
    from distributedarrays_tpu.models import transformer as T
    from distributedarrays_tpu.telemetry import memory as tmem
    from distributedarrays_tpu.train import Trainer, tasks

    sz = ctx.sz
    m = sz["model"]
    cfg = T.Config(dtype=jnp.bfloat16, **m)
    params = T.init_params(jax.random.key(ctx.seed), cfg)
    tokens = jax.random.randint(jax.random.key(ctx.seed + 1),
                                (sz["batch"], m["max_seq"]), 0, m["vocab"],
                                dtype=jnp.int32)
    _assert_compiled(ctx, "train_step (flash fwd+bwd inside)", T.train_step,
                     params, tokens, jnp.float32(sz["lr"]), cfg)
    want0 = ref_loss(params, tokens, m["heads"])
    losses, secs = [], []
    for _ in range(sz["train_steps"]):
        t0 = time.perf_counter()
        params, loss = T.train_step(params, tokens, jnp.float32(sz["lr"]),
                                    cfg)
        losses.append(float(jax.block_until_ready(loss)))
        secs.append(time.perf_counter() - t0)
    ctx.require("train_step: losses finite",
                bool(np.all(np.isfinite(losses))), losses=losses)
    ctx.check("train_step: first loss vs float32 reference forward",
              abs(losses[0] - want0), 5e-2, loss=losses[0], reference=want0,
              cold_s=secs[0], seconds=min(secs[1:]))
    ctx.require("train_step: loss lower at the end than at the start",
                losses[-1] < losses[0], losses=losses)
    del params

    # -- the elastic Trainer on the same widths (f32 flat vector, Adam) ---
    task = tasks.transformer_task(
        vocab=m["vocab"], dim=m["dim"], heads=m["heads"],
        layers=m["layers"], seq=m["max_seq"], batch_size=sz["batch"],
        seed=ctx.seed)
    want0 = ref_loss(task.init_params(jax.random.PRNGKey(ctx.seed)),
                     jnp.asarray(task.batch(0)[0]), m["heads"])
    with Trainer(task, seed=ctx.seed) as tr:
        losses, secs = [], []
        for _ in range(sz["trainer_steps"]):
            t0 = time.perf_counter()
            losses.append(tr.step_once())
            secs.append(time.perf_counter() - t0)
        ctx.require("Trainer: three steps taken, losses finite",
                    tr.step == sz["trainer_steps"]
                    and bool(np.all(np.isfinite(losses))), losses=losses)
        ctx.check("Trainer: first loss vs float32 reference forward",
                  abs(losses[0] - want0), 5e-2, loss=losses[0],
                  reference=want0, cold_s=secs[0], seconds=min(secs[1:]))
    dat.d_closeall()
    ctx.require("Trainer: closed with the HBM ledger at 0",
                tmem.live_bytes() == 0, live_bytes=tmem.live_bytes())


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def ref_tinylm_decode(model, prompt, n_new):
    """Greedy decode of the engine's toy model from its weight tables,
    recomputed from scratch every token (no cache), float64 numpy."""
    import numpy as np
    toks = list(prompt)
    e = model.heads * model.head_dim
    for _ in range(n_new):
        idx = np.asarray(toks) % model.vocab
        x = (model.emb[idx] + model.pos[:len(toks)]).astype(np.float64)
        q = (x[-1] * model.wq).reshape(model.heads, model.head_dim)
        k = (x * model.wk).reshape(-1, model.heads, model.head_dim)
        v = (x * model.wv).reshape(-1, model.heads, model.head_dim)
        s = np.einsum("hd,khd->hk", q, k) / np.sqrt(model.head_dim)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        out = np.einsum("hk,khd->hd", p, v).reshape(e)
        toks.append(int(np.argmax(model.emb.astype(np.float64) @ out)))
    return toks[len(prompt):]


def phase_serve(ctx):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import distributedarrays_tpu as dat
    from distributedarrays_tpu import serve
    from distributedarrays_tpu.models import transformer as T
    from distributedarrays_tpu.telemetry import memory as tmem

    sz = ctx.sz
    m = sz["model"]
    rng = np.random.default_rng(ctx.seed + 2)

    # -- compiled KV-cache decode against the cache-free forward ----------
    cfg = T.Config(dtype=jnp.bfloat16, **m)
    params = T.init_params(jax.random.key(ctx.seed + 2), cfg)
    B, S0, NEW = sz["gen_batch"], sz["gen_prompt"], sz["gen_new"]
    prompt = jax.random.randint(jax.random.key(ctx.seed + 3), (B, S0), 0,
                                m["vocab"], dtype=jnp.int32)
    out, cold, warm = _timed(lambda: T.generate(params, prompt, NEW, cfg))
    out = np.asarray(out)
    ctx.require("generate: shape and prompt kept",
                out.shape == (B, S0 + NEW)
                and np.array_equal(out[:, :S0], np.asarray(prompt)),
                shape=list(out.shape))
    # teacher-forced reference logits over what was generated: every new
    # token must be the reference argmax, up to the margin bf16 rounding
    # can move between two near-tied logits
    logits = np.asarray(jax.jit(ref_forward, static_argnums=2)(
        params, jnp.asarray(out[:, :-1]), m["heads"]))
    step = logits[:, S0 - 1:]                       # predicts S0 .. end
    new = out[:, S0:]
    gap = step.max(axis=-1) - np.take_along_axis(
        step, new[..., None], axis=-1)[..., 0]
    exact = new == step.argmax(axis=-1)
    ctx.check("generate: first new tokens are the float32 argmax "
              "(logit gap)", gap[:, 0].max(), 0.1,
              exact=f"{int(exact[:, 0].sum())}/{B}", cold_s=cold,
              seconds=warm)
    ctx.require("generate: most first tokens equal the argmax exactly",
                exact[:, 0].sum() * 2 > B)
    ctx.check("generate: all new tokens near the float32 argmax "
              "(logit gap)", gap.max(), 0.1,
              exact=f"{int(exact.sum())}/{exact.size}")
    del params, logits

    # -- serve.Server: the batched matmul endpoint ------------------------
    n = sz["serve_n"]
    wh = np.asarray(rng.standard_normal((n, n)), np.float32)
    w = dat.distribute(wh)
    g = w.garray

    def ep(xs):
        y = jnp.matmul(jnp.stack([jnp.asarray(x) for x in xs]), g)
        return list(np.asarray(y[:, 0]))

    srv = serve.Server(serve.ServeConfig(
        max_batch=8, flush_s=0.002, max_queue=32, tenant_rate=1e9,
        tenant_burst=1e9))
    try:
        srv.register("score", ep)
        xs = [np.asarray(rng.standard_normal(n), np.float32)
              for _ in range(sz["serve_requests"])]
        t0 = time.perf_counter()
        futs = [srv.submit("score", x) for x in xs]
        got = np.asarray([f.result(timeout=120) for f in futs], np.float64)
        dt = time.perf_counter() - t0
        want = np.stack(xs).astype(np.float64) @ wh[:, 0].astype(np.float64)
        ctx.check("Server: matmul endpoint answers", _rel_err(got, want),
                  1e-2, requests=len(xs), seconds=dt)

        # -- DecodeEngine behind the same server ---------------------------
        model = serve.TinyLM(seed=ctx.seed)
        eng = serve.DecodeEngine(model, config=serve.DecodeConfig(
            max_new_tokens=sz["decode_new"], poll_s=0.001,
            default_deadline_s=300.0))
        try:
            eng.attach(srv, "decode")
            prompts = [rng.integers(0, model.vocab,
                                    size=sz["decode_prompt"]).tolist()
                       for _ in range(3)]
            t0 = time.perf_counter()
            streams = [srv.submit("decode", p).result(timeout=300)
                       for p in prompts]
            peak_live = tmem.live_bytes()
            got = [s.result(timeout=300) for s in streams]
            dt = time.perf_counter() - t0
            want = [ref_tinylm_decode(model, p, sz["decode_new"])
                    for p in prompts]
            ctx.require("DecodeEngine: three requests match the cache-free "
                        "reference", got == want, got=got, want=want,
                        seconds=dt)
            ctx.require("DecodeEngine: KV pages were on the HBM ledger "
                        "while decoding", peak_live > 0,
                        live_bytes=peak_live)
        finally:
            eng.close()
    finally:
        srv.close()
        w.close()
    dat.d_closeall()
    ctx.require("serve: closed with the HBM ledger at 0",
                tmem.live_bytes() == 0, live_bytes=tmem.live_bytes())


# ---------------------------------------------------------------------------
# phase: multichip (--chips 4 only)
# ---------------------------------------------------------------------------


def _shards(x):
    import numpy as np
    return {s.device: np.asarray(s.data) for s in x.addressable_shards}


def _on_distinct_devices(ctx, what, d, n):
    devs = {s.device for s in d.garray.addressable_shards}
    ctx.require(f"{what}: shards on {n} distinct devices", len(devs) == n,
                devices=sorted(str(x) for x in devs))


def phase_multichip(ctx):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import distributedarrays_tpu as dat
    from distributedarrays_tpu import layout as L, parallel
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.models import stencil
    from distributedarrays_tpu.ops import collective_matmul as cm
    from distributedarrays_tpu.ops import pallas_collectives as pc
    from distributedarrays_tpu.parallel import reshard as R
    from distributedarrays_tpu.telemetry import memory as tmem
    from distributedarrays_tpu.utils import autotune

    sz, p = ctx.sz, ctx.chips
    g = int(round(p ** 0.5))
    if g * g != p:
        raise SmokeFailure(f"--chips {p}: the block layouts need a square "
                           f"device count")
    ranks = list(range(p))
    rng = np.random.default_rng(ctx.seed)
    dat.seed(ctx.seed)
    garr = lambda d: d.garray
    hp = lax.Precision.HIGHEST
    spans = lambda name: len(tm.spans(name))

    # -- BASELINE config 3 as written: 16384^2 f32 on a 2x2 block layout --
    n = sz["n_big"]
    A = dat.drand((n, n), dtype=jnp.float32, procs=ranks, dist=(g, g))
    B = dat.drand((n, n), dtype=jnp.float32, procs=ranks, dist=(g, g))
    _on_distinct_devices(ctx, "config3 A", A, p)
    _on_distinct_devices(ctx, "config3 B", B, p)
    C = dat.matmul(A, B)
    _on_distinct_devices(ctx, "config3 C", C, p)
    _, cold, warm = _timed(lambda: garr(dat.matmul(A, B)))
    rows = np.sort(rng.choice(n, sz["check_rows"], replace=False))
    want = (np.asarray(garr(A)[rows]).astype(np.float64)
            @ np.asarray(B).astype(np.float64))
    ctx.check(f"config3: {n}^2 GEMM on {g}x{g} (seeded rows)",
              _rel_err(np.asarray(garr(C)[rows]), want), 2e-3, rows=len(rows),
              cold_s=cold, seconds=warm)
    del want
    dat.d_closeall()

    # -- the owned GEMM schedules at 8192^2 against the single product ----
    n = sz["n_ring"]
    ah = np.asarray(rng.standard_normal((n, n)), np.float32)
    bh = np.asarray(rng.standard_normal((n, n)), np.float32)
    one = jax.jit(lambda a, b: jnp.dot(a, b))(
        jax.device_put(ah, jax.devices()[0]),
        jax.device_put(bh, jax.devices()[0]))
    want = np.asarray(one)
    del one
    # ring all-gather (1-D TP layout) and Cannon (square grid) through
    # dat.matmul: promoted in the in-memory registry, as a measured win
    # would promote them; the span proves which schedule ran
    for impl, dist, tag, span in (
            ("ring_ag", (p, 1), p, "matmul.ring_ag"),
            ("summa", (g, g), f"{g}x{g}", "matmul.summa")):
        A = dat.distribute(ah, procs=ranks, dist=dist)
        B = dat.distribute(bh, procs=ranks, dist=dist)
        _on_distinct_devices(ctx, f"{impl} A", A, p)
        autotune.record("matmul_impl_dist", autotune.device_key_for(
            n, n, n, tag, A.dtype, B.dtype), impl)
        s0 = spans(span)
        C, cold, warm = _timed(lambda: garr(dat.matmul(A, B)))
        ctx.require(f"{impl}: the owned schedule ran ({span} span)",
                    spans(span) > s0)
        ctx.check(f"{impl} GEMM {n}^2 dist={dist} vs single jnp product",
                  _rel_err(np.asarray(C), want), 2e-3, cold_s=cold,
                  seconds=warm)
        dat.d_closeall()
    # SUMMA panels: dat.matmul keeps them for rectangular grids, which four
    # chips cannot form; the schedule itself runs on the 2x2 mesh
    mesh = L.mesh_for(ranks, (g, g))
    axr, axc = mesh.axis_names
    blk = NamedSharding(mesh, P(axr, axc))
    summa = parallel.run_spmd(
        lambda a, b: cm.summa_matmul(a, b, axr, axc), mesh,
        in_specs=(P(axr, axc), P(axr, axc)), out_specs=P(axr, axc))
    a, b = jax.device_put(ah, blk), jax.device_put(bh, blk)
    C, cold, warm = _timed(lambda: summa(a, b))
    ctx.check(f"summa_matmul {n}^2 on {g}x{g} vs single jnp product",
              _rel_err(np.asarray(C), want), 2e-3, cold_s=cold,
              seconds=warm)
    del a, b, C, want, bh

    # -- dmapreduce over 1e8 elements split four ways ---------------------
    n = sz["n_vec"]
    V = dat.drand((n,), dtype=jnp.float32, procs=ranks, dist=(p,))
    _on_distinct_devices(ctx, "1e8 vector", V, p)
    vh = np.asarray(V).astype(np.float64)
    ss, cold, warm = _timed(lambda: dat.dmapreduce(jnp.square, "sum", V))
    want = float(np.sum(vh * vh))
    ctx.check(f"dmapreduce(square, +) over {n} split {p} ways",
              abs(float(ss) - want) / want, 1e-4, cold_s=cold, seconds=warm)
    del vh
    dat.d_closeall()

    # -- halo stencil, dist=(4,1), against the one-chip result ------------
    n, iters = sz["n_stencil"], sz["stencil_iters"]
    gh = np.asarray(rng.standard_normal((n, n)), np.float32)
    G1 = dat.distribute(gh, procs=[0], dist=(1, 1))
    one = np.asarray(stencil.stencil5(G1, iters=iters))
    Gp = dat.distribute(gh, procs=ranks, dist=(p, 1))
    _on_distinct_devices(ctx, "stencil grid", Gp, p)
    out, cold, warm = _timed(
        lambda: garr(stencil.stencil5(Gp, iters=iters)))
    ctx.check(f"stencil5 {n}^2 dist=({p},1) vs one chip",
              _rel_err(np.asarray(out), one), 1e-5, cold_s=cold,
              seconds=warm)
    ctx.check(f"stencil5 {n}^2 one chip vs numpy",
              _rel_err(one, ref_stencil5(gh, iters)), 1e-5)
    del one, out, gh
    dat.d_closeall()

    # -- reshard through the planner, bit-equal to device_put -------------
    n = sz["n_reshard"]
    xh = np.asarray(rng.standard_normal((n, n)), np.float32)
    # one all_to_all, a chain of one a2a, a chain of one block exchange
    for frm, dst in (((p, 1), (1, p)), ((p, 1), (g, g)), ((1, p), (g, g))):
        src = dat.distribute(xh, procs=ranks, dist=frm)
        _on_distinct_devices(ctx, "reshard source", src, p)
        like = dat.dzeros((n, n), procs=ranks, dist=dst)
        plan = R.plan_reshard(garr(src), like.sharding)
        ctx.require(f"reshard {frm}->{dst}: planned as a collective",
                    plan.collective, strategy=plan.strategy)
        relayed0 = tm.counter_value("reshard.exchange_relayed")
        out, cold, warm = _timed(
            lambda: R.reshard(garr(src), like.sharding))
        relayed = tm.counter_value("reshard.exchange_relayed") - relayed0
        if ctx.on_tpu and p == 4:
            # the 2x2's real coords: the exchange relays its two diagonal
            # pieces over the idle links, the ring legs relay nothing
            exchange = [s[0] for s in plan.steps] == ["exchange"]
            ctx.require(f"reshard {frm}->{dst}: pieces relayed by coords",
                        (relayed > 0) == exchange, relayed=relayed,
                        coords=[list(d.coords) for d in jax.devices()[:p]])
        put = jax.device_put(garr(src), like.sharding)
        got_sh, put_sh = _shards(out), _shards(put)
        ctx.require(f"reshard {frm}->{dst}: placed as asked",
                    out.sharding.is_equivalent_to(like.sharding, 2))
        ctx.require(
            f"reshard {frm}->{dst} {n}^2: bit-equal to device_put",
            got_sh.keys() == put_sh.keys()
            and all(np.array_equal(v, put_sh[d]) for d, v in got_sh.items())
            and np.array_equal(np.asarray(out), xh),
            strategy=plan.strategy,
            steps=[s[0] for s in plan.steps],
            dispatch=tm.spans("reshard")[-1]["labels"].get("dispatch"),
            cold_s=cold, seconds=warm)
        del out, put, got_sh, put_sh
        dat.d_closeall()

    # -- each ring kernel against the lax collective it replaces ----------
    mesh = L.mesh_for(ranks, (p,))
    ax = mesh.axis_names[0]
    interp = ctx.interpret
    m = sz["ring_rows"]

    def ring(f, in_specs, out_spec, *xs):
        fn = parallel.run_spmd(f, mesh, in_specs=in_specs,
                               out_specs=out_spec)
        if ctx.on_tpu:
            ctx.require("ring kernel: tpu_custom_call in lowered text",
                        "tpu_custom_call" in fn.lower(*xs).as_text())
        return _timed(lambda: fn(*xs))

    rows = NamedSharding(mesh, P(ax, None))
    # integer-valued f32: the traveling-partial sum is then exact, so the
    # reduce-scatter too is held to bit equality
    xi = jax.device_put(np.asarray(
        rng.integers(-8, 9, size=(p * m, m)), np.float32), rows)
    moves = (
        ("ring_all_gather",
         lambda x: pc.ring_all_gather(x, ax, dim=0, interpret=interp),
         lambda x: lax.all_gather(x, ax, axis=0, tiled=True), P(ax, None)),
        ("ring_all_to_all",
         lambda x: pc.ring_all_to_all(x, ax, split_dim=1, concat_dim=0,
                                      interpret=interp),
         lambda x: lax.all_to_all(x, ax, 1, 0, tiled=True), P(ax, None)),
        ("ring_reduce_scatter",
         lambda x: pc.ring_reduce_scatter(x, ax, dim=0, chunks=16,
                                          interpret=interp),
         lambda x: lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True),
         P(ax, None)),
    )
    for name, kern, ref, ospec in moves:
        r0 = _rdma_dispatches(tm, name)
        got, cold, warm = ring(kern, (P(ax, None),), ospec, xi)
        want = parallel.run_spmd(ref, mesh, in_specs=(P(ax, None),),
                                 out_specs=ospec)(xi)
        ctx.require(f"{name}: took the rdma path",
                    _rdma_dispatches(tm, name) > r0)
        ctx.require(f"{name} ({p * m}x{m} f32): bit-equal to the lax "
                    f"collective",
                    np.array_equal(np.asarray(got), np.asarray(want)),
                    cold_s=cold, seconds=warm)
        del got, want
    # fused ring GEMMs at a shape the scoped-VMEM gate admits
    k = 256 if not ctx.interpret else 64
    mm = 512 if not ctx.interpret else 64
    xa = jax.device_put(np.asarray(
        rng.standard_normal((p * mm, k)), np.float32).astype(jnp.bfloat16),
        rows)
    wk = jax.device_put(np.asarray(
        rng.standard_normal((k, 2 * k)), np.float32).astype(jnp.bfloat16),
        NamedSharding(mesh, P()))
    bk = jax.device_put(np.asarray(
        rng.standard_normal((p * k, 2 * k)), np.float32
    ).astype(jnp.bfloat16), rows)
    ak = jax.device_put(np.asarray(
        rng.standard_normal((p * mm, p * k)), np.float32
    ).astype(jnp.bfloat16), rows)
    f32 = jnp.float32
    fused = (
        ("ring_allgather_matmul",
         lambda x, w: pc.ring_allgather_matmul(x, w, ax, interpret=interp),
         lambda x, w: jnp.dot(lax.all_gather(x, ax, axis=0, tiled=True)
                              .astype(f32), w.astype(f32), precision=hp),
         (P(ax, None), P()), P(ax, None), (xa, wk)),
        ("ring_allgather_matmul_rhs",
         lambda a, b: pc.ring_allgather_matmul_rhs(a, b, ax,
                                                   interpret=interp),
         lambda a, b: jnp.dot(a.astype(f32), lax.all_gather(
             b, ax, axis=0, tiled=True).astype(f32), precision=hp),
         (P(ax, None), P(ax, None)), P(ax, None), (ak, bk)),
        ("ring_matmul_reducescatter",
         lambda x, w: pc.ring_matmul_reducescatter(x, w, ax,
                                                   interpret=interp),
         lambda x, w: lax.psum_scatter(
             jnp.dot(x.astype(f32), w.astype(f32), precision=hp), ax,
             scatter_dimension=0, tiled=True),
         (P(ax, None), P()), P(ax, None), (xa, wk)),
    )
    for name, kern, ref, ispec, ospec, xs in fused:
        r0 = _rdma_dispatches(tm, name)
        got, cold, warm = ring(kern, ispec, ospec, *xs)
        want = parallel.run_spmd(ref, mesh, in_specs=ispec,
                                 out_specs=ospec)(*xs)
        ctx.require(f"{name}: took the rdma path",
                    _rdma_dispatches(tm, name) > r0)
        ctx.check(f"{name} vs the lax collective + f32 dot",
                  _rel_err(np.asarray(got.astype(f32)), np.asarray(want)),
                  2e-2, shapes=[list(x.shape) for x in xs], cold_s=cold,
                  seconds=warm)
    dat.d_closeall()
    ctx.require("multichip: HBM ledger at 0", tmem.live_bytes() == 0,
                live_bytes=tmem.live_bytes())


PHASES = {"arrays": phase_arrays, "kernels": phase_kernels,
          "train": phase_train, "serve": phase_serve,
          "multichip": phase_multichip}


# ---------------------------------------------------------------------------
# the harness around the phases
# ---------------------------------------------------------------------------


class _CompileClock:
    """Seconds JAX spent in the backend compiler (persistent-cache
    retrieval included) and the number of persistent-cache hits, from
    ``jax.monitoring`` — so a second run in the same command shows the
    cache hitting."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.hits = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _watched_counters(on_tpu):
    """The counters no phase may move: every fallback and, on the chip,
    every ring dispatch that did not take the rdma path (on the CPU
    rehearsal the lax collective IS the path)."""
    from distributedarrays_tpu import telemetry as tm
    out = {}
    for key, val in tm.report()["counters"].items():
        name = key.split("{", 1)[0]
        if name in _WATCHED or (on_tpu and name == _DISPATCH
                                and "path=rdma" not in key):
            out[key] = val
    return out


def run_phase(name, ctx, clock, watchdog_s):
    """Run one phase with RuntimeWarning raised as an error, under a
    watchdog (a hung chip must end the process, not the machine's time
    limit); print its JSON line; return whether it held."""
    ctx.checks = []
    before = _watched_counters(ctx.on_tpu)
    c0, h0 = clock.seconds, clock.hits
    err = None
    dog = threading.Timer(watchdog_s, _hung, args=(name, watchdog_s, ctx))
    dog.daemon = True
    dog.start()
    t0 = time.perf_counter()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            PHASES[name](ctx)
        after = _watched_counters(ctx.on_tpu)
        moved = {k: v - before.get(k, 0) for k, v in after.items()
                 if v != before.get(k, 0)}
        if moved:
            raise SmokeFailure(f"fallback counters moved: {moved}")
        failed = [c["what"] for c in ctx.checks if not c["ok"]]
        if failed:
            raise SmokeFailure(f"checks failed: {failed}")
    except Exception as e:  # noqa: BLE001 — reported, then fails the run
        traceback.print_exc(file=sys.stderr)
        err = f"{type(e).__name__}: {e}"[:2000]
    finally:
        dog.cancel()
    line = {"phase": name, "ok": err is None,
            "seconds": round(time.perf_counter() - t0, 3),
            "compile_seconds": round(clock.seconds - c0, 3),
            "cache_hits": clock.hits - h0,
            "max_err": max((c["err"] for c in ctx.checks if "err" in c),
                           default=0.0),
            "checks": ctx.checks}
    if err:
        line["error"] = err
    print(json.dumps(line), flush=True)
    return err is None


def _last_line(ok, device):
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)


def _hung(name, secs, ctx):
    """Watchdog: a phase that does not end (a hung chip) ends the process,
    with the checks made so far and the contract's last line."""
    print(json.dumps({"phase": name, "ok": False, "checks": ctx.checks,
                      "error": f"watchdog: no end after {secs:.0f}s"}),
          flush=True)
    _last_line(False, ctx.device)
    os._exit(3)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for all data and weights (default 0)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: the multi-chip phase "
                         "and nothing else")
    ap.add_argument("--platform", choices=("cpu",), default=None,
                    help="rehearsal only: run on virtual CPU devices "
                         "(the last line then names the CPU)")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes (with --platform cpu)")
    ap.add_argument("--phases", default=None, metavar="A[,B...]",
                    help="run only these phases of the chosen --chips set")
    ap.add_argument("--watchdog", type=float, default=900.0, metavar="S",
                    help="seconds one phase may take before the process "
                         "ends itself (default 900)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    device = {"platform": None, "kind": None, "count": 0}
    try:
        if args.platform == "cpu":
            import _cpu_harness
            _cpu_harness.force_cpu_mesh(max(args.chips, 1))
        import jax
        from distributedarrays_tpu.utils import native
        from distributedarrays_tpu.utils.compile_cache import \
            enable_compile_cache
        cache_dir = enable_compile_cache()
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        on_tpu = devs[0].platform == "tpu"
        if not on_tpu and args.platform != "cpu":
            raise SmokeFailure(
                f"no TPU: jax.devices()[0].platform is "
                f"{devs[0].platform!r} (rehearse with --platform cpu "
                f"--tiny)")
        if len(devs) < args.chips or (on_tpu and len(devs) != args.chips):
            raise SmokeFailure(f"--chips {args.chips} but JAX reports "
                               f"{len(devs)} device(s)")
        # dispatch may use the committed AUTOTUNE_SEED.json and nothing
        # else: a live autotune cache left by an earlier tune would steer
        # it, so the smoke neither reads nor writes one
        from distributedarrays_tpu.utils import autotune
        if os.path.exists(autotune.default_cache_path()):
            raise SmokeFailure(
                f"a live autotune cache at {autotune.default_cache_path()} "
                f"would steer dispatch: remove it (the smoke runs from "
                f"committed files only)")
        sizes = TINY if args.tiny else REAL
        known = MULTI_PHASES if args.chips > 1 else ONE_CHIP_PHASES
        names = list(known)
        if args.phases:
            names = [s.strip() for s in args.phases.split(",") if s.strip()]
            bad = [s for s in names if s not in known]
            if bad:
                raise SmokeFailure(f"unknown phase(s) {bad} for --chips "
                                   f"{args.chips}; known: {list(known)}")
        clock = _CompileClock()
        print(json.dumps({
            "setup": True, "device": device, "seed": args.seed,
            "sizes": "tiny" if args.tiny else "real",
            "jax": jax.__version__, "compile_cache": cache_dir,
            # which copy tier runs: built from native/chunkcopy.cpp in
            # this run, a prebuilt build/ library, or the numpy fallback
            "native_tier": native.tier(), "phases": names}), flush=True)
        ctx = Ctx(args, sizes, device)
        results = [run_phase(nm, ctx, clock, args.watchdog) for nm in names]
        ok = bool(results) and all(results)
    except Exception as e:  # noqa: BLE001 — the last line must print
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"setup": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        ok = False
    _last_line(ok, device)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
