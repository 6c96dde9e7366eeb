"""Compiled-on-hardware kernel checks (run with ``DAT_TEST_TPU=1``).

The default suite runs every Pallas kernel in interpreter mode on the
virtual CPU mesh; this file is the hardware leg (VERDICT round-2 item 3):
with ``DAT_TEST_TPU=1`` and a real TPU visible, each kernel compiles
through Mosaic and must match its dense oracle.  Single-chip by design —
it exercises kernel lowering (block shapes, VMEM budgets, SMEM scalars),
not cross-chip collectives (the CPU-mesh suite covers those).

Skipped off-hardware so `pytest tests/` stays green everywhere.  The
decision is made inside a module-scoped fixture, never while the module is
imported: every xdist worker imports every test file, and workers that
collect different tests run none.
"""

import os

import numpy as np
import pytest

import jax
from distributedarrays_tpu.parallel.collectives import shard_map_compat
import jax.numpy as jnp


@pytest.fixture(scope="module")
def tpu():
    if os.environ.get("DAT_TEST_TPU") != "1":
        pytest.skip("hardware leg: set DAT_TEST_TPU=1 on a TPU host")
    from distributedarrays_tpu.ops.pallas_gemm import _on_tpu
    if not _on_tpu():
        pytest.skip("no TPU visible")
    return jax.devices()


pytestmark = pytest.mark.usefixtures("tpu")


def test_flash_attention_compiled_fwd_bwd():
    from distributedarrays_tpu.ops.pallas_attention import flash_attention
    from distributedarrays_tpu.models.ring_attention import (
        reference_attention)
    S, H, D = 1024, 4, 64
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (S, H, D), jnp.float32)
    k = jax.random.normal(kk, (S, H, D), jnp.float32)
    v = jax.random.normal(kv, (S, H, D), jnp.float32)
    for causal in (False, True):
        got = np.asarray(flash_attention(q, k, v, causal=causal))
        want = reference_attention(q, k, v, causal=causal)
        # MXU default precision (bf16 passes) tolerance
        assert np.abs(got - want).max() < 2e-2

    def loss(q):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def dense_loss(q):
        s = jnp.einsum("qhd,khd->hqk", q / jnp.sqrt(D), k)
        qi = jnp.arange(S)[:, None]
        ki = jnp.arange(S)[None, :]
        s = jnp.where((ki <= qi)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("hqk,khd->qhd", p, v) ** 2)

    g = jax.grad(loss)(q)
    gd = jax.grad(dense_loss)(q)
    denom = float(jnp.abs(gd).max())
    assert float(jnp.abs(g - gd).max()) / denom < 5e-2


def test_flash_attention_hop_compiled():
    from distributedarrays_tpu.ops.pallas_attention import (
        flash_attention_hop, flash_carry_init)
    from distributedarrays_tpu.models.ring_attention import (
        reference_attention)
    S, H, D = 512, 4, 64
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (S, H, D), jnp.float32)
    k = jax.random.normal(kk, (S, H, D), jnp.float32)
    v = jax.random.normal(kv, (S, H, D), jnp.float32)
    qh, kh, vh = (jnp.transpose(x, (1, 0, 2)) for x in (q, k, v))
    half = S // 2
    # rank-0 q block receives the FUTURE k block first (fully skipped),
    # then its own — the carry must pass through the masked hop unchanged
    m, l, a = flash_carry_init(H, half, D)
    m, l, a = flash_attention_hop(qh[:, :half], kh[:, half:], vh[:, half:],
                                  m, l, a, 0, half, causal=True)
    m, l, a = flash_attention_hop(qh[:, :half], kh[:, :half], vh[:, :half],
                                  m, l, a, 0, 0, causal=True)
    got = np.asarray(jnp.transpose(a / l[:, :, :1], (1, 0, 2)))
    want = reference_attention(q, k, v, causal=True)[:half]
    assert np.abs(got - want).max() < 2e-2


def test_flash_attention_hop_bwd_compiled():
    # the FA2 hop-backward kernels through Mosaic (SMEM offsets, f32
    # contribution outputs): two-hop composition of contributions must
    # match the dense gradient (VERDICT round-3 item 3 hardware leg)
    from distributedarrays_tpu.ops.pallas_attention import (
        _LANE, flash_attention_hop, flash_attention_hop_bwd,
        flash_carry_finalize, flash_carry_init)
    S, H, D = 512, 4, 64
    kq, kk, kv = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(kq, (S, H, D), jnp.float32)
    k = jax.random.normal(kk, (S, H, D), jnp.float32)
    v = jax.random.normal(kv, (S, H, D), jnp.float32)
    qh, kh, vh = (jnp.transpose(x, (1, 0, 2)) for x in (q, k, v))
    half = S // 2
    q0, k0, v0 = qh[:, :half], kh[:, :half], vh[:, :half]
    k1, v1 = kh[:, half:], vh[:, half:]
    sc = float(1.0 / np.sqrt(D))

    # forward over both hops for rank-0's q block, collecting out + lse
    m, l, a = flash_carry_init(H, half, D)
    m, l, a = flash_attention_hop(q0, k0, v0, m, l, a, 0, 0, causal=True)
    m, l, a = flash_attention_hop(q0, k1, v1, m, l, a, 0, half, causal=True)
    oh, lse = flash_carry_finalize(m, l, a, q.dtype)

    g = jnp.ones_like(oh)                                 # dL/dout = 1
    dd = jnp.einsum("hbd,hbd->hb", g.astype(jnp.float32),
                    oh.astype(jnp.float32))
    ddb = jnp.broadcast_to(dd[:, :, None], (H, half, _LANE))
    lseb = jnp.broadcast_to(lse[:, :, None], (H, half, _LANE))
    dq = jnp.zeros((H, half, D), jnp.float32)
    dqc, dk0, dv0 = flash_attention_hop_bwd(q0, k0, v0, g, lseb, ddb,
                                            0, 0, causal=True)
    dq = dq + dqc
    dqc, dk1, dv1 = flash_attention_hop_bwd(q0, k1, v1, g, lseb, ddb,
                                            0, half, causal=True)
    dq = dq + dqc

    def dense_loss(qq, kk_, vv):
        s = jnp.einsum("hqd,hkd->hqk", qq.astype(jnp.float32) * sc,
                       kk_.astype(jnp.float32))
        qi = jnp.arange(half)[:, None]
        ki = jnp.arange(S)[None, :]
        s = jnp.where((ki <= qi)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("hqk,hkd->hqd", p, vv.astype(jnp.float32)))

    gd = jax.grad(dense_loss, (0, 1, 2))(q0, kh, vh)
    denom = max(float(jnp.abs(x).max()) for x in gd)
    assert float(jnp.abs(dq - gd[0]).max()) / denom < 5e-2
    dk = jnp.concatenate([dk0, dk1], axis=1)
    dv = jnp.concatenate([dv0, dv1], axis=1)
    assert float(jnp.abs(dk - gd[1]).max()) / denom < 5e-2
    assert float(jnp.abs(dv - gd[2]).max()) / denom < 5e-2


def test_ring_flash_differentiable_compiled():
    # the full custom_vjp ring path on a 1-rank ring: forward + backward
    # compile through Mosaic and match dense gradients
    from jax.sharding import PartitionSpec as P
    from distributedarrays_tpu import layout as L
    from distributedarrays_tpu.models.ring_attention import (
        ring_flash_attention_kernel)
    from distributedarrays_tpu.ops.pallas_attention import (
        _dense_attention_shd)
    S, H, D = 1024, 4, 64
    q = jax.random.normal(jax.random.key(5), (S, H, D), jnp.float32)
    mesh = L.mesh_for([0], (1, 1, 1))
    ax = mesh.axis_names[0]
    shm = shard_map_compat(
        lambda a, b, c: ring_flash_attention_kernel(a, b, c, ax,
                                                    causal=True),
        mesh=mesh, in_specs=(P(ax),) * 3, out_specs=P(ax), check=False)
    g = jax.jit(jax.grad(lambda x: jnp.sum(shm(x, x, x) ** 2)))(q)
    sc = float(1.0 / np.sqrt(D))
    gd = jax.grad(lambda x: jnp.sum(
        _dense_attention_shd(x, x, x, True, sc) ** 2))(q)
    denom = float(jnp.abs(gd).max())
    assert float(jnp.abs(g - gd).max()) / denom < 5e-2


def test_pallas_matmul_compiled():
    from distributedarrays_tpu.ops.pallas_gemm import pallas_matmul
    for dt, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
        a = jax.random.normal(jax.random.key(2), (2048, 2048), dt)
        b = jax.random.normal(jax.random.key(3), (2048, 2048), dt)
        got = np.asarray(pallas_matmul(a, b)).astype(np.float32)
        want = np.asarray(jnp.matmul(a, b)).astype(np.float32)
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < tol, (dt, rel)


def test_pallas_matmul_int8_compiled():
    # int8 x int8 -> int32 through the real MXU (Mosaic int8 tiling): the
    # dequantized result must track the f32 oracle within quantization error
    from distributedarrays_tpu.ops.pallas_gemm import quantized_matmul
    a = jax.random.normal(jax.random.key(8), (2048, 2048), jnp.float32)
    b = jax.random.normal(jax.random.key(9), (2048, 2048), jnp.float32)
    got = np.asarray(quantized_matmul(a, b))
    want = np.asarray(jnp.matmul(a, b))
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 2e-2, rel


def test_pallas_stencil_compiled():
    from distributedarrays_tpu.ops.pallas_stencil import stencil5_block
    rng = np.random.default_rng(0)
    A = rng.standard_normal((2048, 1024)).astype(np.float32)
    lo = rng.standard_normal((1, 1024)).astype(np.float32)
    hi = rng.standard_normal((1, 1024)).astype(np.float32)
    got = np.asarray(stencil5_block(jnp.asarray(A), jnp.asarray(lo),
                                    jnp.asarray(hi)))
    x = np.concatenate([lo, A, hi], axis=0)
    left = np.concatenate([np.zeros((A.shape[0], 1), A.dtype), A[:, :-1]], 1)
    right = np.concatenate([A[:, 1:], np.zeros((A.shape[0], 1), A.dtype)], 1)
    want = x[:-2] + x[2:] + left + right - 4 * A
    assert np.abs(got - want).max() < 1e-4


def test_pallas_stencil_temporal_compiled():
    # temporal-blocked kernel through Mosaic: k steps, Dirichlet edges
    from distributedarrays_tpu.ops.pallas_stencil import stencil5_multistep
    rng = np.random.default_rng(1)
    A = rng.standard_normal((2048, 1024)).astype(np.float32)
    k = 8
    want = A
    for _ in range(k):
        p = np.zeros((1, A.shape[1]), A.dtype)
        x = np.concatenate([p, want, p], axis=0)
        left = np.concatenate([np.zeros((want.shape[0], 1), A.dtype),
                               want[:, :-1]], 1)
        right = np.concatenate([want[:, 1:],
                                np.zeros((want.shape[0], 1), A.dtype)], 1)
        want = x[:-2] + x[2:] + left + right - 4 * want
    z = jnp.zeros((k, A.shape[1]), jnp.float32)
    got = np.asarray(stencil5_multistep(jnp.asarray(A), z, z, k, True, True))
    # k chained f32 Laplacian steps grow the values ~8x a step: relative
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def test_flash_attention_head_fold_compiled():
    # round-4: the batched-dot grid variant must lower through Mosaic and
    # match the per-head layout on real hardware
    from distributedarrays_tpu.ops.pallas_attention import flash_attention
    S, H, D = 1024, 8, 64
    q = jax.random.normal(jax.random.key(21), (S, H, D), jnp.bfloat16)
    base = np.asarray(flash_attention(q, q, q, causal=True, block_q=256,
                                      block_k=256)).astype(np.float32)
    for hf in (2, 4):
        got = np.asarray(flash_attention(q, q, q, causal=True, block_q=256,
                                         block_k=256, head_fold=hf)
                         ).astype(np.float32)
        rel = np.abs(got - base).max() / max(np.abs(base).max(), 1e-6)
        assert rel < 2e-2, (hf, rel)


def test_four_step_fft_program_lowers_single_chip():
    # the dispatcher never picks the four-step program at p=1, so drive
    # _fft1d_shm_jit directly on a 1-device mesh: the ACTUAL program
    # (reshape + cross-rank FFT + twiddle + transpose shuffle, with its
    # degenerate all_to_alls) must lower on hardware and match numpy
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from distributedarrays_tpu.ops.fft import _fft1d_shm_jit
    n = 4096
    mesh = Mesh(np.array(jax.devices()[:1]), ("d0",))
    x = jnp.asarray(np.random.default_rng(5).standard_normal(n)
                    .astype(np.float32))
    x = jax.device_put(x, NamedSharding(mesh, P("d0")))
    got = np.asarray(_fft1d_shm_jit(mesh, P("d0"), "d0", n, 1, False)(x))
    np.testing.assert_allclose(got, np.fft.fft(np.asarray(x))
                               .astype(np.complex64), rtol=2e-3, atol=2e-3)


def test_uneven_scan_program_lowers_single_chip():
    # an uneven DArray needs >= 2 ranks, so drive the padded-scan program
    # directly on a 1-device mesh with a valid extent SHORTER than the
    # block: the dynamic-index total + masked combine must lower on
    # hardware and match numpy on the valid prefix
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from distributedarrays_tpu.ops.mapreduce import _scan_uneven_shm_jit
    mesh = Mesh(np.array(jax.devices()[:1]), ("d0",))
    sh = NamedSharding(mesh, P("d0"))
    x = np.zeros(256, np.float32)
    x[:200] = np.random.default_rng(6).standard_normal(200)
    xd = jax.device_put(jnp.asarray(x), sh)
    got = np.asarray(_scan_uneven_shm_jit(sh, "sum", 0, "d0")(
        xd, jnp.asarray([200], jnp.int32)))
    np.testing.assert_allclose(got[:200], np.cumsum(x[:200]),
                               rtol=1e-3, atol=1e-3)


def test_matmul_dispatch_pallas_promoted_compiled():
    # banked pallas win must route DArray @ DArray through the Pallas
    # kernel ON HARDWARE and match GSPMD numerics
    import distributedarrays_tpu as dat
    from distributedarrays_tpu.ops import linalg as la
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    try:
        A = np.asarray(jax.random.normal(jax.random.key(30), (1024, 1024),
                                         jnp.float32))
        da = dat.distribute(A, procs=[0], dist=(1, 1))
        db = dat.distribute(A, procs=[0], dist=(1, 1))
        autotune.record("matmul_impl",
                        la._impl_key(1024, 1024, 1024, da.dtype, db.dtype),
                        "pallas")
        got = np.asarray(da @ db)
        want = A @ A
        rel = np.abs(got - want).max() / np.abs(want).max()
        # f32 operands take the MXU's default precision in the kernel
        # (bf16 passes), as GSPMD's own default f32 dot does
        assert rel < 1e-2, rel
    finally:
        autotune.clear()
        dat.d_closeall()


def test_dmatmul_int8_compiled():
    # the DArray-level dynamic int8 GEMM (per-shard Pallas under
    # shard_map on a 1-device mesh) must lower on real hardware
    import distributedarrays_tpu as dat
    try:
        A = np.asarray(jax.random.normal(jax.random.key(40), (1024, 512),
                                         jnp.float32))
        B = np.asarray(jax.random.normal(jax.random.key(41), (512, 768),
                                         jnp.float32))
        got = np.asarray(dat.dmatmul_int8(dat.distribute(A, procs=[0],
                                                         dist=(1, 1)), B))
        want = A @ B
        assert np.abs(got - want).max() / np.abs(want).max() < 3e-2
    finally:
        dat.d_closeall()


def test_rdma_ring_collectives_compiled(tpu):
    if len(tpu) < 2:
        pytest.skip("RDMA ring collectives need >= 2 chips")
    # COMPILED-mode oracle for the PR 8 RDMA rings on a real multi-chip
    # slice: the interpret-mode suite proves the schedule, this proves
    # the Mosaic lowering (semaphore allocation, LOGICAL device ids,
    # credit DMAs) on silicon.  Same bit-identity contract.
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from distributedarrays_tpu.ops import pallas_collectives as PC
    from distributedarrays_tpu.ops.collective_matmul import \
        allgather_matmul_rhs
    from distributedarrays_tpu.parallel.collectives import (run_spmd,
                                                            spmd_mesh)
    p = len(jax.devices())
    mesh = spmd_mesh(p)
    rng = np.random.default_rng(0)
    x = rng.integers(-8, 8, (p * 8, p * 128)).astype(np.float32)
    spec = P("p", None)
    y1 = run_spmd(lambda a: PC.ring_all_gather(a, "p", interpret=False),
                  mesh, (spec,), P(None, None))(x)
    y2 = run_spmd(lambda a: lax.all_gather(a, "p", axis=0, tiled=True),
                  mesh, (spec,), P(None, None))(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    y1 = run_spmd(lambda a: PC.ring_all_to_all(
        a, "p", split_dim=1, concat_dim=0, interpret=False),
        mesh, (spec,), spec)(x)
    y2 = run_spmd(lambda a: lax.all_to_all(
        a, "p", split_axis=1, concat_axis=0, tiled=True),
        mesh, (spec,), spec)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    a = rng.integers(-4, 4, (p * 128, p * 128)).astype(np.float32)
    b = rng.integers(-4, 4, (p * 128, 256)).astype(np.float32)
    y1 = run_spmd(lambda aa, bb: allgather_matmul_rhs(
        aa, bb, "p", rdma=True, interpret=False),
        mesh, (spec, spec), spec)(a, b)
    y2 = run_spmd(lambda aa, bb: allgather_matmul_rhs(aa, bb, "p"),
                  mesh, (spec, spec), spec)(a, b)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
