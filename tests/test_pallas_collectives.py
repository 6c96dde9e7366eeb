"""Interpret-mode oracle suite for the Pallas RDMA ring collectives.

Every RDMA kernel must be bit-identical to its ``lax`` counterpart (the
collectives are pure data movement; the GEMM/reduction kernels are
exercised on integer-valued operands so reassociation cannot round).
Dispatch is exercised through every gate: forced interpret mode, the
``DA_TPU_RDMA=0`` kill switch, missing ``pltpu``, explicit-request
fallback accounting, chunk-depth resolution precedence, and the reshard
planner's RDMA arm (planner ≡ ``device_put`` oracle re-run, staging
bound under a forced tiny chunk target).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import distributedarrays_tpu as dat
from distributedarrays_tpu import layout as L
from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.ops import pallas_collectives as PC
from distributedarrays_tpu.ops.collective_matmul import (
    allgather_matmul, allgather_matmul_rhs, matmul_reducescatter)
from distributedarrays_tpu.parallel import reshard as R
from distributedarrays_tpu.parallel.collectives import run_spmd, spmd_mesh


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def _ints(rng, shape, dtype=np.float32, lo=-8, hi=8):
    return rng.integers(lo, hi, shape).astype(dtype)


# ---------------------------------------------------------------------------
# kernel <-> lax bit-identity oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("dim,dtype", [(0, np.float32), (1, np.float32),
                                       (0, np.int32)])
def test_ring_all_gather_oracle(p, dim, dtype, rng):
    mesh = spmd_mesh(p)
    x = _ints(rng, (p * 4, 2 * 128), dtype)
    spec = P("p", None)
    out = P(None, None)
    y1 = run_spmd(lambda a: PC.ring_all_gather(a, "p", dim=dim,
                                               interpret=True),
                  mesh, (spec,), out)(x)
    y2 = run_spmd(lambda a: lax.all_gather(a, "p", axis=dim, tiled=True),
                  mesh, (spec,), out)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_ring_all_gather_bf16_3d(rng):
    p = 8
    mesh = spmd_mesh(p)
    x = jnp.asarray(_ints(rng, (p * 2, 4, 128)), jnp.bfloat16)
    spec = P("p", None, None)
    out = P(None, None, None)
    y1 = run_spmd(lambda a: PC.ring_all_gather(a, "p", dim=1,
                                               interpret=True),
                  mesh, (spec,), out)(x)
    y2 = run_spmd(lambda a: lax.all_gather(a, "p", axis=1, tiled=True),
                  mesh, (spec,), out)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


@pytest.mark.parametrize("p", [2, 3, 4, 5, 8])
@pytest.mark.parametrize("chunks", [None, 4])
def test_ring_all_to_all_oracle(p, chunks, rng):
    # powers of two pair the ranks up (me ^ off), 3 and 5 go round the
    # ring (me + off): ring_schedules.a2a_peer
    mesh = spmd_mesh(p)
    x = _ints(rng, (p * 4, p * 12))
    spec = P("p", None)
    y1 = run_spmd(lambda a: PC.ring_all_to_all(
        a, "p", split_dim=1, concat_dim=0, chunks=chunks, interpret=True),
        mesh, (spec,), spec)(x)
    y2 = run_spmd(lambda a: lax.all_to_all(
        a, "p", split_axis=1, concat_axis=0, tiled=True),
        mesh, (spec,), spec)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("chunks", [None, 4])
def test_ring_reduce_scatter_oracle(p, chunks, rng):
    mesh = spmd_mesh(p)
    # integer-valued so the ring's summation order is exact
    x = _ints(rng, (p * p * 4, 64))
    spec = P("p", None)
    y1 = run_spmd(lambda a: PC.ring_reduce_scatter(
        a, "p", dim=0, chunks=chunks, interpret=True),
        mesh, (spec,), spec)(x)
    y2 = run_spmd(lambda a: lax.psum_scatter(
        a, "p", scatter_dimension=0, tiled=True),
        mesh, (spec,), spec)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


@pytest.mark.parametrize("p", [2, 4, 8])
def test_fused_allgather_matmul_oracle(p, rng):
    mesh = spmd_mesh(p)
    m_loc, k, n = 8, 4 * p, 16
    x = _ints(rng, (p * m_loc, k), lo=-4, hi=4)
    w = _ints(rng, (k, n), lo=-4, hi=4)
    specs = (P("p", None), P(None, None))
    out = P(None, None)
    y1 = run_spmd(lambda a, b: allgather_matmul(a, b, "p", rdma=True,
                                                interpret=True),
                  mesh, specs, out)(x, w)
    y2 = run_spmd(lambda a, b: allgather_matmul(a, b, "p"),
                  mesh, specs, out)(x, w)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(y1), x @ w)


@pytest.mark.parametrize("p", [2, 4, 8])
def test_fused_allgather_matmul_rhs_oracle(p, rng):
    mesh = spmd_mesh(p)
    a = _ints(rng, (p * 8, p * 8), lo=-4, hi=4)
    b = _ints(rng, (p * 8, 16), lo=-4, hi=4)
    specs = (P("p", None), P("p", None))
    out = P("p", None)
    y1 = run_spmd(lambda aa, bb: allgather_matmul_rhs(
        aa, bb, "p", rdma=True, interpret=True), mesh, specs, out)(a, b)
    y2 = run_spmd(lambda aa, bb: allgather_matmul_rhs(aa, bb, "p"),
                  mesh, specs, out)(a, b)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(y1), a @ b)


@pytest.mark.parametrize("p", [2, 4, 8])
def test_fused_matmul_reducescatter_oracle(p, rng):
    mesh = spmd_mesh(p)
    x = _ints(rng, (p * 8, 8 * p), lo=-4, hi=4)
    w = _ints(rng, (8 * p, 16), lo=-4, hi=4)
    specs = (P(None, "p"), P("p", None))
    out = P("p", None)
    y1 = run_spmd(lambda a, b: matmul_reducescatter(
        a, b, "p", rdma=True, interpret=True), mesh, specs, out)(x, w)
    y2 = run_spmd(lambda a, b: matmul_reducescatter(a, b, "p"),
                  mesh, specs, out)(x, w)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(y1), x @ w)


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_rdma_ring_attention_oracle(p, causal, rng):
    from distributedarrays_tpu.models.ring_attention import (
        reference_attention, ring_attention_kernel,
        ring_attention_rdma_kernel)
    mesh = spmd_mesh(p)
    b, h, dh = 16, 2, 32
    q, k, v = (rng.standard_normal((p * b, h, dh)).astype(np.float32)
               for _ in range(3))
    spec = P("p", None, None)
    y1 = run_spmd(lambda a, bb, c: ring_attention_rdma_kernel(
        a, bb, c, "p", causal=causal, interpret=True),
        mesh, (spec,) * 3, spec)(q, k, v)
    y2 = run_spmd(lambda a, bb, c: ring_attention_kernel(
        a, bb, c, "p", causal=causal), mesh, (spec,) * 3, spec)(q, k, v)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y1),
                               reference_attention(q, k, v, causal=causal),
                               atol=1e-4)


def test_ring_attention_darray_entry_rdma(monkeypatch, rng):
    # the DArray entry dispatches through rdma_mode(): armed-in-interpret
    # it must produce the same result as the XLA path
    from distributedarrays_tpu.models.ring_attention import ring_attention
    p, b, h, dh = 8, 8, 2, 16
    q, k, v = (rng.standard_normal((p * b, h, dh)).astype(np.float32)
               for _ in range(3))
    ds = dict(procs=list(range(p)), dist=[p, 1, 1])
    dq, dk, dv = (dat.distribute(a, **ds) for a in (q, k, v))
    out_xla = np.asarray(ring_attention(dq, dk, dv, causal=True))
    monkeypatch.setenv("DA_TPU_RDMA", "interpret")
    out_rdma = np.asarray(ring_attention(dq, dk, dv, causal=True))
    np.testing.assert_allclose(out_rdma, out_xla, atol=1e-5)
    dat.d_closeall()


# ---------------------------------------------------------------------------
# dispatch gates
# ---------------------------------------------------------------------------


def test_kill_switch_forces_xla(monkeypatch):
    monkeypatch.setenv("DA_TPU_RDMA", "0")
    assert PC.rdma_mode() is None
    assert PC.rdma_mode(interpret=True) is None   # kill switch dominates
    monkeypatch.setenv("DA_TPU_RDMA", "interpret")
    assert PC.rdma_mode() == "interpret"
    monkeypatch.delenv("DA_TPU_RDMA")
    # auto mode on CPU: quiet fallback
    assert PC.rdma_mode() is None


def test_explicit_request_counts_fallback_hits(monkeypatch, rng):
    from distributedarrays_tpu.utils import debug as dbg
    monkeypatch.setenv("DA_TPU_RDMA", "1")
    key = "pallas_collectives:platform not tpu"
    dbg._warned.discard(key)
    before = tm.counter_value("fallback.hits", key=key)
    with pytest.warns(RuntimeWarning, match="DA_TPU_RDMA requested"):
        assert PC.rdma_mode() is None
    assert tm.counter_value("fallback.hits", key=key) == before + 1
    # warned once, counted every time
    assert PC.rdma_mode() is None
    assert tm.counter_value("fallback.hits", key=key) == before + 2


def test_xla_fallback_is_bit_identical(monkeypatch, rng):
    # with RDMA killed the wrappers ARE the lax collectives
    monkeypatch.setenv("DA_TPU_RDMA", "0")
    p = 4
    mesh = spmd_mesh(p)
    x = _ints(rng, (p * 4, 128))
    spec = P("p", None)
    out = P(None, None)
    before = tm.counter_value("pallas_collectives.dispatch",
                              op="ring_all_gather", path="xla")
    y1 = run_spmd(lambda a: PC.ring_all_gather(a, "p", interpret=True),
                  mesh, (spec,), out)(x)
    y2 = run_spmd(lambda a: lax.all_gather(a, "p", axis=0, tiled=True),
                  mesh, (spec,), out)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert tm.counter_value("pallas_collectives.dispatch",
                            op="ring_all_gather", path="xla") > before


def test_rdma_dispatch_counter_labels(rng):
    p = 4
    mesh = spmd_mesh(p)
    x = _ints(rng, (p * 4, 128))
    before = tm.counter_value("pallas_collectives.dispatch",
                              op="ring_all_gather", path="rdma")
    run_spmd(lambda a: PC.ring_all_gather(a, "p", interpret=True),
             mesh, (P("p", None),), P(None, None))(x)
    assert tm.counter_value("pallas_collectives.dispatch",
                            op="ring_all_gather", path="rdma") > before


def test_split_equals_concat_keeps_lax(rng):
    # split_dim == concat_dim is outside the direct-scatter scheme
    p = 4
    mesh = spmd_mesh(p)
    x = _ints(rng, (p * 8, 16))
    spec = P("p", None)
    y1 = run_spmd(lambda a: PC.ring_all_to_all(
        a, "p", split_dim=0, concat_dim=0, interpret=True),
        mesh, (spec,), spec)(x)
    y2 = run_spmd(lambda a: lax.all_to_all(
        a, "p", split_axis=0, concat_axis=0, tiled=True),
        mesh, (spec,), spec)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


# ---------------------------------------------------------------------------
# chunk-depth knob
# ---------------------------------------------------------------------------


def test_resolve_chunks_precedence(monkeypatch):
    from distributedarrays_tpu.utils import autotune
    # derived: from DA_TPU_RESHARD_CHUNK_MB
    monkeypatch.delenv(PC.CHUNKS_ENV, raising=False)
    monkeypatch.setenv("DA_TPU_RESHARD_CHUNK_MB", "1")
    n, src = PC.resolve_chunks(3 * 2**20, "t1", 1, 2)
    assert (n, src) == (3, "derived")
    # autotune entry beats derived
    key = autotune.device_key_for("t1", 1, 2)
    autotune.record("rdma_chunks", key, (7,))
    try:
        n, src = PC.resolve_chunks(3 * 2**20, "t1", 1, 2)
        assert (n, src) == (7, "autotune")
        # malformed entry degrades to derived
        autotune.record("rdma_chunks", key, "garbage")
        n, src = PC.resolve_chunks(3 * 2**20, "t1", 1, 2)
        assert (n, src) == (3, "derived")
        # env beats everything
        monkeypatch.setenv(PC.CHUNKS_ENV, "5")
        n, src = PC.resolve_chunks(3 * 2**20, "t1", 1, 2)
        assert (n, src) == (5, "env")
    finally:
        autotune.record("rdma_chunks", key, None)


def test_chunk_fit_divisors():
    assert PC._chunk_fit(12, 5) == 4
    assert PC._chunk_fit(12, 100) == 12
    assert PC._chunk_fit(7, 3) == 1
    assert PC._chunk_fit(8, 0) == 1


# ---------------------------------------------------------------------------
# reshard planner with RDMA armed
# ---------------------------------------------------------------------------


_GRIDS_2D = [(8, 1), (1, 8), (4, 1), (1, 4), (2, 1), (1, 2), (1, 1),
             (4, 2), (2, 4)]


def _shardings_for(shape, grid):
    n = int(np.prod(grid))
    return L.sharding_for(list(range(n)), grid, shape)


def test_reshard_oracle_sweep_rdma_armed(monkeypatch, rng):
    # the PR 4 planner ≡ device_put oracle sweep, re-run with the RDMA
    # kernels armed in interpret mode: every grid pair must still be
    # byte-identical, and the collective strategies must have dispatched
    # on the rdma path
    import itertools
    monkeypatch.setenv("DA_TPU_RDMA", "interpret")
    shape = (16, 24)
    A = rng.standard_normal(shape).astype(np.float32)
    seen = set()
    def a2a_rdma():
        # over every send window: the counter carries ``inflight`` too
        return sum(v for k, v in tm.report()["counters"].items()
                   if k.startswith("pallas_collectives.dispatch{")
                   and "op=ring_all_to_all,path=rdma" in k)

    before = a2a_rdma()
    for gs, gd in itertools.product(_GRIDS_2D, _GRIDS_2D):
        src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
        x = jax.device_put(A, src)
        plan = R.plan_reshard(x, dst)
        seen.add(plan.strategy)
        y = R.reshard(x, dst)
        oracle = jax.device_put(A, dst)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle))
    # (sharded -> replicated pairs are exercised by the staging-bound
    # test: this sweep's (1,1) grid is a single device, not replication)
    assert "all_to_all" in seen
    assert a2a_rdma() > before


def test_reshard_rdma_staging_bound(monkeypatch, rng):
    # acceptance: under a forced tiny chunk target with RDMA armed, the
    # recorded staging high-water stays within 2x the budget
    from distributedarrays_tpu.telemetry import memory as tmem
    monkeypatch.setenv("DA_TPU_RDMA", "interpret")
    monkeypatch.setenv("DA_TPU_RESHARD_CHUNK_MB", "0.0005")
    target = int(0.0005 * 2**20)
    shape = (64, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, (8, 1)), _shardings_for(shape, (1, 8))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "all_to_all" and plan.nchunks > 1
    y = R.reshard(x, dst, plan=plan)
    np.testing.assert_array_equal(np.asarray(y), A)
    assert tmem.staging_peak("reshard.all_to_all") <= 2 * target
    rep = NamedSharding(src.mesh, P())
    plang = R.plan_reshard(x, rep)
    assert plang.strategy == "all_gather"
    z = R.reshard(x, rep, plan=plang)
    np.testing.assert_array_equal(np.asarray(z), A)
    assert tmem.staging_peak("reshard.all_gather") <= 2 * target


def test_reshard_span_labels_dispatch(monkeypatch, rng):
    from distributedarrays_tpu.telemetry import tracing
    monkeypatch.setenv("DA_TPU_RDMA", "interpret")
    shape = (16, 24)
    A = rng.standard_normal(shape).astype(np.float32)
    x = jax.device_put(A, _shardings_for(shape, (8, 1)))
    R.reshard(x, _shardings_for(shape, (1, 8)))
    labeled = [s for s in tracing.spans("reshard")
               if s.get("labels", {}).get("dispatch") == "rdma"]
    assert labeled, "no reshard span labeled dispatch=rdma"
    assert "rdma_chunks" in labeled[-1]["labels"]


def test_reshard_rdma_vs_xla_bit_identical(monkeypatch, rng):
    # flipping the env re-jits (the program is keyed on the mode) and
    # both lowerings produce identical bytes
    shape = (32, 40)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, (8, 1)), _shardings_for(shape, (1, 8))
    x = jax.device_put(A, src)
    monkeypatch.setenv("DA_TPU_RDMA", "0")
    y_xla = np.asarray(R.reshard(x, dst))
    monkeypatch.setenv("DA_TPU_RDMA", "interpret")
    y_rdma = np.asarray(R.reshard(x, dst))
    np.testing.assert_array_equal(y_xla, y_rdma)


# ---------------------------------------------------------------------------
# no discarded final hop (the satellite fix): the last ring iteration
# must not pay a ppermute whose result is thrown away
# ---------------------------------------------------------------------------


class _PermuteCounter:
    def __init__(self, monkeypatch):
        self.n = 0
        real = lax.ppermute

        def counted(*a, **k):
            self.n += 1
            return real(*a, **k)

        monkeypatch.setattr(jax.lax, "ppermute", counted)


def test_ring_attention_no_final_rotation(monkeypatch):
    # the dense ring kernel's final accumulate is unrolled outside the
    # loop WITHOUT a rotation: exactly 2 trace-time ppermutes (k and v,
    # inside the loop body), none in the epilogue
    from distributedarrays_tpu.models import ring_attention as RA
    mesh = spmd_mesh(4)
    spec = P("p", None, None)
    cnt = _PermuteCounter(monkeypatch)
    fn = run_spmd(lambda q, k, v: RA.ring_attention_kernel(q, k, v, "p"),
                  mesh, (spec,) * 3, spec)
    s = jax.ShapeDtypeStruct((16, 2, 8), jnp.float32)
    fn.lower(s, s, s)
    assert cnt.n == 2, f"expected 2 traced ppermutes, got {cnt.n}"


def test_pipeline_skips_final_tick_send(monkeypatch):
    # GPipe: one in-loop send, none in the unrolled final tick; 1F1B:
    # two in-loop sends (activation down + cotangent up), none final
    from distributedarrays_tpu.models import pipeline as PL
    mesh = spmd_mesh(4)
    PL._pipeline_jit.cache_clear()
    cnt = _PermuteCounter(monkeypatch)
    fn = PL._pipeline_jit(mesh)
    fn.lower(jax.ShapeDtypeStruct((4, 2, 8), jnp.float32),
             jax.ShapeDtypeStruct((4, 1, 8, 8), jnp.float32),
             jax.ShapeDtypeStruct((4, 1, 8), jnp.float32))
    assert cnt.n == 1, f"GPipe: expected 1 traced ppermute, got {cnt.n}"


def test_pipeline_forward_unchanged_by_hop_skip(rng):
    # semantic pin for the skip: pipeline output still equals the
    # sequential stage composition
    from distributedarrays_tpu.models import pipeline as PL
    mesh = spmd_mesh(4)
    M, B, H = 5, 3, 8
    W = rng.standard_normal((4, 1, H, H)).astype(np.float32) * 0.3
    b = rng.standard_normal((4, 1, H)).astype(np.float32) * 0.1
    mb = rng.standard_normal((M, B, H)).astype(np.float32)
    out = np.asarray(PL.pipeline_forward({"W": W, "b": b}, mb, mesh))
    want = mb
    for s in range(4):
        want = np.asarray(PL._stage_fn(jnp.asarray(want.reshape(M * B, H)),
                                       jnp.asarray(W[s]),
                                       jnp.asarray(b[s]))).reshape(M, B, H)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# VMEM gates + comm accounting
# ---------------------------------------------------------------------------


def test_gemm_ring_eligibility_gate():
    # a tile set over the scoped-VMEM budget must be rejected for the
    # compiled path (CPU: judge the predicate directly)
    assert PC.gemm_ring_eligible("ag", (128, 512), (512, 256), 4, 4)
    assert not PC.gemm_ring_eligible("ag", (4096, 4096), (4096, 4096), 4, 4)
    assert PC.gemm_ring_eligible("rs", (256, 128), (128, 256), 4, 4)


def test_comm_bytes_recorded_on_dispatch(rng):
    p = 4
    mesh = spmd_mesh(p)
    x = _ints(rng, (p * 4, 128))
    before = tm.comm_bytes("ring_all_gather")
    run_spmd(lambda a: PC.ring_all_gather(a, "p", interpret=True),
             mesh, (P("p", None),), P(None, None))(x)
    after = tm.comm_bytes("ring_all_gather")
    assert after > before


def test_disabled_telemetry_subprocess():
    # the dispatch path must collapse to plain work under
    # DA_TPU_TELEMETRY=0 (no counter writes, identical numerics)
    code = (
        "import numpy as np\n"
        "import jax, jax.numpy as jnp\n"
        "from jax import lax\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from distributedarrays_tpu.parallel.collectives import "
        "run_spmd, spmd_mesh\n"
        "from distributedarrays_tpu.ops import pallas_collectives as PC\n"
        "import distributedarrays_tpu.telemetry as tm\n"
        "assert not tm.enabled()\n"
        "p = 4\n"
        "mesh = spmd_mesh(p)\n"
        "x = np.arange(p * 4 * 128, dtype=np.float32)"
        ".reshape(p * 4, 128)\n"
        "y1 = run_spmd(lambda a: PC.ring_all_gather(a, 'p', "
        "interpret=True), mesh, (P('p', None),), P(None, None))(x)\n"
        "y2 = run_spmd(lambda a: lax.all_gather(a, 'p', axis=0, "
        "tiled=True), mesh, (P('p', None),), P(None, None))(x)\n"
        "assert np.array_equal(np.asarray(y1), np.asarray(y2))\n"
        "print('OK')\n"
    )
    env = dict(os.environ, DA_TPU_TELEMETRY="0", JAX_PLATFORMS="cpu")
    env.pop("DA_TPU_RDMA", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# mesh-coordinate addressing (PR 19: per-axis sub-rings on 2-D meshes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("grid,axis_i", [((4, 2), 0), ((4, 2), 1),
                                         ((2, 2, 2), 1)])
def test_ring_all_gather_mesh_axes_oracle(grid, axis_i, rng):
    # armed along one axis of a multi-axis mesh, the kernel must equal
    # the per-axis lax.all_gather (on CPU the interpret demotion routes
    # through the lax fallback — the dispatch seam under test)
    mesh = L.mesh_for(list(range(int(np.prod(grid)))), grid)
    names = mesh.axis_names
    ax = names[axis_i]
    ndim = len(grid)
    x = _ints(rng, tuple(8 * g for g in grid))
    spec = P(*names)
    out = P(*[None if i == axis_i else names[i] for i in range(ndim)])
    y1 = run_spmd(lambda a: PC.ring_all_gather(
        a, ax, dim=axis_i, interpret=True, mesh_axes=names),
        mesh, (spec,), out)(x)
    y2 = run_spmd(lambda a: lax.all_gather(a, ax, axis=axis_i, tiled=True),
                  mesh, (spec,), out)(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_ring_all_to_all_mesh_axes_oracle(rng):
    grid = (4, 2)
    mesh = L.mesh_for(list(range(8)), grid)
    names = mesh.axis_names
    x = _ints(rng, (32, 16))
    spec = P("d0", "d1")
    y1 = run_spmd(lambda a: PC.ring_all_to_all(
        a, "d0", split_dim=1, concat_dim=0, interpret=True,
        mesh_axes=names), mesh, (spec,), P(None, ("d1", "d0")))(x)
    y2 = run_spmd(lambda a: lax.all_to_all(
        a, "d0", split_axis=1, concat_axis=0, tiled=True),
        mesh, (spec,), P(None, ("d1", "d0")))(x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_arm_mesh_validates_and_demotes():
    # unknown armed axis fails loudly
    with pytest.raises(ValueError, match="not in mesh axes"):
        PC._arm_mesh("compiled", "bogus", ("d0", "d1"))
    # 1-D (or omitted) meshes keep logical addressing
    assert PC._arm_mesh("compiled", "d0", None) == ("compiled", None)
    assert PC._arm_mesh("compiled", "d0", ("d0",)) == ("compiled", None)
    # multi-axis + interpret demotes to the lax fallback (interpret-mode
    # DMA only discharges on 1-D meshes); compiled keeps MESH addressing
    assert PC._arm_mesh("interpret", "d1", ("d0", "d1")) == (None, None)
    assert PC._arm_mesh("compiled", "d1", ("d0", "d1")) == \
        ("compiled", ("d0", "d1"))


def test_fused_matmul_helpers_accept_mesh_axes(rng):
    # the collective_matmul helpers forward mesh_axes to the fused
    # kernels; on a multi-axis CPU mesh the interpret demotion keeps the
    # lax ring and results stay exact
    grid = (4, 2)
    mesh = L.mesh_for(list(range(8)), grid)
    names = mesh.axis_names
    a = _ints(rng, (32, 16))
    b = _ints(rng, (32, 16))
    specs = (P("d0", None), P("d0", None))
    out = P("d0", None)
    y1 = run_spmd(lambda aa, bb: allgather_matmul_rhs(
        aa, bb, "d0", rdma=True, interpret=True, mesh_axes=names),
        mesh, specs, out)(a, b)
    y2 = run_spmd(lambda aa, bb: allgather_matmul_rhs(aa, bb, "d0"),
                  mesh, specs, out)(a, b)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6)
