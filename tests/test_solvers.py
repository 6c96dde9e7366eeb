"""Solver-suite tests: distributed matrix-free operators against dense
oracles, Krylov convergence (CG / BiCGStab / GMRES) on the 8-device
mesh, the multigrid-preconditioned iteration-count win, the streaming
solve service (updates, cancel-frees-residency), the ``solver.spmv`` /
``solver.solve`` spans — and the solver chaos
leg (seeded device loss mid-CG shrinks the operands onto survivors and
still converges to the fault-free answer).

CI runs this file twice: the plain unit leg, and the `solver-chaos` leg
under pinned DA_TPU_FAULT_SEED + DA_TPU_CHECK_DIVERGENCE=1.
"""

import json

import numpy as np
import pytest
import scipy.sparse as sps

import distributedarrays_tpu as dat
from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.resilience import elastic, faults
from distributedarrays_tpu.serve import Cancelled
from distributedarrays_tpu.solvers import (DenseOperator, Multigrid,
                                           SolverService, SparseOperator,
                                           StencilOperator, bicgstab, cg,
                                           gmres, poisson2d_dense)
from distributedarrays_tpu.telemetry import memory as tmem
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401 (fixture)


@pytest.fixture(autouse=True)
def _clean_resilience():
    faults.clear()
    elastic.manager().reset()
    yield
    faults.clear()
    elastic.manager().reset()


def _vec(op, arr):
    """Distribute a host vector/grid on the operator's preferred layout."""
    procs, dist = op.vector_layout()
    return dat.distribute(np.asarray(arr, dtype=np.float32), procs=procs,
                          dist=list(dist))


def _banded(n, *, sym=False):
    """A well-conditioned banded test matrix (nonsymmetric by default)."""
    lower = 0.5 if not sym else -1.0
    return (3.0 * np.eye(n) - np.eye(n, k=1)
            + lower * np.eye(n, k=-1)).astype(np.float32)


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------


def test_poisson2d_dense_is_spd():
    A = poisson2d_dense(4, 5)
    assert A.shape == (20, 20)
    np.testing.assert_array_equal(A, A.T)
    assert np.linalg.eigvalsh(A.astype(np.float64)).min() > 0


# ---------------------------------------------------------------------------
# operators vs oracles
# ---------------------------------------------------------------------------


def test_dense_operator_matches_host(rng):
    n = 32
    A = rng.standard_normal((n, n)).astype(np.float32)
    op = DenseOperator(A)
    assert len(op.vector_layout()[0]) > 1      # genuinely sharded
    x = rng.standard_normal(n).astype(np.float32)
    xd = _vec(op, x)
    y = op.apply(xd)
    np.testing.assert_allclose(np.asarray(dat.gather(y)), A @ x,
                               rtol=2e-5, atol=2e-5)
    y.close()
    xd.close()
    op.close()


def test_sparse_operator_matches_dense(rng):
    n = 64
    A = _banded(n)
    x = rng.standard_normal(n).astype(np.float32)
    for built in (A, sps.csr_matrix(A)):
        op = SparseOperator(built)
        assert op.nnz == int(np.count_nonzero(A))
        assert op._p > 1                       # halo path exercised
        xd = _vec(op, x)
        y = op.apply(xd)
        np.testing.assert_allclose(np.asarray(dat.gather(y)), A @ x,
                                   rtol=2e-5, atol=2e-5)
        y.close()
        xd.close()


def test_sparse_operator_from_darray(rng):
    # n matches test_sparse_operator_matches_dense so the SpMV programs
    # hit the in-process jit cache — this test's subject is the
    # DArray -> chunk-offset COO reassembly, which is host-side
    n = 64
    A = _banded(n)
    dA = dat.distribute(A)
    op = SparseOperator(dA)                    # routed through ddata_bcoo
    dA.close()
    x = rng.standard_normal(n).astype(np.float32)
    xd = _vec(op, x)
    y = op.apply(xd)
    np.testing.assert_allclose(np.asarray(dat.gather(y)), A @ x,
                               rtol=2e-5, atol=2e-5)
    y.close()
    xd.close()


def test_sparse_partition_coarsens_for_wide_bandwidth(rng):
    # one entry reaching 40 columns off-diagonal: every multi-rank block
    # size (8, 16, 32 rows) is narrower than the reach, so the partition
    # must coarsen to a single rank — and stay correct
    n = 64
    A = _banded(n)
    A[0, 40] = 2.0
    op = SparseOperator(A)
    assert op._p == 1
    x = rng.standard_normal(n).astype(np.float32)
    xd = _vec(op, x)
    y = op.apply(xd)
    np.testing.assert_allclose(np.asarray(dat.gather(y)), A @ x,
                               rtol=2e-5, atol=2e-5)
    y.close()
    xd.close()


def test_stencil_operator_matches_kron_oracle(rng):
    nx, ny = 8, 8
    op = StencilOperator((nx, ny), scale=0.5)
    dense = poisson2d_dense(nx, ny, scale=0.5)
    x = rng.standard_normal((nx, ny)).astype(np.float32)
    xd = _vec(op, x)
    y = op.apply(xd)
    np.testing.assert_allclose(np.asarray(dat.gather(y)),
                               (dense @ x.ravel()).reshape(nx, ny),
                               rtol=2e-5, atol=2e-5)
    y.close()
    xd.close()


def test_operator_align_accepts_foreign_layout(rng):
    # a vector distributed on a different rank set/layout is re-seated
    # through the planner, the caller's copy untouched
    op = StencilOperator((8, 8))
    x = rng.standard_normal((8, 8)).astype(np.float32)
    xd = dat.distribute(x, procs=[0, 1], dist=[1, 2])
    y = op.apply(xd)
    np.testing.assert_allclose(
        np.asarray(dat.gather(y)),
        (poisson2d_dense(8, 8) @ x.ravel()).reshape(8, 8),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(dat.gather(xd)), x)
    y.close()
    xd.close()


# ---------------------------------------------------------------------------
# Krylov convergence on >= 2 devices
# ---------------------------------------------------------------------------


def test_cg_poisson_converges_to_dense_oracle(rng):
    nx, ny = 16, 16
    op = StencilOperator((nx, ny))
    b = rng.standard_normal((nx, ny)).astype(np.float32)
    bd = _vec(op, b)
    res = cg(op, bd, tol=1e-6)
    assert res.converged and res.outcome == "converged"
    assert len(set(int(p) for p in res.x.pids.flat)) >= 2
    assert len(res.history) == res.iterations > 1
    assert res.residual <= 1e-6 * np.linalg.norm(b)
    oracle = np.linalg.solve(poisson2d_dense(nx, ny).astype(np.float64),
                             b.ravel().astype(np.float64))
    np.testing.assert_allclose(np.asarray(res.x.garray).ravel(), oracle,
                               atol=5e-4)
    res.x.close()
    bd.close()


def test_cg_dense_and_sparse_operators(rng):
    n = 40
    A = _banded(n, sym=True)                  # SPD tridiagonal
    b = rng.standard_normal(n).astype(np.float32)
    oracle = np.linalg.solve(A.astype(np.float64), b.astype(np.float64))
    for op in (DenseOperator(A), SparseOperator(sps.csr_matrix(A))):
        bd = _vec(op, b)
        res = cg(op, bd, tol=1e-7)
        assert res.converged, res.outcome
        np.testing.assert_allclose(np.asarray(res.x.garray), oracle,
                                   atol=1e-3)
        res.x.close()
        bd.close()
        if hasattr(op, "close"):
            op.close()


def test_cg_maxiter_typed_outcome(rng):
    op = StencilOperator((16, 16))
    bd = _vec(op, rng.standard_normal((16, 16)))
    res = cg(op, bd, tol=1e-12, maxiter=3)
    assert res.outcome == "maxiter" and not res.converged
    assert res.iterations == 3 and len(res.history) == 3
    res.x.close()
    bd.close()


def test_bicgstab_and_gmres_nonsymmetric(rng):
    n = 48
    A = _banded(n)
    b = rng.standard_normal(n).astype(np.float32)
    oracle = np.linalg.solve(A.astype(np.float64), b.astype(np.float64))
    for solve in (bicgstab, gmres):
        op = SparseOperator(A)
        bd = _vec(op, b)
        res = solve(op, bd, tol=1e-7)
        assert res.converged, (solve.__name__, res.outcome, res.detail)
        assert res.solver == solve.__name__
        np.testing.assert_allclose(np.asarray(res.x.garray), oracle,
                                   atol=1e-3, err_msg=solve.__name__)
        res.x.close()
        bd.close()


def test_gmres_restart_and_warm_start(rng):
    nx, ny = 16, 16
    op = StencilOperator((nx, ny))
    bd = _vec(op, rng.standard_normal((nx, ny)))
    res = gmres(op, bd, tol=1e-6, restart=5)   # forces outer restarts
    assert res.converged and res.iterations > 5
    # warm start from the solution: the entry residual check converges
    # without growing a Krylov space (looser tol — the recomputed f32
    # residual sits a hair above the Givens estimate the solve stopped on)
    res2 = gmres(op, bd, x0=res.x, tol=1e-5)
    assert res2.converged and res2.iterations == 0
    assert len(res2.history) == 1              # the entry residual
    res2.x.close()
    res.x.close()
    bd.close()


# ---------------------------------------------------------------------------
# multigrid preconditioning
# ---------------------------------------------------------------------------


def test_mgcg_converges_in_far_fewer_iterations(rng):
    nx, ny = 32, 32
    op = StencilOperator((nx, ny))
    b = rng.standard_normal((nx, ny)).astype(np.float32)
    bd = _vec(op, b)
    plain = cg(op, bd, tol=1e-6)
    mg = cg(op, bd, tol=1e-6, M=Multigrid(op))
    assert plain.converged and mg.converged
    assert mg.iterations < plain.iterations / 2, \
        (mg.iterations, plain.iterations)
    np.testing.assert_allclose(np.asarray(mg.x.garray),
                               np.asarray(plain.x.garray), atol=1e-3)
    plain.x.close()
    mg.x.close()
    bd.close()


def test_multigrid_requires_stencil_operator():
    with pytest.raises(TypeError):
        Multigrid(DenseOperator(np.eye(8, dtype=np.float32)))


# ---------------------------------------------------------------------------
# observability: one solver.spmv span a matvec, one solver.solve a solve
# ---------------------------------------------------------------------------


def test_spmv_spans_say_which_operator_ran(telemetry_capture, rng):
    op = StencilOperator((16, 16))
    bd = _vec(op, rng.standard_normal((16, 16)))
    res = cg(op, bd, tol=1e-12, maxiter=5)
    res.x.close()
    bd.close()
    sop = SparseOperator(_banded(64))
    vd = _vec(sop, np.ones(64))
    y = sop.apply(vd)
    y.close()
    vd.close()

    spans = telemetry_capture.spans("solver.spmv")
    assert len(spans) >= 6
    assert {s["labels"]["op"] for s in spans} == {"stencil", "bcoo"}
    by_op = {s["labels"]["op"]: s["labels"] for s in spans}
    assert int(by_op["stencil"]["n"]) == 256
    assert int(by_op["bcoo"]["n"]) == 64
    assert all(int(s["labels"]["ranks"]) >= 1 for s in spans)
    # the matvecs of the solve are children of its solver.solve span
    solve = telemetry_capture.spans("solver.solve")[-1]
    assert solve["labels"]["solver"] == "cg"
    assert int(solve["labels"]["n"]) == 256
    kids = [s for s in spans if s["labels"]["op"] == "stencil"]
    assert kids and all(s["parent_id"] == solve["span_id"] for s in kids)
    telemetry_capture.assert_counter("solver.iterations", 5, solver="cg")


# ---------------------------------------------------------------------------
# the solver chaos leg
# ---------------------------------------------------------------------------


def test_chaos_device_loss_mid_cg_converges_on_survivors(rng, monkeypatch):
    """Seeded plan downs device 5 on the fourth CG iteration: recovery
    probes, shrinks the registered operands onto the survivors, the
    segment re-derives the operator partition and restarts the Krylov
    space from the current x — and the final answer matches the
    fault-free solve to solver tolerance."""
    nx, ny = 16, 16
    op = StencilOperator((nx, ny))
    b = rng.standard_normal((nx, ny)).astype(np.float32)
    bd = _vec(op, b)
    free = cg(op, bd, tol=1e-6)
    assert free.converged and free.recoveries == 0
    x_free = np.asarray(free.x.garray).copy()
    free.x.close()

    plan = [{"site": "solver.iterate", "action": "device_loss", "at": 4,
             "count": 1, "device": 5}]
    monkeypatch.setenv("DA_TPU_FAULT_PLAN", json.dumps(plan))
    monkeypatch.setenv("DA_TPU_FAULT_SEED", "1234")
    faults.configure()
    retries0 = tm.counter_value("recovery.retries", verdict="device_loss")

    chaos_op = StencilOperator((nx, ny))
    res = cg(chaos_op, bd, tol=1e-6)
    assert res.converged, (res.outcome, res.detail)
    assert res.recoveries >= 1
    assert [h["action"] for h in faults.history()] == ["device_loss"]
    assert tm.counter_value("recovery.retries",
                            verdict="device_loss") > retries0
    # operands live strictly on survivors
    assert 5 not in elastic.manager().live_ranks()
    assert 5 not in {int(p) for p in res.x.pids.flat}
    np.testing.assert_allclose(np.asarray(res.x.garray).ravel(),
                               x_free.ravel(), atol=5e-4)
    res.x.close()
    bd.close()


# ---------------------------------------------------------------------------
# streaming solve service
# ---------------------------------------------------------------------------


def test_service_streams_iterations_and_result(rng):
    nx, ny = 16, 16
    rhs = rng.standard_normal((nx, ny)).astype(np.float32)
    svc = SolverService()
    try:
        stream = svc.submit({"kind": "poisson", "grid": (nx, ny), "b": rhs},
                            tol=1e-6)
        updates = list(stream)                 # (iter, residual) as they land
        summary = stream.result(timeout=120)
    finally:
        svc.close()
    assert summary["outcome"] == "converged"
    assert [it for it, _ in updates] == \
        list(range(1, summary["iterations"] + 1))
    assert len(updates) > 5
    assert updates[-1][1] < updates[0][1]      # residual actually fell
    assert summary["history"] == [r for _, r in updates]
    oracle = np.linalg.solve(poisson2d_dense(nx, ny).astype(np.float64),
                             rhs.ravel().astype(np.float64))
    np.testing.assert_allclose(summary["x"].ravel(), oracle, atol=5e-4)
    assert tmem.live_bytes() == 0              # residency freed with request


def test_service_dense_system_and_bad_method(rng):
    n = 32
    A = _banded(n, sym=True)
    b = rng.standard_normal(n).astype(np.float32)
    svc = SolverService()
    try:
        with pytest.raises(ValueError):
            svc.submit({"kind": "dense", "A": A, "b": b}, method="qr")
        stream = svc.submit({"kind": "dense", "A": A, "b": b}, tol=1e-7)
        summary = stream.result(timeout=120)
    finally:
        svc.close()
    np.testing.assert_allclose(
        summary["x"],
        np.linalg.solve(A.astype(np.float64), b.astype(np.float64)),
        atol=1e-3)


def test_service_cancel_frees_residency(rng):
    # a solve that cannot converge keeps iterating until cancel; the
    # stream resolves typed Cancelled and the dispatch's finally frees
    # the system's operand residency
    rhs = rng.standard_normal((32, 32)).astype(np.float32)
    svc = SolverService()
    try:
        stream = svc.submit({"kind": "poisson", "grid": (32, 32), "b": rhs},
                            precond="multigrid", tol=1e-30, maxiter=100_000)
        with pytest.raises(Cancelled):
            for it, _res in stream:
                if it >= 3:
                    stream.cancel()
        assert stream.cancelled() and stream.done()
        summary = stream.future.result(timeout=60)   # dispatch succeeded
        assert summary["outcome"] == "cancelled"
        assert summary["iterations"] < 100_000
    finally:
        svc.close()
    assert tmem.live_bytes() == 0
