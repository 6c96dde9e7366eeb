"""Distributed linear algebra tests (reference src/linalg.jl semantics;
oracle = numpy, mirroring the reference's GEMM checks test/darray.jl:921-924)."""

import numpy as np
import pytest

import jax.numpy as jnp

import distributedarrays_tpu as dat
from distributedarrays_tpu import DArray
from distributedarrays_tpu.ops import linalg as la


@pytest.fixture
def mats(rng):
    A = rng.standard_normal((48, 32)).astype(np.float32)
    B = rng.standard_normal((32, 40)).astype(np.float32)
    return A, B


def test_ddot_dnorm(rng):
    x = rng.standard_normal(1000).astype(np.float32)
    y = rng.standard_normal(1000).astype(np.float32)
    dx, dy = dat.distribute(x), dat.distribute(y)
    assert np.allclose(float(la.ddot(dx, dy)), np.dot(x, y), rtol=1e-4)
    assert np.allclose(float(la.dnorm(dx)), np.linalg.norm(x), rtol=1e-5)
    assert np.allclose(float(la.dnorm(dx, 1)), np.abs(x).sum(), rtol=1e-5)
    assert np.allclose(float(la.dnorm(dx, np.inf)), np.abs(x).max(), rtol=1e-6)
    with pytest.raises(ValueError):
        la.ddot(dx, dat.dzeros((7,)))


def test_axpy(rng):
    x = rng.standard_normal(100).astype(np.float32)
    y = rng.standard_normal(100).astype(np.float32)
    dx, dy = dat.distribute(x), dat.distribute(y.copy())
    out = la.axpy_(2.5, dx, dy)
    assert out is dy
    assert np.allclose(np.asarray(dy), 2.5 * x + y, rtol=1e-5)
    with pytest.raises(ValueError):
        la.axpy_(1.0, dat.dzeros((7,)), dy)


def test_scalar_scaling(rng):
    A = rng.standard_normal((16, 16)).astype(np.float32)
    d = dat.distribute(A.copy())
    la.rmul_(d, 3.0)
    assert np.allclose(np.asarray(d), A * 3, rtol=1e-6)
    la.lmul_(0.5, d)
    assert np.allclose(np.asarray(d), A * 1.5, rtol=1e-6)


def test_diagonal_scaling(rng):
    A = rng.standard_normal((12, 8)).astype(np.float32)
    dl = rng.standard_normal(12).astype(np.float32)
    dr = rng.standard_normal(8).astype(np.float32)
    d = dat.distribute(A.copy())
    la.lmul_diag(dl, d)
    assert np.allclose(np.asarray(d), dl[:, None] * A, rtol=1e-5)
    d2 = dat.distribute(A.copy())
    la.rmul_diag(d2, dr)
    assert np.allclose(np.asarray(d2), A * dr[None, :], rtol=1e-5)
    with pytest.raises(ValueError):
        la.lmul_diag(dr, d)  # wrong length


def test_transpose_adjoint(mats):
    A, _ = mats
    d = dat.distribute(A, procs=range(8), dist=(4, 2))
    t = d.T
    assert isinstance(t, DArray)
    assert t.dims == (32, 48)
    assert t.pids.shape == (2, 4)
    assert np.allclose(np.asarray(t), A.T)
    z = (dat.distribute(A.astype(np.complex64) + 1j)).garray
    dz = dat.distribute(np.asarray(z))
    adj = la.dadjoint(dz)
    assert np.allclose(np.asarray(adj), np.conj(np.asarray(z)).T)


def test_matmul_dd(mats):
    A, B = mats
    da = dat.distribute(A, procs=range(8), dist=(4, 2))
    db = dat.distribute(B, procs=range(8), dist=(2, 4))
    C = da @ db
    assert isinstance(C, DArray)
    assert C.dims == (48, 40)
    assert np.allclose(np.asarray(C), A @ B, rtol=1e-4, atol=1e-4)
    # result rows follow A's row grid (reference linalg.jl:261-311)
    assert C.pids.shape[0] == 4


def test_matmul_mixed_plain(mats):
    A, B = mats
    da = dat.distribute(A)
    C = da @ B                      # plain numpy rhs
    assert np.allclose(np.asarray(C), A @ B, rtol=1e-4, atol=1e-4)
    C2 = A @ dat.distribute(B)      # plain numpy lhs
    assert np.allclose(np.asarray(C2), A @ B, rtol=1e-4, atol=1e-4)


def test_matvec(mats, rng):
    A, _ = mats
    x = rng.standard_normal(32).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    y = da @ dat.distribute(x)
    assert y.dims == (48,)
    assert np.allclose(np.asarray(y), A @ x, rtol=1e-4, atol=1e-4)


def test_mul_into_cuts_contract(mats):
    A, B = mats
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    db = dat.distribute(B)
    C_good = dat.dzeros((48, 40), procs=range(4), dist=(4, 1))
    la.mul_into(C_good, da, db)
    assert np.allclose(np.asarray(C_good), A @ B, rtol=1e-4, atol=1e-4)
    # row-cuts mismatch must throw (reference linalg.jl:201)
    C_bad = dat.dzeros((48, 40), procs=range(3), dist=(3, 1))
    with pytest.raises(ValueError, match="row cuts"):
        la.mul_into(C_bad, da, db)


def test_mul_into_alpha_beta(mats, rng):
    A, B = mats
    C0 = rng.standard_normal((48, 40)).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    db = dat.distribute(B)
    C = dat.distribute(C0.copy(), procs=range(4), dist=(4, 1))
    assert C.cuts[0] == da.cuts[0]
    la.mul_into(C, da, db, alpha=2.0, beta=0.5)
    assert np.allclose(np.asarray(C), 2.0 * (A @ B) + 0.5 * C0,
                       rtol=1e-4, atol=1e-4)


def test_matmul_dim_mismatch(mats):
    A, B = mats
    with pytest.raises(ValueError):
        dat.distribute(A) @ dat.distribute(A)


def test_matmul_uneven_rows(rng):
    # 50 rows over 4 chunks: uneven layout must still produce correct GEMM
    A = rng.standard_normal((50, 20)).astype(np.float32)
    B = rng.standard_normal((20, 30)).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    C = da @ dat.distribute(B)
    assert np.allclose(np.asarray(C), A @ B, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# matmul implementation dispatch (VERDICT round-3 item 4): the owned GEMM
# schedules behind the autotune registry, jnp.matmul as the default
# ---------------------------------------------------------------------------


def test_matmul_default_impl_is_jnp(mats, monkeypatch):
    A, B = mats
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    calls = []
    monkeypatch.setattr(la, "_try_pallas_gemm",
                        lambda *a: calls.append(1) or None)
    da = dat.distribute(A, procs=[0], dist=(1, 1))
    C = da @ dat.distribute(B, procs=[0], dist=(1, 1))
    assert np.allclose(np.asarray(C), A @ B, rtol=1e-4, atol=1e-4)
    assert not calls, "pallas path must not run without a banked win"
    dat.d_closeall()


def test_matmul_registry_promotes_pallas(mats, monkeypatch):
    A, B = mats
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    da = dat.distribute(A, procs=[0], dist=(1, 1))
    db = dat.distribute(B, procs=[0], dist=(1, 1))
    key = la._impl_key(48, 40, 32, da.garray.dtype, db.garray.dtype)
    autotune.record("matmul_impl", key, "pallas")
    called = []
    orig = la._try_pallas_gemm
    monkeypatch.setattr(la, "_try_pallas_gemm",
                        lambda *a: called.append(1) or orig(*a))
    C = da @ db
    assert called, "banked pallas win must route through the pallas path"
    assert np.allclose(np.asarray(C), A @ B, rtol=1e-3, atol=1e-3)
    # multi-device operands stay on the GSPMD path even with the entry
    da4 = dat.distribute(A, procs=range(4), dist=(4, 1))
    key4 = la._impl_key(48, 40, 32, da4.garray.dtype, db.garray.dtype)
    autotune.record("matmul_impl", key4, "pallas")
    C4 = da4 @ dat.distribute(B)
    assert np.allclose(np.asarray(C4), A @ B, rtol=1e-4, atol=1e-4)
    autotune.clear()
    dat.d_closeall()


def test_matmul_ring_allgather_dispatch(rng, monkeypatch):
    # the 1-D TP shape: A row-chunked (p,1) x B contraction-chunked (p,1)
    # -> C row-chunked (p,1), run as ONE overlapped-ring shard_map program
    # when the registry promotes it
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    A = rng.standard_normal((16, 32)).astype(np.float32)
    B = rng.standard_normal((32, 12)).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    db = dat.distribute(B, procs=range(4), dist=(4, 1))
    called = []
    orig = la._ring_ag_gemm
    monkeypatch.setattr(la, "_ring_ag_gemm",
                        lambda *a: called.append(1) or orig(*a))
    # default (no banked entry): GSPMD path
    C0 = da @ db
    assert not called
    assert np.allclose(np.asarray(C0), A @ B, rtol=1e-4, atol=1e-4)
    # promoted: ring path, both out-of-place and mul_into
    autotune.record("matmul_impl_dist",
                    la._impl_key(16, 12, 32, 4, da.dtype, db.dtype),
                    "ring_ag")
    C1 = da @ db
    assert called, "banked ring win must route through the ring schedule"
    assert np.allclose(np.asarray(C1), A @ B, rtol=1e-4, atol=1e-4)
    assert list(C1.pids.shape) == [4, 1] and C1.cuts[0] == da.cuts[0]
    called.clear()
    C2 = dat.dzeros((16, 12), procs=range(4), dist=(4, 1))
    la.mul_into(C2, da, db)
    assert called
    assert np.allclose(np.asarray(C2), A @ B, rtol=1e-4, atol=1e-4)
    # alpha/beta mode stays off the ring
    called.clear()
    C3 = dat.dzeros((16, 12), procs=range(4), dist=(4, 1))
    la.mul_into(C3, da, db, alpha=2.0)
    assert not called
    assert np.allclose(np.asarray(C3), 2 * (A @ B), rtol=1e-4, atol=1e-4)
    autotune.clear()
    dat.d_closeall()


def test_matmul_summa_dispatch(rng, monkeypatch):
    # the square 2-D-grid shape (BASELINE config 3): A and B block-
    # distributed on the SAME (g,g) grid -> C on that grid, run as ONE
    # Cannon double-ring shard_map program when the registry promotes it
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    A = rng.standard_normal((16, 24)).astype(np.float32)
    B = rng.standard_normal((24, 8)).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(2, 2))
    db = dat.distribute(B, procs=range(4), dist=(2, 2))
    called = []
    orig = la._summa_gemm
    monkeypatch.setattr(la, "_summa_gemm",
                        lambda *a: called.append(1) or orig(*a))
    # default (no banked entry): GSPMD path
    C0 = da @ db
    assert not called
    assert np.allclose(np.asarray(C0), A @ B, rtol=1e-4, atol=1e-4)
    # promoted: Cannon path, both out-of-place and mul_into
    autotune.record("matmul_impl_dist",
                    la._impl_key(16, 8, 24, "2x2", da.dtype, db.dtype),
                    "summa")
    C1 = da @ db
    assert called, "banked summa win must route through the Cannon ring"
    assert np.allclose(np.asarray(C1), A @ B, rtol=1e-4, atol=1e-4)
    assert list(C1.pids.shape) == [2, 2] and C1.cuts[0] == da.cuts[0]
    called.clear()
    C2 = dat.dzeros((16, 8), procs=range(4), dist=(2, 2))
    la.mul_into(C2, da, db)
    assert called
    assert np.allclose(np.asarray(C2), A @ B, rtol=1e-4, atol=1e-4)
    # alpha/beta mode stays off the ring
    called.clear()
    C3 = dat.dzeros((16, 8), procs=range(4), dist=(2, 2))
    la.mul_into(C3, da, db, alpha=2.0)
    assert not called
    assert np.allclose(np.asarray(C3), 2 * (A @ B), rtol=1e-4, atol=1e-4)
    # MISMATCHED grids ((2,4) A vs (4,2) B) are NOT eligible even with a
    # banked entry — the tile schedules need both operands on ONE grid
    da2 = dat.distribute(A, procs=range(8), dist=(2, 4))
    db2 = dat.distribute(B, procs=range(8), dist=(4, 2))
    autotune.record("matmul_impl_dist",
                    la._impl_key(16, 8, 24, "2x4", da2.dtype, db2.dtype),
                    "summa")
    called.clear()
    C4 = da2 @ db2
    assert not called
    assert np.allclose(np.asarray(C4), A @ B, rtol=1e-4, atol=1e-4)
    autotune.clear()
    dat.d_closeall()


def test_matmul_summa_rectangular_dispatch(rng, monkeypatch):
    # a SAME-grid rectangular (2,4) layout routes to the masked-psum
    # SUMMA panel schedule when promoted (square grids take Cannon)
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    A = rng.standard_normal((16, 24)).astype(np.float32)
    B = rng.standard_normal((24, 8)).astype(np.float32)
    da = dat.distribute(A, procs=range(8), dist=(2, 4))
    db = dat.distribute(B, procs=range(8), dist=(2, 4))
    called = []
    orig = la._summa_gemm
    monkeypatch.setattr(la, "_summa_gemm",
                        lambda *a: called.append(1) or orig(*a))
    C0 = da @ db                       # default: GSPMD
    assert not called
    assert np.allclose(np.asarray(C0), A @ B, rtol=1e-4, atol=1e-4)
    autotune.record("matmul_impl_dist",
                    la._impl_key(16, 8, 24, "2x4", da.dtype, db.dtype),
                    "summa")
    C1 = da @ db
    assert called, "banked rect-grid win must route through summa_matmul"
    assert np.allclose(np.asarray(C1), A @ B, rtol=1e-4, atol=1e-4)
    assert list(C1.pids.shape) == [2, 4]
    autotune.clear()
    dat.d_closeall()


def test_tune_matmul_impl_summa_banks_winner():
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    times = {"jnp": 1.0, "summa": 0.5}
    seen = []

    def timer(op, a, b):
        assert a.shape == (16, 24) and b.shape == (24, 8)
        name = "jnp" if not seen else "summa"
        seen.append(name)
        return times[name]

    winner, results = la.tune_matmul_impl_summa(
        16, 8, 24, g=2, timer=timer, persist=False)
    assert winner == "summa" and results == times
    f32 = jnp.float32(0).dtype
    assert autotune.get("matmul_impl_dist",
                        la._impl_key(16, 8, 24, "2x2", f32, f32)) == "summa"
    with pytest.raises(ValueError, match="divisible"):
        la.tune_matmul_impl_summa(15, 8, 24, g=2, timer=timer)
    # rectangular grid: same flow, rxc-tagged key
    winner, results = la.tune_matmul_impl_summa(
        16, 8, 24, g=(2, 4), timer=lambda op, a, b: 1.0, persist=False)
    assert set(results) == {"jnp", "summa"}
    assert autotune.get("matmul_impl_dist",
                        la._impl_key(16, 8, 24, "2x4", f32, f32)) is not None
    autotune.clear()


def test_tune_matmul_impl_banks_winner():
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    # injectable timer: declare pallas the winner deterministically
    times = {"jnp": 2.0, "pallas": 1.0}
    seq = iter(["jnp", "pallas"])

    def timer(op, a, b):
        assert a.shape == (256, 256) and b.shape == (256, 256)
        return times[next(seq)]

    winner, results = la.tune_matmul_impl(256, 256, 256, jnp.float32,
                                          timer=timer, persist=False)
    assert winner == "pallas" and results == times
    f32 = jnp.float32(0).dtype
    key = la._impl_key(256, 256, 256, f32, f32)
    assert autotune.get("matmul_impl", key) == "pallas"
    # the key is platform-fenced: a winner banked here must be invisible
    # under any other device kind
    assert autotune.get("matmul_impl",
                        autotune.key_for(256, 256, 256, f32, f32)) is None
    autotune.clear()


def test_tune_matmul_impl_dist_banks_winner():
    from distributedarrays_tpu.utils import autotune
    autotune.clear()
    times = {"jnp": 1.0, "ring_ag": 0.5}
    seen = []

    def timer(op, a, b):
        # real sharded operands reach the timer
        assert a.shape == (64, 32) and b.shape == (32, 16)
        name = "jnp" if not seen else "ring_ag"
        seen.append(name)
        return times[name]

    winner, results = la.tune_matmul_impl_dist(
        64, 16, 32, p=4, timer=timer, persist=False)
    assert winner == "ring_ag" and results == times
    f32 = jnp.float32(0).dtype
    assert autotune.get("matmul_impl_dist",
                        la._impl_key(64, 16, 32, 4, f32, f32)) == "ring_ag"
    with pytest.raises(ValueError, match="devices"):
        la.tune_matmul_impl_dist(64, 16, 32, p=1, timer=timer)
    with pytest.raises(ValueError, match="divisible"):
        la.tune_matmul_impl_dist(63, 16, 32, p=4, timer=timer)
    autotune.clear()


def test_dmatmul_int8_single_device(rng):
    A = rng.standard_normal((128, 64)).astype(np.float32)
    B = rng.standard_normal((64, 96)).astype(np.float32)
    da = dat.distribute(A, procs=[0], dist=(1, 1))
    C = dat.dmatmul_int8(da, B)
    ref = A @ B
    assert np.abs(np.asarray(C) - ref).max() / np.abs(ref).max() < 3e-2


def test_dmatmul_int8_row_sharded(rng):
    A = rng.standard_normal((128, 64)).astype(np.float32)
    B = rng.standard_normal((64, 96)).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    C = dat.dmatmul_int8(da, dat.distribute(B))
    assert list(C.pids.shape) == [4, 1]
    ref = A @ B
    assert np.abs(np.asarray(C) - ref).max() / np.abs(ref).max() < 3e-2
    dat.d_closeall()


def test_dmatmul_int8_square_grid(rng):
    # both operands on one (2,2) grid: int8 panels + per-panel scales
    # ride the Cannon double ring (cannon_matmul_int8)
    A = rng.standard_normal((64, 64)).astype(np.float32)
    B = rng.standard_normal((64, 32)).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(2, 2))
    db = dat.distribute(B, procs=range(4), dist=(2, 2))
    C = dat.dmatmul_int8(da, db)
    assert list(C.pids.shape) == [2, 2]
    ref = A @ B
    assert np.abs(np.asarray(C) - ref).max() / np.abs(ref).max() < 3e-2
    dat.d_closeall()


def test_dmatmul_int8_validation(rng):
    A = rng.standard_normal((50, 64)).astype(np.float32)  # uneven rows
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    with pytest.raises(ValueError, match="even"):
        dat.dmatmul_int8(da, np.zeros((64, 8), np.float32))
    db = dat.distribute(rng.standard_normal((64, 32)).astype(np.float32),
                        procs=range(8), dist=(2, 4))
    da2 = dat.distribute(rng.standard_normal((16, 64)).astype(np.float32),
                         procs=range(8), dist=(2, 4))
    with pytest.raises(ValueError, match="grid"):
        dat.dmatmul_int8(da2, db)
    with pytest.raises(ValueError, match="mismatch"):
        dat.dmatmul_int8(dat.distribute(A, procs=[0], dist=(1, 1)),
                         np.zeros((8, 8), np.float32))
    dat.d_closeall()


def test_dmatmul_int8_host_array_lhs(rng):
    # plain ndarray A lands on a supported layout automatically
    A = rng.standard_normal((128, 64)).astype(np.float32)   # 128 % 8 == 0
    B = rng.standard_normal((64, 96)).astype(np.float32)
    C = dat.dmatmul_int8(A, B)
    ref = A @ B
    assert np.abs(np.asarray(C) - ref).max() / np.abs(ref).max() < 3e-2
    A2 = rng.standard_normal((51, 64)).astype(np.float32)   # indivisible
    C2 = dat.dmatmul_int8(A2, B)
    ref2 = A2 @ B
    assert np.abs(np.asarray(C2) - ref2).max() / np.abs(ref2).max() < 3e-2
    dat.d_closeall()


@pytest.mark.parametrize("n", [64, 72])
def test_readme_opening_lines_transposed_operand(n):
    # README's opening four lines on the whole mesh: r.T holds the SAME
    # eight devices in another order, which one jitted program refuses
    # unless the operand is re-laid first (found by PR 21's rehearsal)
    d = dat.drand((n, n))
    r = dat.dmap(jnp.sin, d) + d * 2.0
    assert sorted(int(p) for p in r.T.pids.flat) == \
        sorted(int(p) for p in d.pids.flat)
    s = float(dat.dsum(r))
    C = d @ r.T
    dh, rh = np.asarray(d, np.float64), np.asarray(r, np.float64)
    np.testing.assert_allclose(s, rh.sum(), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(C), dh @ rh.T, rtol=1e-4,
                               atol=1e-4)
    # elementwise with the transposed operand takes the same path
    np.testing.assert_allclose(np.asarray(d + r.T), dh + rh.T, rtol=1e-5)
