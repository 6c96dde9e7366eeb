"""Map/reduce tests (reference src/mapreduce.jl semantics; oracle = numpy,
mirroring e.g. test/darray.jl:398-441 reduction checks)."""

import numpy as np
import pytest

import jax.numpy as jnp

import distributedarrays_tpu as dat
from distributedarrays_tpu import DArray


@pytest.fixture
def dA(rng):
    A = rng.standard_normal((40, 24)).astype(np.float32)
    return A, dat.distribute(A, procs=range(8), dist=(4, 2))


def test_whole_array_reductions(dA):
    A, d = dA
    assert np.allclose(float(dat.dsum(d)), A.sum(), rtol=1e-4)
    assert np.allclose(float(dat.dmaximum(d)), A.max())
    assert np.allclose(float(dat.dminimum(d)), A.min())
    assert np.allclose(float(dat.dmean(d)), A.mean(), rtol=1e-5)
    assert np.allclose(float(dat.dstd(d)), A.std(ddof=1), rtol=1e-4)
    assert np.allclose(float(dat.dvar(d, ddof=1)), A.var(ddof=1), rtol=1e-4)


def test_mapreduce(dA):
    A, d = dA
    # mapreduce(abs2, +, D) — BASELINE config 2 semantics
    got = float(dat.dmapreduce(jnp.square, "sum", d))
    assert np.allclose(got, (A ** 2).sum(), rtol=1e-4)
    got = float(dat.dmapreduce(jnp.abs, "max", d))
    assert np.allclose(got, np.abs(A).max())


def test_dim_reductions_keepdims(dA):
    A, d = dA
    for dims, axis in [(0, 0), (1, 1), ((0, 1), (0, 1))]:
        r = dat.dsum(d, dims=dims)
        want = A.sum(axis=axis, keepdims=True)
        assert isinstance(r, DArray)
        assert r.dims == want.shape
        assert np.allclose(np.asarray(r), want, rtol=1e-4)


def test_dim_reduction_layout_follows_grid(dA):
    A, d = dA
    r = dat.dsum(d, dims=1)   # reduce over the 2-chunk dim
    # result keeps the 4-way chunking of dim 0 (mapreduce.jl:54-66)
    assert r.pids.shape[0] == 4
    assert np.allclose(np.asarray(r), A.sum(axis=1, keepdims=True), rtol=1e-4)


def test_all_any_count(rng):
    A = rng.standard_normal((30, 10)).astype(np.float32)
    d = dat.distribute(A)
    assert bool(dat.dall(d < 100)) is True
    assert bool(dat.dany(d > 100)) is False
    got = int(dat.dcount(lambda a: a > 0, d))
    assert got == int((A > 0).sum())


def test_extrema(dA):
    A, d = dA
    lo, hi = dat.dextrema(d)
    assert np.allclose(float(lo), A.min())
    assert np.allclose(float(hi), A.max())
    lo_d, hi_d = dat.dextrema(d, dims=1)
    assert np.allclose(np.asarray(lo_d), A.min(axis=1, keepdims=True))
    assert np.allclose(np.asarray(hi_d), A.max(axis=1, keepdims=True))


def test_map_localparts_even_shardmap(rng):
    A = rng.standard_normal((40, 8)).astype(np.float32)
    d = dat.distribute(A, procs=range(8), dist=(4, 2))
    r = dat.map_localparts(lambda lp: lp * 2.0, d)
    assert np.allclose(np.asarray(r), A * 2, rtol=1e-6)


def test_map_localparts_two_args(rng):
    A = rng.standard_normal((16, 8)).astype(np.float32)
    B = rng.standard_normal((16, 8)).astype(np.float32)
    da = dat.distribute(A, procs=range(4), dist=(4, 1))
    db = dat.distribute(B, procs=range(4), dist=(4, 1))
    r = dat.map_localparts(jnp.add, da, db)
    assert np.allclose(np.asarray(r), A + B, rtol=1e-6)


def test_map_localparts_uneven_host_path(rng):
    A = rng.standard_normal((50, 8)).astype(np.float32)   # uneven dim-0 cuts
    d = dat.distribute(A, procs=range(4), dist=(4, 1))
    r = dat.map_localparts(lambda lp: np.asarray(lp) + 1.0, d)
    assert np.allclose(np.asarray(r), A + 1, rtol=1e-6)
    assert r.cuts[0] == d.cuts[0]


def test_map_localparts_into(rng):
    A = rng.standard_normal((16, 8)).astype(np.float32)
    d = dat.distribute(A, procs=range(4), dist=(4, 1))
    dest = dat.dzeros((16, 8), procs=range(4), dist=(4, 1))
    dat.map_localparts_into(lambda lp: lp * 3.0, dest, d)
    assert np.allclose(np.asarray(dest), A * 3, rtol=1e-6)


def test_samedist(rng):
    A = rng.standard_normal((40, 24)).astype(np.float32)
    d = dat.distribute(A, procs=range(8), dist=(8, 1))
    like = dat.dzeros((40, 24), procs=range(8), dist=(2, 4))
    r = dat.samedist(d, like)
    assert r.pids.shape == (2, 4)
    assert np.array_equal(np.asarray(r), A)
    with pytest.raises(ValueError):
        dat.samedist(d, dat.dzeros((3, 3)))


def test_mapslices(rng):
    # reference mapslices (mapreduce.jl:191-208)
    A = rng.standard_normal((24, 16)).astype(np.float32)
    d = dat.distribute(A)
    r = dat.mapslices(lambda col: col / jnp.linalg.norm(col), d, dims=0)
    want = A / np.linalg.norm(A, axis=0, keepdims=True)
    assert np.allclose(np.asarray(r), want, rtol=1e-5)


def test_mapslices_untraceable_host_fallback(rng):
    # f using concrete numpy cannot trace; the host path must cover it
    A = rng.standard_normal((24, 16)).astype(np.float32)
    d = dat.distribute(A)

    def untraceable(col):
        c = np.asarray(col)
        return c / np.linalg.norm(c)

    r = dat.mapslices(untraceable, d, dims=0)
    want = A / np.linalg.norm(A, axis=0, keepdims=True)
    assert np.allclose(np.asarray(r), want, rtol=1e-5)


def test_mapslices_shape_change(rng):
    A = rng.standard_normal((24, 16)).astype(np.float32)
    d = dat.distribute(A)
    r = dat.mapslices(lambda col: jnp.sum(col, keepdims=True), d, dims=0)
    want = A.sum(axis=0, keepdims=True)
    assert r.dims == want.shape
    assert np.allclose(np.asarray(r), want, rtol=1e-4)


def test_mapslices_3d_middle_dim(rng):
    # regression: nested-vmap axis bookkeeping — slice along the MIDDLE dim
    # of a non-square 3-D array must act on that dim, not a neighbor
    A = rng.standard_normal((3, 5, 7)).astype(np.float32)
    d = dat.distribute(A)
    r = dat.mapslices(jnp.cumsum, d, dims=1)
    want = np.cumsum(A, axis=1)
    assert r.dims == want.shape
    assert np.allclose(np.asarray(r), want, rtol=1e-5)
    r2 = dat.mapslices(jnp.cumsum, d, dims=2)
    assert np.allclose(np.asarray(r2), np.cumsum(A, axis=2), rtol=1e-5)


def test_ppeval(rng):
    # reference ppeval (mapreduce.jl:210-323): slicewise along the last dim
    A = rng.standard_normal((8, 8, 4)).astype(np.float32)
    B = rng.standard_normal((8, 8, 4)).astype(np.float32)
    da, db = dat.distribute(A), dat.distribute(B)
    r = dat.ppeval(jnp.matmul, da, db)
    want = np.stack([A[:, :, k] @ B[:, :, k] for k in range(4)], axis=-1)
    assert np.allclose(np.asarray(r), want, rtol=1e-4, atol=1e-5)


def test_ppeval_extent_mismatch(rng):
    da = dat.distribute(rng.standard_normal((4, 3)).astype(np.float32))
    db = dat.distribute(rng.standard_normal((4, 5)).astype(np.float32))
    with pytest.raises(ValueError):
        dat.ppeval(jnp.add, da, db)


def test_reduce_on_subdarray(rng):
    A = rng.standard_normal((30, 30)).astype(np.float32)
    d = dat.distribute(A)
    v = d[5:25, 10:20]
    assert np.allclose(float(dat.dsum(v)), A[5:25, 10:20].sum(), rtol=1e-4)


# ---------------------------------------------------------------------------
# arbitrary binary-op reduce (reference mapreduce.jl:17-35 accepts any
# associative op; VERDICT round-1 gap #26)
# ---------------------------------------------------------------------------


def test_dreduce_binary_traced_min(rng):
    import functools
    A = rng.standard_normal((50, 7)).astype(np.float32)
    d = dat.distribute(A)
    op = lambda a, b: jnp.minimum(a, b) * 1
    got = float(dat.dreduce(op, d))
    want = functools.reduce(lambda a, b: min(a, b), A.reshape(-1).tolist())
    assert got == np.float32(want)


def test_dreduce_binary_operator_add_ints():
    import operator
    A = np.arange(1, 101, dtype=np.int32).reshape(10, 10)
    d = dat.distribute(A)
    got = int(dat.dreduce(operator.add, d))
    assert got == A.sum()


def test_dreduce_binary_with_dims(rng):
    A = rng.standard_normal((12, 5)).astype(np.float32)
    d = dat.distribute(A)
    r = dat.dreduce(lambda a, b: jnp.maximum(a, b), d, dims=0)
    want = A.max(axis=0, keepdims=True)
    assert r.dims == want.shape
    np.testing.assert_array_equal(np.asarray(r), want)


def test_dmapreduce_binary_abs2_max(rng):
    A = rng.standard_normal((40,)).astype(np.float32)
    d = dat.distribute(A)
    got = float(dat.dmapreduce(lambda x: x * x, lambda a, b: jnp.maximum(a, b), d))
    assert got == np.float32((A * A).max())


def test_dreduce_binary_untraceable_host_fallback():
    # an op XLA cannot trace (Python float branching) takes the host fold
    import functools
    A = np.arange(1, 21, dtype=np.float32)
    d = dat.distribute(A)
    def op(a, b):
        fa, fb = float(a), float(b)  # forces concretization -> untraceable
        return fa if fa > fb else fb
    got = dat.dreduce(op, d)
    assert float(got) == functools.reduce(op, A.tolist())


def test_dreduce_binary_empty_raises():
    d = dat.dzeros((0,), dtype=np.float32)
    with pytest.raises(ValueError):
        dat.dreduce(lambda a, b: a + b, d)


def test_dreduce_named_ops_still_work(rng):
    # the binary-op detection must not capture jnp-style reducers
    A = rng.standard_normal((20, 4)).astype(np.float32)
    d = dat.distribute(A)
    assert np.allclose(float(dat.dreduce("sum", d)), A.sum(), rtol=1e-4)
    assert np.allclose(float(dat.dreduce(jnp.sum, d)), A.sum(), rtol=1e-4)


def test_dreduce_binary_noncommutative_matches_left_fold():
    # associative but NOT commutative: "first non-nan" — the tree fold must
    # pair adjacent operands (order-preserving), matching a left fold
    import functools
    A = np.array([np.nan, 2.0, 3.0, np.nan, 5.0], dtype=np.float32)
    d = dat.distribute(A)
    op = lambda a, b: jnp.where(jnp.isnan(a), b, a)
    got = float(dat.dreduce(op, d))
    want = functools.reduce(lambda a, b: b if np.isnan(a) else a, A.tolist())
    assert got == np.float32(want) == np.float32(2.0)


def test_dreduce_binary_untraceable_with_dims():
    # scalar-only Python op + dims: host fold applies per kept position
    import functools
    A = np.arange(24, dtype=np.float32).reshape(4, 6)
    d = dat.distribute(A)
    def op(a, b):
        return float(a) if float(a) > float(b) else float(b)
    r = dat.dreduce(op, d, dims=0)
    want = A.max(axis=0, keepdims=True)
    assert r.dims == want.shape
    np.testing.assert_array_equal(np.asarray(r), want)


def test_dreduce_numpy_ufunc_binary():
    # np.ufunc has no inspectable signature; nin==2 must route it binary
    A = np.arange(1, 13, dtype=np.float32).reshape(3, 4)
    d = dat.distribute(A)
    assert float(dat.dreduce(np.maximum, d)) == A.max()
    assert np.isclose(float(dat.dreduce(np.add, d)), A.sum())


# ---------------------------------------------------------------------------
# round-3 (VERDICT item 7): fallbacks warn once, genuine errors propagate
# ---------------------------------------------------------------------------


def test_map_localparts_fallback_warns_once(rng):
    import warnings as W
    from distributedarrays_tpu.ops.mapreduce import map_localparts

    def untraceable_chunk_fn(a):
        return np.asarray(a) * 2        # numpy on a tracer -> trace fails

    d = dat.distribute(rng.standard_normal((32, 8)).astype(np.float32))
    with W.catch_warnings(record=True) as rec:
        W.simplefilter("always")
        r = map_localparts(untraceable_chunk_fn, d)
        r2 = map_localparts(untraceable_chunk_fn, d)
    np.testing.assert_allclose(np.asarray(r), np.asarray(d) * 2)
    np.testing.assert_allclose(np.asarray(r2), np.asarray(d) * 2)
    msgs = [w for w in rec if "shard_map fast path" in str(w.message)]
    assert len(msgs) == 1, [str(w.message) for w in rec]  # once per site
    dat.d_closeall()


def test_map_localparts_genuine_error_propagates(rng):
    from distributedarrays_tpu.ops.mapreduce import map_localparts

    def broken_fn(a):
        raise RuntimeError("kernel bug 0xdead")

    d = dat.distribute(rng.standard_normal((16, 4)).astype(np.float32))
    with pytest.raises(RuntimeError, match="kernel bug 0xdead"):
        map_localparts(broken_fn, d)
    dat.d_closeall()


# ---------------------------------------------------------------------------
# round-3: distributed scans (parallel prefix) — dcumsum / dcumprod
# ---------------------------------------------------------------------------


def test_dcumsum_sharded_axis(rng):
    A = rng.standard_normal((32, 8)).astype(np.float32)
    d = dat.distribute(A, procs=range(8), dist=(4, 2))
    got = dat.dcumsum(d, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.cumsum(A, axis=0),
                               rtol=1e-5, atol=1e-5)
    assert got.cuts == d.cuts
    got1 = dat.dcumsum(d, axis=1)
    np.testing.assert_allclose(np.asarray(got1), np.cumsum(A, axis=1),
                               rtol=1e-5, atol=1e-5)
    dat.d_closeall()


def test_dcumsum_unsharded_axis_and_negative(rng):
    A = rng.standard_normal((16, 6)).astype(np.float32)
    d = dat.distribute(A, procs=range(4), dist=(4, 1))   # dim 1 unsharded
    got = dat.dcumsum(d, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.cumsum(A, axis=1),
                               rtol=1e-5, atol=1e-5)
    dat.d_closeall()


def test_dcumprod_and_int_dtype(rng):
    A = rng.integers(1, 3, (24,)).astype(np.int32)
    d = dat.distribute(A, procs=range(8))
    got = dat.dcumprod(d)
    np.testing.assert_array_equal(np.asarray(got), np.cumprod(A))
    assert got.dtype == jnp.int32
    dat.d_closeall()


def test_dcumsum_uneven_layout_keeps_cuts(rng):
    A = rng.standard_normal((50,)).astype(np.float32)
    d = dat.distribute(A, procs=range(4))     # cuts [0,13,26,38,50]
    got = dat.dcumsum(d)
    np.testing.assert_allclose(np.asarray(got), np.cumsum(A),
                               rtol=1e-4, atol=1e-4)
    assert got.cuts == d.cuts
    dat.d_closeall()


def test_dcumsum_validation(rng):
    d = dat.dzeros((8,), procs=range(4))
    with pytest.raises(ValueError, match="axis"):
        dat.dcumsum(d, axis=2)
    with pytest.raises(TypeError, match="DArray"):
        dat.dcumsum(np.zeros(4))
    dat.d_closeall()


def test_dcummax_dcummin(rng):
    A = rng.standard_normal((32, 8)).astype(np.float32)
    d = dat.distribute(A, procs=range(8), dist=(4, 2))
    np.testing.assert_array_equal(np.asarray(dat.dcummax(d, axis=0)),
                                  np.maximum.accumulate(A, axis=0))
    np.testing.assert_array_equal(np.asarray(dat.dcummin(d, axis=1)),
                                  np.minimum.accumulate(A, axis=1))
    # int dtype neutral (iinfo, not -inf)
    B = rng.integers(-50, 50, (24,)).astype(np.int32)
    db = dat.distribute(B, procs=range(8))
    np.testing.assert_array_equal(np.asarray(dat.dcummax(db)),
                                  np.maximum.accumulate(B))
    # uneven host path
    V = dat.distribute(rng.standard_normal(50).astype(np.float32),
                       procs=range(4))
    np.testing.assert_array_equal(np.asarray(dat.dcummin(V)),
                                  np.minimum.accumulate(np.asarray(V)))
    dat.d_closeall()


def test_dcummax_bool_and_inf_edge_cases(rng):
    # bool dtype on the sharded axis (iinfo would reject bool), and a
    # leading all -inf chunk (finfo.min neutral would corrupt -inf data)
    B = rng.random(24) > 0.5
    db = dat.distribute(B, procs=range(8))
    np.testing.assert_array_equal(np.asarray(dat.dcummax(db)),
                                  np.maximum.accumulate(B))
    A = rng.standard_normal(32).astype(np.float32)
    A[:4] = -np.inf                          # rank 0's whole chunk
    da = dat.distribute(A, procs=range(8))
    np.testing.assert_array_equal(np.asarray(dat.dcummax(da)),
                                  np.maximum.accumulate(A))
    dat.d_closeall()


# ---------------------------------------------------------------------------
# round-4: uneven scans run the padded compiled path (no host gather)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,np_scan", [
    ("dcumsum", np.cumsum), ("dcumprod", np.cumprod),
    ("dcummax", np.maximum.accumulate), ("dcummin", np.minimum.accumulate)])
def test_uneven_scan_all_kinds(kind, np_scan, rng):
    import warnings
    x = (rng.standard_normal(50) * 0.5 + 1.0).astype(np.float32)
    d = dat.distribute(x, procs=range(4))     # cuts [13,13,12,12]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        got = getattr(dat, kind)(d)
    np.testing.assert_allclose(np.asarray(got), np_scan(x),
                               rtol=1e-4, atol=1e-5)
    assert got.cuts == d.cuts


def test_uneven_2d_scan_both_axes(rng):
    A = rng.standard_normal((50, 6)).astype(np.float32)
    d = dat.distribute(A, procs=range(8), dist=(4, 2))  # dim0 uneven
    got0 = dat.dcumsum(d, axis=0)             # scan along the uneven dim
    np.testing.assert_allclose(np.asarray(got0), np.cumsum(A, axis=0),
                               rtol=1e-4, atol=1e-4)
    got1 = dat.dcumsum(d, axis=1)             # uneven elsewhere, even here
    np.testing.assert_allclose(np.asarray(got1), np.cumsum(A, axis=1),
                               rtol=1e-4, atol=1e-4)
    assert got0.cuts == d.cuts and got1.cuts == d.cuts


def test_uneven_scan_zero_sized_chunk(rng):
    # 3 elements over 4 ranks: one chunk is empty -> neutral contribution
    x = rng.standard_normal(3).astype(np.float32)
    d = dat.distribute(x, procs=range(4))
    got = dat.dcumsum(d)
    np.testing.assert_allclose(np.asarray(got), np.cumsum(x), rtol=1e-5)


def test_uneven_scan_bool_cummax(rng):
    x = np.array([0, 0, 1, 0, 0, 0, 1, 0, 0, 0], dtype=bool)
    d = dat.distribute(x, procs=range(4))
    got = dat.dcummax(d)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.maximum.accumulate(x))


def test_scan_jit_wrappers_are_cached(rng):
    # regression: repeated scans must reuse one jit wrapper per
    # (layout, kind, axis) — a lost lru_cache means a recompile per call
    from distributedarrays_tpu.ops import mapreduce as MR
    d = dat.distribute(rng.standard_normal(64).astype(np.float32),
                       procs=range(4))
    h0 = MR._scan_shm_jit.cache_info().hits
    dat.dcumsum(d); dat.dcumsum(d)
    assert MR._scan_shm_jit.cache_info().hits > h0
    du = dat.distribute(rng.standard_normal(50).astype(np.float32),
                        procs=range(4))
    h1 = MR._scan_uneven_shm_jit.cache_info().hits
    dat.dcumsum(du); dat.dcumsum(du)
    assert MR._scan_uneven_shm_jit.cache_info().hits > h1
    dat.d_closeall()


# ---------------------------------------------------------------------------
# the deviation reductions: one shifted pass (ops/mapreduce.py `_moments`)
# ---------------------------------------------------------------------------

_DEV_FNS = {"std": (dat.dstd, np.std), "var": (dat.dvar, np.var)}
# (shape, procs, dist): the layouts of the virtual CPU devices; both dims pass
# the sample's caps (8 and 128), so a shift comes from a part of a slice
_DEV_LAYOUTS = {
    "1x1": ((160, 264), 1, (1, 1)), "4x1": ((160, 264), 4, (4, 1)),
    "1x4": ((160, 264), 4, (1, 4)), "2x2": ((160, 264), 4, (2, 2)),
    "uneven4x2": ((162, 262), 8, (4, 2)),
}


def _f64(fn, A, dims, ddof):
    wide = np.complex128 if np.iscomplexobj(A) else np.float64
    return fn(np.asarray(A).astype(wide), axis=dims, ddof=ddof,
              keepdims=dims is not None)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


@pytest.mark.parametrize("dims", [None, 0, 1, (0, 1)],
                         ids=["whole", "dims0", "dims1", "dims01"])
@pytest.mark.parametrize("ddof", [0, 1])
@pytest.mark.parametrize("layout", list(_DEV_LAYOUTS))
@pytest.mark.parametrize("name", list(_DEV_FNS))
def test_deviation_parity_float32(rng, name, layout, ddof, dims):
    """float32 on every layout against numpy float64: 2e-6 (``jnp.std`` on
    the same inputs reads to 4e-7, the shifted pass to 5e-7)."""
    shape, procs, dist = _DEV_LAYOUTS[layout]
    A = (3.0 + 2.0 * rng.standard_normal(shape)).astype(np.float32)
    d = dat.distribute(A, procs=range(procs), dist=dist)
    fn, ref = _DEV_FNS[name]
    got = fn(d, dims=dims, ddof=ddof)
    want = _f64(ref, A, dims, ddof)
    assert np.shape(got) == np.shape(want)
    assert got.dtype == np.float32
    assert _rel(got, want) < 2e-6


def _dev_input(rng, kind):
    A = 3.0 + 2.0 * rng.standard_normal((160, 264))
    if kind == "bf16":
        A = A.astype(jnp.bfloat16)
    elif kind == "int32":
        A = rng.integers(-50, 50, A.shape).astype(np.int32)
    elif kind == "complex64":
        A = (A + 1j * rng.standard_normal(A.shape)).astype(np.complex64)
    else:
        A = A.astype(np.float32)
    d = dat.distribute(A, procs=range(4), dist=(2, 2))
    return (A[8:150, 16:250], d[8:150, 16:250]) if kind == "view" else (A, d)


# kind -> (tolerance against numpy float64, result dtype).  bfloat16
# accumulates in float32 and rounds the result to bfloat16 (2^-8); integer
# and complex input keep jnp.std / jnp.var
_DEV_KINDS = {
    "view": (2e-6, np.float32), "bf16": (4e-3, jnp.bfloat16),
    "int32": (2e-6, np.float32), "complex64": (2e-6, np.float32),
}


@pytest.mark.parametrize("dims", [None, 1], ids=["whole", "dims1"])
@pytest.mark.parametrize("kind", list(_DEV_KINDS))
@pytest.mark.parametrize("name", list(_DEV_FNS))
def test_deviation_parity_views_and_dtypes(rng, name, kind, dims):
    tol, dtype = _DEV_KINDS[kind]
    A, d = _dev_input(rng, kind)
    assert isinstance(d, dat.SubDArray if kind == "view" else DArray)
    fn, ref = _DEV_FNS[name]
    got = getattr(d, name)(dims=dims)       # the method: ddof 1, as dstd
    assert got.dtype == dtype
    assert _rel(got, _f64(ref, A, dims, 1)) < tol
    assert np.array_equal(np.asarray(fn(d, dims=dims)), np.asarray(got))


@pytest.mark.parametrize("dims", [None, 0], ids=["whole", "dims0"])
@pytest.mark.parametrize("mapper", ["square", "holds_an_array"])
@pytest.mark.parametrize("name", list(_DEV_FNS))
def test_deviation_parity_mapper(rng, name, mapper, dims):
    """``dmapreduce(f, "std", d)``: ddof 0, as ``jnp.std``'s default.  A
    mapper that holds an array of the whole shape cannot take the corner
    alone; its own result is cut."""
    A = (3.0 + 2.0 * rng.standard_normal((160, 264))).astype(np.float32)
    W = rng.standard_normal((160, 264)).astype(np.float32)
    d = dat.distribute(A, procs=range(4), dist=(2, 2))
    if mapper == "square":
        f, mapped = jnp.square, A.astype(np.float64) ** 2
    else:
        Wj = jnp.asarray(W)
        f, mapped = (lambda a: a - Wj), A.astype(np.float64) - W
    got = dat.dmapreduce(f, name, d, dims=dims)
    assert _rel(got, _f64(_DEV_FNS[name][1], mapped, dims, 0)) < 2e-6


def _cell_data(rng, n):
    A, B, C = (rng.random((n, n), dtype=np.float32) for _ in range(3))
    return (np.sin(A) + B * C).astype(np.float32)


def _large_mean(rng, n):
    return (1e4 + rng.standard_normal((n, n))).astype(np.float32)


def _planted_corner(rng, n):
    X = _large_mean(rng, n)
    X[:64, :1024] = 0.0     # zeros where the sample is taken
    return X


# case -> (data, size, limit against float64, limit beyond jnp.std's own gap)
_DEV_NUMERICS = {
    "plain_2048": (lambda rng, n: (3.0 + 2.0 * rng.standard_normal(
        (n, n))).astype(np.float32), 2048, 2e-6, 1e-6),
    "cell_data_4096": (_cell_data, 4096, 3e-7, 3e-7),
    "large_mean_4096": (_large_mean, 4096, 1e-5, 1e-6),
    # the zeros widen the deviation to 1240 about a mean of 9844, whose own
    # float32 rounding both forms carry: 4e-6 to 2.2e-5 by the draw
    "planted_corner_2048": (_planted_corner, 2048, 5e-5, 1e-6),
}


@pytest.mark.parametrize("case", list(_DEV_NUMERICS))
@pytest.mark.parametrize("name", list(_DEV_FNS))
def test_deviation_numerics_the_shift_rests_on(rng, name, case):
    """The stream cell's data; a mean 1e4 deviations from zero (the unshifted
    E[x^2] - mean^2 gives NaN there); and a corner unlike the rest, which
    only the second pass can meet.  Each within its limit of float64 and no
    further from it than the two-pass ``jnp.std`` by more than float32
    rounding.  ``var`` doubles a relative gap of ``std``."""
    make, n, limit, beyond = _DEV_NUMERICS[case]
    twice = 2 if name == "var" else 1
    X = make(rng, n)
    fn, ref = _DEV_FNS[name]
    want = ref(X.astype(np.float64), ddof=1)
    got = _rel(fn(dat.distribute(X)), want)
    two_pass = _rel(getattr(jnp, name)(jnp.asarray(X), ddof=1), want)
    assert got < twice * limit
    assert got < two_pass + twice * beyond


@pytest.mark.parametrize("dims", [None, 0], ids=["whole", "dims0"])
@pytest.mark.parametrize("value", [0.1, 1e4 / 3, -7.25e-3])
@pytest.mark.parametrize("name", list(_DEV_FNS))
def test_deviation_of_a_constant_is_exactly_zero(name, value, dims):
    d = dat.distribute(np.full((300, 136), value, np.float32),
                       procs=range(4), dist=(2, 2))
    got = np.asarray(_DEV_FNS[name][0](d, dims=dims))
    assert np.all(got == 0.0)


def test_deviation_build_counter_says_the_form(rng):
    """Which form a call compiled to, counted when the program is traced
    and never a step."""
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.ops import mapreduce as mr

    def builds(form):
        return tm.counter_value("jit.builds", fn="reduction_moments",
                                form=form)

    mr._reduction_jit.cache_clear()
    A = rng.standard_normal((40, 24)).astype(np.float32)
    before = builds("moments"), builds("numpy")
    d = dat.distribute(A, procs=range(4), dist=(2, 2))
    for _ in range(3):
        dat.dstd(d)
    assert (builds("moments"), builds("numpy")) == (before[0] + 1, before[1])
    dat.dstd(dat.distribute(A.astype(np.int32), procs=range(4), dist=(2, 2)))
    assert (builds("moments"), builds("numpy")) == (before[0] + 1,
                                                    before[1] + 1)
    # a mapper decides the form by what it returns
    dat.dmapreduce(lambda a: a > 0, "var", d)
    assert builds("numpy") == before[1] + 2


@pytest.mark.parametrize("mapper,dims", [(None, None), (None, 0),
                                         (jnp.square, None)],
                         ids=["dsum", "dsum_dims0", "sumsq"])
def test_other_reductions_programs_are_unchanged(mapper, dims):
    """The guard for the cells that call ``dsum`` and ``sumsq``: their
    jaxpr is that of the plain map-then-reduce, letter for letter."""
    import jax
    from distributedarrays_tpu.ops import mapreduce as mr

    def fn(a):
        m = mapper(a) if mapper is not None else a
        if dims is None:
            return jnp.sum(m)
        return jnp.sum(m, axis=(dims,), keepdims=True)

    x = jax.ShapeDtypeStruct((64, 48), jnp.float32)
    axes = None if dims is None else (dims,)
    got = jax.make_jaxpr(mr._reduction_jit(mapper, jnp.sum, axes, ()))(x)
    assert str(got) == str(jax.make_jaxpr(jax.jit(fn))(x))
