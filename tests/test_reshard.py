"""Layout-aware reshard planner: plan correctness, plan caching, chunked
collective lowering, and the incremental-mutation fast paths.

The planner's contract: whatever strategy it picks, the result must be
byte-identical to the ``jax.device_put`` oracle; the chunked collective
path must account only its *moved* bytes (no full-array blowup); and
repeated reshards of one layout pair must hit the plan cache.
"""

import itertools
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import distributedarrays_tpu as dat
from distributedarrays_tpu import layout as L
from distributedarrays_tpu.parallel import reshard as R
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401 (fixture)


# ---------------------------------------------------------------------------
# block algebra (layout.cut_intersections / chunk_span)
# ---------------------------------------------------------------------------


def test_cut_intersections_covers_extent():
    a = [0, 13, 26, 38, 50]
    b = [0, 25, 50]
    overlaps = L.cut_intersections(a, b)
    # the overlaps tile [0, 50) exactly, in order
    assert overlaps[0][2] == 0 and overlaps[-1][3] == 50
    for (prev, nxt) in zip(overlaps, overlaps[1:]):
        assert prev[3] == nxt[2]
    # every overlap lies inside both claimed chunks
    for ai, bi, lo, hi in overlaps:
        assert a[ai] <= lo < hi <= a[ai + 1]
        assert b[bi] <= lo < hi <= b[bi + 1]


def test_cut_intersections_identity_and_mismatch():
    c = [0, 10, 20]
    assert L.cut_intersections(c, c) == [(0, 0, 0, 10), (1, 1, 10, 20)]
    with pytest.raises(ValueError):
        L.cut_intersections([0, 10], [0, 20])


def test_cut_intersections_empty_chunks():
    # empty chunks (equal cut entries) produce no overlap entries
    a = [0, 1, 2, 3, 3, 3, 3, 3, 3]          # trailing empties (sz < nc)
    b = [0, 3]
    overlaps = L.cut_intersections(a, b)
    assert [(o[0], o[2], o[3]) for o in overlaps] == \
        [(0, 0, 1), (1, 1, 2), (2, 2, 3)]


def test_chunk_span():
    cuts = [0, 13, 26, 38, 50]
    assert L.chunk_span(cuts, 12, 27) == (0, 2)
    assert L.chunk_span(cuts, 13, 26) == (1, 1)
    assert L.chunk_span(cuts, 0, 50) == (0, 3)
    assert L.chunk_span(cuts, 7, 7) == (0, -1)   # empty interval


# ---------------------------------------------------------------------------
# planner output ≡ device_put oracle (property sweep over layout pairs)
# ---------------------------------------------------------------------------


def _shardings_for(shape, grid):
    n = int(np.prod(grid))
    return L.sharding_for(list(range(n)), grid, shape)


_GRIDS_2D = [(8, 1), (1, 8), (4, 1), (1, 4), (2, 1), (1, 2), (1, 1),
             (4, 2), (2, 4)]


def test_planner_matches_device_put_oracle_2d(rng):
    # every src/dst grid pair on a divisible 2-D shape: planner result ==
    # the plain device_put oracle, whatever strategy was planned
    shape = (16, 24)
    A = rng.standard_normal(shape).astype(np.float32)
    seen = set()
    for gs, gd in itertools.product(_GRIDS_2D, _GRIDS_2D):
        src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
        x = jax.device_put(A, src)
        plan = R.plan_reshard(x, dst)
        seen.add(plan.strategy)
        y = R.reshard(x, dst)
        assert y.sharding == dst or plan.strategy == "noop", (gs, gd)
        oracle = jax.device_put(A, dst)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle)), \
            (gs, gd, plan.strategy)
    # the sweep must have exercised the planned collective lowerings,
    # not just fallbacks
    assert "all_to_all" in seen
    assert {"noop", "device_put"} <= seen


def test_planner_matches_oracle_random_uneven_cuts(rng):
    # random (often uneven / ragged) 1-D layout pairs via distribute +
    # samedist: uneven pairs take the fallback, even pairs the
    # collective — both must equal the host oracle
    for n, ps, pd in [(50, 4, 2), (64, 8, 4), (37, 4, 8), (48, 8, 8),
                      (29, 2, 4), (96, 8, 2)]:
        A = rng.standard_normal(n).astype(np.float32)
        d = dat.distribute(A, procs=list(range(ps)), dist=[ps])
        like = dat.dzeros((n,), procs=list(range(pd)), dist=[pd])
        r = dat.samedist(d, like)
        np.testing.assert_array_equal(np.asarray(r), A)
        assert [int(c) for c in r.cuts[0]] == [int(c) for c in like.cuts[0]]
        dat.d_closeall()


def test_planner_matches_oracle_skinny_vector_layouts(rng):
    # the solver loops re-seat skinny operands between operator
    # partitions every recovery attempt: (n, 1) column vectors and
    # single-row-block layouts (one grid row per rank, the degenerate
    # chunking a StencilOperator on p == nx ranks produces).  Every such
    # planner pair must equal the plain device_put oracle.
    row_grids = [(8, 1), (4, 1), (2, 1), (1, 1)]
    for shape in [(64, 1), (8, 1), (8, 8)]:    # (8, *): 1-row blocks on p=8
        A = rng.standard_normal(shape).astype(np.float32)
        for gs, gd in itertools.product(row_grids, row_grids):
            src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
            x = jax.device_put(A, src)
            y = R.reshard(x, dst)
            assert y.sharding == dst or gs == gd, (shape, gs, gd)
            oracle = jax.device_put(A, dst)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle),
                                          err_msg=f"{shape} {gs}->{gd}")


def test_samedist_oracle_vector_and_single_row_blocks(rng):
    # the DArray-level leg of the same sweep: (n, 1) vectors moved with
    # samedist across rank counts, including single-row blocks (p == n)
    for n, ps, pd in [(8, 8, 2), (8, 2, 8), (64, 8, 8), (64, 8, 4)]:
        A = rng.standard_normal((n, 1)).astype(np.float32)
        d = dat.distribute(A, procs=list(range(ps)), dist=[ps, 1])
        like = dat.dzeros((n, 1), procs=list(range(pd)), dist=[pd, 1])
        r = dat.samedist(d, like)
        np.testing.assert_array_equal(np.asarray(r), A)
        assert [int(c) for c in r.cuts[0]] == [int(c) for c in like.cuts[0]]
        dat.d_closeall()


def test_planner_replicated_and_gather_strategies(telemetry_capture, rng):
    tm = telemetry_capture
    shape = (32, 16)
    A = rng.standard_normal(shape).astype(np.float32)
    sharded = _shardings_for(shape, (8, 1))
    rep = NamedSharding(sharded.mesh, P())
    x = jax.device_put(A, sharded)
    plan = R.plan_reshard(x, rep)
    assert plan.strategy == "all_gather"
    # assert the dispatch that RAN, not values only: the compiled gather
    # must not give way to device_put (it did, silently, while jax 0.9's
    # replication check refused its out-spec)
    fb0 = tm.counter_value("reshard.collective_fallbacks", reason="runtime")
    b0 = tm.comm_bytes("reshard")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        z = R.reshard(x, rep)
    np.testing.assert_array_equal(np.asarray(z), A)
    assert tm.counter_value("reshard.collective_fallbacks",
                            reason="runtime") == fb0
    assert tm.comm_bytes("reshard") - b0 == plan.moved_bytes
    assert any(e.get("strategy") == "all_gather"
               and e.get("dispatch") == "xla"
               for e in tm.events("comm") if e.get("name") == "reshard"), \
        tm.events("comm")
    # replicated -> sharded is comm-free local slicing
    plan2 = R.plan_reshard(z, sharded)
    assert plan2.strategy == "local_slice" and plan2.moved_bytes == 0
    w = R.reshard(z, sharded)
    assert w.sharding == sharded
    np.testing.assert_array_equal(np.asarray(w), A)


def test_chunked_lowering_matches_oracle(rng, monkeypatch):
    # force tiny staging chunks so the pre-slice all_to_all chunking and
    # the chunked all_gather actually run, then check exactness
    monkeypatch.setenv("DA_TPU_RESHARD_CHUNK_MB", "0.0005")
    shape = (64, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, (8, 1)), _shardings_for(shape, (1, 8))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "all_to_all" and plan.nchunks > 1
    y = R.reshard(x, dst, plan=plan)
    np.testing.assert_array_equal(np.asarray(y), A)
    rep = NamedSharding(src.mesh, P())
    plang = R.plan_reshard(x, rep)
    assert plang.strategy == "all_gather" and plang.nchunks > 1
    z = R.reshard(x, rep, plan=plang)
    np.testing.assert_array_equal(np.asarray(z), A)


# ---------------------------------------------------------------------------
# plan cache + telemetry
# ---------------------------------------------------------------------------


def test_plan_cache_hits_via_telemetry(telemetry_capture, rng):
    tm = telemetry_capture
    shape = (16, 8)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, (8, 1)), _shardings_for(shape, (1, 8))
    x = jax.device_put(A, src)
    R.plan_reshard(x, dst)                    # may build or already cached
    req0 = tm.counter_value("reshard.plan_requests")
    build0 = tm.counter_value("reshard.plan_builds")
    for _ in range(5):
        R.plan_reshard(x, dst)
    assert tm.assert_counter("reshard.plan_requests", req0 + 5) == req0 + 5
    # repeated same-layout-pair planning hits the lru — zero new builds
    assert tm.counter_value("reshard.plan_builds") - build0 == 0


def test_reshard_comm_bytes_bounded_by_plan(telemetry_capture, rng):
    # peak-memory guard: the chunked path accounts exactly the plan's
    # moved bytes — never the full logical array
    tm = telemetry_capture
    shape = (64, 64)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, (8, 1)), _shardings_for(shape, (1, 8))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "all_to_all"
    b0 = tm.comm_bytes("reshard")
    y = R.reshard(x, dst, plan=plan)
    y.block_until_ready()
    delta = tm.comm_bytes("reshard") - b0
    assert delta == plan.moved_bytes
    assert delta < plan.total_bytes           # no full-array blowup
    assert plan.moved_bytes == plan.total_bytes * 7 // 8
    # the strategy is attributed on the span and the plan event
    spans = tm.spans("reshard")
    assert any(s.get("labels", {}).get("strategy") == "all_to_all"
               for s in spans)


def test_plan_event_journaled(telemetry_capture, rng):
    tm = telemetry_capture
    shape = (8, 32)
    A = rng.standard_normal(shape).astype(np.float32)
    x = jax.device_put(A, _shardings_for(shape, (1, 8)))
    R.plan_reshard(x, _shardings_for(shape, (8, 1)))
    evs = tm.events("reshard")
    assert any(e.get("name") == "plan" and "strategy" in e for e in evs)


# ---------------------------------------------------------------------------
# rewired call sites
# ---------------------------------------------------------------------------


def test_rebind_routes_through_planner(telemetry_capture, rng):
    tm = telemetry_capture
    A = rng.standard_normal((16, 8)).astype(np.float32)
    src = dat.distribute(A, dist=(8, 1))
    dest = dat.dzeros((16, 8), dist=(1, 8))
    b0 = tm.comm_bytes("reshard")
    dat.copyto_(dest, src)                     # dest._rebind(src.garray)
    np.testing.assert_array_equal(np.asarray(dest), A)
    # moved-bytes accounting: (p-1)/p of the array, not all of it
    assert tm.comm_bytes("reshard") - b0 == 16 * 8 * 4 * 7 // 8
    dat.d_closeall()


def test_samedist_aligned_fast_path_no_copy(telemetry_capture, rng):
    tm = telemetry_capture
    a = dat.distribute(rng.standard_normal((16, 8)).astype(np.float32))
    b = dat.dzeros((16, 8), dtype=np.float32)
    b0 = tm.comm_bytes("reshard")
    c = dat.samedist(a, b)
    # no reshard bytes AND no buffer copy — c co-owns a's buffer
    assert tm.comm_bytes("reshard") - b0 == 0
    assert c.garray is a.garray
    # shared-ownership: closing either side must not invalidate the other
    c.close()
    assert not a.garray.is_deleted()
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(a))          # still readable
    a.close()


def test_samedist_share_released_on_rebind(rng):
    # a holder that REBINDS (fill_/mutation) leaves the share group, so
    # the remaining holder's close() must eagerly delete the old buffer
    # (regression: the token used to keep counting the departed holder
    # and pinned the buffer past every close)
    a = dat.distribute(np.ones((16, 8), np.float32))
    b = dat.dzeros((16, 8), dtype=np.float32)
    c = dat.samedist(a, b)
    shared_buf = c.garray
    a.fill_(0.0)                               # a rebinds, leaves group
    c.close()                                  # sole holder: eager delete
    assert shared_buf.is_deleted()
    np.testing.assert_allclose(np.asarray(a), 0.0)   # a unaffected
    a.close()


def test_samedist_shared_buffer_close_order_reversed(rng):
    a = dat.distribute(rng.standard_normal((8, 8)).astype(np.float32))
    ref = np.asarray(a).copy()
    b = dat.dzeros((8, 8), dtype=np.float32)
    c = dat.samedist(a, b)
    a.close()                                  # original goes first
    np.testing.assert_array_equal(np.asarray(c), ref)
    dat.d_closeall()


def test_broadcast_align_routes_through_planner(rng):
    # mismatched committed layouts in one elementwise op: the aligned arg
    # goes through _put_global -> parallel.reshard; result is correct
    A = rng.standard_normal((16, 8)).astype(np.float32)
    B = rng.standard_normal((16, 8)).astype(np.float32)
    da = dat.distribute(A, dist=(8, 1))
    db = dat.distribute(B, dist=(1, 8))
    r = da + db
    np.testing.assert_allclose(np.asarray(r), A + B, rtol=1e-6)
    dat.d_closeall()


# ---------------------------------------------------------------------------
# incremental mutation of padded (uneven) layouts
# ---------------------------------------------------------------------------


def test_incremental_slice_mutate_touches_owner_blocks_only(
        telemetry_capture, rng):
    tm = telemetry_capture
    A = rng.standard_normal(50).astype(np.float32)
    d = dat.distribute(A.copy(), procs=[0, 1, 2, 3], dist=[4])
    b0 = tm.comm_bytes("reshard")
    d[10:30] = 99.0
    want = A.copy()
    want[10:30] = 99.0
    np.testing.assert_array_equal(np.asarray(d), want)
    delta = tm.comm_bytes("reshard") - b0
    # only the touched window is accounted — sub-full-array traffic
    assert 0 < delta <= 20 * 4
    assert delta < 50 * 4
    # the update never depadded: no blocked_pad reshard events recorded
    evs = [e for e in tm.events("comm")
           if e.get("name") == "reshard" and e.get("op") == "blocked_pad"]
    assert not evs
    d.close()


def test_incremental_mutate_2d_multiblock(rng):
    B = rng.standard_normal((50, 30)).astype(np.float32)
    e = dat.distribute(B.copy(), dist=[4, 2])
    want = B.copy()
    e[7, 3:25] = 5.0
    want[7, 3:25] = 5.0
    e[4:40, 2] = np.arange(36, dtype=np.float32)
    want[4:40, 2] = np.arange(36)
    e[12:14, 14:16] = np.array([[1., 2.], [3., 4.]], np.float32)
    want[12:14, 14:16] = [[1, 2], [3, 4]]
    np.testing.assert_array_equal(np.asarray(e), want)
    # pad regions stay zero after incremental writes
    padded = np.asarray(jax.device_get(e.garray_padded))
    cuts_r, cuts_c = e.cuts
    bs = L.block_sizes(e.cuts)
    for bi in range(len(cuts_r) - 1):
        valid = cuts_r[bi + 1] - cuts_r[bi]
        np.testing.assert_allclose(
            padded[bi * bs[0] + valid:(bi + 1) * bs[0], :], 0.0)
    e.close()


def test_incremental_mutate_scalar_setitem_padded(rng):
    A = rng.standard_normal(50).astype(np.float32)
    d = dat.distribute(A.copy(), dist=[4])
    with dat.allowscalar(True):
        d[13] = 7.0
    want = A.copy()
    want[13] = 7.0
    np.testing.assert_array_equal(np.asarray(d), want)
    d.close()


def test_subdarray_copyto_incremental(rng):
    A = rng.standard_normal(50).astype(np.float32)
    d = dat.distribute(A.copy(), dist=[4])
    dat.copyto_(d[20:40], np.ones(20, np.float32))
    want = A.copy()
    want[20:40] = 1.0
    np.testing.assert_array_equal(np.asarray(d), want)
    d.close()


def test_advanced_indexing_still_full_path(rng):
    # array keys are not basic: must fall back to the full-array path and
    # stay correct
    A = rng.standard_normal(50).astype(np.float32)
    d = dat.distribute(A.copy(), dist=[4])
    idx = np.array([3, 17, 44])
    d[idx] = 0.5
    want = A.copy()
    want[idx] = 0.5
    np.testing.assert_array_equal(np.asarray(d), want)
    d.close()


def test_padded_fill_zero_redistribution(telemetry_capture, rng):
    tm = telemetry_capture
    d = dat.distribute(rng.standard_normal(50).astype(np.float32), dist=[4])
    b0 = tm.comm_bytes("reshard")
    d.fill_(5.0)
    assert tm.comm_bytes("reshard") - b0 == 0    # no depad/repad round trip
    np.testing.assert_allclose(np.asarray(d), 5.0)
    padded = np.asarray(jax.device_get(d.garray_padded))
    np.testing.assert_allclose(padded[51:52], 0.0)   # pad stays zero
    b1 = tm.comm_bytes("reshard")
    d.rand_()
    assert tm.comm_bytes("reshard") - b1 == 0
    v = np.asarray(d)
    assert v.shape == (50,) and len(np.unique(v)) > 10
    padded = np.asarray(jax.device_get(d.garray_padded))
    np.testing.assert_allclose(padded[51:52], 0.0)
    d.close()


def test_padded_fill_2d_matches_logical(rng):
    d = dat.distribute(rng.standard_normal((50, 30)).astype(np.float32),
                       dist=[4, 2])
    d.fill_(2.5)
    np.testing.assert_allclose(np.asarray(d), 2.5)
    assert float(dat.dsum(d)) == pytest.approx(50 * 30 * 2.5, rel=1e-5)
    d.close()


# ---------------------------------------------------------------------------
# device-side __eq__
# ---------------------------------------------------------------------------


def test_eq_darray_device_side_no_gather(telemetry_capture, rng):
    tm = telemetry_capture
    A = rng.standard_normal((16, 8)).astype(np.float32)
    a = dat.distribute(A)
    b = dat.distribute(A.copy())
    c = dat.distribute(A + 1.0)
    d2h0 = tm.comm_bytes("d2h")
    assert a == b
    assert not (a == c)
    assert a != c
    # the compare ran on device: no gather-sized d2h traffic
    assert tm.comm_bytes("d2h") - d2h0 == 0
    # numpy operand still works (host path)
    assert a == A
    sub = a[0:16, 0:8]
    assert sub == b
    dat.d_closeall()


def test_eq_shape_mismatch_and_foreign_types(rng):
    a = dat.distribute(rng.standard_normal((4, 4)).astype(np.float32))
    b = dat.distribute(rng.standard_normal((2, 8)).astype(np.float32))
    assert not (a == b)
    assert a != b
    # foreign type: __eq__ returns NotImplemented, Python resolves to False
    assert (a == "nope") is False
    dat.d_closeall()


# ---------------------------------------------------------------------------
# DAL007
# ---------------------------------------------------------------------------


def test_dal007_flags_cross_sharding_device_put():
    from distributedarrays_tpu.analysis import lint_source
    bad = (
        "import jax\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "def f(x, mesh):\n"
        "    return jax.device_put(x, NamedSharding(mesh, P('d0')))\n"
    )
    findings = [f for f in lint_source(bad, "pkg/ops/thing.py")
                if f.code == "DAL007"]
    assert len(findings) == 1


def test_dal007_silent_in_reshard_home_and_on_devices():
    from distributedarrays_tpu.analysis import lint_source
    src = (
        "import jax\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "def f(x, mesh):\n"
        "    return jax.device_put(x, NamedSharding(mesh, P('d0')))\n"
    )
    assert not [f for f in lint_source(
        src, "distributedarrays_tpu/parallel/reshard.py")
        if f.code == "DAL007"]
    dev = (
        "import jax\n"
        "def f(x):\n"
        "    device = jax.devices()[0]\n"
        "    return jax.device_put(x, device)\n"
    )
    assert not [f for f in lint_source(dev, "pkg/m.py")
                if f.code == "DAL007"]


def test_dal007_suppressible():
    from distributedarrays_tpu.analysis import lint_source
    src = (
        "import jax\n"
        "def f(x, sharding):\n"
        "    return jax.device_put(x, sharding)  "
        "# dalint: disable=DAL007 — justified\n"
    )
    fs = [f for f in lint_source(src, "pkg/m.py") if f.code == "DAL007"]
    assert len(fs) == 1 and fs[0].suppressed


# ---------------------------------------------------------------------------
# multi-axis chain lowering (PR 19: general per-axis collective sequences)
# ---------------------------------------------------------------------------


def _needed_bytes(shape, itemsize, src, dst):
    """What the two layouts need moved, from the block algebra alone."""
    s_cuts, s_own = R.layout_of_sharding(src, shape)
    d_cuts, d_own = R.layout_of_sharding(dst, shape)
    return R._moved_elems(shape, s_cuts, s_own, d_cuts, d_own) * itemsize


def _assert_shards_equal_device_put(y, A, dst):
    want = {s.device.id: np.asarray(s.data)
            for s in jax.device_put(A, dst).addressable_shards}
    got = {s.device.id: np.asarray(s.data) for s in y.addressable_shards}
    assert got.keys() == want.keys()
    for dev, block in got.items():
        np.testing.assert_array_equal(block, want[dev])


def _last_reshard_labels(tm):
    return tm.spans("reshard")[-1]["labels"]


_GRID_SETS = [[(4, 1), (1, 4), (2, 2)], [(8, 1), (1, 8), (4, 2), (2, 4)]]
_GRID_PAIRS = [pair for grids in _GRID_SETS
               for pair in itertools.permutations(grids, 2)]


@pytest.mark.parametrize("gs,gd", _GRID_PAIRS,
                         ids=[f"{a}->{b}".replace(" ", "")
                              for a, b in _GRID_PAIRS])
def test_block_layout_pair_moves_what_it_needs(telemetry_capture, rng,
                                               gs, gd):
    # every repartition between upstream's block layouts on 4 and on 8
    # ranks: the plan moves exactly what the two layouts need (no digit
    # is gathered only to be sliced away), every shard is bit-equal to
    # device_put's, and the span says which dispatch ran
    tm = telemetry_capture
    shape = (48, 64)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.collective, (plan.strategy, plan.reason)
    assert plan.moved_bytes == _needed_bytes(shape, 4, src, dst)
    gathered = {s[1] for s in plan.steps if s[0] == "gather"}
    sliced = {s[1] for s in plan.steps if s[0] == "slice"}
    assert not gathered & sliced, plan.steps
    fb0 = tm.counter_value("reshard.collective_fallbacks", reason="runtime")
    b0 = tm.comm_bytes("reshard")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        y = R.reshard(x, dst)
    assert y.sharding.is_equivalent_to(dst, y.ndim)
    _assert_shards_equal_device_put(y, A, dst)
    assert tm.counter_value("reshard.collective_fallbacks",
                            reason="runtime") == fb0
    assert tm.comm_bytes("reshard") - b0 == plan.moved_bytes
    labels = _last_reshard_labels(tm)
    assert labels["strategy"] == plan.strategy
    assert labels["dispatch"] == "xla"      # no ring kernel armed on a CPU


def test_chain_matches_oracle_multiaxis_pairs(telemetry_capture, rng):
    # same-device-set multi-axis repartitions lower to the collective
    # chain (NOT device_put) and stay bit-identical to the oracle; the
    # counter says which kinds of step ran
    tm = telemetry_capture
    shape = (48, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    want = {((8, 1), (4, 2)): ["a2a"], ((4, 2), (8, 1)): ["a2a"],
            ((4, 2), (2, 4)): ["exchange"], ((2, 4), (4, 2)): ["exchange"],
            ((1, 8), (4, 2)): ["exchange"], ((2, 2), (4, 1)): ["a2a"]}
    for (gs, gd), kinds in want.items():
        src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
        x = jax.device_put(A, src)
        plan = R.plan_reshard(x, dst)
        assert plan.strategy == "chain", (gs, gd, plan.strategy,
                                          plan.reason)
        assert [s[0] for s in plan.steps] == kinds, (gs, gd)
        ran0 = tm.counter_value("reshard.chain_steps", kind=kinds[0])
        y = R.reshard(x, dst)
        assert tm.counter_value("reshard.chain_steps",
                                kind=kinds[0]) == ran0 + 1
        assert y.sharding.is_equivalent_to(dst, y.ndim), (gs, gd)
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(jax.device_put(A, dst)))


def test_chain_two_axis_repartition_halves_moved_bytes(rng):
    # the acceptance shape: a (p,1) -> (p/2,2) repartition is ONE
    # axis-wise all_to_all moving exactly half the array
    shape = (64, 64)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, (8, 1)), _shardings_for(shape, (4, 2))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "chain"
    assert [s[0] for s in plan.steps] == ["a2a"]
    assert plan.moved_bytes * 2 == plan.total_bytes
    np.testing.assert_array_equal(
        np.asarray(R.reshard(x, dst)),
        np.asarray(jax.device_put(A, dst)))


def test_chain_mesh_axis_transpose(telemetry_capture, rng):
    # P(d0,d1) -> P(d1,d0) on one (4,2) mesh: neither digit is the minor
    # one of a dim it could leave, so the whole move is one exchange of
    # eighths of a block (it was gather + a2a + slice, 2.5x the array)
    tm = telemetry_capture
    shape = (48, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    mesh = L.mesh_for(list(range(8)), (4, 2))
    src = NamedSharding(mesh, P("d0", "d1"))
    dst = NamedSharding(mesh, P("d1", "d0"))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "chain", plan.reason
    assert [s[0] for s in plan.steps] == ["exchange"]
    assert plan.moved_bytes == _needed_bytes(shape, 4, src, dst)
    assert plan.moved_bytes * 4 == plan.total_bytes * 3
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        y = R.reshard(x, dst)
    assert y.sharding.is_equivalent_to(dst, y.ndim)
    _assert_shards_equal_device_put(y, A, dst)
    assert _last_reshard_labels(tm)["dispatch"] == "xla"


@pytest.mark.parametrize("mode", ["interpret", "0"])
def test_exchange_is_ppermutes_whatever_is_armed(telemetry_capture, rng,
                                                 monkeypatch, mode):
    # armed ring kernels (interpret) or none: an exchange-only chain runs
    # the same ppermutes, dispatches no ring kernel and says "xla"; a
    # chain with an a2a step says what its a2a rode
    tm = telemetry_capture
    monkeypatch.setenv("DA_TPU_RDMA", mode)
    shape = (32, 64)
    A = rng.standard_normal(shape).astype(np.float32)

    def ring_dispatches():
        return sum(tm.counter_value("pallas_collectives.dispatch", path=p)
                   for p in ("rdma", "interpret", "compiled", "lax",
                             "fallback"))

    src, dst = _shardings_for(shape, (1, 4)), _shardings_for(shape, (2, 2))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert [s[0] for s in plan.steps] == ["exchange"]
    d0 = ring_dispatches()
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        y = R.reshard(x, dst)
    _assert_shards_equal_device_put(y, A, dst)
    assert ring_dispatches() == d0
    assert _last_reshard_labels(tm)["dispatch"] == "xla"
    text = R._chain_jit(L.mesh_for(list(plan.ranks), plan.mesh_shape), 2,
                        plan.src_comp, plan.dst_comp, plan.steps,
                        None).lower(x).as_text()
    assert "collective_permute" in text
    assert "all_gather" not in text and "all_to_all" not in text
    # the leg after it in the benchmark's cycle is one a2a
    back = _shardings_for(shape, (4, 1))
    assert [s[0] for s in R.plan_reshard(y, back).steps] == ["a2a"]
    z = R.reshard(y, back)
    _assert_shards_equal_device_put(z, A, back)
    assert _last_reshard_labels(tm)["dispatch"] == \
        ("rdma" if mode == "interpret" else "xla")


def test_chain_matches_oracle_3d_mesh(rng):
    # a 3-D (2,2,2) mesh flattening onto a 2-D grid
    shape = (8, 8, 8)
    A = rng.standard_normal(shape).astype(np.float32)
    mesh = L.mesh_for(list(range(8)), (2, 2, 2))
    src = NamedSharding(mesh, P("d0", "d1", "d2"))
    dst = _shardings_for(shape, (2, 4, 1))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "chain", plan.reason
    np.testing.assert_array_equal(
        np.asarray(R.reshard(x, dst)),
        np.asarray(jax.device_put(A, dst)))


def test_chain_partial_replication_is_comm_free(rng):
    # P(None,d1) -> P(d0,d1): every rank already holds its block — the
    # chain is all local slices and the plan predicts zero moved bytes
    shape = (48, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    mesh = L.mesh_for(list(range(8)), (4, 2))
    src = NamedSharding(mesh, P(None, "d1"))
    dst = NamedSharding(mesh, P("d0", "d1"))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "chain", plan.reason
    assert all(s[0] == "slice" for s in plan.steps)
    assert plan.moved_bytes == 0
    np.testing.assert_array_equal(
        np.asarray(R.reshard(x, dst)),
        np.asarray(jax.device_put(A, dst)))


def test_chain_staging_bounded_under_tiny_chunk_target(
        rng, monkeypatch, telemetry_capture):
    # forced ~512 B chunk target: every chain step is chunked and the
    # OBSERVED staging watermark stays within 2x the budget
    tm = telemetry_capture
    monkeypatch.setenv("DA_TPU_RESHARD_CHUNK_MB", "0.0005")
    from distributedarrays_tpu.telemetry import memory as tmem
    shape = (64, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    mesh = L.mesh_for(list(range(8)), (4, 2))
    target = 2 * int(0.0005 * 2**20)
    for src, dst in [
            (_shardings_for(shape, (8, 1)), _shardings_for(shape, (4, 2))),
            (NamedSharding(mesh, P("d0", "d1")),
             NamedSharding(mesh, P("d1", "d0")))]:
        x = jax.device_put(A, src)
        plan = R.plan_reshard(x, dst)
        assert plan.strategy == "chain"
        assert plan.nchunks > 1
        assert plan.staging_bytes <= target, plan.steps
        y = R.reshard(x, dst)
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(jax.device_put(A, dst)))
    assert 0 < tmem.staging_peak("reshard.chain") <= target


def test_gather_put_on_replicated_subset(rng):
    # a shrink onto a strict device subset whose target is replicated
    # (the uneven-survivor elastic shape): chain-gather on the source
    # mesh, then a comm-free restriction
    shape = (48, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    src = _shardings_for(shape, (8, 1))
    dst = NamedSharding(L.mesh_for(list(range(6)), (6, 1)), P(None, None))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "gather_put", plan.reason
    # a gather that nothing slices back is no detour: it stays a gather
    assert {s[0] for s in plan.steps} == {"gather"}
    y = R.reshard(x, dst)
    assert {d.id for d in y.sharding.device_set} == set(range(6))
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(jax.device_put(A, dst)))


def test_replicated_destination_still_gathers(rng):
    # a multi-axis grid onto a fully replicated layout: every digit
    # leaves for good, so the plan is gathers and the whole array
    # arrives on every rank but the one block it had
    shape = (48, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    src = _shardings_for(shape, (4, 2))
    dst = NamedSharding(src.mesh, P(None, None))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == "chain", plan.reason
    assert {s[0] for s in plan.steps} == {"gather"}
    assert plan.moved_bytes == _needed_bytes(shape, 4, src, dst)
    np.testing.assert_array_equal(np.asarray(R.reshard(x, dst)), A)


def test_exchange_plan_at_8gib_stages_one_chunk():
    # the shape ISSUE 23 asked of the benchmark's cycle (32768 x 65536
    # f32 over 2x2): leg 2 is one exchange of 0.75 of the array whose
    # transient is a chunk of a piece, not a doubled block
    shape = (32768, 65536)
    src, dst = _shardings_for(shape, (1, 4)), _shardings_for(shape, (2, 2))
    plan = R.plan_reshard(shape, dst, src_sharding=src, itemsize=4)
    assert [s[0] for s in plan.steps] == ["exchange"]
    assert plan.moved_bytes * 4 == plan.total_bytes * 3
    assert 0 < plan.staging_bytes <= R._chunk_target_bytes()
    kind, axis, rounds, before, after, chunk_axis, nchunks, moved = \
        plan.steps[0]
    assert (axis, rounds) == (-1, 2)
    assert (before, after) == (plan.src_comp, plan.dst_comp)
    assert nchunks == plan.nchunks > 1 and moved == plan.moved_bytes


def test_chain_plan_stamps_domain_byte_split(rng, monkeypatch):
    # with two failure domains split mid-mesh, the a2a along the major
    # axis crosses domains and the plan's intra/cross stamps say so
    from distributedarrays_tpu.resilience import domains
    monkeypatch.setenv("DA_TPU_DOMAINS", "4,4")
    domains.reset()
    try:
        shape = (64, 64)
        A = rng.standard_normal(shape).astype(np.float32)
        src = _shardings_for(shape, (8, 1))
        dst = _shardings_for(shape, (4, 2))
        x = jax.device_put(A, src)
        plan = R.plan_reshard(x, dst)
        assert plan.strategy == "chain"
        # the single a2a runs along the minor (intra-domain) digit: the
        # sub-groups {0,1},{2,3},... never span the 4|4 domain boundary
        assert plan.cross_bytes == 0
        assert plan.intra_bytes == plan.moved_bytes > 0
        # transpose on the (4,2) mesh is one exchange, split piece by
        # piece: some pieces change domain, some stay inside one
        mesh = L.mesh_for(list(range(8)), (4, 2))
        tsrc = NamedSharding(mesh, P("d0", "d1"))
        tdst = NamedSharding(mesh, P("d1", "d0"))
        xt = jax.device_put(A, tsrc)
        tplan = R.plan_reshard(xt, tdst)
        assert tplan.strategy == "chain"
        assert [s[0] for s in tplan.steps] == ["exchange"]
        assert 0 < tplan.cross_bytes < tplan.moved_bytes
        assert tplan.intra_bytes + tplan.cross_bytes == tplan.moved_bytes
        np.testing.assert_array_equal(
            np.asarray(R.reshard(xt, tdst)),
            np.asarray(jax.device_put(A, tdst)))
    finally:
        domains.reset()


def test_collective_fallback_counter_reason_labels(telemetry_capture, rng):
    tm = telemetry_capture
    shape = (48, 48)
    A = rng.standard_normal(shape).astype(np.float32)
    # device sets differ with a properly-sharded destination: counted
    # under reason=device_set
    src = _shardings_for(shape, (8, 1))
    dst = _shardings_for(shape, (4, 1))
    x = jax.device_put(A, src)
    c0 = tm.counter_value("reshard.collective_fallbacks",
                          reason="device_set")
    R.reshard(x, dst)
    assert tm.counter_value("reshard.collective_fallbacks",
                            reason="device_set") == c0 + 1
    # extended dtypes (PRNG keys) force device_put under reason=dtype
    keys = jax.random.split(jax.random.key(0), 48)
    ks = jax.device_put(keys, _shardings_for((48,), (8,)))
    kdst = NamedSharding(L.mesh_for(list(range(8)), (8,)), P(None))
    d0 = tm.counter_value("reshard.collective_fallbacks", reason="dtype")
    R.reshard(ks, kdst)
    assert tm.counter_value("reshard.collective_fallbacks",
                            reason="dtype") == d0 + 1


# --- uneven multi-axis cuts at the planner level (uneven NamedShardings
# are not constructible under this jax, so the ceil-pad lowering is
# exercised against synthetic owner maps) ---


class _FakeDev:
    def __init__(self, i):
        self.id = i


class _FakeSharding:
    """Minimal devices_indices_map carrier: one rank per block, blocks in
    row-major grid order over explicit per-dim cut vectors."""

    def __init__(self, cuts_per_dim, ranks):
        self.cuts = cuts_per_dim
        self.ranks = ranks

    def devices_indices_map(self, shape):
        grids = [len(c) - 1 for c in self.cuts]
        out = {}
        for r, coord in zip(self.ranks,
                            itertools.product(*[range(g) for g in grids])):
            out[_FakeDev(r)] = tuple(
                slice(self.cuts[d][coord[d]], self.cuts[d][coord[d] + 1])
                for d in range(len(grids)))
        return out


def _ceil_cuts(n, g):
    c = -(-n // g)
    return [min(k * c, n) for k in range(g + 1)]


def test_pad_chain_plans_for_agreeing_ceil_cuts():
    # n=14 over 8 then 4 chunks: both ceil layouts pad to 16 -> the
    # planner lowers through the padded even chain
    tgt = R._chunk_target_bytes()
    p = R._build_plan(
        (14, 8), 4,
        _FakeSharding([_ceil_cuts(14, 8), [0, 8]], list(range(8))),
        _FakeSharding([_ceil_cuts(14, 4), [0, 4, 8]], list(range(8))),
        tgt)
    assert p.strategy == "chain"
    assert p.pad_shape == (16, 8)
    assert [s[0] for s in p.steps] == ["a2a"]


def test_pad_chain_exchanges_on_the_even_analog():
    # (1,4)->(2,2) with 7 rows: the destination's ceil cuts pad to 8, and
    # the exchange runs on the (8,8) analog between pad and slice-back
    p = R._build_plan(
        (7, 8), 4,
        _FakeSharding([[0, 7], [0, 2, 4, 6, 8]], list(range(4))),
        _FakeSharding([_ceil_cuts(7, 2), [0, 4, 8]], list(range(4))),
        R._chunk_target_bytes())
    assert p.strategy == "chain"
    assert p.pad_shape == (8, 8)
    assert [s[0] for s in p.steps] == ["exchange"]
    assert p.moved_bytes == 8 * 8 * 4 * 3 // 4


def test_pad_chain_rejects_disagreeing_or_arbitrary_cuts():
    tgt = R._chunk_target_bytes()
    # ceil pads disagree (52 vs 50): fallback, counted as uneven
    p = R._build_plan(
        (50, 2), 4,
        _FakeSharding([_ceil_cuts(50, 4), [0, 2]], list(range(4))),
        _FakeSharding([_ceil_cuts(50, 2), [0, 1, 2]], list(range(4))),
        tgt)
    assert p.strategy == "device_put"
    assert R._fallback_reason(p.reason) == "uneven"
    # arbitrary (non-ceil) cuts: fallback, counted as uneven
    p = R._build_plan(
        (16,), 4,
        _FakeSharding([[0, 3, 16]], [0, 1]),
        _FakeSharding([[0, 8, 16]], [0, 1]), tgt)
    assert p.strategy == "device_put"
    assert R._fallback_reason(p.reason) == "uneven"


def test_fallback_reason_canonicalization():
    fr = R._fallback_reason
    assert fr("uneven source shards") == "uneven"
    assert fr("dst dim not divisible") == "uneven"
    assert fr("device sets differ") == "device_set"
    assert fr("source not replicated on dst devices") == "device_set"
    assert fr("extended dtype") == "dtype"
    assert fr("multi-dim chunk grid") == "multi_axis"
    assert fr("replicated blocks or rank order differs") == "multi_axis"
    assert fr("opaque layouts (ValueError)") == "shape"
