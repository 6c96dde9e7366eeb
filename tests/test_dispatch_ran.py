"""Which path RAN, asserted on the CPU mesh before it costs a chip run.

A path that silently gives way (a compiled reshard to ``device_put``, an
owned GEMM schedule to GSPMD, a ring kernel to ``lax``, a reduction to a
host fold) passes every numeric test and shows on the chip only as
``fallback_hits`` or a slow step.  Each case here runs ONE entry point on
the suite's virtual CPU devices and asserts what ran, from the labels and
counters that say so (``strategy``, ``dispatch``, ``reshard.chain_steps``,
the ``matmul.*`` span, the ``mapreduce`` root span) together with zero
movement of every fallback counter (or the pinned movement, for the one
pair known to give way) and the result against numpy.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import distributedarrays_tpu as dat
from distributedarrays_tpu import layout as L
from distributedarrays_tpu import parallel
from distributedarrays_tpu.ops import collective_matmul as cm
from distributedarrays_tpu.ops import linalg as la
from distributedarrays_tpu.parallel import reshard as R
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401
from distributedarrays_tpu.utils import autotune

FALLBACKS = ("fallback.hits", "reshard.collective_fallbacks")
STEP_KINDS = ("a2a", "gather", "slice", "exchange")


def _fallbacks(tm) -> dict:
    """Every fallback counter, whatever its labels."""
    return {k: v for k, v in tm.report()["counters"].items()
            if k.startswith(FALLBACKS)}


def _moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _span_counts(tm) -> dict:
    return {k: v["count"] for k, v in tm.span_stats().items()}


# ---------------------------------------------------------------------------
# redistribution: every strategy the planner can emit
# ---------------------------------------------------------------------------


def _grid(shape, grid):
    return L.sharding_for(list(range(int(np.prod(grid)))), grid, shape)


def _named(shape, grid, *spec):
    """A sharding on ``grid``'s mesh by axis position: ``_named(s, (4, 2),
    0, None)`` shards dim 0 over the first mesh axis and replicates over
    the second."""
    mesh = _grid(shape, grid).mesh
    return NamedSharding(mesh, P(*[None if a is None else mesh.axis_names[a]
                                   for a in spec]))


SHAPE = (48, 64)

# id: (source, destination, rdma, strategy, dispatch, chain steps that run,
#      fallback counters that move)
_RESHARD_CASES = {
    "all_to_all": (
        lambda: _grid(SHAPE, (8, 1)), lambda: _grid(SHAPE, (1, 8)),
        None, "all_to_all", "xla", {}, {}),
    "all_to_all-ring": (
        lambda: _grid(SHAPE, (8, 1)), lambda: _grid(SHAPE, (1, 8)),
        "interpret", "all_to_all", "rdma", {}, {}),
    "all_gather": (
        lambda: _grid(SHAPE, (8, 1)), lambda: _named(SHAPE, (8, 1)),
        None, "all_gather", "xla", {}, {}),
    "all_gather-ring": (
        lambda: _grid(SHAPE, (8, 1)), lambda: _named(SHAPE, (8, 1)),
        "interpret", "all_gather", "rdma", {}, {}),
    "local_slice": (
        lambda: _named(SHAPE, (8, 1)), lambda: _grid(SHAPE, (8, 1)),
        None, "local_slice", "xla", {}, {}),
    "chain-a2a": (
        lambda: _grid(SHAPE, (8, 1)), lambda: _grid(SHAPE, (4, 2)),
        None, "chain", "xla", {"a2a": 1}, {}),
    "chain-a2a-ring": (
        lambda: _grid(SHAPE, (8, 1)), lambda: _grid(SHAPE, (4, 2)),
        "interpret", "chain", "rdma", {"a2a": 1}, {}),
    "chain-gather": (
        lambda: _grid(SHAPE, (4, 2)), lambda: _named(SHAPE, (4, 2), 0),
        None, "chain", "xla", {"gather": 1}, {}),
    "chain-gather-ring": (
        lambda: _grid(SHAPE, (4, 2)), lambda: _named(SHAPE, (4, 2), 0),
        "interpret", "chain", "rdma", {"gather": 1}, {}),
    "chain-slice": (
        lambda: _named(SHAPE, (4, 2), 0), lambda: _grid(SHAPE, (4, 2)),
        None, "chain", "xla", {"slice": 1}, {}),
    "chain-exchange": (
        lambda: _grid(SHAPE, (1, 4)), lambda: _grid(SHAPE, (2, 2)),
        None, "chain", "xla", {"exchange": 1}, {}),
    # an exchange is ppermutes whatever is armed: "xla" on every platform
    "chain-exchange-armed": (
        lambda: _grid(SHAPE, (4, 2)), lambda: _grid(SHAPE, (2, 4)),
        "interpret", "chain", "xla", {"exchange": 1}, {}),
    "replication": (
        lambda: _grid(SHAPE, (4, 2)), lambda: _named(SHAPE, (4, 2)),
        None, "chain", "xla", {"gather": 2}, {}),
    "gather_put": (
        lambda: _grid(SHAPE, (8, 1)),
        lambda: L.sharding_for(list(range(7)), (7, 1), SHAPE),
        None, "gather_put", "xla", {"gather": 1}, {}),
    # known to go through device_put, pinned as such: disjoint or shrunken
    # device sets with a properly sharded destination are device_put's by
    # design, and counted
    "device_put-device_set": (
        lambda: _grid(SHAPE, (8, 1)), lambda: _grid(SHAPE, (4, 1)),
        None, "device_put", "xla", {},
        {"reshard.collective_fallbacks{reason=device_set}": 1}),
    # ROADMAP 2A item 10: P(d1,d0) -> P(d0,d1) on one mesh plans as a chain
    # and fails to lower; it gives way to device_put, warned and counted.
    # When the planner refuses or lowers it, this case changes with it.
    "device_put-mesh-transpose": (
        lambda: _named(SHAPE, (4, 2), 1, 0),
        lambda: _named(SHAPE, (4, 2), 0, 1),
        None, "chain", "xla", {},
        {"reshard.collective_fallbacks{reason=runtime}": 1,
         "fallback.hits{key=reshard:chain:ValueError}": 1}),
}


@pytest.mark.parametrize("case", sorted(_RESHARD_CASES))
def test_reshard_strategy_ran(telemetry_capture, monkeypatch, rng, case):
    tm = telemetry_capture
    src, dst, rdma, strategy, dispatch, steps, gives_way = \
        _RESHARD_CASES[case]
    if rdma:
        monkeypatch.setenv("DA_TPU_RDMA", rdma)
    else:
        monkeypatch.delenv("DA_TPU_RDMA", raising=False)
    A = rng.standard_normal(SHAPE).astype(np.float32)
    src, dst = src(), dst()
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert plan.strategy == strategy, (plan.strategy, plan.reason)
    fb0 = _fallbacks(tm)
    ran0 = {k: tm.counter_value("reshard.chain_steps", kind=k)
            for k in STEP_KINDS}
    b0 = tm.comm_bytes("reshard")
    with warnings.catch_warnings():
        # a path that gives way warns (once a process): an error where
        # none is pinned, and not this case's subject where one is
        warnings.simplefilter("ignore" if gives_way else "error",
                              RuntimeWarning)
        y = R.reshard(x, dst)
    assert y.sharding.is_equivalent_to(dst, y.ndim)
    np.testing.assert_array_equal(np.asarray(y), A)
    labels = tm.spans("reshard")[-1]["labels"]
    assert labels["strategy"] == strategy
    assert labels["dispatch"] == dispatch
    ran = {k: tm.counter_value("reshard.chain_steps", kind=k) - ran0[k]
           for k in STEP_KINDS}
    assert {k: v for k, v in ran.items() if v} == steps
    assert _moved(fb0, _fallbacks(tm)) == gives_way
    assert tm.comm_bytes("reshard") - b0 == plan.moved_bytes
    # what ran, and nothing else, is what the span says
    assert set(labels) == {
        "op", "strategy", "dispatch", "rdma_chunks", "rdma_chunks_source",
        "rdma_inflight", "shape", "dtype", "src_dim", "dst_dim", "nparts", "nsteps",
        "intra_bytes", "cross_bytes"}


@pytest.mark.parametrize("gs,gd,strategy", [
    ((4, 1), (1, 4), "all_to_all"), ((1, 4), (2, 2), "chain"),
    ((2, 2), (4, 1), "chain")],
    ids=["leg1", "leg2", "leg3"])
def test_distribute_legs_of_the_reshard_cell(telemetry_capture, rng, gs, gd,
                                             strategy):
    # the benchmark's cycle (4,1) -> (1,4) -> (2,2) -> (4,1) through the
    # public entry point: one reshard span a leg, none gives way
    tm = telemetry_capture
    A = rng.standard_normal(SHAPE).astype(np.float32)
    d = dat.distribute(A, procs=range(4), dist=gs)
    fb0, n0 = _fallbacks(tm), len(tm.spans("reshard"))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        e = dat.distribute(d, procs=range(4), dist=gd)
    spans = tm.spans("reshard")[n0:]
    assert [s["labels"]["strategy"] for s in spans] == [strategy]
    assert spans[0]["labels"]["dispatch"] == "xla"
    assert _moved(fb0, _fallbacks(tm)) == {}
    assert tuple(e.pids.shape) == gd
    np.testing.assert_array_equal(np.asarray(e), A)


# ---------------------------------------------------------------------------
# GEMM: every implementation reachable on the CPU mesh
# ---------------------------------------------------------------------------


def _operands(rng, n, procs, dist):
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    return (a, b, dat.distribute(a, procs=procs, dist=dist),
            dat.distribute(b, procs=procs, dist=dist))


@pytest.fixture
def registry():
    """The autotune registry, cleared afterwards (the suite's convention,
    as in tests/test_linalg.py): a promotion made here must not steer
    another test's dispatch."""
    yield autotune
    autotune.clear()


# id: (ranks, dist, promotion (kernel, grid tag, implementation), rdma,
#      spans that must run, spans that must not, labels of the last)
_GEMM_CASES = {
    "xla-1dev": (1, (1, 1), None, None, {"matmul"},
                 {"pallas.matmul", "matmul.ring_ag", "matmul.summa"}, {}),
    "xla-4x1": (4, (4, 1), None, None, {"matmul"},
                {"matmul.ring_ag", "matmul.summa"}, {}),
    "xla-2x2": (4, (2, 2), None, None, {"matmul"},
                {"matmul.ring_ag", "matmul.summa"}, {}),
    "pallas_matmul": (1, (1, 1), ("matmul_impl", None, "pallas"), None,
                      {"matmul", "pallas.matmul"}, {"matmul.ring_ag"}, {}),
    "ring_ag": (4, (4, 1), ("matmul_impl_dist", 4, "ring_ag"), None,
                {"matmul", "matmul.ring_ag"}, {"matmul.summa"},
                {"dispatch": "xla", "ranks": 4}),
    "ring_ag-ring": (4, (4, 1), ("matmul_impl_dist", 4, "ring_ag"),
                     "interpret", {"matmul", "matmul.ring_ag"},
                     {"matmul.summa"}, {"dispatch": "rdma", "ranks": 4}),
    "cannon": (4, (2, 2), ("matmul_impl_dist", "2x2", "summa"), None,
               {"matmul", "matmul.summa"}, {"matmul.ring_ag"},
               {"grid": "2x2", "ranks": 4}),
    "summa": (8, (2, 4), ("matmul_impl_dist", "2x4", "summa"), None,
              {"matmul", "matmul.summa"}, {"matmul.ring_ag"},
              {"grid": "2x4", "ranks": 8}),
}


@pytest.mark.parametrize("case", sorted(_GEMM_CASES))
def test_matmul_implementation_ran(telemetry_capture, monkeypatch, registry,
                                   rng, case):
    tm = telemetry_capture
    ranks, dist, promote, rdma, must, must_not, labels = _GEMM_CASES[case]
    if rdma:
        monkeypatch.setenv("DA_TPU_RDMA", rdma)
    else:
        monkeypatch.delenv("DA_TPU_RDMA", raising=False)
    n = 128
    a, b, A, B = _operands(rng, n, range(ranks), dist)
    if promote:
        kernel, tag, impl = promote
        key = (la._impl_key(n, n, n, A.dtype, B.dtype) if tag is None
               else la._impl_key(n, n, n, tag, A.dtype, B.dtype))
        registry.record(kernel, key, impl)
    fb0, s0 = _fallbacks(tm), _span_counts(tm)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        C = dat.matmul(A, B)
    ran = {k for k, v in _span_counts(tm).items() if v != s0.get(k, 0)}
    assert must <= ran, (must, ran)
    assert not must_not & ran, (must_not, ran)
    assert _moved(fb0, _fallbacks(tm)) == {}
    np.testing.assert_allclose(np.asarray(C), a @ b, rtol=2e-2, atol=2e-2)
    if labels:
        name = sorted(must - {"matmul"})[0]
        got = tm.spans(name)[-1]["labels"]
        assert {k: got[k] for k in labels} == labels


def test_matmul_ring_ag_without_promotion_stays_gspmd(telemetry_capture,
                                                      registry, rng):
    # the owned schedules run by measured promotion only: the same layout
    # with no registry entry is XLA's, and says so
    tm = telemetry_capture
    a, b, A, B = _operands(rng, 64, range(4), (4, 1))
    assert la._dist_impl_choice(64, 64, 64, 4, A.dtype, B.dtype) == "jnp"
    s0 = _span_counts(tm)
    C = dat.matmul(A, B)
    assert _span_counts(tm).get("matmul.ring_ag", 0) == \
        s0.get("matmul.ring_ag", 0)
    np.testing.assert_allclose(np.asarray(C), a @ b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rdma", [False, True], ids=["lax", "ring"])
def test_ring_reduce_scatter_matmul_ran(telemetry_capture, rng, rdma):
    # the TP layer's second half: x @ w reduce-scattered over the ring.
    # Not reachable from dat.matmul; run as its users do, in a shard_map
    tm = telemetry_capture
    n = 96                          # a shape of this test's own: traced here
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    mesh = L.mesh_for(list(range(4)), (4,))
    ax = mesh.axis_names[0]
    fn = parallel.run_spmd(
        lambda x, w: cm.matmul_reducescatter(
            x, w, ax, rdma=rdma, interpret=True if rdma else None),
        mesh, in_specs=(P(None, ax), P(ax, None)), out_specs=P(ax, None))
    x = jax.device_put(a, NamedSharding(mesh, P(None, ax)))
    w = jax.device_put(b, NamedSharding(mesh, P(ax, None)))
    fb0 = _fallbacks(tm)
    d0 = tm.counter_value("pallas_collectives.dispatch",
                          op="ring_matmul_reducescatter", path="rdma")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        out = fn(x, w)
    assert tm.counter_value("pallas_collectives.dispatch",
                            op="ring_matmul_reducescatter",
                            path="rdma") - d0 == (1 if rdma else 0)
    assert _moved(fb0, _fallbacks(tm)) == {}
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)


# id: (ranks, dist of A, B distributed too, reshard spans a call)
_INT8_CASES = {
    "one-device": (1, (1, 1), False),
    "rows-shm": (4, (4, 1), False),
    "cannon": (4, (2, 2), True),
}


@pytest.mark.parametrize("case", sorted(_INT8_CASES))
def test_int8_matmul_path_ran(telemetry_capture, rng, case):
    tm = telemetry_capture
    ranks, dist, b_too = _INT8_CASES[case]
    n = 128
    a, b, A, B = _operands(rng, n, range(ranks), dist)
    # traced anew, so that the kernel's trace-time span is this call's
    jax.clear_caches()
    fb0, s0 = _fallbacks(tm), _span_counts(tm)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        C = la.dmatmul_int8(A, B if b_too else b)
    ran = _moved(s0, _span_counts(tm))
    # the int8 kernel was traced into the program that ran (three hops of
    # the 2x2 Cannon ring trace it more than once), and no float schedule
    assert ran.get("pallas.matmul_int8", 0) >= 1, ran
    assert not {"matmul", "pallas.matmul", "matmul.summa",
                "matmul.ring_ag"} & set(ran), ran
    assert _moved(fb0, _fallbacks(tm)) == {}
    assert tuple(C.pids.shape) == dist
    want = a @ b
    err = np.abs(np.asarray(C) - want).max() / np.abs(want).max()
    assert err < 3e-2, err          # dynamic int8 quantization, not a bug


# ---------------------------------------------------------------------------
# reductions and scans: every named entry, even and uneven layouts
# ---------------------------------------------------------------------------

_LAYOUTS = {"even": ((48, 16), (4, 2)), "uneven": ((50, 18), (4, 2))}


def _pos(a):
    return a > 0


def _larger(a, b):
    return jnp.maximum(a, b)


# name: (entry, numpy reference, `mapreduce` root spans a call)
_REDUCTIONS = {
    "dsum": (dat.dsum, np.sum, 1),
    "dprod": (lambda d: dat.dprod(d), np.prod, 1),
    "dmaximum": (dat.dmaximum, np.max, 1),
    "dminimum": (dat.dminimum, np.min, 1),
    "dmean": (dat.dmean, np.mean, 1),
    "dall": (lambda d: dat.dall(dat.djit(_pos)(d)),
             lambda a: np.all(a > 0), 1),
    "dany": (lambda d: dat.dany(dat.djit(_pos)(d)),
             lambda a: np.any(a > 0), 1),
    "dvar": (dat.dvar, lambda a: np.var(a, ddof=1), 1),
    "dstd": (dat.dstd, lambda a: np.std(a, ddof=1), 1),
    "dcount": (lambda d: dat.dcount(_pos, d),
               lambda a: np.count_nonzero(a > 0), 1),
    "dmapreduce": (lambda d: dat.dmapreduce(jnp.square, "sum", d),
                   lambda a: np.sum(a * a), 1),
    "dreduce-binary": (lambda d: dat.dreduce(_larger, d), np.max, 1),
    "dextrema": (dat.dextrema, lambda a: (np.min(a), np.max(a)), 0),
}

_SCANS = {
    "dcumsum": (dat.dcumsum, np.cumsum),
    "dcumprod": (dat.dcumprod, np.cumprod),
    "dcummax": (dat.dcummax, np.maximum.accumulate),
    "dcummin": (dat.dcummin, np.minimum.accumulate),
}


def _array(rng, layout):
    shape, dist = _LAYOUTS[layout]
    # near 1 so that a product over 800 elements stays a float32
    A = (1.0 + 0.01 * rng.standard_normal(shape)).astype(np.float32)
    A[0, 0] = -1.0                  # one negative: dall/dany/dcount differ
    return A, dat.distribute(A, procs=range(8), dist=dist)


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("name", sorted(_REDUCTIONS))
def test_reduction_ran_compiled(telemetry_capture, rng, name, layout):
    tm = telemetry_capture
    entry, ref, roots = _REDUCTIONS[name]
    A, d = _array(rng, layout)
    assert bool(d._padded) == (layout == "uneven")
    fb0, s0 = _fallbacks(tm), _span_counts(tm)
    host0 = tm.comm_bytes("d2h")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        got = entry(d)
    ran = _moved(s0, _span_counts(tm))
    # one root span a call whichever entry it came through; an inner
    # dmapreduce of a composed entry would show as a second one
    assert ran.get("mapreduce", 0) == roots, ran
    assert "mapreduce.host_fold" not in ran, ran
    assert _moved(fb0, _fallbacks(tm)) == {}
    assert tm.comm_bytes("d2h") == host0      # nothing gathered to reduce
    np.testing.assert_allclose(np.asarray(got, dtype=np.float64),
                               np.asarray(ref(A), dtype=np.float64),
                               rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("name", sorted(_SCANS))
def test_scan_ran_compiled(telemetry_capture, rng, name, layout):
    tm = telemetry_capture
    entry, ref = _SCANS[name]
    A, d = _array(rng, layout)
    fb0 = _fallbacks(tm)
    host0, moved0 = tm.comm_bytes("d2h"), tm.comm_bytes("reshard")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        got = entry(d, 0)
    # one SPMD program on the layout as it is: nothing gathered to the
    # host, nothing redistributed, the cuts kept
    assert _moved(fb0, _fallbacks(tm)) == {}
    assert tm.comm_bytes("d2h") == host0
    assert tm.comm_bytes("reshard") == moved0
    assert got.cuts == d.cuts and tuple(got.pids.shape) == tuple(d.pids.shape)
    np.testing.assert_allclose(np.asarray(got), ref(A, axis=0),
                               rtol=2e-4, atol=1e-5)
