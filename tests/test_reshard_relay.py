"""The relayed block exchange against the ``jax.device_put`` oracle, on
virtual CPU devices that are given a TPU slice's coordinates; the routing
itself is tested in ``test_reshard_routes.py``, which also says why these
are two small files.
"""

import warnings

import numpy as np
import pytest

import jax

from distributedarrays_tpu import layout as L
from distributedarrays_tpu.parallel import reshard as R
from distributedarrays_tpu.telemetry.fixtures import telemetry_capture  # noqa: F401 (fixture)

from test_reshard_routes import _COORDS, _exchange_rounds, _shardings_for


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


def _permutes(fn, x):
    return fn.lower(x).as_text().count("collective_permute")


@pytest.fixture
def chip_coords(monkeypatch):
    """Give the virtual CPU devices the coordinates of a TPU slice, by
    device id, for the programs built inside the test."""
    def inject(topo):
        coords = _COORDS[topo]
        monkeypatch.setattr(
            R, "_device_coords",
            lambda mesh: tuple(coords[d.id] for d in mesh.devices.flat))
        R._chain_jit.cache_clear()
        R._chain_routes.cache_clear()
    yield inject
    R._chain_jit.cache_clear()
    R._chain_routes.cache_clear()


def test_exchange_without_relays_is_the_parents_program(chip_coords, rng):
    # devices without coords, and coords on which no pair can be relayed
    # to any gain: one collective-permute a round a chunk, as before
    shape = (48, 64)
    A = rng.standard_normal(shape).astype(np.float32)
    for topo, gs, gd in ((None, (1, 4), (2, 2)), ("line", (1, 4), (2, 2)),
                         ("2x4", (4, 2), (2, 4))):
        R._chain_jit.cache_clear()
        R._chain_routes.cache_clear()
        if topo:
            chip_coords(topo)
        src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
        x = jax.device_put(A, src)
        plan = R.plan_reshard(x, dst)
        mesh = L.mesh_for(list(plan.ranks), plan.mesh_shape)
        fn = R._chain_jit(mesh, 2, plan.src_comp, plan.dst_comp, plan.steps,
                          None)
        assert R._chain_routes(mesh, plan.steps)[1] == 0
        assert _permutes(fn, x) == plan.steps[0][2] * plan.nchunks
    # and the 2x2's leg 2 with its coords: a hop more for round 0
    chip_coords("2x2")
    src, dst = _shardings_for(shape, (1, 4)), _shardings_for(shape, (2, 2))
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    mesh = L.mesh_for(list(plan.ranks), plan.mesh_shape)
    fn = R._chain_jit(mesh, 2, plan.src_comp, plan.dst_comp, plan.steps, None)
    assert R._chain_routes(mesh, plan.steps)[1] == 2 and _permutes(fn, x) == 3


# topology, layouts, pieces relayed (0: the routes leave XLA's alone)
_RELAY_CASES = [("2x2", (1, 4), (2, 2), 2), ("2x4", (2, 4), (4, 2), 4),
                ("2x4", (4, 2), (2, 4), 0), ("2x4", (1, 8), (4, 2), 6),
                ("4x2", (1, 8), (4, 2), 4), ("cube", (1, 8), (2, 4), 8)]


@pytest.mark.parametrize("chunk_mb", [None, "0.0005"],
                         ids=["unchunked", "chunked"])
@pytest.mark.parametrize(
    "topo,gs,gd,want", _RELAY_CASES,
    ids=[f"{t}:{a}->{b}".replace(" ", "") for t, a, b, _n in _RELAY_CASES])
def test_relayed_exchange_matches_device_put(telemetry_capture, rng,
                                             monkeypatch, chip_coords,
                                             topo, gs, gd, want, chunk_mb):
    # virtual CPU devices given a slice's coordinates: the pieces the
    # routes relay travel hop by hop and land bit-equal to device_put;
    # the counter reads what the routes say, the byte count what the
    # layouts need
    tm = telemetry_capture
    if chunk_mb:
        monkeypatch.setenv("DA_TPU_RESHARD_CHUNK_MB", chunk_mb)
    chip_coords(topo)
    shape = (96, 128)
    A = rng.standard_normal(shape).astype(np.float32)
    src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
    x = jax.device_put(A, src)
    plan = R.plan_reshard(x, dst)
    assert [s[0] for s in plan.steps] == ["exchange"]
    assert (plan.nchunks > 1) == bool(chunk_mb)
    relays, was, now = R._route_exchange(
        _exchange_rounds(shape, gs, gd), _COORDS[topo])
    assert sum(len(chains) for chains in relays) == want
    assert (now < was) == (want > 0)
    b0 = tm.comm_bytes("reshard")
    n0 = tm.counter_value("reshard.exchange_relayed")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        y = R.reshard(x, dst)
    _assert_shards_equal_device_put(y, A, dst)
    assert tm.counter_value("reshard.exchange_relayed") == n0 + want
    assert tm.comm_bytes("reshard") - b0 == plan.moved_bytes == \
        _needed_bytes(shape, 4, src, dst)
    assert tm.counter_value("reshard.collective_fallbacks",
                            reason="runtime") == 0
    build = [e for e in tm.events("jit")
             if e.get("fn") == "reshard_chain"][-1]
    assert (build["link_load_direct"], build["link_load"]) == (was, now)
