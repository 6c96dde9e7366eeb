"""The block exchange's routes over the chips' links: the pure routing
function (``reshard._route_exchange``) on injected coordinates, and what
``reshard._device_coords`` passes on to it.

Two small files (this one and ``test_reshard_relay.py``) and not a section
of ``test_reshard.py``: the driver's six workers take the test files
largest first, so a file's count of tests decides what runs beside the
wall-clock bounds of ``test_serve.py`` and ``test_decode.py``; with these
cases in it ``test_reshard.py`` led the queue and ran its tail beside the
former (CHANGES.md, PR 34).  Files this small are queued after both.
"""

import itertools

import numpy as np
import pytest

from distributedarrays_tpu import layout as L
from distributedarrays_tpu.parallel import reshard as R


def _shardings_for(shape, grid):
    n = int(np.prod(grid))
    return L.sharding_for(list(range(n)), grid, shape)


# chip coordinates in the order of the device ids, as the TPU runtime
# reports them for v5e:2x2, v5e:2x4 and v5e:4x2
_COORDS_2X2 = ((0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0))
_COORDS_2X4 = tuple((x, y, 0) for y in range(4) for x in range(2))
_COORDS_4X2 = ((0, 0, 0), (1, 0, 0), (2, 0, 0), (3, 0, 0),
               (0, 1, 0), (1, 1, 0), (2, 1, 0), (3, 1, 0))
_COORDS_LINE = ((0, 0, 0), (1, 0, 0), (2, 0, 0), (3, 0, 0))
_COORDS_CUBE = tuple((x, y, z) for z in range(2) for y in range(2)
                     for x in range(2))
_COORDS = {"2x2": _COORDS_2X2, "2x4": _COORDS_2X4, "4x2": _COORDS_4X2,
           "line": _COORDS_LINE, "cube": _COORDS_CUBE}


def _exchange_rounds(shape, gs, gd):
    """``_exchange_dests`` of the one exchange step ``gs -> gd`` plans."""
    src, dst = _shardings_for(shape, gs), _shardings_for(shape, gd)
    plan = R.plan_reshard(shape, dst, src_sharding=src, itemsize=4)
    assert [s[0] for s in plan.steps] == ["exchange"], plan.steps
    before, after = plan.steps[0][3:5]
    r, s = R._exchange_ratios(plan.mesh_shape, before, after)
    return R._exchange_dests(tuple(plan.mesh_shape), before, after, r, s)


def _link_loads(dests, relays, coords):
    """Pieces on each directed link: a relayed piece along its chain,
    every other piece along XLA's dimension-ordered route."""
    load = {}
    for t, dest in enumerate(dests):
        chains = {chain[0]: chain for chain in relays[t]}
        for src, dst in enumerate(dest):
            route = [coords[c] for c in chains[src]] if src in chains \
                else R._dimension_ordered(coords[src], coords[dst])
            for link in zip(route, route[1:]):
                load[link] = load.get(link, 0) + 1
    return load


def _hops(a, b):
    return sum(abs(x - y) for x, y in zip(a, b))


def test_route_exchange_2x2_relays_the_diagonal_over_the_idle_links():
    # leg 2 of the benchmark's cycle: chips 1 and 2 each send a piece
    # across the diagonal; XLA's routes 1->0->2 and 2->3->1 share links
    # with the neighbour pieces (two pieces on four links, none on the
    # other four), the relays 1->3->2 and 2->0->1 put one on each of eight
    dests = _exchange_rounds((48, 64), (1, 4), (2, 2))
    assert dests == ((0, 2, 1, 3), (2, 0, 3, 1))
    direct = _link_loads(dests, ((), ()), _COORDS_2X2)
    assert sorted(direct.values()) == [2, 2, 2, 2]
    relays, was, now = R._route_exchange(dests, _COORDS_2X2)
    assert relays == (((1, 3, 2), (2, 0, 1)), ())
    assert (was, now) == (2, 1)
    load = _link_loads(dests, relays, _COORDS_2X2)
    assert len(load) == 8 and set(load.values()) == {1}
    # no assignment of the two pieces' two routes each does better
    best = min(
        max(_link_loads(dests, (tuple(c for c in pick if c), ()),
                        _COORDS_2X2).values())
        for pick in itertools.product([None, (1, 3, 2)], [None, (2, 0, 1)]))
    assert best == now


_ROUTE_CASES = [(topo, gs, gd) for topo in ("2x4", "4x2", "cube")
                for gs, gd in (((4, 2), (2, 4)), ((2, 4), (4, 2)),
                               ((1, 8), (4, 2)), ((1, 8), (2, 4)))]


@pytest.mark.parametrize(
    "topo,gs,gd", _ROUTE_CASES,
    ids=[f"{t}:{a}->{b}".replace(" ", "") for t, a, b in _ROUTE_CASES])
def test_route_exchange_does_no_harm_on_eight_chips(topo, gs, gd):
    coords = _COORDS[topo]
    dests = _exchange_rounds((48, 64), gs, gd)
    relays, was, now = R._route_exchange(dests, coords)
    direct = _link_loads(dests, ((),) * len(dests), coords)
    assert was == max(direct.values())
    assert now == max(_link_loads(dests, relays, coords).values()) <= was
    # a relay is chosen only where it lowers the busiest link's load
    assert (now < was) == any(relays)
    for t, chains in enumerate(relays):
        assert len({c[0] for c in chains}) == len(chains)
        for chain in chains:
            assert dests[t][chain[0]] == chain[-1]
            # never a pair one hop apart; every hop between neighbours;
            # a shortest route
            assert _hops(coords[chain[0]], coords[chain[-1]]) == \
                len(chain) - 1 >= 2
            assert all(_hops(coords[a], coords[b]) == 1
                       for a, b in zip(chain, chain[1:]))
        # the round's ppermutes stay partial permutations, and every
        # rank that does not keep its piece is some level's arrival
        sent = {c[0] for c in chains}
        pairs = [(c, to) for c, to in enumerate(dests[t])
                 if c != to and c not in sent]
        ends = []
        for group in R._hop_groups(pairs, chains):
            for prs, e in group:
                assert len({a for a, _ in prs}) == len(prs)
                assert len({b for _, b in prs}) == len(prs)
                ends += e
        assert sorted(ends) == sorted(
            to for c, to in enumerate(dests[t]) if c != to)


def test_route_exchange_leaves_one_hop_pairs_and_unknown_links_alone():
    dests = _exchange_rounds((48, 64), (1, 4), (2, 2))
    none = ((),) * len(dests)
    # no coords (CPU, interpret): nothing is known of the links
    assert R._route_exchange(dests, None) == (none, 0, 0)
    # four chips in a line: 0->2 is two hops, but along one axis there
    # is one shortest route, XLA's own
    relays, was, now = R._route_exchange(dests, _COORDS_LINE)
    assert relays == none and was == now
    # every pair one hop apart (leg 3's partners), and two-hop pairs
    # whose own routes already share no link
    assert R._route_exchange(((1, 0, 3, 2),), _COORDS_2X2) == (((),), 1, 1)
    dests8 = _exchange_rounds((48, 64), (4, 2), (2, 4))
    assert R._route_exchange(dests8, _COORDS_2X4) == \
        (((),) * len(dests8), 1, 1)
    # a pair more than half way round an axis could ride a torus's
    # wrap-around link, which coords do not show: left to XLA
    ring = tuple((x, y, 0) for y in range(2) for x in range(4))
    far = ((7, 1, 2, 3, 4, 5, 6, 0),)       # (0,0) <-> (3,1)
    assert R._route_exchange(far, ring)[0] == ((),)


class _Chip:
    """A device as the routing reads it: its ``coords`` alone."""

    def __init__(self, coords=None):
        if coords is not None:
            self.coords = list(coords)


def test_device_coords_reads_plain_tuples_or_none():
    class _M:
        def __init__(self, devs):
            self.devices = np.asarray(devs, dtype=object)

    chips = [_Chip(c) for c in _COORDS_2X2]
    assert R._device_coords(_M(chips)) == _COORDS_2X2
    # CPU devices report none; two cores of one chip share theirs
    assert R._device_coords(L.mesh_for(list(range(4)), (2, 2))) is None
    assert R._device_coords(_M(chips[:3] + [_Chip()])) is None
    assert R._device_coords(_M(chips[:3] + [_Chip((0, 0, 0))])) is None
    # the links and XLA's routes were read on the 2x2 alone: any other
    # slice, and a part of the 2x2, keeps XLA's routes
    for topo in ("2x4", "4x2", "line", "cube"):
        assert R._device_coords(
            _M([_Chip(c) for c in _COORDS[topo]])) is None
    assert R._device_coords(_M(chips[:2])) is None
