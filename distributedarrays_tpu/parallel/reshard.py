"""Layout-aware reshard planner: chunked collective redistribution.

Every redistribution in the framework used to be one whole-array
``jax.device_put``: correct, but it moves (and peaks at) the FULL logical
array even when the two layouts share most of their bytes.  "Memory-
efficient array redistribution through portable collective communication"
(arXiv:2112.01075) shows that any reshard decomposes into a short sequence
of all-to-all / all-gather / dynamic-slice stages whose peak per-device
memory is bounded by src-shard + dst-shard + one staging chunk; DrJAX
(arXiv:2403.07128) shows that keeping that movement inside one compiled
program is what makes it scale.  This module is that planner:

1. **Plan** (:func:`plan_reshard`) — pure metadata.  The chunk-intersection
   transfer plan between a source and destination layout is block algebra
   on cut vectors (``layout.cut_intersections``): which global regions
   must cross a device boundary, and therefore how many bytes the reshard
   *has* to move (``moved_bytes`` — the (p-1)/p fraction for an even
   repartition, 0 for a pure relabeling).  Plans are ``lru_cache``d on
   ``(shape, itemsize, src sharding, dst sharding)`` exactly the way the
   identity resharder caches on sharding alone, so a hot loop resharding
   the same layout pair replans nothing (``reshard.plan_requests`` vs
   ``reshard.plan_builds`` counters expose the hit rate).

2. **Lower** (:func:`reshard`) — divisible single-axis repartitions become
   ONE compiled shard_map program over a canonical 1-D mesh, built from
   ``parallel.collectives.pall_to_all``/``pgather`` (the same collectives
   fft.py uses for its repartitions), **chunked along the largest eligible
   axis** so the staging buffer stays bounded by
   ``DA_TPU_RESHARD_CHUNK_MB`` (default 64) instead of the whole shard:

   - shard dim *i* → shard dim *j*:  tiled ``all_to_all`` per chunk;
   - shard dim *i* → replicated:     tiled ``all_gather`` per chunk;
   - replicated → shard dim *j*:     a local ``dynamic_slice`` (no comm).

3. **Lower the general case** — moves no single collective covers
   (multi-axis repartitions, mesh-axis transposes, partial replication)
   factorize over a *common refinement* of the two device grids
   (arXiv 2112.01075): the owner maps are digitized into a mixed-radix
   mesh whose axes each carry ONE per-axis collective — an
   ``all_to_all`` for an axis moving between array dims, an
   ``all_gather`` for an axis leaving, a local dynamic-slice for an axis
   appearing — composed as one compiled shard_map *chain* (strategy
   ``chain``).  Where that schedule would gather an axis only to slice
   it back later (the axis to move is not the minor one of its dim),
   the detour becomes one direct block *exchange*: every rank sends each
   piece ``source block ∩ destination block`` straight to its new owner,
   in rounds of one ``ppermute`` each.  Start-aligned ceil-uneven
   layouts ride the same chain
   between a comm-free pad and slice-back; device-set-shrinking moves
   whose destination is replicated enough gather collectively on the
   source mesh first (``gather_put``).  The chain planner is
   topology-aware: each mesh axis is classified intra- vs cross-domain
   against ``resilience.domains`` and the plan/span carry
   ``intra_bytes``/``cross_bytes``, with intra-domain exchanges
   scheduled first.

4. **Fall back** — whatever remains takes the ``device_put`` path
   (compiled identity program when the device set is unchanged), counted
   under ``reshard.collective_fallbacks`` with a canonical ``reason=``
   label (uneven | multi_axis | device_set | dtype | shape | runtime).
   Either way the chosen strategy is recorded via a ``reshard``/``plan``
   journal event and as the ``strategy`` label of the ``reshard`` span,
   so Perfetto and ``telemetry summarize`` attribute bytes per strategy.

``dalint`` rule DAL007 flags direct cross-sharding ``jax.device_put`` on
DArray buffers outside this module, so new code routes through here.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import math
import os

import numpy as np

import jax
from jax import lax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import layout as L
from .. import telemetry as _tm
from ..resilience import faults as _fl
from .collectives import pall_to_all, pgather, shard_map_compat

__all__ = ["ReshardPlan", "plan_reshard", "reshard", "plan_stats",
           "layout_of_sharding"]


_CHUNK_MB_ENV = "DA_TPU_RESHARD_CHUNK_MB"

# cross-product cap: a plan is metadata, not a workload — layouts whose
# intersection grid exceeds this fall back to the whole-array estimate
_MAX_PLAN_REGIONS = 65536


def _chunk_target_bytes() -> int:
    try:
        mb = float(os.environ.get(_CHUNK_MB_ENV, "64"))
    except ValueError:
        mb = 64.0
    return max(int(mb * 1024 * 1024), 1)


# ---------------------------------------------------------------------------
# plan metadata
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReshardPlan:
    """The transfer plan between two layouts — pure metadata, hashable.

    ``moved_bytes`` is the number of bytes that must cross a device
    boundary (summed over receiving devices), from the chunk-intersection
    algebra; ``total_bytes`` the logical array size.  ``strategy`` is one
    of ``noop`` (same sharding object), ``all_to_all`` / ``all_gather`` /
    ``local_slice`` (the compiled single-collective lowerings), ``chain``
    / ``gather_put`` (the general per-axis collective chain over the
    refined mesh — see the module docstring), or ``device_put``
    (fallback; ``reason`` says why).

    Chain plans also carry: ``mesh_shape`` (refined mesh axis sizes,
    major→minor over the canonical rank order ``ranks``), ``src_comp`` /
    ``dst_comp`` (per array dim, the mesh-axis indices sharding it,
    major→minor), ``steps`` (the scheduled ops, each
    ``(kind, axis, q, src_dim, dst_dim, chunk_axis, nchunks,
    moved_bytes)`` with kind ``a2a``, ``gather`` or ``slice`` along one
    mesh axis; an ``exchange`` step has axis -1, ``q`` rounds, and in
    place of the two dims the composites before and after it),
    ``pad_shape`` (ceil-uneven layouts: the even analog
    the chain runs on, between a comm-free pad and slice-back),
    ``staging_bytes`` (the worst step's staging piece) and the
    topology split ``intra_bytes``/``cross_bytes``."""

    strategy: str
    shape: tuple
    itemsize: int
    moved_bytes: int
    total_bytes: int
    src_dim: int | None = None
    dst_dim: int | None = None
    nparts: int = 1
    ranks: tuple = ()
    chunk_axis: int | None = None
    nchunks: int = 1
    reason: str = ""
    steps: tuple = ()
    mesh_shape: tuple = ()
    src_comp: tuple = ()
    dst_comp: tuple = ()
    pad_shape: tuple = ()
    staging_bytes: int = 0
    intra_bytes: int = 0
    cross_bytes: int = 0

    @property
    def collective(self) -> bool:
        return self.strategy in ("all_to_all", "all_gather", "local_slice",
                                 "chain", "gather_put")


def layout_of_sharding(sharding, shape):
    """The (cuts, owners) layout a sharding implies for ``shape``:
    per-dim cut vectors of the physical shard grid, and a dict mapping
    each block's grid coordinates to the sorted tuple of device ranks
    holding it (>1 entry = replication along some mesh axis)."""
    m = sharding.devices_indices_map(tuple(int(s) for s in shape))
    starts: list[set] = [set([0]) for _ in shape]
    for idx in m.values():
        for d, sl in enumerate(idx):
            starts[d].add(int(sl.start or 0))
    cuts = [sorted(s) + [int(n)] for s, n in zip(starts, shape)]
    owners: dict[tuple, list] = {}
    for dev, idx in m.items():
        ci = tuple(cuts[d].index(int(sl.start or 0))
                   for d, sl in enumerate(idx))
        owners.setdefault(ci, []).append(int(dev.id))
    return cuts, {k: tuple(sorted(v)) for k, v in owners.items()}


def _moved_elems(shape, src_cuts, src_owners, dst_cuts, dst_owners) -> int:
    """Elements that must cross a device boundary: for every region in the
    N-D chunk-intersection grid, count it once per destination device that
    does not already hold it."""
    per_dim = [L.cut_intersections(sc, dc)
               for sc, dc in zip(src_cuts, dst_cuts)]
    nregions = math.prod(len(o) for o in per_dim) if per_dim else 1
    if nregions > _MAX_PLAN_REGIONS:
        raise ValueError(f"plan too large: {nregions} regions")
    moved = 0
    for combo in itertools.product(*per_dim):
        n = 1
        for (_ai, _bi, lo, hi) in combo:
            n *= (hi - lo)
        sci = tuple(c[0] for c in combo)
        dci = tuple(c[1] for c in combo)
        sown = src_owners.get(sci, ())
        for dv in dst_owners.get(dci, ()):
            if dv not in sown:
                moved += n
    return moved


def _grid_of(cuts) -> tuple[int, ...]:
    return tuple(len(c) - 1 for c in cuts)


def _uniform(cuts) -> bool:
    sizes = np.diff(np.asarray(cuts, dtype=np.int64))
    return sizes.size == 0 or len(set(sizes.tolist())) == 1


def _singleton_rank_order(owners, grid, dim):
    """The per-block owner ranks of a layout sharded on exactly one dim,
    in block order — None if any block is replicated/multi-owned."""
    order = []
    for k in range(grid[dim]):
        ci = tuple(k if d == dim else 0 for d in range(len(grid)))
        own = owners.get(ci, ())
        if len(own) != 1:
            return None
        order.append(own[0])
    return tuple(order)


def _smallest_divisor_at_least(n: int, k: int) -> int:
    """Smallest divisor of ``n`` that is >= ``k`` (``n`` itself at worst)."""
    if k <= 1:
        return 1
    for d in range(k, n + 1):
        if n % d == 0:
            return d
    return n


def _pick_chunking(shape, itemsize, src_dim, dst_dim, p, strategy,
                   chunk_target):
    """(chunk_axis, nchunks): chunk along the largest eligible axis so one
    staging piece stays under ``chunk_target`` bytes per device.  For
    all_to_all the dst dim itself is eligible (the kernel pre-slices so
    tiled chunks land in dst-block order); the src/concat dim never is
    (its chunk results would interleave)."""
    local_bytes = math.prod(shape) * itemsize // max(p, 1)
    want = -(-local_bytes // chunk_target)          # ceil
    if want <= 1:
        return None, 1
    cands = []
    for d in range(len(shape)):
        if d == src_dim:
            continue
        if d == dst_dim:
            if strategy != "all_to_all":
                continue
            units = shape[d] // p
        else:
            units = shape[d]
        if units > 1:
            cands.append((units, d))
    if not cands:
        return None, 1
    units, axis = max(cands)
    return axis, _smallest_divisor_at_least(units, min(want, units))


# ---------------------------------------------------------------------------
# general lowering: mixed-radix factorization → per-axis collective chain
# ---------------------------------------------------------------------------
#
# arXiv 2112.01075: any even redistribution factorizes over a common
# refinement of the two layouts' device grids.  We recover that refinement
# from the owner maps alone: flatten whichever side covers every rank
# exactly once (row-major over its grid) into a canonical rank order, then
# split that order's mixed radix until BOTH sides' block coordinates are
# per-digit linear functions of the rank index.  Each refined digit is one
# mesh axis, each side becomes a composite PartitionSpec over those axes,
# and the move is a short schedule of per-axis collectives.  Order is
# forced by contiguity: a dim's factors leave minor-first and arrive
# major-first, so every concat/slice touches contiguous blocks.

_MAX_CHAIN_RANKS = 4096


def _linear_weight(vals):
    """The weight w when ``vals`` is v ↦ v*w (w may be 0) — else None."""
    w = vals[1] if len(vals) > 1 else 0
    return w if all(v == k * w for k, v in enumerate(vals)) else None


def _side_coords(own, pos, nranks):
    """Per-rank block coordinates in canonical order; None unless every
    rank owns exactly one block."""
    out = [None] * nranks
    for ci, ranks in own.items():
        for r in ranks:
            c = pos.get(r)
            if c is None or out[c] is not None:
                return None
            out[c] = ci
    return None if any(v is None for v in out) else out


def _digitize(ndim, s_grid, s_own, d_grid, d_own):
    """``(canon_ranks, digit_sizes, strides, src_comp, dst_comp)`` — the
    common mixed-radix refinement of the two owner maps — or None when no
    such factorization exists (rank-order mismatch, replication on both
    sides, non-radix block assignment)."""
    ranks = sorted({r for o in s_own.values() for r in o})
    nr = len(ranks)
    if nr > _MAX_CHAIN_RANKS or nr < 2:
        return None
    ps = math.prod(s_grid) if s_grid else 1
    pd = math.prod(d_grid) if d_grid else 1
    if ps == nr:
        canon_grid, canon_own = s_grid, s_own
    elif pd == nr:
        canon_grid, canon_own = d_grid, d_own
    else:                    # replication on BOTH sides: no full flatten
        return None
    canon = []
    for coords in itertools.product(*(range(g) for g in canon_grid)):
        o = canon_own.get(coords, ())
        if len(o) != 1:
            return None
        canon.append(o[0])
    pos = {r: i for i, r in enumerate(canon)}
    if len(pos) != nr:
        return None
    scoord = _side_coords(s_own, pos, nr)
    dcoord = _side_coords(d_own, pos, nr)
    if scoord is None or dcoord is None:
        return None
    if any(scoord[0]) or any(dcoord[0]):     # not start-aligned
        return None
    digits = []                              # (size, stride), major→minor
    stride = nr
    for g in canon_grid:
        stride //= g
        if g > 1:
            digits.append((g, stride))
    for coord in (scoord, dcoord):
        for d in range(ndim):
            k = 0
            while k < len(digits):
                q, t = digits[k]
                vals = [coord[v * t][d] for v in range(q)]
                if _linear_weight(vals) is not None:
                    k += 1
                    continue
                for a in range(2, q):        # split into (q//a, a)
                    if q % a:
                        continue
                    if all(vals[v] == vals[(v // a) * a] + vals[v % a]
                           for v in range(q)):
                        digits[k:k + 1] = [(q // a, t * a), (a, t)]
                        break
                else:
                    return None
    comps = []
    for coord in (scoord, dcoord):
        wmap = {}                            # digit -> (dim, weight)
        for m, (q, t) in enumerate(digits):
            hot = [d for d in range(ndim) if coord[t][d]]
            if len(hot) > 1:                 # one digit, two dims: not a
                return None                  # valid block grid
            if hot:
                wmap[m] = (hot[0], coord[t][hot[0]])
        comp = []
        for d in range(ndim):
            mine = sorted((w, m) for m, (dd, w) in wmap.items() if dd == d)
            exp = 1
            for w, m in mine:                # minor → major: exact radix
                if w != exp:
                    return None
                exp *= digits[m][0]
            comp.append(tuple(m for _w, m in reversed(mine)))
        for c in range(nr):                  # exhaustive: every rank's
            for d in range(ndim):            # block decomposes exactly
                v = sum(((c // digits[m][1]) % digits[m][0]) * wmap[m][1]
                        for m in comp[d])
                if v != coord[c][d]:
                    return None
        comps.append(tuple(comp))
    sizes = tuple(q for q, _t in digits)
    strides = tuple(t for _q, t in digits)
    return tuple(canon), sizes, strides, comps[0], comps[1]


def _domain_of():
    """``rank -> failure domain`` under the current topology, or None when
    there is none (every byte then counts as intra-domain)."""
    try:
        from ..resilience import domains as _dom
        topo = _dom.topology()
    except Exception:
        return None

    def dom(r):
        try:
            return topo.domain_of(r)
        except KeyError:
            return ("uncovered", r)

    return dom


def _digit_cross_domain(canon, q, t):
    """True when some sub-group along this digit spans failure domains —
    an exchange along it rides the DCN, not fast intra-domain links."""
    dom = _domain_of()
    if dom is None:
        return False
    nr = len(canon)
    for base in range(nr):
        if (base // t) % q:
            continue                         # not a group anchor
        if len({dom(canon[base + v * t]) for v in range(q)}) > 1:
            return True
    return False


def _schedule_chain(sizes, src_comp, dst_comp, cross):
    """Ordered ``(kind, digit, src_dim, dst_dim)`` ops transforming the
    source composites into the destination composites.  When several
    exchanges are simultaneously legal, intra-domain ones go first (the
    hierarchical tier: fast links early, the cross-domain residue
    coalesces into the fewest late exchanges)."""
    state = [list(c) for c in src_comp]
    target = [list(c) for c in dst_comp]
    loc = {m: (j, k) for j, c in enumerate(dst_comp)
           for k, m in enumerate(c)}
    ops = []
    for _ in range(4 * len(sizes) + 4):
        if state == target:
            return ops
        cands = []
        for i, st in enumerate(state):
            if not st:
                continue
            m = st[-1]
            at = loc.get(m)
            if at is not None:
                j, k = at
                if j != i and len(state[j]) == k and \
                        state[j] == target[j][:k]:
                    cands.append((cross.get(m, False), i,
                                  ("a2a", m, i, j)))
        if cands:
            op = min(cands)[2]
            _kind, m, i, j = op
            state[i].pop()
            state[j].append(m)
            ops.append(op)
            continue
        placed = {m for st in state for m in st}
        progressed = False
        for j, tg in enumerate(target):
            k = len(state[j])
            if k < len(tg) and state[j] == tg[:k] and tg[k] not in placed:
                ops.append(("slice", tg[k], None, j))
                state[j].append(tg[k])
                progressed = True
                break
        if progressed:
            continue
        # unblock first: if some digit could a2a into dim j but j's tail
        # holds extra digits past the correct prefix, gathering j's tail
        # enables the cheaper exchange (gather+a2a beats gather+gather
        # for a mesh-axis transpose)
        for i, st in enumerate(state):
            if not st:
                continue
            at = loc.get(st[-1])
            if at is None:
                continue
            j, k = at
            if j != i and len(state[j]) > k and \
                    state[j][:k] == target[j][:k]:
                ops.append(("gather", state[j][-1], j, None))
                state[j].pop()
                progressed = True
                break
        if progressed:
            continue
        for i, st in enumerate(state):
            if st and st != target[i][:len(st)]:
                ops.append(("gather", st[-1], i, None))
                st.pop()
                progressed = True
                break
        if not progressed:
            return None
    return None


def _unravel(flat, radices):
    """Row-major digits of ``flat`` over ``radices``.  With
    :func:`_ravel`, the index arithmetic of the block exchange: on numpy
    arrays over all ranks at plan time, on traced scalars inside the
    compiled program."""
    out = []
    for q in reversed(radices):
        out.append(flat % q)
        flat = flat // q
    return out[::-1]


def _ravel(digits, radices):
    """Row-major flat index of ``digits`` over ``radices``."""
    flat = 0
    for v, q in zip(digits, radices):
        flat = flat * q + v
    return flat


def _exchange_ratios(sizes, a_comp, b_comp, shape=None):
    """``(r, s)`` of a direct block exchange between two composite
    layouts of one refined mesh: per dim, ``r`` pieces of a source block
    go to ``r`` different destination blocks (the dim gains digits) and
    ``s`` pieces from ``s`` source blocks fill a destination block (it
    loses digits).  None when the exchange does not apply: the two
    states place different digits (net replication is a ``gather``'s or
    a ``slice``'s job), or a dim's block counts (or, where the array's
    ``shape`` is given, its extent) do not divide, so the pieces
    ``source block ∩ destination block`` have no one shape."""
    if sorted(m for c in a_comp for m in c) != \
            sorted(m for c in b_comp for m in c):
        return None
    r, s = [], []
    for d, (ca, cb) in enumerate(zip(a_comp, b_comp)):
        pa = math.prod(sizes[m] for m in ca)
        pb = math.prod(sizes[m] for m in cb)
        if (pa % pb and pb % pa) or \
                (shape is not None and shape[d] % max(pa, pb)):
            return None
        r.append(max(pb // pa, 1))
        s.append(max(pa // pb, 1))
    return tuple(r), tuple(s)


def _exchange_slots(sizes, a_comp, b_comp, r, s, coords):
    """What a rank at ``coords`` needs to take part in every round:
    its source block index per dim, the flat slot ``j`` that every piece
    it sends fills in its destination block, and the flat index ``k``
    that every piece it receives has in its source block.  Round ``t``
    carries the pieces with ``(k + j) % rounds == t``: the pieces a rank
    sends differ in ``k`` and those it receives differ in ``j``, so in a
    round each rank sends at most one piece and receives at most one."""
    def block(comp):        # per dim, the block index its digits spell
        return [_ravel([coords[m] for m in c], [sizes[m] for m in c])
                for c in comp]

    a, b = block(a_comp), block(b_comp)
    j = _ravel([ad % sd for ad, sd in zip(a, s)], s)
    k = _ravel([bd % rd for bd, rd in zip(b, r)], r)
    return a, j, k


@functools.lru_cache(maxsize=256)
def _exchange_dests(sizes, a_comp, b_comp, r, s):
    """Per round, the receiving rank (linear index over the refined
    mesh) of the piece each rank sends — None unless every round is a
    permutation of the ranks."""
    nr = math.prod(sizes)
    coords = list(np.indices(sizes).reshape(len(sizes), nr))
    a, j, _k = _exchange_slots(sizes, a_comp, b_comp, r, s, coords)
    rounds = math.prod(r)
    dests = []
    for t in range(rounds):
        k = _unravel((t - j) % rounds, r)
        to = list(coords)                    # digits neither side places
        for d, cb in enumerate(b_comp):      # stay: the exchange runs in
            blk = (a[d] * r[d] + k[d]) // s[d]     # each replica group
            for m, v in zip(cb, _unravel(blk, [sizes[m] for m in cb])):
                to[m] = v
        dest = _ravel(to, sizes)
        if len(set(dest.tolist())) != nr:
            return None
        dests.append(tuple(int(v) for v in dest))
    return tuple(dests)


_MAX_ROUTES = 16        # shortest routes weighed for one piece


def _device_coords(mesh):
    """For each rank of ``mesh`` (row-major) the physical coordinates of
    its chip, as plain tuples.  None where the devices report none (CPU,
    interpret), where two ranks share coordinates (cores of one chip,
    chips of different slices), and where the chips are not the four of
    a 2x2: which links join the ranks, and which of them XLA takes, has
    been read on that slice alone (PERF.md, PRs 26 and 34), so every
    other one keeps XLA's routes until it can be measured."""
    coords = []
    for dev in mesh.devices.flat:
        c = getattr(dev, "coords", None)
        if c is None:
            return None
        coords.append(tuple(int(v) for v in c))
    extents = sorted(len({c[ax] for c in coords})
                     for ax in range(len(coords[0])))
    if len(set(coords)) != len(coords) or len(coords) != 4 \
            or extents[-2:] != [2, 2]:
        return None
    return tuple(coords)


def _dimension_ordered(a, b):
    """The chips a piece passes from ``a`` to ``b`` when XLA routes its
    ``collective-permute``: one axis after the other, the first axis
    first (read on the 2x2: 1->0->2 and 2->3->1, PERF.md, PR 26)."""
    path, cur = [a], list(a)
    for ax in range(len(a)):
        while cur[ax] != b[ax]:
            cur[ax] += 1 if b[ax] > cur[ax] else -1
            path.append(tuple(cur))
    return tuple(path)


def _shortest_routes(a, b, chips):
    """Every shortest route from ``a`` to ``b`` over ``chips``: a step
    along one axis at a time, always towards ``b``.  ``coords`` say
    nothing of wrap-around links, so none is assumed."""
    if a == b:
        yield (a,)
        return
    for ax in range(len(a)):
        if a[ax] != b[ax]:
            nxt = a[:ax] + (a[ax] + (1 if b[ax] > a[ax] else -1),) \
                + a[ax + 1:]
            if nxt in chips:
                for rest in _shortest_routes(nxt, b, chips):
                    yield (a,) + rest


def _links(route):
    """The directed links a route crosses."""
    return set(zip(route, route[1:]))


def _route_exchange(dests, coords):
    """Choose, per piece of a block exchange, the route its bytes take
    over the chips' links.  ``dests`` are :func:`_exchange_dests`'
    rounds, ``coords`` each rank's chip (:func:`_device_coords`).  All
    pieces have one size and all rounds are in flight together, so a
    directed link's load is the number of pieces that cross it in any
    round.  A piece between neighbours (one step on one axis) stays a
    direct ``ppermute``; so does one that a torus would send the other
    way round an axis (more than half its extent: whether that link
    exists cannot be seen).  Every other piece starts on the route XLA
    gives it (:func:`_dimension_ordered`) and is moved to another
    shortest route through chips of this mesh as long as that takes it
    off a busiest link without making another link as busy: a descent,
    which never raises the busiest link's load.  Ties go to the direct
    ``ppermute``.

    Returns ``(relays, direct_load, load)``: per round the chains of
    ranks ``(source, relay, ..., destination)`` of the pieces to relay
    hop by hop, and the busiest link's load in pieces under XLA's routes
    and under the chosen ones.  No relay unless the load falls; without
    ``coords`` no relay and loads of 0."""
    none = ((),) * len(dests)
    if coords is None:
        return none, 0, 0
    chips = {c: rank for rank, c in enumerate(coords)}
    extent = [max(c[ax] for c in coords) - min(c[ax] for c in coords) + 1
              for ax in range(len(coords[0]))]
    load = collections.Counter()
    pieces = {}         # (round, source) -> [XLA's route, chosen, others]
    for t, dest in enumerate(dests):
        for src, dst in enumerate(dest):
            a, b = coords[src], coords[dst]
            direct = _dimension_ordered(a, b)
            load.update(_links(direct))
            if len(direct) > 2 and all(
                    2 * abs(x - y) <= e for x, y, e in zip(a, b, extent)):
                others = [r for r in itertools.islice(
                    _shortest_routes(a, b, chips), _MAX_ROUTES)
                    if r != direct]
                if others:
                    pieces[t, src] = [direct, direct, others]
    direct_load = max(load.values(), default=0)

    def move(piece, route, below, off=None):
        """Put ``piece`` on ``route`` if that takes it off a link that
        carries ``off`` pieces (where given) and every link new to it
        stays under ``below``."""
        old, new = _links(piece[1]) - _links(route), \
            _links(route) - _links(piece[1])
        if any(load[ln] + 1 >= below for ln in new) or \
                (off is not None and all(load[ln] != off for ln in old)):
            return False
        load.subtract(old)
        load.update(new)
        piece[1] = route
        return True

    # each move lowers (busiest load, links that busy): it ends
    moved = True
    while moved:
        moved = False
        top = max(load.values(), default=0)
        for piece in pieces.values():
            for route in [piece[0]] + piece[2]:
                moved |= move(piece, route, top, off=top)
    top = max(load.values(), default=0)
    if top >= direct_load:
        return none, direct_load, direct_load
    relays = [[] for _ in dests]
    for (t, _src), piece in pieces.items():
        # ties go to the direct ppermute
        if piece[1] != piece[0] and not move(piece, piece[0], top + 1):
            relays[t].append(tuple(chips[c] for c in piece[1]))
    return tuple(tuple(r) for r in relays), direct_load, top


def _hop_groups(pairs, relays):
    """Pack a round's transfers into ``ppermute``s.  ``pairs`` are its
    direct ``(source, destination)`` pairs, ``relays`` its chains from
    :func:`_route_exchange`.  A group is a list of levels
    ``(pairs, ends)``: level ``h`` is one ``ppermute`` that carries what
    level ``h - 1`` delivered one hop on (level 0 carries the ranks' own
    pieces), and ``ends`` are the ranks whose piece has arrived with it.
    A chain's hops join the first group in which every level stays a
    partial permutation (a rank sends at most once and receives at most
    once), the direct pairs' group first, and open a group where none
    does."""
    groups = [[(list(pairs), [to for _c, to in pairs])]] if pairs else []
    for chain in relays:
        hops = list(zip(chain, chain[1:]))
        for g in groups:
            if not any(s == a or d == b
                       for (s, d), (prs, _e) in zip(hops, g)
                       for a, b in prs):
                break
        else:
            g = []
            groups.append(g)
        g.extend(([], []) for _ in range(len(hops) - len(g)))
        for hop, (prs, _e) in zip(hops, g):
            prs.append(hop)
        g[len(hops) - 1][1].append(chain[-1])
    return groups


def _fuse_detours(ops, sizes, src_comp, work):
    """Replace every detour of the schedule — a digit gathered and later
    sliced back, with whatever runs in between — by ONE ``exchange`` op
    from the state before the gather to the state after the slice.  The
    detour moves whole blocks to drop most of them; the exchange moves
    only the pieces that change owner.  Ops outside a detour, and a
    detour the exchange cannot express, stay as scheduled."""
    states = [tuple(tuple(c) for c in src_comp)]
    for kind, m, i, j in ops:
        st = [list(c) for c in states[-1]]
        if kind in ("a2a", "gather"):
            st[i].pop()
        if kind in ("a2a", "slice"):
            st[j].append(m)
        states.append(tuple(tuple(c) for c in st))
    out, g = [], 0
    while g < len(ops):
        end = g
        while ops[g][0] == "gather":
            # the detour ends at the last slice of a digit gathered in it
            gathered = {op[1] for op in ops[g:end + 1] if op[0] == "gather"}
            last = max((e for e in range(end + 1, len(ops))
                        if ops[e][0] == "slice" and ops[e][1] in gathered),
                       default=end)
            if last == end:
                break
            end = last
        if end > g:
            a, b = states[g], states[end + 1]
            ratios = _exchange_ratios(sizes, a, b, work)
            if ratios is not None and \
                    _exchange_dests(sizes, a, b, *ratios) is not None:
                out.append(("exchange", None, a, b))
                g = end + 1
                continue
        out.append(ops[g])
        g += 1
    return out


def _pick_step_chunking(local, itemsize, concat_dim, split_dim, q,
                        chunk_target):
    """(chunk_axis, nchunks) for one chain step — :func:`_pick_chunking`
    over the step's evolving LOCAL shape.  -1 = unchunked."""
    lbytes = math.prod(local) * itemsize
    want = -(-lbytes // chunk_target)
    if want <= 1:
        return -1, 1
    cands = []
    for d in range(len(local)):
        if d == concat_dim:
            continue
        units = local[d] // q if d == split_dim else local[d]
        if units > 1:
            cands.append((units, d))
    if not cands:
        return -1, 1
    # of equally long axes the major one: its chunks are contiguous
    units, axis = max(cands, key=lambda c: (c[0], -c[1]))
    return axis, _smallest_divisor_at_least(units, min(want, units))


def _chain_steps(shape, itemsize, sizes, strides, src_comp, ops, canon,
                 cross, chunk_target):
    """Resolve scheduled ops into executable steps with per-step
    chunking, moved bytes, the staging high-water, and the intra/cross
    domain byte split."""
    nr = len(canon)
    local = [shape[d] // math.prod([sizes[m] for m in src_comp[d]] or [1])
             for d in range(len(shape))]
    steps = []
    moved = staging = intra = crossb = 0
    dom = _domain_of()
    for kind, m, i, j in ops:
        lelems = math.prod(local) if local else 1
        if kind == "exchange":
            # i, j are the composites before and after; the piece is what
            # one rank sends one peer, and the only transient
            r, s = _exchange_ratios(sizes, i, j)
            dests = _exchange_dests(sizes, i, j, r, s)
            piece = [n // rd for n, rd in zip(local, r)]
            ca, nc = _pick_step_chunking(piece, itemsize, None, None, 1,
                                         chunk_target)
            pbytes = math.prod(piece) * itemsize
            sent = [(c, to) for dest in dests for c, to in enumerate(dest)
                    if to != c]
            far = sum(1 for c, to in sent
                      if dom is not None and dom(canon[c]) != dom(canon[to]))
            mstep = len(sent) * pbytes
            moved += mstep
            crossb += far * pbytes
            intra += (len(sent) - far) * pbytes
            staging = max(staging, -(-pbytes // nc))
            local = [p * sd for p, sd in zip(piece, s)]
            steps.append((kind, -1, len(dests), i, j, ca, nc, mstep))
            continue
        q = sizes[m]
        ca, nc, mstep, stg = -1, 1, 0, 0
        if kind == "a2a":
            ca, nc = _pick_step_chunking(local, itemsize, i, j, q,
                                         chunk_target)
            mstep = nr * (lelems - lelems // q) * itemsize
            local[i] *= q
            local[j] //= q
            stg = -(-(lelems * itemsize) // max(nc, 1))
        elif kind == "gather":
            # the transient is the GATHERED output (q x the input), so
            # both the chunk count and the staging watermark budget
            # against the post-gather local shape
            local[i] *= q
            ca, nc = _pick_step_chunking(local, itemsize, i, None, q,
                                         chunk_target)
            mstep = nr * lelems * (q - 1) * itemsize
            stg = -(-(lelems * q * itemsize) // max(nc, 1))
        else:                                # slice: no comm, no staging
            local[j] //= q
        moved += mstep
        staging = max(staging, stg)
        if cross.get(m, False) and kind != "slice":
            crossb += mstep
        else:
            intra += mstep
        steps.append((kind, m, q, -1 if i is None else i,
                      -1 if j is None else j, ca, nc, mstep))
    return tuple(steps), moved, staging, intra, crossb


def _try_chain(shape, itemsize, s_grid, s_own, d_grid, d_own, total,
               chunk_target, pad_shape=()):
    """A ``chain`` plan for the even general case (on ``pad_shape``, the
    even analog, when the real layouts are ceil-uneven) — None when the
    layouts don't share a mixed-radix refinement."""
    work = tuple(pad_shape) or tuple(shape)
    dig = _digitize(len(work), s_grid, s_own, d_grid, d_own)
    if dig is None:
        return None
    canon, sizes, strides, src_comp, dst_comp = dig
    if not sizes:
        return None
    for comp in (src_comp, dst_comp):
        for d in range(len(work)):
            if work[d] % math.prod([sizes[m] for m in comp[d]] or [1]):
                return None
    cross = {m: _digit_cross_domain(canon, sizes[m], strides[m])
             for m in range(len(sizes))}
    ops = _schedule_chain(sizes, src_comp, dst_comp, cross)
    if not ops:
        return None
    ops = _fuse_detours(ops, sizes, src_comp, work)
    steps, moved, staging, intra, crossb = _chain_steps(
        work, itemsize, sizes, strides, src_comp, ops, canon, cross,
        chunk_target)
    return ReshardPlan("chain", tuple(shape), itemsize, moved, total,
                       nparts=len(canon), ranks=canon,
                       nchunks=max(s[6] for s in steps),
                       steps=steps, mesh_shape=sizes, src_comp=src_comp,
                       dst_comp=dst_comp,
                       pad_shape=tuple(pad_shape)
                       if tuple(pad_shape) != tuple(shape) else (),
                       staging_bytes=staging, intra_bytes=intra,
                       cross_bytes=crossb)


def _try_pad_chain(shape, itemsize, s_cuts, s_own, d_cuts, d_own, total,
                   chunk_target):
    """Start-aligned ceil-uneven layouts whose per-dim pads agree: run
    the even chain on the padded analog between a comm-free pad and
    slice-back (ceil cuts put every pad byte on the trailing shard)."""
    pad = []
    for d, n in enumerate(shape):
        need = None
        for cuts in (s_cuts[d], d_cuts[d]):
            g = len(cuts) - 1
            if g <= 1:
                continue
            c = cuts[1] - cuts[0]
            if c <= 0 or list(cuts) != [min(k * c, n) for k in range(g + 1)]:
                return None                  # not start-aligned ceil cuts
            want = g * c
            if need is None:
                need = want
            elif need != want:
                return None                  # the sides' pads disagree
        pad.append(need if need is not None else n)
    if tuple(pad) == tuple(shape):
        return None                          # actually even: not ours
    return _try_chain(shape, itemsize, _grid_of(s_cuts), s_own,
                      _grid_of(d_cuts), d_own, total, chunk_target,
                      pad_shape=tuple(pad))


def _try_gather_put(shape, itemsize, s_grid, s_own, d_own, total,
                    chunk_target):
    """Device-set-shrinking moves (elastic re-layout): when the
    destination is replicated enough — fewer blocks than ranks, the
    signature of ``layout.sharding_for``'s divisibility rule after an
    uneven shrink — gather collectively ON the source mesh, then
    restrict to the survivors with a comm-free device_put (every
    survivor already holds the bytes)."""
    s_ranks = sorted({r for o in s_own.values() for r in o})
    d_ranks = {r for o in d_own.values() for r in o}
    if not d_ranks < set(s_ranks):
        return None
    if len(d_own) >= len(d_ranks):
        return None                  # properly sharded: device_put wins
    ndim = len(shape)
    rep_own = {tuple([0] * ndim): tuple(s_ranks)}
    plan = _try_chain(shape, itemsize, s_grid, s_own,
                      tuple([1] * ndim), rep_own, total, chunk_target)
    if plan is None:
        return None
    return dataclasses.replace(plan, strategy="gather_put")


@functools.lru_cache(maxsize=512)
def _plan_cached(shape, itemsize, src_sharding, dst_sharding,
                 chunk_target) -> ReshardPlan:
    # lru-miss body: once per distinct layout pair — the cold path the
    # plan-cache counters track
    _tm.count("reshard.plan_builds")
    plan = _build_plan(shape, itemsize, src_sharding, dst_sharding,
                       chunk_target)
    if _tm.enabled():
        _tm.event("reshard", "plan", strategy=plan.strategy,
                  shape=list(shape), moved_bytes=plan.moved_bytes,
                  total_bytes=plan.total_bytes, nparts=plan.nparts,
                  nchunks=plan.nchunks, reason=plan.reason)
    return plan


def _build_plan(shape, itemsize, src, dst, chunk_target) -> ReshardPlan:
    total = math.prod(shape) * itemsize if shape else itemsize

    def fallback(reason, moved=None):
        return ReshardPlan("device_put", shape, itemsize,
                           total if moved is None else moved, total,
                           reason=reason)

    if src == dst:
        return ReshardPlan("noop", shape, itemsize, 0, total)
    try:
        s_cuts, s_own = layout_of_sharding(src, shape)
        d_cuts, d_own = layout_of_sharding(dst, shape)
        moved = _moved_elems(shape, s_cuts, s_own, d_cuts, d_own) * itemsize
    except Exception as e:                           # introspection failed
        return fallback(f"opaque layouts ({type(e).__name__})")
    s_ranks_all = {r for own in s_own.values() for r in own}
    d_ranks_all = {r for own in d_own.values() for r in own}
    s_grid, d_grid = _grid_of(s_cuts), _grid_of(d_cuts)
    # uniform start-0/end-n cuts are automatically divisible
    even = all(_uniform(c) for c in s_cuts) and \
        all(_uniform(c) for c in d_cuts)
    if s_ranks_all != d_ranks_all:
        if even and d_ranks_all < s_ranks_all:
            gp = _try_gather_put(shape, itemsize, s_grid, s_own, d_own,
                                 total, chunk_target)
            if gp is not None:
                return gp
        return fallback("device sets differ", moved)
    if not even:
        pc = _try_pad_chain(shape, itemsize, s_cuts, s_own, d_cuts, d_own,
                            total, chunk_target)
        if pc is not None:
            return pc
        if any(not _uniform(c) for c in s_cuts):
            return fallback("uneven source shards", moved)
        return fallback("uneven destination shards", moved)
    s_sh = [d for d, g in enumerate(s_grid) if g > 1]
    d_sh = [d for d, g in enumerate(d_grid) if g > 1]

    why = None
    if len(s_sh) > 1 or len(d_sh) > 1:
        why = "multi-dim chunk grid"
    elif s_sh and d_sh:
        i, j = s_sh[0], d_sh[0]
        p = s_grid[i]
        if i == j or d_grid[j] != p:
            why = "incompatible repartition widths"
        else:
            src_order = _singleton_rank_order(s_own, s_grid, i)
            dst_order = _singleton_rank_order(d_own, d_grid, j)
            if src_order is None or dst_order is None or \
                    src_order != dst_order:
                why = "replicated blocks or rank order differs"
            else:
                ca, nc = _pick_chunking(shape, itemsize, i, j, p,
                                        "all_to_all", chunk_target)
                return ReshardPlan("all_to_all", shape, itemsize, moved,
                                   total, src_dim=i, dst_dim=j, nparts=p,
                                   ranks=src_order, chunk_axis=ca,
                                   nchunks=nc)
    elif s_sh:
        i = s_sh[0]
        p = s_grid[i]
        src_order = _singleton_rank_order(s_own, s_grid, i)
        if src_order is None:
            why = "replicated source blocks"
        else:
            ca, nc = _pick_chunking(shape, itemsize, i, None, p,
                                    "all_gather", chunk_target)
            return ReshardPlan("all_gather", shape, itemsize, moved, total,
                               src_dim=i, dst_dim=None, nparts=p,
                               ranks=src_order, chunk_axis=ca, nchunks=nc)
    elif d_sh:
        j = d_sh[0]
        p = d_grid[j]
        dst_order = _singleton_rank_order(d_own, d_grid, j)
        if dst_order is None:
            why = "replicated destination blocks"
        else:
            # every dst device must already hold the (replicated) source
            src_everywhere = all(set(dst_order) <= set(own)
                                 for own in s_own.values())
            if not src_everywhere:
                why = "source not replicated on dst devices"
            else:
                return ReshardPlan("local_slice", shape, itemsize, 0,
                                   total, src_dim=None, dst_dim=j,
                                   nparts=p, ranks=dst_order)
    elif moved == 0:
        # same placement under a different sharding object: device_put is
        # a zero-copy relabel
        return fallback("placement-equal", moved=0)
    else:
        why = "no sharded dims on either side"
    # the single-collective fast paths passed: the general chain covers
    # multi-axis repartitions, mesh-axis transposes and partial
    # replication over a common mixed-radix refinement
    ch = _try_chain(shape, itemsize, s_grid, s_own, d_grid, d_own, total,
                    chunk_target)
    if ch is not None:
        return ch
    return fallback(why, moved)


def plan_reshard(x, dst_sharding, *, src_sharding=None,
                 itemsize=None) -> ReshardPlan:
    """The transfer plan for moving ``x`` (a jax.Array, or a shape tuple
    with ``src_sharding``/``itemsize`` given) onto ``dst_sharding``.
    Cached per layout pair; pure metadata — nothing moves."""
    if hasattr(x, "sharding"):
        shape = tuple(int(s) for s in x.shape)
        src_sharding = x.sharding
        try:
            itemsize = int(np.dtype(x.dtype).itemsize)
        except TypeError:
            # extended dtypes (PRNG keys) have no numpy itemsize; the
            # collective lowerings can't slice them anyway — plan the
            # counted device_put directly (bytes in element units)
            n = math.prod(shape) if shape else 1
            return ReshardPlan("device_put", shape, 1, n, n,
                               reason="extended dtype")
    else:
        shape = tuple(int(s) for s in x)
        if src_sharding is None or itemsize is None:
            raise ValueError("shape-form plan_reshard needs src_sharding "
                             "and itemsize")
    _tm.count("reshard.plan_requests")
    return _plan_cached(shape, int(itemsize), src_sharding, dst_sharding,
                        _chunk_target_bytes())


def plan_stats() -> dict:
    """Plan-cache statistics (hits/misses/size) — the `_resharder`-style
    lru the tentpole caches plans in."""
    ci = _plan_cached.cache_info()
    return {"hits": ci.hits, "misses": ci.misses, "size": ci.currsize}


# ---------------------------------------------------------------------------
# compiled lowering
# ---------------------------------------------------------------------------


def _spec_for(dim, ndim, axis):
    if dim is None:
        return P()
    return P(*[axis if d == dim else None for d in range(ndim)])


def _a2a_chunked(x, axis, split_dim, concat_dim, p, chunk_axis, nchunks):
    """Tiled all_to_all, chunked so one staging piece stays bounded.
    Chunking along the split dim pre-slices so each chunk's tiled
    exchange lands every rank the k-th contiguous slice of ITS dst block
    — plain chunking along the split dim would interleave ranks."""
    if nchunks <= 1:
        return pall_to_all(x, axis, split_dim=split_dim,
                           concat_dim=concat_dim)
    if chunk_axis == split_dim:
        jp = x.shape[split_dim] // p
        step = jp // nchunks
        outs = []
        for k in range(nchunks):
            piece = jnp.concatenate(
                [lax.slice_in_dim(x, r * jp + k * step,
                                  r * jp + (k + 1) * step,
                                  axis=split_dim)
                 for r in range(p)], axis=split_dim)
            outs.append(pall_to_all(piece, axis, split_dim=split_dim,
                                    concat_dim=concat_dim))
        return jnp.concatenate(outs, axis=split_dim)
    step = x.shape[chunk_axis] // nchunks
    outs = [pall_to_all(
        lax.slice_in_dim(x, k * step, (k + 1) * step, axis=chunk_axis),
        axis, split_dim=split_dim, concat_dim=concat_dim)
        for k in range(nchunks)]
    return jnp.concatenate(outs, axis=chunk_axis)


def _gather_chunked(x, axis, dim, chunk_axis, nchunks):
    """Tiled all_gather along ``dim``, chunked along ``chunk_axis``."""
    if nchunks <= 1:
        return pgather(x, axis, tiled=True, dim=dim)
    step = x.shape[chunk_axis] // nchunks
    outs = [pgather(
        lax.slice_in_dim(x, k * step, (k + 1) * step, axis=chunk_axis),
        axis, tiled=True, dim=dim)
        for k in range(nchunks)]
    return jnp.concatenate(outs, axis=chunk_axis)


@functools.lru_cache(maxsize=512)
def _collective_jit(mesh, strategy, ndim, src_dim, dst_dim, p,
                    chunk_axis, nchunks, rdma=None):
    """ONE compiled shard_map program for a planned single-axis
    repartition, chunked so each collective stages at most 1/nchunks of
    the local shard.  With ``rdma`` set (``"compiled"``/``"interpret"``,
    from :func:`ops.pallas_collectives.rdma_mode`) the inner exchange is
    the Pallas RDMA ring kernel instead of the XLA collective: chunk
    DMAs land directly at their output offsets (no XLA-level staging
    loop needed — the kernel double-buffers internally), overlapping
    wire time with the slice/concat work."""
    _tm.count("jit.builds", fn="reshard_collective")
    # cold path: lru-miss body, once per distinct planned program
    _tm.event("jit", "build", fn="reshard_collective",  # dalint: disable=DAL003
              strategy=strategy, nchunks=nchunks, rdma=str(rdma))
    axis = mesh.axis_names[0]
    in_spec = _spec_for(src_dim, ndim, axis)
    out_spec = _spec_for(dst_dim, ndim, axis) if strategy != "all_gather" \
        else P(*([None] * ndim))

    @jax.named_scope(f"reshard.{strategy}")
    def kernel(x):
        if rdma and strategy in ("all_to_all", "all_gather"):
            from ..ops import pallas_collectives as _pc
            interp = rdma == "interpret"
            if strategy == "all_to_all":
                return _pc.ring_all_to_all(x, axis, split_dim=dst_dim,
                                           concat_dim=src_dim,
                                           interpret=interp)
            return _pc.ring_all_gather(x, axis, dim=src_dim,
                                       interpret=interp)
        if strategy == "all_to_all":
            return _a2a_chunked(x, axis, dst_dim, src_dim, p, chunk_axis,
                                nchunks)
        if strategy == "all_gather":
            return _gather_chunked(x, axis, src_dim, chunk_axis, nchunks)
        # local_slice: replicated -> sharded, zero communication
        r = lax.axis_index(axis)
        blk = x.shape[dst_dim] // p
        return lax.dynamic_slice_in_dim(x, r * blk, blk, axis=dst_dim)

    # pallas_call has no shard_map replication rule, and the VMA check
    # types lax.all_gather's output as varying although every rank holds
    # the same gathered value: both must opt out of the check for their
    # out-spec to be accepted (the other XLA variants keep it)
    opt_out = bool(rdma) or strategy == "all_gather"
    return jax.jit(shard_map_compat(kernel, mesh, in_spec, out_spec,
                                    check=False if opt_out else None))


def _run_collective(x, dst_sharding, plan: ReshardPlan, rdma=None):
    with _tm.span("reshard.program", _journal=False):
        mesh = L.mesh_for(list(plan.ranks), (plan.nparts,))
        fn = _collective_jit(mesh, plan.strategy, len(plan.shape),
                             plan.src_dim, plan.dst_dim, plan.nparts,
                             plan.chunk_axis, plan.nchunks, rdma)
    with _tm.span("reshard.dispatch", _journal=False):
        y = fn(x)
        if y.sharding != dst_sharding:
            # equivalent placement under the caller's sharding object —
            # zero-copy relabel
            y = jax.device_put(y, dst_sharding)
    return y


def _comp_spec(comp, ndim):
    """PartitionSpec from per-dim mesh-axis composites (indices into the
    refined mesh's ``d{i}`` axis names, major→minor)."""
    entries = []
    for d in range(ndim):
        c = comp[d] if d < len(comp) else ()
        if not c:
            entries.append(None)
        elif len(c) == 1:
            entries.append(f"d{c[0]}")
        else:
            entries.append(tuple(f"d{m}" for m in c))
    return P(*entries)


def _exchange_blocks(x, names, sizes, a_comp, b_comp, chunk_axis, nchunks,
                     route):
    """The direct block exchange of a chain, inside its shard_map body:
    per round every rank cuts the piece that leaves it out of its source
    block, one ``ppermute`` over the whole refined mesh carries the
    pieces, and each lands at its final offset in the output block.  A
    piece whose owner does not change is copied locally.  Chunked along
    ``chunk_axis`` so one transient stays under the chunk target.
    ``route`` is the step's entry of :func:`_chain_routes`: the rounds'
    destinations and, where pieces are relayed, their chains; those
    travel one ``ppermute`` a hop (:func:`_exchange_relayed`)."""
    r, s, dests, relays = route
    rounds = len(dests)
    coords = [lax.axis_index(n) for n in names]
    _a, j, k = _exchange_slots(sizes, a_comp, b_comp, r, s, coords)
    me = lax.axis_index(names)
    piece = [n // rd for n, rd in zip(x.shape, r)]
    chunk = list(piece)
    if nchunks > 1:
        chunk[chunk_axis] //= nchunks
    # every element of the output block is written by exactly one piece
    out = lax.empty(tuple(p * sd for p, sd in zip(piece, s)), x.dtype)
    sends = []
    for t, dest in enumerate(dests):
        # the piece this rank sends in round t, and the slot the piece
        # it receives fills (see _exchange_slots)
        at_src = [kd * p for kd, p in
                  zip(_unravel((t - j) % rounds, r), piece)]
        at_dst = [jd * p for jd, p in
                  zip(_unravel((t - k) % rounds, s), piece)]
        pairs = [(c, to) for c, to in enumerate(dest) if to != c]
        keeps = jnp.asarray([to == c for c, to in enumerate(dest)])[me] \
            if len(pairs) < len(dest) else None
        sends.append((at_src, at_dst, pairs, keeps))
    steps = [[c * chunk[d] if d == chunk_axis else 0 for d in range(x.ndim)]
             for c in range(nchunks)]
    if any(relays):
        return _exchange_relayed(x, names, me, len(dests[0]), sends, relays,
                                 chunk, steps, out)
    # chunk by chunk through all rounds, so that the rounds' transfers,
    # which ride different links, are in flight together.  Not the ticks
    # of _exchange_relayed: with nothing relayed their barriers order
    # nothing and cost a quarter more ((1,4)->(2,2) on the 2x2 under
    # XLA's routes: 53.0 ms against this loop's 41.7; PERF.md, PR 34)
    for step in steps:
        for at_src, at_dst, pairs, keeps in sends:
            got = part = lax.dynamic_slice(
                x, [o + e for o, e in zip(at_src, step)], chunk)
            if pairs:
                got = lax.ppermute(part, names, pairs)
                if keeps is not None:
                    got = jnp.where(keeps, part, got)
            out = lax.dynamic_update_slice(
                out, got, [o + e for o, e in zip(at_dst, step)])
    return out


def _exchange_relayed(x, names, me, nranks, sends, relays, chunk, steps,
                      out):
    """The chunk loop of :func:`_exchange_blocks` where pieces are
    relayed: a tick a chunk, and in a tick every transfer in flight goes
    one hop, so hop 2 of chunk ``c`` flies with hop 1 of chunk ``c + 1``
    and with the round of direct pairs, each on links of its own.  Left
    to XLA's scheduler two of the three streams fly and the third
    follows them, and the placing bunches at the end (PERF.md, PR 34);
    so each tick's operands pass one ``optimization_barrier`` together
    with what arrived a tick before, the output block and the source
    block.  Between two barriers lie a tick's ``ppermute``s and, under
    their wire, the placing of the earlier tick's pieces and the cutting
    of the next tick's.  A relay holds the chunk in transit and nothing
    more."""
    plans = []
    for (at_src, at_dst, pairs, keeps), chains in zip(sends, relays):
        sent = {chain[0] for chain in chains}
        groups = _hop_groups([p for p in pairs if p[0] not in sent], chains)
        arrivals = [ends for g in groups for _prs, ends in g if ends]
        pick = keeps
        if len(arrivals) > 1:
            # pieces arrive with different ppermutes: which one is this
            # rank's (0: the piece it keeps)
            which = np.zeros(nranks, np.int32)
            for n, ends in enumerate(arrivals):
                which[ends] = n + 1
            pick = jnp.asarray(which)[me]
        plans.append((at_src, at_dst, groups, len(arrivals), pick))

    def cut(x, step):
        return [lax.dynamic_slice(x, [o + e for o, e in zip(p[0], step)],
                                  chunk) for p in plans]

    def transfer(plan, part, step):
        """One chunk of one round's pieces: yields the operand of each
        ``ppermute`` and takes it back from the tick's barrier; returns
        what :func:`place` needs."""
        _at_src, at_dst, groups, narrive, pick = plan
        arrived = []
        for g in groups:
            carry = part
            for prs, ends in g:
                carry = lax.ppermute((yield carry), names, prs)
                if ends:
                    arrived.append(carry)
        return part, arrived, narrive, pick, \
            [o + e for o, e in zip(at_dst, step)]

    def place(out, part, arrived, narrive, pick, at):
        got = part
        if narrive > 1:
            got = lax.select_n(pick, part, *arrived)
        elif narrive:
            got = arrived[0]
            if pick is not None:
                got = jnp.where(pick, part, got)
        return lax.dynamic_update_slice(out, got, at)

    flying = []         # [transfer, operand of its next ppermute]
    due = []            # what the transfers that ended a tick ago return
    parts = cut(x, steps[0])
    tick = 0
    while tick < len(steps) or flying or due:
        ended = []
        if tick < len(steps):
            for plan, part in zip(plans, parts):
                f = transfer(plan, part, steps[tick])
                try:
                    flying.append([f, next(f)])
                except StopIteration as e:   # every rank keeps its piece
                    ended.append(e.value)
        tick += 1
        ops, held, out, x = lax.optimization_barrier(
            ([op for _f, op in flying], [d[:2] for d in due], out, x))
        still = []
        for (f, _op), op in zip(flying, ops):
            try:
                still.append([f, f.send(op)])
            except StopIteration as e:
                ended.append(e.value)
        flying = still
        for (part, arrived), d in zip(held, due):
            out = place(out, part, list(arrived), *d[2:])
        if tick < len(steps):
            parts = cut(x, steps[tick])
        due = ended
    return out


@functools.lru_cache(maxsize=512)
def _chain_routes(mesh, steps):
    """What a planned chain's ``exchange`` steps send where, decided
    once for the program that emits it and the counter that reports it:
    per step (None for the other kinds) ``(r, s, dests, relays)`` for
    :func:`_exchange_blocks`, then the number of pieces the chain sends
    by relay, and the busiest link's load in pieces under XLA's routes
    and under the chosen ones (:func:`_route_exchange`)."""
    coords = _device_coords(mesh)
    routes, relayed, direct_load, load = [], 0, 0, 0
    for step in steps:
        if step[0] != "exchange":
            routes.append(None)
            continue
        r, s = _exchange_ratios(mesh.axis_sizes, step[3], step[4])
        dests = _exchange_dests(mesh.axis_sizes, step[3], step[4], r, s)
        relays, was, now = _route_exchange(dests, coords)
        routes.append((r, s, dests, relays))
        relayed += sum(len(chains) for chains in relays)
        direct_load, load = max(direct_load, was), max(load, now)
    return tuple(routes), relayed, direct_load, load


@functools.lru_cache(maxsize=512)
def _chain_jit(mesh, ndim, src_comp, dst_comp, steps, rdma=None):
    """ONE compiled shard_map program running a planned per-axis
    collective chain over the refined device mesh — the general lowering
    (arXiv 2112.01075's per-axis decomposition).  With ``rdma`` set the
    a2a/gather steps ride the Pallas RDMA ring kernels with
    mesh-coordinate device ids (``mesh_axes``) when the mesh is
    multi-axis; interpret mode demotes multi-axis arming to the lax
    fallback inside the kernel, so CPU runs stay correct."""
    _tm.count("jit.builds", fn="reshard_chain")
    in_spec = _comp_spec(src_comp, ndim)
    out_spec = _comp_spec(dst_comp, ndim)
    names = mesh.axis_names
    mesh_axes = tuple(names) if len(names) > 1 else None
    routes, _relayed, direct_load, load = _chain_routes(mesh, steps)
    # cold path: lru-miss body, once per distinct planned chain
    _tm.event("jit", "build", fn="reshard_chain",  # dalint: disable=DAL003
              steps=len(steps), rdma=str(rdma),
              link_load_direct=direct_load, link_load=load)

    @jax.named_scope("reshard.chain")
    def kernel(x):
        from ..ops import pallas_collectives as _pc
        for n, (kind, m, q, i, j, ca, nc) in enumerate(
                s[:7] for s in steps):
            name = f"d{m}"
            with jax.named_scope(f"step{n}.{kind}"):
                if kind == "a2a":
                    if rdma:
                        x = _pc.ring_all_to_all(
                            x, name, split_dim=j, concat_dim=i,
                            interpret=rdma == "interpret",
                            mesh_axes=mesh_axes)
                    else:
                        x = _a2a_chunked(x, name, j, i, q,
                                         ca if ca >= 0 else None, nc)
                elif kind == "gather":
                    if rdma:
                        x = _pc.ring_all_gather(
                            x, name, dim=i, interpret=rdma == "interpret",
                            mesh_axes=mesh_axes)
                    else:
                        x = _gather_chunked(x, name, i,
                                            ca if ca >= 0 else None, nc)
                elif kind == "exchange":
                    # i, j: the composites before and after; ppermute
                    # whether or not the ring kernels are armed
                    x = _exchange_blocks(x, tuple(names), mesh.axis_sizes,
                                         i, j, ca, nc, routes[n])
                else:                        # slice: local, no comm
                    r = lax.axis_index(name)
                    blk = x.shape[j] // q
                    x = lax.dynamic_slice_in_dim(x, r * blk, blk, axis=j)
        return x

    # composite specs + optional pallas_call inside: opt out of the
    # replication check (multi-axis inference has no rule for either)
    return jax.jit(shard_map_compat(kernel, mesh, in_spec, out_spec,
                                    check=False))


@functools.lru_cache(maxsize=256)
def _pad_jit(mesh, src_comp, shape, pad_shape):
    """Compiled ceil-pad: grow each uneven dim to its even analog under
    the same placement — ceil cuts put every pad byte on the trailing
    shard, so nothing crosses a device."""
    _tm.count("jit.builds", fn="reshard_pad")
    widths = tuple((0, p - s) for s, p in zip(shape, pad_shape))
    out = NamedSharding(mesh, _comp_spec(src_comp, len(shape)))
    return jax.jit(lambda x: jnp.pad(x, widths), out_shardings=out)


@functools.lru_cache(maxsize=256)
def _slice_back_jit(dst_sharding, shape):
    """Compiled slice from the even analog back to the logical extent,
    placed under the caller's (ceil-uneven) destination sharding."""
    _tm.count("jit.builds", fn="reshard_slice")
    idx = tuple(slice(0, s) for s in shape)
    return jax.jit(lambda y: y[idx], out_shardings=dst_sharding)


def _run_chain(x, dst_sharding, plan: ReshardPlan, rdma=None):
    with _tm.span("reshard.program", _journal=False):
        mesh = L.mesh_for(list(plan.ranks), plan.mesh_shape)
        fn = _chain_jit(mesh, len(plan.shape), plan.src_comp,
                        plan.dst_comp, plan.steps, rdma)
    with _tm.span("reshard.dispatch", _journal=False):
        if plan.pad_shape:
            x = _pad_jit(mesh, plan.src_comp, plan.shape,
                         plan.pad_shape)(x)
        y = fn(x)
        for step in plan.steps:
            _tm.count("reshard.chain_steps", kind=step[0])
        relayed = _chain_routes(mesh, plan.steps)[1]
        if relayed:
            _tm.count("reshard.exchange_relayed", relayed)
        if plan.pad_shape:
            return _slice_back_jit(dst_sharding, plan.shape)(y)
        if plan.strategy == "gather_put":
            # restrict the now-replicated buffer to the survivor subset —
            # comm-free: every destination device already holds the bytes
            return _device_put_path(y, dst_sharding)
        if y.sharding != dst_sharding:
            y = jax.device_put(y, dst_sharding)
        return y


@functools.lru_cache(maxsize=None)
def _resharder(sharding):
    """Compiled identity program placing its input under ``sharding`` —
    the fallback mover (and the multi-controller-legal one: XLA inserts
    the DCN/ICI collective; eager device_put cannot cross hosts)."""
    _tm.count("jit.builds", fn="resharder")
    # cold path: lru-miss body, once per distinct target sharding
    _tm.event("jit", "build", fn="resharder",  # dalint: disable=DAL003
              to=str(sharding))
    return jax.jit(lambda x: x, out_shardings=sharding)


def _device_put_path(x, dst_sharding):
    if getattr(x, "size", 1) == 0:
        # XLA rejects out_shardings on zero-element results; device_put
        # places them fine
        return jax.device_put(x, dst_sharding)
    if isinstance(x, jax.Array) and \
            not getattr(dst_sharding, "is_fully_addressable", True) and \
            getattr(x.sharding, "device_set", None) == \
            dst_sharding.device_set:
        # process-spanning move: eager device_put cannot cross hosts —
        # the compiled identity program can (XLA inserts the collective)
        return _resharder(dst_sharding)(x)
    return jax.device_put(x, dst_sharding)


def _fallback_reason(reason: str) -> str:
    """Canonical residue class for the ``reason=`` label on
    ``reshard.collective_fallbacks`` — why a move still falls back
    (uneven | multi_axis | device_set | dtype | shape)."""
    r = reason.lower()
    if "uneven" in r or "divisible" in r:
        return "uneven"
    if "device set" in r or "not replicated on dst" in r:
        return "device_set"
    if "dtype" in r:
        return "dtype"
    if "multi-dim" in r or "incompatible" in r or "rank order" in r \
            or "replicated" in r:
        return "multi_axis"
    return "shape"


def reshard(x, dst_sharding, *, op: str = "reshard",
            plan: ReshardPlan | None = None):
    """Move ``x`` onto ``dst_sharding`` via the planned strategy.

    The single funnel for cross-sharding data movement (DAL007): plans
    are cached per layout pair, divisible single-axis repartitions run as
    one compiled chunked-collective program, the general case runs the
    per-axis collective chain over the refined mesh, and the residue
    takes the ``device_put`` path (counted, with a canonical ``reason=``
    label).  Telemetry: a ``reshard`` span labeled with the strategy and
    the plan's ``intra_bytes``/``cross_bytes`` domain split, and comm
    bytes = the plan's *moved* bytes (what must cross a device
    boundary), not the whole array."""
    if getattr(x, "sharding", None) == dst_sharding:
        return x
    # host phase 1 of a leg: the plan (cached per layout pair) and the
    # dispatch it resolves to; phases 2 and 3 are in _run_collective /
    # _run_chain, phase 4 is the caller wrapping the result
    with _tm.span("reshard.plan", _journal=False):
        if plan is None:
            plan = plan_reshard(x, dst_sharding)
        if plan.strategy == "noop":
            return x
        if plan.collective:
            try:
                ext = jax.dtypes.issubdtype(getattr(x, "dtype", None),
                                            jax.dtypes.extended)
            except Exception:
                ext = False
            if ext:
                # extended dtypes (PRNG key arrays) have no collective
                # lowering — planned from shardings alone, gated on dtype
                # here
                plan = dataclasses.replace(plan, strategy="device_put",
                                           reason="extended dtype")
        # RDMA dispatch decided eagerly so the compiled program is keyed on
        # it (flipping DA_TPU_RDMA re-jits) and the span says which path ran
        rdma = None
        rdma_chunks = 0
        chunks_src = rdma_inflight = ""
        if any(s[0] in ("a2a", "gather") for s in plan.steps):
            # a2a and gather steps ride the ring kernels when the platform
            # arms them (mesh-coordinate addressing on multi-axis meshes);
            # an exchange is ppermutes either way and a slice is local, so
            # a chain of those alone ran "xla" whatever is armed
            from ..ops import pallas_collectives as _pc
            rdma = _pc.rdma_mode()
        elif plan.collective and plan.strategy in ("all_to_all", "all_gather"):
            from ..ops import pallas_collectives as _pc
            rdma = _pc.rdma_mode()
            if rdma and plan.strategy == "all_to_all":
                dtype_str = str(getattr(x, "dtype", "float32"))
                lshape = tuple(s // plan.nparts if d == plan.src_dim else s
                               for d, s in enumerate(plan.shape))
                # the kernel concats along the plan's src dim; clamping here
                # keeps the span's label equal to the depth it runs
                rdma_chunks, chunks_src = _pc.a2a_chunks_for(
                    lshape, dtype_str, plan.nparts, plan.src_dim)
                rdma_inflight = _pc.a2a_inflight(plan.nparts, rdma_chunks)
    with _tm.span("reshard", op=op, strategy=plan.strategy,
                  dispatch="rdma" if rdma else "xla",
                  rdma_chunks=rdma_chunks, rdma_chunks_source=chunks_src,
                  rdma_inflight=rdma_inflight, shape=list(plan.shape),
                  dtype=str(getattr(x, "dtype", "float32")),
                  src_dim=plan.src_dim, dst_dim=plan.dst_dim,
                  nparts=plan.nparts, nsteps=len(plan.steps),
                  # hierarchical-tier provenance: how many of the moved
                  # bytes stay on fast intra-domain links vs cross the DCN
                  intra_bytes=plan.intra_bytes,
                  cross_bytes=plan.cross_bytes):
        if plan.collective:
            # chaos site: an armed fault plan can abort the planned
            # collective here — mid-reshard, before any chunk moves, so
            # the source buffer is still intact for the retry
            _fl.check("reshard.chunk", strategy=plan.strategy, op=op)
            try:
                # staging high-water: one chunk piece of the local shard
                # is what the chunked lowering stages per device.  This
                # is PLAN-DERIVED (XLA's internal staging buffers are not
                # jax-observable) — it audits the chunking the planner
                # actually chose (nchunks) against the
                # DA_TPU_RESHARD_CHUNK_MB budget, catching selection
                # regressions, not compiled-program memory use
                local = plan.total_bytes // max(plan.nparts, 1)
                piece = -(-local // max(plan.nchunks, 1))
                if plan.staging_bytes:
                    # chain: the planner pre-computed the worst step's
                    # staging piece over the evolving local shape
                    piece = plan.staging_bytes
                if rdma and plan.strategy == "all_to_all":
                    # the RDMA ring lands chunk DMAs at their final
                    # output offsets; what stages per device is one
                    # in-flight chunk window, not an XLA concat buffer
                    piece = min(piece,
                                -(-local // max(rdma_chunks, 1)))
                with _tm.memory.staging(f"reshard.{plan.strategy}", piece):
                    if plan.steps:
                        out = _run_chain(x, dst_sharding, plan, rdma)
                    else:
                        out = _run_collective(x, dst_sharding, plan, rdma)
                if _tm.enabled():
                    _tm.record_comm("reshard", plan.moved_bytes, op=op,
                                    strategy=plan.strategy,
                                    dispatch="rdma" if rdma else "xla",
                                    shape=list(plan.shape))
                return out
            except Exception as e:
                # the compiled path must never cost correctness; fall
                # through to device_put, loudly once per signature
                _tm.count("reshard.collective_fallbacks", reason="runtime")
                from ..utils.debug import warn_once
                warn_once(
                    f"reshard:{plan.strategy}:{type(e).__name__}",
                    f"reshard: compiled {plan.strategy} lowering failed "
                    f"({type(e).__name__}: {e}); falling back to "
                    f"device_put")
        if plan.strategy == "device_put" and plan.moved_bytes:
            # the residue: why does this move still fall back?
            # (placement-equal relabels move nothing and are not one)
            _tm.count("reshard.collective_fallbacks",
                      reason=_fallback_reason(plan.reason))
        if _tm.enabled():
            _tm.record_comm("reshard", plan.moved_bytes, op=op,
                            strategy="device_put", shape=list(plan.shape))
        return _device_put_path(x, dst_sharding)
