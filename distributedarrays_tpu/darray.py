"""The DArray: a global-view distributed array backed by a sharded jax.Array.

TPU-native re-design of /root/reference/src/darray.jl (834 LoC).  The
reference keeps per-worker chunks in remote Julia processes and stitches them
together with eager RPC; here the *global* array is a single ``jax.Array``
laid out across the device mesh by ``NamedSharding``, and every operation is
a traced/compiled XLA program over it — communication is compiler-inserted
collectives over ICI, not messages.

What survives from the reference is the user-visible layout model
(darray.jl:25-55): an explicit N-D chunk grid (``pids``), per-chunk global
index ranges (``indices``), per-dimension cut vectors (``cuts``), uneven
chunks included, plus ``localpart``/``localindices``/``locate`` and the
constructor family (``dzeros dones dfill drand drandn distribute ddata``).

Mutation semantics: ``jax.Array`` is immutable, so the mutating API
(``fill_``, ``d[...] = v``, ``map_into``) rebinds the underlying buffer
inside the same ``DArray`` wrapper — user-visible semantics match the
reference's in-place ops (darray.jl:822-834) without fighting XLA.
"""

from __future__ import annotations

import functools
import itertools
import numbers
import threading
import weakref
from typing import Any, Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import core
from . import layout as L
from . import telemetry as _tm
from .core import allowscalar, _scalar_indexing_allowed

__all__ = [
    "DArray",
    "SubDArray",
    "DData",
    "darray",
    "darray_like",
    "dfromfunction",
    "from_chunks",
    "dzeros",
    "dones",
    "dfill",
    "drand",
    "drandint",
    "dsample",
    "drandn",
    "distribute",
    "ddata",
    "gather",
    "localpart",
    "localindices",
    "locate",
    "makelocal",
    "allowscalar",
    "seed",
    "current_rank",
    "copyto_",
    "dcat",
    "dfetch",
    "isassigned",
]


# ---------------------------------------------------------------------------
# RNG plumbing (reference uses per-worker GLOBAL_RNG; we keep one controller
# key-chain so results are reproducible under `seed`)
# ---------------------------------------------------------------------------

# created lazily so that `import distributedarrays_tpu` has no JAX
# backend-initialization side effect (users must be able to set jax.config
# after importing this package)
_rng_key = None


def seed(n: int) -> None:
    """Reset the controller RNG chain (reference: per-worker Random.seed!,
    test/runtests.jl:23)."""
    global _rng_key
    _rng_key = jax.random.key(n)


def _next_key():
    global _rng_key
    if _rng_key is None:
        _rng_key = jax.random.key(1234)
    _rng_key, sub = jax.random.split(_rng_key)
    return sub


def current_rank() -> int:
    """Rank of the calling SPMD task, 0 on the controller (reference:
    ``myid()``)."""
    return core.current_rank()


# ---------------------------------------------------------------------------
# cached jitted helpers (jit wrappers are cached so XLA compile caches stay warm)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _filler(kind: str, dims: tuple, dtype, sharding):
    if kind == "fill":
        fn = lambda v: jnp.full(dims, v, dtype)
    elif kind == "rand":
        fn = lambda key: jax.random.uniform(key, dims, dtype=dtype)
    elif kind == "randn":
        fn = lambda key: jax.random.normal(key, dims, dtype=dtype)
    else:  # pragma: no cover
        raise ValueError(kind)
    return jax.jit(fn, out_shardings=sharding)


def _resharder(sharding):
    """Compiled identity placement program (kept as a thin alias: the one
    cache now lives in ``parallel.reshard``, next to the transfer-plan
    cache that keys on both endpoints)."""
    from .parallel import reshard as _rs
    return _rs._resharder(sharding)


# ---------------------------------------------------------------------------
# Blocked padding (uneven layouts): physical storage is the logical chunk
# grid with every chunk padded to the per-dim max extent, sharded one block
# per device — so an uneven DArray stores ~1/grid per device instead of a
# full replica along the ragged axis (reference stores uneven chunks
# distributed, darray.jl:279-296).  The pad region always holds zeros.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _blocked_pad_jit(cuts_key, psharding):
    """logical (dims) -> blocked-padded (pdims) buffer, zero pad."""
    cuts = [list(c) for c in cuts_key]
    bs = L.block_sizes(cuts)

    def fn(x):
        for d, c in enumerate(cuts):
            nc, b = len(c) - 1, bs[d]
            if nc == 0 or b * nc == c[-1]:
                continue
            pieces = []
            for k in range(nc):
                piece = jax.lax.slice_in_dim(x, c[k], c[k + 1], axis=d)
                if c[k + 1] - c[k] < b:
                    pw = [(0, 0)] * x.ndim
                    pw[d] = (0, b - (c[k + 1] - c[k]))
                    piece = jnp.pad(piece, pw)
                pieces.append(piece)
            x = jnp.concatenate(pieces, axis=d)
        return x

    return jax.jit(fn, out_shardings=psharding)


@functools.lru_cache(maxsize=None)
def _blocked_unpad_jit(cuts_key, lsharding):
    """blocked-padded (pdims) -> logical (dims) global array."""
    cuts = [list(c) for c in cuts_key]
    bs = L.block_sizes(cuts)

    def fn(x):
        for d, c in enumerate(cuts):
            nc, b = len(c) - 1, bs[d]
            if nc == 0 or b * nc == c[-1]:
                continue
            pieces = [jax.lax.slice_in_dim(x, k * b, k * b + (c[k + 1] - c[k]),
                                           axis=d)
                      for k in range(nc) if c[k + 1] > c[k]]
            x = jnp.concatenate(pieces, axis=d) if pieces else \
                jax.lax.slice_in_dim(x, 0, 0, axis=d)
        return x

    return jax.jit(fn, out_shardings=lsharding)


@functools.lru_cache(maxsize=None)
def _blocked_filler(kind: str, cuts_key, dtype, psharding):
    """Fill/rand program emitting straight into blocked-padded physical
    form (valid chunk regions filled, pad kept zero) — in-place fills on
    uneven layouts do ZERO redistribution: no logical-array generate, no
    re-pad, one compiled program with the padded sharding."""
    cuts = [list(c) for c in cuts_key]
    bs = L.block_sizes(cuts)
    pdims = L.padded_dims(cuts)
    sizes = [np.diff(np.asarray(c, dtype=np.int64)) for c in cuts]

    def valid_mask():
        m = None
        for d, (b, sz) in enumerate(zip(bs, sizes)):
            if pdims[d] == 0 or b == 0:
                continue
            idx = jnp.arange(pdims[d])
            ok = (idx % b) < jnp.asarray(sz)[idx // b]
            shape = [1] * len(pdims)
            shape[d] = pdims[d]
            m = ok.reshape(shape) if m is None else m & ok.reshape(shape)
        return m

    if kind == "fill":
        def fn(v):
            return jnp.where(valid_mask(), jnp.full(pdims, v, dtype),
                             jnp.zeros((), dtype))
    elif kind == "rand":
        def fn(key):
            return jnp.where(valid_mask(),
                             jax.random.uniform(key, pdims, dtype=dtype),
                             jnp.zeros((), dtype))
    else:  # pragma: no cover
        raise ValueError(kind)
    return jax.jit(fn, out_shardings=psharding)


def _host_blocked_pad(arr: np.ndarray, cuts, bs, pdims) -> np.ndarray:
    """numpy blocked pad — used at construction so each device receives only
    its block (never a full logical replica)."""
    out = np.zeros(pdims, dtype=arr.dtype)
    grid = tuple(len(c) - 1 for c in cuts)
    for ci in np.ndindex(*grid):
        src = tuple(slice(c[k], c[k + 1]) for c, k in zip(cuts, ci))
        dst = tuple(slice(k * b, k * b + (c[k + 1] - c[k]))
                    for c, b, k in zip(cuts, bs, ci))
        out[dst] = arr[src]
    return out


def _cuts_key(cuts) -> tuple:
    return tuple(tuple(int(x) for x in c) for c in cuts)


# ---------------------------------------------------------------------------
# DArray
# ---------------------------------------------------------------------------


# one process-wide lock for share-group membership (_shared reads/writes
# and count updates): group formation and departure must be atomic, or
# two concurrent aligned samedist calls on one source could mint two
# tokens for one buffer and under-count its holders
_share_lock = threading.Lock()


class _BufShare:
    """Shared-ownership token for one jax buffer referenced by more than
    one DArray (the aligned ``samedist`` fast path): ``close()`` deletes
    the device buffer only when the LAST holder releases it, so skipping
    the defensive copy cannot invalidate the other wrapper."""

    __slots__ = ("buf", "count")

    def __init__(self, buf, count: int = 1):
        self.buf = buf
        self.count = count

    def release(self, buf) -> bool:
        """True iff the caller should delete ``buf`` now.  A holder that
        rebound to a different buffer owns that one exclusively.  When
        the last holder leaves, the token drops its own reference too —
        it must never outlive the group and pin the buffer."""
        with _share_lock:
            if buf is not self.buf:
                return True
            self.count -= 1
            last = self.count <= 0
            if last:
                self.buf = None
            return last


def _share_buffer(src: "DArray", dst: "DArray") -> None:
    """Record that ``src`` and ``dst`` now hold the same buffer."""
    buf = src._data
    with _share_lock:
        tok = src._shared
        if tok is None or tok.buf is not buf:
            tok = _BufShare(buf, 1)
            src._shared = tok
        tok.count += 1
        dst._shared = tok
    # HBM ledger mirrors the group: the shared bytes are counted ONCE
    # (dst's ctor-tracked duplicate entry is dissolved into src's) and
    # released only when the last co-owner closes
    _tm.memory.share(src.id, dst.id)


def _finalize_darray(did):
    """Finalizer body: registry AND ledger stay tidy when a DArray is
    collected without an explicit close (refcounting already freed the
    HBM; the ledger entry must follow it)."""
    core.unregister(did)
    try:
        _tm.memory.untrack(did)
    except Exception:  # pragma: no cover — interpreter-shutdown safety
        pass


class DArray:
    """Global-view distributed array (reference ``mutable struct DArray``,
    darray.jl:25-55).

    Fields mirror the reference: ``id`` (registry key), ``dims`` (global
    shape), ``pids`` (N-D grid of owning device ranks), ``indices`` (grid of
    per-chunk global index ranges), ``cuts`` (per-dim cut vectors).  The
    payload is ``_data``: one sharded ``jax.Array`` whose NamedSharding axes
    follow the chunk grid.
    """

    __slots__ = (
        "id",
        "dims",
        "pids",
        "indices",
        "cuts",
        "_data",
        "_sharding",
        "_padded",
        "_bs",
        "_psharding",
        "_closed",
        "_mutlock",
        "_shared",
        "__weakref__",
    )

    def __init__(self, data: jax.Array, pids: np.ndarray, indices: np.ndarray,
                 cuts: list, did=None):
        self.id = did if did is not None else core.next_did()
        if len(cuts) != getattr(data, "ndim", len(np.shape(data))):
            raise ValueError(
                f"cuts rank {len(cuts)} != data rank {np.ndim(data)}")
        dims = tuple(int(c[-1]) for c in cuts)
        self.dims = dims
        self.pids = pids
        self.indices = indices
        self.cuts = cuts
        self._bs = L.block_sizes(cuts)
        pdims = L.padded_dims(cuts)
        self._padded = pdims != dims
        if self._padded:
            grid = tuple(len(c) - 1 for c in cuts)
            flat_pids = [int(p) for p in pids.flat]
            psh = L.padded_sharding_for(flat_pids, grid, pdims)
            if tuple(data.shape) == pdims:
                if getattr(data, "sharding", psh) != psh:
                    from .parallel import reshard as _rs
                    data = _rs.reshard(data, psh, op="padded_relayout")
            elif tuple(data.shape) == dims:
                with _tm.span("reshard", op="blocked_pad"):
                    if _tm.enabled():
                        _tm.record_comm("reshard", _tm.nbytes_of(data),
                                        op="blocked_pad")
                    data = _blocked_pad_jit(_cuts_key(cuts), psh)(data)
            else:
                raise ValueError(f"data shape {tuple(data.shape)} matches "
                                 f"neither dims {dims} nor padded {pdims}")
            self._psharding = psh
            # ops-facing sharding of the *logical* view (uneven axes
            # replicated — the pre-padding physical layout, now transient)
            self._sharding = L.sharding_for(flat_pids, grid, dims)
        else:
            if tuple(data.shape) != dims:
                raise ValueError(
                    f"data shape {tuple(data.shape)} != cuts dims {dims}")
            self._psharding = None
            self._sharding = data.sharding
        self._data = data
        self._closed = False
        self._shared = None          # _BufShare when a buffer is co-owned
        # serializes read-modify-write mutations (set_localpart/setitem)
        # from concurrent SPMD rank tasks: the reference's workers own
        # disjoint chunks in separate processes, here they share one buffer
        self._mutlock = threading.Lock()
        core.register(self)
        if _tm.enabled():
            _tm.memory.track(self.id, self._data, site="ctor")
        # finalizer → close_by_id fan-out in the reference (darray.jl:47-49);
        # here plain refcounting already frees HBM, the finalizer keeps
        # the registry and the HBM ledger tidy.
        weakref.finalize(self, _finalize_darray, self.id)

    # -- basic protocol ----------------------------------------------------

    @property
    def shape(self):
        return self.dims

    @property
    def ndim(self):
        return len(self.dims)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(np.prod(self.dims)) if self.dims else 1

    @property
    def sharding(self):
        return self._sharding

    @property
    def garray(self) -> jax.Array:
        """The logical global jax.Array (TPU-native escape hatch).

        Even layouts: the stored sharded buffer, as-is (the performance
        path).  Uneven layouts: reassembled on the fly from the
        blocked-padded buffer — one compiled slice+concat program whose
        result replicates the ragged axes (transient; the at-rest storage
        stays one block per device)."""
        self._check_open()
        if not self._padded:
            return self._data
        return _blocked_unpad_jit(_cuts_key(self.cuts), self._sharding)(
            self._data)

    @property
    def garray_padded(self) -> jax.Array:
        """The at-rest physical buffer: the blocked-padded sharded array for
        uneven layouts (one max-chunk-sized block per device, zero pad), or
        exactly ``garray`` for even ones."""
        self._check_open()
        return self._data

    def __len__(self):
        if not self.dims:
            raise TypeError("len() of 0-d DArray")
        return self.dims[0]

    def __repr__(self):
        grid = "x".join(str(s) for s in self.pids.shape) if self.pids.ndim else "1"
        return (f"DArray(id={self.id}, dims={self.dims}, dtype={self.dtype}, "
                f"chunks={grid}, ranks={sorted(int(p) for p in set(self.pids.flat))})")

    def __hash__(self):
        # reference hashes on the id (darray.jl:72)
        return hash(self.id)

    def __array__(self, dtype=None, copy=None):
        a = np.asarray(self._gather_host())
        if dtype is not None:
            a = a.astype(dtype, copy=False)
        return a

    # NOTE: deliberately NOT defining __jax_array__ — pytree registration
    # (below) already lets DArrays enter jnp ops and transforms at jit
    # boundaries, and __jax_array__ would additionally hijack reflected
    # operators (jax.Array + DArray would stop deferring to __radd__).

    def __bool__(self):
        # numpy/Julia semantics: only size-1 arrays have a truth value
        if self.size != 1:
            raise ValueError(
                "truth value of a multi-element DArray is ambiguous; use "
                "dall()/dany()")
        return bool(np.asarray(self).reshape(()))

    def __iter__(self):
        # iterating gathers — guard like scalar indexing
        _scalar_indexing_allowed()
        return iter(np.asarray(self))

    def __float__(self):
        if self.size != 1:
            raise TypeError("only size-1 DArray converts to float")
        return float(np.asarray(self).reshape(()))

    # -- lifecycle ---------------------------------------------------------

    def _check_open(self):
        if self._closed:
            raise RuntimeError(f"DArray {self.id} is closed")

    def _close(self, _unregister=True):
        if not self._closed:
            self._closed = True
            sh = self._shared
            self._shared = None
            # ledger release first (always runs — the ledger must drain
            # even if telemetry was disabled mid-run); bytes are freed
            # only when this was the entry's last co-owner
            _tm.memory.untrack(self.id)
            if sh is None or sh.release(self._data):
                try:
                    self._data.delete()
                except Exception:
                    pass
            self._data = None
            if _unregister:
                core.unregister(self.id)

    def _leave_share(self):
        """Detach from a shared-buffer group BEFORE ``_data`` is replaced
        (rebind/mutation): the departing holder must not leave the token
        counting it — otherwise the remaining holder's ``close()`` would
        under-count and never eagerly delete, and the token's reference
        would pin the old buffer past every close."""
        tok = self._shared
        if tok is None:
            return
        self._shared = None
        tok.release(self._data)

    def close(self):
        """Release device buffers now (reference ``close(d)``, core.jl:105)."""
        self._close()

    def _release_wrapper(self):
        """Drop this wrapper from the registry WITHOUT deleting the buffer —
        used when buffer ownership moved into another DArray."""
        self._closed = True
        self._data = None
        _tm.memory.untrack(self.id)
        core.unregister(self.id)

    # -- layout queries ----------------------------------------------------

    def localpartindex(self, pid: int | None = None) -> tuple | None:
        """Grid coordinates of the chunk owned by ``pid`` (reference
        ``localpartindex``, darray.jl:309-318); None if not a participant."""
        pid = current_rank() if pid is None else pid
        hits = np.argwhere(self.pids == pid)
        if hits.size == 0:
            return None
        return tuple(int(x) for x in hits[0])

    def localindices(self, pid: int | None = None) -> tuple:
        """Global index ranges of this rank's chunk (darray.jl:394-400)."""
        ci = self.localpartindex(pid)
        if ci is None:
            return tuple(range(0, 0) for _ in self.dims)
        return self.indices[ci]

    def localpart(self, pid: int | None = None) -> jax.Array:
        """This rank's chunk of the global array (darray.jl:330-339).

        Fast path: when the logical layout coincides with the physical XLA
        shard layout, this returns the addressable shard with no copy;
        otherwise the logical chunk is sliced out of the global array.
        """
        self._check_open()
        ci = self.localpartindex(pid)
        if ci is None:
            return jnp.empty((0,) * max(self.ndim, 1), dtype=self.dtype)
        idx = self.indices[ci]
        if self._padded:
            shard = self._padded_shard(ci, idx)
            if shard is not None:
                return shard
            return self.garray[tuple(slice(r.start, r.stop) for r in idx)]
        shard = self._physical_shard_matching(idx)
        if shard is not None:
            return shard
        return self._data[tuple(slice(r.start, r.stop) for r in idx)]

    def _physical_shard_matching(self, idx):
        try:
            for s in self._data.addressable_shards:
                sl = s.index
                if len(sl) == len(idx) and all(
                    (x.start or 0) == r.start and (x.stop if x.stop is not None else self.dims[d]) == r.stop
                    for d, (x, r) in enumerate(zip(sl, idx))
                ):
                    return s.data
        except Exception:
            pass
        return None

    def _padded_shard(self, ci, idx):
        """Addressable-shard fast path for uneven layouts: grid cell ``ci``'s
        chunk lives in the physical block starting at ``ci*block_size``; its
        valid region is a device-local slice — no cross-device traffic."""
        starts = tuple(int(c) * b for c, b in zip(ci, self._bs))
        try:
            for s in self._data.addressable_shards:
                if len(s.index) == len(starts) and all(
                    (x.start or 0) == st for x, st in zip(s.index, starts)
                ):
                    return s.data[tuple(slice(0, len(r)) for r in idx)]
        except Exception:
            pass
        return None

    @property
    def lp(self):
        """Sugar for ``localpart`` (reference ``d[:L]``, darray.jl:371-382)."""
        return self.localpart()

    @lp.setter
    def lp(self, value):
        self.set_localpart(value)

    def set_localpart(self, value, pid: int | None = None):
        """Replace this rank's chunk (reference ``d[:L] = v``, darray.jl:378-382)."""
        self._check_open()
        ci = self.localpartindex(pid)
        if ci is None:
            raise ValueError(f"rank {pid if pid is not None else current_rank()} "
                             f"holds no chunk of {self!r}")
        idx = self.indices[ci]
        value = jnp.asarray(value, dtype=self.dtype)
        want = tuple(len(r) for r in idx)
        if value.shape != want:
            raise ValueError(f"localpart shape {value.shape} != chunk shape {want}")
        if self._padded:
            # write straight into the owner's physical block (pad stays 0)
            psl = tuple(slice(b * c, b * c + len(r))
                        for b, c, r in zip(self._bs, ci, idx))
            with self._mutlock:
                self._check_open()
                g2 = self._data.at[psl].set(value)
                if g2.sharding != self._psharding:
                    g2 = jax.device_put(g2, self._psharding)  # dalint: disable=DAL007 — padded-buffer placement restore, not a cross-layout reshard
                self._leave_share()
                self._data = g2
                if _tm.enabled():
                    _tm.memory.track(self.id, g2, site="set_localpart")
            return
        sl = tuple(slice(r.start, r.stop) for r in idx)
        self._mutate(lambda g: g.at[sl].set(value))

    def locate(self, *I: int) -> tuple:
        """Chunk-grid coordinates owning global index I (darray.jl:448-456)."""
        return L.locate(self.cuts, *I)

    def chunk(self, pid: int) -> jax.Array:
        """Chunk owned by ``pid`` (reference ``chunk(d, pid)``, darray.jl:458)."""
        return self.localpart(pid)

    def procs(self):
        return self.pids

    # -- data movement -----------------------------------------------------

    @_tm.traced(name="gather")
    def _gather_host(self):
        self._check_open()
        g = self.garray
        if not g.is_fully_addressable:
            # process-spanning array: jax.device_get would raise jax's
            # opaque non-addressable RuntimeError.  Route through the
            # symmetric multi-controller gather instead — legitimate
            # under SPMD discipline (every process executes the same
            # program, so every process is inside this same call).
            # (comm accounting happens inside gather_global — recording
            # d2h here too would double-count every cross-host gather)
            from .parallel import multihost
            return multihost.gather_global(g)
        if _tm.enabled():
            _tm.record_comm("d2h", _tm.nbytes_of(g), op="gather",
                            shape=list(self.dims))
        return jax.device_get(g)

    def _mutate(self, updater):
        """Atomic read-modify-write of the backing buffer: every partial
        mutation (chunk/region updates) must go through here so concurrent
        SPMD rank tasks cannot lose each other's disjoint writes."""
        with self._mutlock:
            self._rebind(updater(self.garray))

    def _mutate_region(self, key, value):
        """Region update.  Even layouts: one ``.at[...].set`` on the
        sharded buffer (as before).  Padded (uneven) layouts with basic
        int/slice keys: INCREMENTAL — the update touches only the owner
        blocks' physical regions of the blocked-padded buffer (the same
        at-set ``set_localpart`` does for exact chunks), instead of the
        depad → update → repad full-array round trip.  Advanced keys fall
        back to the full-array path."""
        self._check_open()
        basic = all(
            isinstance(k, int)
            or (isinstance(k, slice) and k.step in (None, 1))
            for k in key)
        if not self._padded or not basic:
            self._mutate(lambda g: g.at[tuple(key)].set(value))
            return
        lo, hi = [], []
        for d, k in enumerate(key):
            if isinstance(k, int):
                lo.append(k)
                hi.append(k + 1)
            else:
                lo.append(k.start)
                hi.append(k.stop)
        if any(h <= l for l, h in zip(lo, hi)):
            return                                   # empty region: no-op
        region_shape = tuple(h - l for l, h in zip(lo, hi))
        v = jnp.asarray(value, dtype=self.dtype)
        # numpy basic-index semantics: value broadcasts to the result
        # shape (int-indexed dims removed); reinsert size-1 dims there
        for d, k in enumerate(key):
            if isinstance(k, int) and v.ndim < len(region_shape):
                v = jnp.expand_dims(v, d)
        v = jnp.broadcast_to(v, region_shape)
        spans = [L.chunk_span(c, l, h)
                 for c, l, h in zip(self.cuts, lo, hi)]
        # One eager at-set per owner block.  The buffer is SHARDED, so
        # each set copies only the touched devices' blocks — k block
        # writes stay bounded by ~one padded-buffer copy per device
        # total, vs the old depad→update→repad path which materialized
        # the ragged-axis-REPLICATED logical array on every device.
        touched = 0
        with self._mutlock:
            self._check_open()
            with _tm.span("reshard", op="incremental_mutate"):
                g2 = self._data
                for ci in itertools.product(
                        *[range(a, b + 1) for a, b in spans]):
                    psl, vsl, n = [], [], 1
                    for d, k in enumerate(ci):
                        cs, ce = self.cuts[d][k], self.cuts[d][k + 1]
                        il, ih = max(cs, lo[d]), min(ce, hi[d])
                        if il >= ih:
                            n = 0
                            break
                        b = self._bs[d]
                        psl.append(slice(b * k + (il - cs),
                                         b * k + (ih - cs)))
                        vsl.append(slice(il - lo[d], ih - lo[d]))
                        n *= ih - il
                    if n == 0:
                        continue
                    g2 = g2.at[tuple(psl)].set(v[tuple(vsl)])
                    touched += n * v.dtype.itemsize
                if _tm.enabled():
                    # owner-block bytes only — the sub-full-array traffic
                    # the incremental path exists to deliver
                    _tm.record_comm("reshard", touched,
                                    op="incremental_mutate",
                                    shape=list(region_shape))
                if g2.sharding != self._psharding:
                    g2 = jax.device_put(g2, self._psharding)  # dalint: disable=DAL007 — padded-buffer placement restore, not a cross-layout reshard
                self._leave_share()
                self._data = g2
                if _tm.enabled():
                    _tm.memory.track(self.id, g2, site="mutate")

    def _rebind(self, new_data: jax.Array):
        """Swap the backing buffer in place (mutation-API support).
        ``new_data`` is always the *logical* global array; uneven layouts
        re-pad it into blocked physical form."""
        self._check_open()
        if new_data.shape != tuple(self.dims):
            raise ValueError("rebind shape mismatch")
        self._leave_share()
        if self._padded:
            with _tm.span("reshard", op="blocked_pad"):
                if _tm.enabled():
                    _tm.record_comm("reshard", _tm.nbytes_of(new_data),
                                    op="blocked_pad", shape=list(self.dims))
                self._data = _blocked_pad_jit(_cuts_key(self.cuts),
                                              self._psharding)(new_data)
            if _tm.enabled():
                _tm.memory.track(self.id, self._data, site="rebind")
            return
        if new_data.sharding != self._sharding:
            # planner-routed: repeated same-layout-pair rebinds hit the
            # plan cache; divisible repartitions run the chunked
            # collective program instead of a whole-array device_put
            from .parallel import reshard as _rs
            new_data = _rs.reshard(new_data, self._sharding, op="rebind")
        self._data = new_data
        if _tm.enabled():
            _tm.memory.track(self.id, new_data, site="rebind")

    def with_data(self, new_data: jax.Array, did=None) -> "DArray":
        """New DArray with this layout and ``new_data`` (same global shape)."""
        if not self._padded:
            new_data = _to_sharding(new_data, self._sharding)
        # padded: the ctor's blocked-pad jit places it, whatever its sharding
        return DArray(new_data, self.pids.copy(),
                      self.indices, self.cuts, did=did)

    # -- indexing ----------------------------------------------------------

    def __getitem__(self, key):
        self._check_open()
        key = _normalize_key(key, self.dims)
        if all(isinstance(k, int) for k in key):
            # scalar read: guarded remote fetch (darray.jl:649-659)
            _scalar_indexing_allowed()
            if self._padded:
                # fetch from the owning block directly (no reassembly)
                ci = self.locate(*key)
                local = tuple(b * c + (k - r.start) for b, c, k, r in zip(
                    self._bs, ci, key, self.indices[ci]))
                return self._data[local]
            return self._data[tuple(key)]
        # range indexing returns a lazy view (darray.jl:661)
        return SubDArray(self, key)

    def __setitem__(self, key, value):
        self._check_open()
        key = _normalize_key(key, self.dims)
        if all(isinstance(k, int) for k in key):
            _scalar_indexing_allowed()
        if isinstance(value, DArray):
            value = value.garray
        elif isinstance(value, SubDArray):
            value = value.materialize()
        self._mutate_region(key, value)

    def makelocal(self, *I) -> jax.Array:
        """Materialize the region ``I`` as a dense local array
        (reference ``makelocal``, darray.jl:345-368: local view when the
        region lies within this rank's chunk, else a gathering copy — under
        single-controller JAX both are an XLA slice)."""
        self._check_open()
        if not I:
            return self.garray
        key = _normalize_key(tuple(I) if len(I) > 1 else I[0], self.dims)
        key = tuple(slice(k, k + 1) if isinstance(k, int) else k for k in key)
        return self.garray[key]

    # -- conveniences ------------------------------------------------------

    def copy(self) -> "DArray":
        """Independent copy with the same layout (darray.jl:689-697)."""
        return self.with_data(jnp.copy(self.garray))

    def __deepcopy__(self, memo):
        c = memo.get(id(self))
        if c is None:
            memo[id(self)] = c = self.copy()
        return c

    def similar(self, dtype=None, dims=None) -> "DArray":
        """Uninitialized-alike array (reference similar, darray.jl:238-241):
        same layout when dims match, default layout otherwise."""
        dtype = self.dtype if dtype is None else dtype
        if dims is None or tuple(dims) == self.dims:
            return self.with_data(
                _filler("fill", self.dims, np.dtype(dtype), self._sharding)(
                    jnp.zeros((), dtype)))
        return dzeros(tuple(dims), dtype=dtype,
                      procs=[int(p) for p in self.pids.flat])

    def __eq__(self, other):
        """WHOLE-ARRAY equality: one Python bool, True iff shapes match and
        every element is equal — the reference's Base.== semantics
        (darray.jl:403-441).  NOT numpy semantics: ``a == b`` never returns
        an elementwise array here, while ``<``, ``<=``, ``>``, ``>=`` ARE
        elementwise.  For an elementwise comparison use
        ``dmap(jnp.equal, a, b)``.

        DArray/SubDArray operands compare DEVICE-SIDE (one compiled
        array_equal over the sharded buffers — no host gather); only
        numpy inputs and cross-device-set operands take the host path."""
        if isinstance(other, (DArray, SubDArray)):
            oshape = tuple(other.dims) if isinstance(other, DArray) \
                else tuple(other.shape)
            if oshape != self.dims:
                return False
            try:
                og = other.garray if isinstance(other, DArray) \
                    else other.materialize()
                return bool(jnp.array_equal(self.garray, og))
            except Exception:
                # committed to disjoint device sets (or similar): the
                # compiled compare cannot bind both — host fallback
                other = np.asarray(other)
        elif not isinstance(other, (np.ndarray, jax.Array)):
            return NotImplemented
        if tuple(np.shape(other)) != self.dims:
            return False
        return bool(jnp.array_equal(self.garray, jnp.asarray(other)))

    def __ne__(self, other):
        r = self.__eq__(other)
        return NotImplemented if r is NotImplemented else not r

    def reshape(self, *dims) -> "DArray":
        """Reshaped copy with a default layout for the new dims
        (reference reshape(::DVector, dims), darray.jl:612-635)."""
        if len(dims) == 1 and isinstance(dims[0], (tuple, list)):
            dims = tuple(dims[0])
        dims = tuple(int(d) for d in dims)
        if int(np.prod(dims)) != self.size:
            raise ValueError(f"cannot reshape size {self.size} into {dims}")
        pids = sorted(set(int(p) for p in self.pids.flat))
        return _wrap_global(jnp.reshape(self.garray, dims), procs=pids)

    def astype(self, dtype) -> "DArray":
        g = self.garray
        return self.with_data(_fresh(g.astype(dtype), g))

    def fill_(self, x) -> "DArray":
        """In-place fill (reference ``fill!``, darray.jl:822-827).  Padded
        layouts fill the blocked physical buffer directly (pad stays
        zero) — zero redistribution."""
        if self._padded:
            with self._mutlock:
                self._check_open()
                self._leave_share()
                self._data = _blocked_filler(
                    "fill", _cuts_key(self.cuts), np.dtype(self.dtype),
                    self._psharding)(jnp.asarray(x, dtype=self.dtype))
                if _tm.enabled():
                    _tm.memory.track(self.id, self._data, site="fill_")
            return self
        sh = self._sharding
        self._rebind(_filler("fill", self.dims, np.dtype(self.dtype), sh)(
            jnp.asarray(x, dtype=self.dtype)))
        return self

    def rand_(self) -> "DArray":
        """In-place uniform refill (reference ``rand!``, darray.jl:829-834).
        Padded layouts generate straight into blocked physical form."""
        if self._padded:
            with self._mutlock:
                self._check_open()
                self._leave_share()
                self._data = _blocked_filler(
                    "rand", _cuts_key(self.cuts), np.dtype(self.dtype),
                    self._psharding)(_next_key())
                if _tm.enabled():
                    _tm.memory.track(self.id, self._data, site="rand_")
            return self
        self._rebind(_filler("rand", self.dims, np.dtype(self.dtype),
                             self._sharding)(_next_key()))
        return self


# ---------------------------------------------------------------------------
# SubDArray: lazy view (reference SubDArray = SubArray{...,DArray},
# darray.jl:64-65; materialization logic darray.jl:584-602,699-820)
# ---------------------------------------------------------------------------


class SubDArray:
    """A lazy view of a region of a DArray.

    The reference's SubDArray→Array machinery (darray.jl:699-820) hand-rolls
    per-chunk index algebra because chunks live in other processes; on a
    global-view jax.Array the same semantics are one XLA gather, so this
    class only carries (parent, index) and materializes on demand.
    """

    __slots__ = ("parent", "key")

    def __init__(self, parent: DArray, key: tuple):
        self.parent = parent
        self.key = key

    @property
    def shape(self):
        return _result_shape(self.key, self.parent.dims)

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def dtype(self):
        return self.parent.dtype

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    def materialize(self) -> jax.Array:
        """Dense jax.Array of the viewed region (reference Array(::SubDArray),
        darray.jl:584-596, incl. the whole-chunk fast path via locate)."""
        self.parent._check_open()
        if any(not isinstance(k, (int, slice)) for k in self.key):
            # advanced indexing: apply the raw key so jnp uses numpy's
            # broadcast-and-place rules — keeps the data consistent with
            # what _result_shape promised for self.shape
            return self.parent.garray[self.key]
        key = tuple(slice(k, k + 1) if isinstance(k, int) else k for k in self.key)
        out = self.parent.garray[key]
        # squeeze integer-indexed dims like numpy basic indexing
        squeeze = tuple(i for i, k in enumerate(self.key) if isinstance(k, int))
        if squeeze:
            out = jnp.squeeze(out, axis=squeeze)
        return out

    def __array__(self, dtype=None, copy=None):
        a = np.asarray(jax.device_get(self.materialize()))
        if dtype is not None:
            a = a.astype(dtype, copy=False)
        return a

    def copy(self) -> DArray:
        """Distribute the viewed region as a fresh DArray (reference
        ``copy(::SubDArray)``, darray.jl:676-677)."""
        return distribute(self.materialize())

    def __getitem__(self, key):
        return self.materialize()[key]

    def __eq__(self, other):
        if isinstance(other, (DArray, SubDArray)):
            oshape = tuple(other.dims) if isinstance(other, DArray) \
                else tuple(other.shape)
            if oshape != tuple(self.shape):
                return False
            try:
                og = other.garray if isinstance(other, DArray) \
                    else other.materialize()
                return bool(jnp.array_equal(self.materialize(), og))
            except Exception:
                other = np.asarray(other)
        elif not isinstance(other, (np.ndarray, jax.Array)):
            return NotImplemented
        if tuple(np.shape(other)) != tuple(self.shape):
            return False
        return bool(jnp.array_equal(self.materialize(), jnp.asarray(other)))

    def __hash__(self):
        return id(self)

    def __repr__(self):
        return f"SubDArray(parent={self.parent.id}, key={self.key}, shape={self.shape})"


# ---------------------------------------------------------------------------
# numpy-style reduction methods, wired onto BOTH DArray and SubDArray (like
# the operator surface).  Semantics follow the reference/Julia, not numpy:
# `dims=` reductions KEEP reduced dims with size 1, and std/var default to
# the corrected estimator (ddof=1).
# ---------------------------------------------------------------------------


def _method_reduce(attr_name, fn_name, doc, defaults):
    def m(self, dims=None, **kw):
        from .ops import mapreduce as _mr
        merged = {**defaults, **kw}
        return getattr(_mr, fn_name)(self, dims=dims, **merged)
    m.__name__ = attr_name
    m.__doc__ = doc
    return m


_REDUCE_METHODS = {
    "sum": ("dsum", "Distributed sum; `dims=` keeps reduced dims (size 1).", {}),
    "mean": ("dmean", "Distributed mean; `dims=` keeps reduced dims.", {}),
    "std": ("dstd", "Corrected std (ddof=1 default, Julia semantics).", {}),
    "var": ("dvar", "Corrected variance (ddof=1 default, Julia semantics).",
            {}),
    "min": ("dminimum", "Distributed minimum; `dims=` keeps reduced dims.", {}),
    "max": ("dmaximum", "Distributed maximum; `dims=` keeps reduced dims.", {}),
    "prod": ("dprod", "Distributed product; `dims=` keeps reduced dims.", {}),
    "all": ("dall", "True iff every element is truthy.", {}),
    "any": ("dany", "True iff any element is truthy.", {}),
}

for _mname, (_fname, _doc, _defaults) in _REDUCE_METHODS.items():
    _m = _method_reduce(_mname, _fname, _doc, _defaults)
    setattr(DArray, _mname, _m)
    setattr(SubDArray, _mname, _m)


SubOrDArray = (DArray, SubDArray)


# ---------------------------------------------------------------------------
# index normalization helpers
# ---------------------------------------------------------------------------


def _normalize_key(key, dims):
    if not isinstance(key, tuple):
        key = (key,)
    if any(k is Ellipsis for k in key):
        i = key.index(Ellipsis)
        fill = len(dims) - (len(key) - 1)
        key = key[:i] + (slice(None),) * fill + key[i + 1:]
    if len(key) < len(dims):
        key = key + (slice(None),) * (len(dims) - len(key))
    if len(key) > len(dims):
        raise IndexError(f"too many indices for {len(dims)}-d DArray")
    out = []
    for d, k in enumerate(key):
        n = dims[d]
        if isinstance(k, (int, np.integer)):
            k = int(k)
            if k < 0:
                k += n
            if not (0 <= k < n):
                raise IndexError(f"index {k} out of bounds for dim {d} (size {n})")
            out.append(k)
        elif isinstance(k, slice):
            out.append(slice(*k.indices(n)))
        elif isinstance(k, range):
            out.append(slice(k.start, k.stop, k.step))
        else:
            out.append(jnp.asarray(k))
    return tuple(out)


def _result_shape(key, dims):
    """Shape of ``d[key]`` under numpy/jax advanced-indexing rules: all
    advanced indices (arrays; ints join as 0-d) broadcast together into ONE
    dim block, placed at the first advanced position when they are
    consecutive, else moved to the front."""
    adv = [(i, np.shape(k)) for i, k in enumerate(key)
           if not isinstance(k, slice)]
    has_arrays = any(s != () for _, s in adv)
    bshape = np.broadcast_shapes(*[s for _, s in adv]) if has_arrays else ()
    positions = [i for i, _ in adv]
    consecutive = positions == list(range(positions[0],
                                          positions[0] + len(positions))) \
        if positions else True
    shape = []
    if bshape and not consecutive:
        shape.extend(bshape)
    emitted = not bshape or not consecutive
    for d, k in enumerate(key):
        if isinstance(k, slice):
            shape.append(len(range(*k.indices(dims[d]))))
        elif not emitted:
            shape.extend(bshape)
            emitted = True
    return tuple(shape)


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------


def _idxs_from_cuts(cuts, grid) -> np.ndarray:
    """Object grid of per-chunk global index-range tuples derived from the
    cut vectors (shared by from_chunks / darray_from_cuts / pytree
    unflatten)."""
    idxs = np.empty(tuple(grid), dtype=object)
    for ci in np.ndindex(*grid):
        idxs[ci] = tuple(range(cuts[d][ci[d]], cuts[d][ci[d] + 1])
                         for d in range(len(cuts)))
    return idxs


def _resolve_layout(dims, procs=None, dist=None):
    dims = tuple(int(d) for d in dims)
    if procs is None:
        procs = L.all_ranks()
    procs = list(procs)
    if dist is None:
        dist = L.defaultdist(dims, procs)
    dist = [int(c) for c in dist]
    if len(dist) != len(dims):
        raise ValueError(f"dist {dist} rank != dims {dims} rank")
    n = int(np.prod(dist)) if dist else 1
    if n > len(procs):
        raise ValueError(f"layout {dist} needs {n} ranks, have {len(procs)}")
    use = procs[:n]
    idxs, cuts = L.chunk_idxs(dims, dist)
    pids = np.asarray(use, dtype=np.int64).reshape(tuple(dist) if dist else ())
    sharding = L.sharding_for(use, dist, dims)
    return dims, pids, idxs, cuts, sharding


def _wrap_global(data: jax.Array, procs=None, dist=None) -> DArray:
    dims, pids, idxs, cuts, sharding = _resolve_layout(data.shape, procs, dist)
    return DArray(_to_sharding(data, sharding), pids, idxs, cuts)


def _to_sharding(data: jax.Array, sharding) -> jax.Array:
    if getattr(data, "sharding", None) == sharding:
        return data
    return _put_global(data, sharding)


def _spans_processes(sharding) -> bool:
    """True when a sharding's devices belong to >1 controller process.
    PROCESS-INDEPENDENT (unlike ``is_fully_addressable``): in
    multi-controller SPMD every branch that can enter a compiled program
    must be taken identically by every process, or the job deadlocks."""
    try:
        return len({d.process_index for d in sharding.device_set}) > 1
    except Exception:
        return False


def _put_global(host, sharding) -> jax.Array:
    """Place host/device data under ``sharding``.

    Single-controller: one ``device_put`` (the DestinationSerializer scatter,
    serialize.jl:45-87).  Multi-controller: device data that spans
    processes is resharded by ONE compiled identity program — XLA inserts
    the DCN/ICI collective; eager ``device_put`` cannot move bytes between
    hosts.  Host data: every process calls this with the same global array
    and contributes only its addressable shards — the JAX analog of each
    worker receiving only its own chunk.  All branch predicates here are
    process-independent (see ``_spans_processes``); the branches that may
    diverge per process (`device_put` vs `make_array_from_callback`) are
    both collective-free."""
    with _tm.span("put_global", _journal=False):
        return _put_global_impl(host, sharding)


def _put_global_impl(host, sharding) -> jax.Array:
    from .parallel import reshard as _rs
    if isinstance(host, jax.Array) and _spans_processes(host.sharding):
        if host.sharding.device_set == sharding.device_set:
            # same devices, new layout: planner-routed — ONE compiled
            # program (chunked collective when the layouts divide, the
            # cached identity resharder otherwise); both are legal under
            # multi-controller SPMD (every process enters this call)
            return _rs.reshard(host, sharding, op="put_global")
        # device sets differ (e.g. a reduction shrank the rank grid below
        # the process count): replicate over the SOURCE mesh — compiled,
        # every owning process participates — then fall through to the
        # host-scatter path with the local replica every process now holds
        from jax.sharding import NamedSharding, PartitionSpec
        if _tm.enabled():
            _tm.record_comm("replicate", _tm.nbytes_of(host),
                            op="put_global", shape=list(host.shape))
        rep = _resharder(NamedSharding(
            host.sharding.mesh, PartitionSpec()))(host)
        host = np.asarray(rep.addressable_data(0))
    if getattr(sharding, "is_fully_addressable", True):
        # moving an existing device array to a new layout is a reshard —
        # planner-routed; placing host data is a host→device scatter
        if isinstance(host, jax.Array):
            return _rs.reshard(host, sharding, op="put_global")
        if _tm.enabled():
            _tm.record_comm("h2d", _tm.nbytes_of(host),
                            op="device_put", shape=list(np.shape(host)))
        return jax.device_put(host, sharding)  # dalint: disable=DAL007 — host→device scatter, no source sharding to plan from
    arr = np.asarray(host)
    if _tm.enabled():
        _tm.record_comm("h2d", arr.nbytes, op="make_array_from_callback",
                        shape=list(arr.shape))
    # explicit dtype: a process owning NO shard of this array (device-
    # subset layouts) cannot infer it from the callback
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx], dtype=arr.dtype)


def _place_chunked(host, pids: np.ndarray, cuts, sharding) -> jax.Array:
    """Place host data for a DArray ctor: even layouts go straight to their
    distributed sharding; uneven layouts are blocked-padded ON HOST first so
    each device receives only its own block (never a logical replica)."""
    bs = L.block_sizes(cuts)
    pdims = L.padded_dims(cuts)
    dims = tuple(int(c[-1]) for c in cuts)
    if pdims == dims:
        return _put_global(host, sharding)
    grid = tuple(len(c) - 1 for c in cuts)
    psh = L.padded_sharding_for([int(p) for p in pids.flat], grid, pdims)
    return _put_global(_host_blocked_pad(np.asarray(host), cuts, bs, pdims),
                       psh)


def _fresh(val: jax.Array, *sources) -> jax.Array:
    """Guarantee ``val`` owns its buffers: no-op conversions (``device_put``
    with the current sharding, ``astype`` with the current dtype,
    ``jnp.asarray`` of a jax.Array) return their *input object*, and two
    DArrays must never share one buffer — ``close()`` on either would
    delete the other's data.  The reference always copies here
    (copyto!/distribute/deepcopy)."""
    return jnp.copy(val) if any(val is s for s in sources) else val


def _assemble_host(dims, dtype, parts, idxs_list) -> np.ndarray:
    """Stitch per-chunk host buffers into one contiguous global array.

    Uses the native thread-parallel copier (utils/native.py,
    native/chunkcopy.cpp) when it can win; numpy slicing otherwise."""
    host = np.empty(dims, dtype=dtype)
    offs = [tuple(r.start for r in idx) for idx in idxs_list]
    from .utils import native
    if native.worth_using(host.nbytes, len(parts)):
        native.assemble(host, [np.ascontiguousarray(p) for p in parts], offs)
    else:
        # numpy assignment handles non-contiguous sources directly
        for c, idx in zip(parts, idxs_list):
            host[tuple(slice(r.start, r.stop) for r in idx)] = c
    return host


def darray(init: Callable, dims, procs=None, dist=None) -> DArray:
    """Build a DArray by calling ``init(index_ranges)`` once per chunk.

    Reference: generic ctor darray.jl:76-118 (asyncmap of remote
    ``construct_localparts``).  Arbitrary Python init closures are not
    XLA-traceable, so this runs eagerly on host per chunk and ships the
    assembled global array once (`jax.device_put` scatters per the sharding —
    the moral equivalent of the reference's DestinationSerializer,
    serialize.jl:45-87).  Use dzeros/drand/... for the compiled fast path.
    """
    dims, pids, idxs, cuts, sharding = _resolve_layout(dims, procs, dist)
    parts = {}
    dtype = None
    for ci in np.ndindex(*pids.shape) if pids.shape else [()]:
        p = np.asarray(init(idxs[ci]))
        want = tuple(len(r) for r in idxs[ci])
        if p.shape != want:
            raise ValueError(
                f"init returned shape {p.shape} for chunk {ci}, expected {want}")
        # homogeneity check: all chunks must agree on dtype, else the ctor
        # rolls back (reference darray.jl:89-94)
        if dtype is None:
            dtype = p.dtype
        elif p.dtype != dtype:
            raise TypeError(
                f"chunk dtypes differ: {dtype} vs {p.dtype} "
                "(reference requires homogeneous localparts, darray.jl:89-94)")
        parts[ci] = p
    order = list(parts.keys())
    host = _assemble_host(dims, dtype, [parts[ci] for ci in order],
                          [idxs[ci] for ci in order])
    return DArray(_place_chunked(host, pids, cuts, sharding), pids, idxs, cuts)


def darray_like(init: Callable, d: DArray) -> DArray:
    """Same-layout ctor (reference ``DArray(init, d::DArray)``, darray.jl:234)."""
    pids = [int(p) for p in d.pids.flat]
    return darray(init, d.dims, pids, list(d.pids.shape))


def dfromfunction(f: Callable, dims, procs=None, dist=None,
                  compiled: bool = True) -> DArray:
    """Build a DArray from a function of GLOBAL indices — the first-class
    analog of the reference's ``@DArray [f(i, j) for i in .., j in ..]``
    comprehension ctor (darray.jl:214-231), with ``np.fromfunction``
    calling conventions: ``f`` receives one index-grid array per
    dimension (0-based) and returns the element values.

    ``compiled=True`` (default, for traceable ``f``): the whole array is
    built in ONE jitted program with the target sharding — each device
    materializes only its own chunk's iota and values, nothing is shipped
    from host.  ``compiled=False`` (or automatically when ``f`` is not
    traceable): per-chunk host evaluation through ``darray``, matching
    the reference's eager comprehension semantics for arbitrary code.
    """
    dims = tuple(int(d) for d in dims)
    if compiled:
        _, pids, idxs, cuts, sharding = _resolve_layout(dims, procs, dist)

        def build():
            grids = jnp.meshgrid(
                *[jnp.arange(n) for n in dims], indexing="ij") \
                if dims else []
            return jnp.asarray(f(*grids))
        try:
            out = jax.jit(build, out_shardings=sharding)()
        except Exception:
            out = None                # untraceable f: eager per-chunk path
        if out is not None:
            if tuple(out.shape) != dims:
                raise ValueError(
                    f"f returned shape {tuple(out.shape)}, expected {dims}")
            return DArray(out, pids, idxs, cuts)
    return darray(
        lambda idx: np.fromfunction(
            lambda *gs: f(*[g + r.start for g, r in zip(gs, idx)]),
            tuple(len(r) for r in idx), dtype=int),
        dims, procs, dist)


def from_chunks(chunks: np.ndarray, procs=None) -> DArray:
    """Assemble a DArray from an object-grid of host/device chunks,
    reconstructing indices/cuts from chunk sizes (reference from-refs ctor,
    darray.jl:182-212).  Chunk sizes may be uneven; empty chunks are kept."""
    if isinstance(chunks, (list, tuple)):
        # a plain sequence is a 1-D grid of chunks; build the object array
        # explicitly (np.asarray would stack equal-shaped chunks into a 2-D
        # array of scalars)
        seq = list(chunks)
        chunks = np.empty(len(seq), dtype=object)
        for i, c in enumerate(seq):
            chunks[i] = c
    else:
        chunks = np.asarray(chunks, dtype=object)
    grid = chunks.shape
    nd = np.ndim(chunks.flat[0]) if chunks.size else 0
    if len(grid) != nd:
        raise ValueError(
            f"chunk grid rank {len(grid)} must equal chunk ndim {nd} "
            "(reference from-refs ctor, darray.jl:182-212)")
    cuts = []
    for d in range(nd):
        c = [0]
        for j in range(grid[d] if d < len(grid) else 1):
            sel = [0] * len(grid)
            sel[d] = j
            c.append(c[-1] + int(np.shape(chunks[tuple(sel)])[d]))
        cuts.append(c)
    dims = tuple(c[-1] for c in cuts)
    if procs is None:
        procs = L.all_ranks()
    n = int(np.prod(grid)) if grid else 1
    pids = np.asarray(procs[:n], dtype=np.int64).reshape(grid)
    idxs = _idxs_from_cuts(cuts, grid)
    dtype = np.result_type(*[np.asarray(chunks[ci]).dtype
                             for ci in np.ndindex(*grid)])
    parts = [np.asarray(chunks[ci], dtype=dtype) for ci in np.ndindex(*grid)]
    idxs_list = [idxs[ci] for ci in np.ndindex(*grid)]
    host = _assemble_host(dims, dtype, parts, idxs_list)
    sharding = L.sharding_for(list(pids.flat), grid, dims)
    return DArray(_place_chunked(host, pids, cuts, sharding), pids, idxs, cuts)


def darray_from_cuts(host, procs, cuts) -> DArray:
    """Wrap an already-assembled global host/device array with an explicit
    (possibly non-default) cut layout — one device_put, no chunk
    round-trip.  Used by checkpoint restore; complements ``from_chunks``
    (which assembles from separate chunk buffers)."""
    cuts = [list(int(x) for x in c) for c in cuts]
    dims = tuple(c[-1] for c in cuts)
    if tuple(np.shape(host)) != dims:
        raise ValueError(f"host shape {np.shape(host)} != cuts dims {dims}")
    grid = tuple(len(c) - 1 for c in cuts)
    n = int(np.prod(grid)) if grid else 1
    procs = list(procs)
    if len(procs) < n:
        raise ValueError(f"layout {grid} needs {n} ranks, got {len(procs)}")
    use = procs[:n]
    pids = np.asarray(use, dtype=np.int64).reshape(grid)
    idxs = _idxs_from_cuts(cuts, grid)
    # physical sharding follows the same dims-divisibility rule as every
    # other constructor (L.sharding_for): logical cuts may be uneven while
    # the physical layout stays sharded wherever XLA allows
    sharding = L.sharding_for(use, grid, dims)
    return DArray(_place_chunked(host, pids, cuts, sharding), pids, idxs, cuts)


def dzeros(dims, dtype=jnp.float32, procs=None, dist=None) -> DArray:
    """Distributed zeros (reference dzeros, darray.jl:460-476)."""
    dims, pids, idxs, cuts, sh = _resolve_layout(_as_dims(dims), procs, dist)
    data = _filler("fill", dims, np.dtype(dtype), sh)(jnp.zeros((), dtype))
    return DArray(data, pids, idxs, cuts)


def dones(dims, dtype=jnp.float32, procs=None, dist=None) -> DArray:
    """Distributed ones (reference dones, darray.jl:478-482)."""
    dims, pids, idxs, cuts, sh = _resolve_layout(_as_dims(dims), procs, dist)
    data = _filler("fill", dims, np.dtype(dtype), sh)(jnp.ones((), dtype))
    return DArray(data, pids, idxs, cuts)


def dfill(v, dims, procs=None, dist=None) -> DArray:
    """Distributed fill (reference dfill, darray.jl:484-499)."""
    dims = _as_dims(dims)
    v = jnp.asarray(v)
    dims, pids, idxs, cuts, sh = _resolve_layout(dims, procs, dist)
    data = _filler("fill", dims, np.dtype(v.dtype), sh)(v)
    return DArray(data, pids, idxs, cuts)


def drand(dims, dtype=jnp.float32, procs=None, dist=None) -> DArray:
    """Distributed uniform [0,1) (reference drand, darray.jl:501-519).

    Generated *on device* with `jax.random` under jit with the target
    sharding — no host round-trip (contrast with the reference's per-worker
    host RNG)."""
    dims, pids, idxs, cuts, sh = _resolve_layout(_as_dims(dims), procs, dist)
    data = _filler("rand", dims, np.dtype(dtype), sh)(_next_key())
    return DArray(data, pids, idxs, cuts)


def drandint(low, high, dims, dtype=jnp.int32, procs=None, dist=None
             ) -> DArray:
    """Distributed uniform integers in [low, high) — the reference's
    ``drand(r::UnitRange, dims)`` form (test/darray.jl:641-647)."""
    dims, pids, idxs, cuts, sh = _resolve_layout(_as_dims(dims), procs, dist)
    data = _randint_filler(dims, np.dtype(dtype), sh)(
        _next_key(), jnp.asarray(int(low)), jnp.asarray(int(high)))
    return DArray(data, pids, idxs, cuts)


@functools.lru_cache(maxsize=None)
def _randint_filler(dims, dtype, sharding):
    # low/high ride as traced args so varying bounds reuse one executable
    fn = lambda key, lo, hi: jax.random.randint(key, dims, lo, hi,
                                                dtype=dtype)
    return jax.jit(fn, out_shardings=sharding)


def dsample(values, dims, procs=None, dist=None) -> DArray:
    """Distributed draws from an explicit value set — the reference's
    ``drand(arr::Array, dims)`` form (test/darray.jl:648-654)."""
    values = jnp.ravel(jnp.asarray(values))
    if values.shape[0] == 0:
        raise ValueError("dsample: empty value set")
    dims, pids, idxs, cuts, sh = _resolve_layout(_as_dims(dims), procs, dist)
    data = _sample_filler(dims, int(values.shape[0]),
                          np.dtype(values.dtype), sh)(_next_key(), values)
    return DArray(data, pids, idxs, cuts)


@functools.lru_cache(maxsize=None)
def _sample_filler(dims, nvals, dtype, sharding):
    def fn(key, values):
        idx = jax.random.randint(key, dims, 0, nvals)
        return values[idx]
    return jax.jit(fn, out_shardings=sharding)


def drandn(dims, dtype=jnp.float32, procs=None, dist=None) -> DArray:
    """Distributed standard normal (reference drandn, darray.jl:521-532)."""
    dims, pids, idxs, cuts, sh = _resolve_layout(_as_dims(dims), procs, dist)
    data = _filler("randn", dims, np.dtype(dtype), sh)(_next_key())
    return DArray(data, pids, idxs, cuts)


def _as_dims(dims):
    if isinstance(dims, (int, np.integer)):
        return (int(dims),)
    return tuple(int(d) for d in dims)


@_tm.traced(name="distribute")
def distribute(A, procs=None, dist=None, like: DArray | None = None) -> DArray:
    """Distribute a host/device array (reference distribute, darray.jl:544-572).

    ``jax.device_put`` with a NamedSharding performs the per-destination
    scatter that the reference implements with its DestinationSerializer
    (serialize.jl:45-87): each device receives only its own slice.
    """
    _tm.count("op.distribute")
    if isinstance(A, DArray):
        A = A.garray
    elif isinstance(A, SubDArray):
        A = A.materialize()
    A = jnp.asarray(A) if not isinstance(A, (np.ndarray, jax.Array)) else A
    if like is not None:
        dims, pids, idxs, cuts, sharding = _resolve_layout(
            np.shape(A), [int(p) for p in like.pids.flat], list(like.pids.shape))
    else:
        dims, pids, idxs, cuts, sharding = _resolve_layout(np.shape(A), procs, dist)
    placed = _place_chunked(A, pids, cuts, sharding)
    # host phase 4 of a leg (1 to 3 are in parallel/reshard.py): the
    # result DArray, its registration and its ledger entry
    with _tm.span("distribute.wrap", _journal=False):
        return DArray(_fresh(placed, A), pids, idxs, cuts)


# ---------------------------------------------------------------------------
# module-level parity functions
# ---------------------------------------------------------------------------


def localpart(d, pid: int | None = None):
    """Chunk of ``d`` owned by ``pid`` / the current SPMD rank
    (reference localpart, darray.jl:330-339).  Plain arrays are their own
    localpart (darray.jl:341-343)."""
    if isinstance(d, DArray):
        return d.localpart(pid)
    if isinstance(d, DData):
        return d.localpart(pid)
    if isinstance(d, SubDArray):
        return d.materialize()
    return d


def localindices(d: DArray, pid: int | None = None):
    if isinstance(d, DArray):
        return d.localindices(pid)
    return tuple(range(0, s) for s in np.shape(d))


def locate(d: DArray, *I):
    return d.locate(*I)


def makelocal(d: DArray, *I):
    if isinstance(d, DArray):
        return d.makelocal(*I)
    return jnp.asarray(d)[tuple(I)] if I else jnp.asarray(d)


# ---------------------------------------------------------------------------
# ddata: distributed non-array data (reference darray.jl:120-157)
# ---------------------------------------------------------------------------


class DData:
    """A distributed container of arbitrary per-rank Python objects.

    The reference builds this as ``DArray{T,1,T}`` whose localpart is a single
    value (darray.jl:120-148).  Arbitrary objects are not expressible as one
    jax.Array, so this is the host-object sharded container the survey calls
    for (SURVEY.md §7 hard-parts); jax.Arrays placed in it are device_put to
    their owner's device.
    """

    __slots__ = ("id", "pids", "_parts", "_closed", "__weakref__")

    def __init__(self, parts: dict[int, Any], pids: list[int]):
        self.id = core.next_did()
        self.pids = np.asarray(pids, dtype=np.int64)
        self._parts = parts
        self._closed = False
        core.register(self)
        weakref.finalize(self, core.unregister, self.id)

    @property
    def dims(self):
        return (len(self.pids),)

    def localpart(self, pid: int | None = None):
        pid = current_rank() if pid is None else pid
        if pid not in self._parts:
            raise KeyError(f"rank {pid} holds no part of this ddata")
        return self._parts[pid]

    def set_localpart(self, v, pid: int | None = None):
        pid = current_rank() if pid is None else pid
        self._parts[pid] = v

    def gather(self) -> list:
        """All parts in pid order (reference gather, darray.jl:150-157)."""
        return [self._parts[int(p)] for p in self.pids]

    def close(self):
        self._closed = True
        self._parts = {}
        core.unregister(self.id)

    def _close(self, _unregister=True):
        self._closed = True
        self._parts = {}
        if _unregister:
            core.unregister(self.id)

    def __len__(self):
        return len(self.pids)

    def __repr__(self):
        return f"DData(id={self.id}, ranks={list(self.pids)})"


def ddata(*, init: Callable | None = None, pids: Sequence[int] | None = None,
          data: Sequence | None = None) -> DData:
    """Distributed per-rank values (reference ddata, darray.jl:120-148).

    ``init(pididx)`` is called once per rank, or ``data`` (length divisible
    by nranks) is split evenly across ranks."""
    if pids is None:
        pids = L.all_ranks()
    pids = [int(p) for p in pids]
    parts: dict[int, Any] = {}
    if data is not None:
        n = len(data)
        if n % len(pids) != 0:
            raise ValueError(f"data length {n} not divisible by {len(pids)} ranks")
        per = n // len(pids)
        for i, p in enumerate(pids):
            chunk = data[i * per:(i + 1) * per]
            parts[p] = chunk[0] if per == 1 else list(chunk)
    elif init is not None:
        for i, p in enumerate(pids):
            parts[p] = init(i)
    else:
        for p in pids:
            parts[p] = None
    return DData(parts, pids)


# ---------------------------------------------------------------------------
# pytree registration: DArrays drop into any JAX transform (jit/grad/vmap,
# jnp ops).  Flatten yields the sharded global array; unflatten rebuilds the
# wrapper for concrete arrays and passes tracers straight through, so inside
# a traced function a DArray argument simply *is* its global array.
# ---------------------------------------------------------------------------


def _darray_flatten(d: DArray):
    aux = (tuple(tuple(c) for c in d.cuts), tuple(d.pids.shape),
           tuple(int(p) for p in d.pids.flat))
    return (d.garray,), aux


def _darray_unflatten(aux, children):
    data, = children
    if not isinstance(data, jax.Array) or isinstance(data, jax.core.Tracer):
        # inside a transform: behave as the raw (traced) global array
        return data
    cuts, grid, pids_flat = aux
    if tuple(data.shape) != tuple(c[-1] for c in cuts):
        # shape changed under the transform (e.g. vmap/reduction output):
        # hand back the plain array rather than a mislabeled DArray
        return data
    try:
        expect = L.sharding_for(list(pids_flat), grid, tuple(data.shape))
        if data.sharding != expect:
            # device placement diverged from the recorded layout (e.g. a
            # device_put inside the transform): a DArray whose metadata
            # contradicts reality is worse than a plain array
            return data
    except Exception:
        return data
    pids = np.asarray(pids_flat, dtype=np.int64).reshape(grid)
    return DArray(data, pids, _idxs_from_cuts(cuts, grid),
                  [list(c) for c in cuts])


jax.tree_util.register_pytree_node(DArray, _darray_flatten, _darray_unflatten)


def copyto_(dest, src) -> "DArray":
    """Copy ``src`` into ``dest`` in place (reference copyto!(dest::
    SubOrDArray, src), darray.jl:679-687: per-worker local copy of the
    aligned view — here one XLA reshard/copy)."""
    _tm.count("op.copyto_")
    if isinstance(dest, SubDArray):
        key = dest.key
        parent = dest.parent
        val = src.garray if isinstance(src, DArray) else (
            src.materialize() if isinstance(src, SubDArray) else jnp.asarray(src))
        if tuple(val.shape) != tuple(dest.shape):
            # same contract as the DArray path / reference DimensionMismatch
            raise ValueError(f"copyto_: src shape {tuple(val.shape)} != view "
                             f"shape {tuple(dest.shape)}")
        # region-routed: uneven-layout views update only the owner blocks
        parent._mutate_region(key, val)
        return dest
    if not isinstance(dest, DArray):
        raise TypeError("copyto_ expects a DArray or SubDArray destination")
    raw = src.garray if isinstance(src, DArray) else (
        src.materialize() if isinstance(src, SubDArray) else jnp.asarray(src))
    if tuple(raw.shape) != dest.dims:
        raise ValueError(f"copyto_: src shape {tuple(raw.shape)} != dest "
                         f"dims {dest.dims}")
    dest._rebind(_fresh(raw.astype(dest.dtype), raw, src))
    return dest


def dcat(dim: int, *ds) -> "DArray":
    """Concatenate distributed arrays along ``dim`` (reference hcat/vcat,
    mapreduce.jl:18-19)."""
    vals = [x.garray if isinstance(x, DArray) else
            (x.materialize() if isinstance(x, SubDArray) else jnp.asarray(x))
            for x in ds]
    out = jnp.concatenate(vals, axis=dim)
    first = next((x for x in ds if isinstance(x, DArray)), None)
    procs = [int(p) for p in first.pids.flat] if first is not None else None
    return _wrap_global(out, procs=procs)


def dfetch(d: DArray, *i: int):
    """Fetch one element without the scalar guard (reference Base.fetch(d,i),
    darray.jl:386-391 — an explicit, intentional remote fetch)."""
    return d.garray[tuple(i)]


def isassigned(d, *i: int) -> bool:
    """True iff ``d[i...]`` is in bounds and holds a value (reference
    Base.isassigned, darray.jl:663-674: attempt the raw fetch, False on
    BoundsError/UndefRefError, rethrow anything else).

    Dense DArray chunks are always materialized, so this reduces to a
    bounds check; for ``DData`` it additionally requires the owning rank's
    part to exist."""
    if isinstance(d, DData):
        if len(i) != 1:
            return False
        k = int(i[0])
        return 0 <= k < len(d.pids) and int(d.pids[k]) in d._parts
    if isinstance(d, SubDArray):
        if len(i) != len(d.shape):
            return False
        try:
            return all(-n <= int(k) < n for k, n in zip(i, d.shape))
        except (TypeError, ValueError):
            return False
    if not isinstance(d, DArray):
        raise TypeError(f"isassigned expects a DArray/SubDArray/DData, "
                        f"got {type(d).__name__}")
    d._check_open()
    if len(i) != len(d.dims):
        return False
    try:
        _normalize_key(tuple(int(k) for k in i) if len(i) != 1 else int(i[0]),
                       d.dims)
    except IndexError:
        return False
    return True


def gather(d):
    """Gather distributed data to the controller.

    - ``DData`` → list of per-rank parts (reference gather, darray.jl:150-157)
    - ``DArray``/``SubDArray`` → dense numpy array (reference ``Array(d)``,
      darray.jl:574-596)
    """
    if isinstance(d, DData):
        return d.gather()
    if isinstance(d, (DArray, SubDArray)):
        return np.asarray(d)
    return d
