"""Checkpoint / resume for distributed arrays.

The reference has **no** checkpoint subsystem (SURVEY.md §5: "Checkpoint /
resume: none") — serializing a DArray over Julia's wire just moves ids
(serialize.jl:1-42).  A complete TPU framework needs durable state, so this
module provides it natively:

``save(path, tree)`` / ``load(path)`` checkpoint any pytree containing
DArrays, DDatas, jax.Arrays, numpy arrays, and plain Python values.
DArrays round-trip **with their layout**: dims, chunk grid, cuts and rank
assignment are restored exactly, and shard placement happens at load time
through the same sharding machinery as construction (one device_put
scatter per array).  Storage is a JSON-metadata file plus either a
self-contained ``.npz`` (default) or an Orbax PyTree store
(``save(..., store="orbax")`` — the chunked, multi-host-capable tier);
the layout-metadata format is shared, so both stores restore identically.

``CheckpointManager`` adds the training-loop tier on top: stepped
checkpoints under one directory, **async** saves (device→host snapshot
happens synchronously at ``save()``; serialization and disk IO run on a
background thread so the train loop isn't stalled), atomic publication
(write to a hidden temp dir, rename into place), and ``max_to_keep``
rotation of completed steps.

**Integrity:** every payload array's CRC32 is recorded in the step
metadata at save time and re-verified on restore; a mismatch raises
:class:`CheckpointIntegrityError`.  ``CheckpointManager.restore()``
treats a corrupt step exactly like a partially-published one — it
quarantines the bad step directory (renamed to ``.quarantine_step_*``,
so it never counts as restorable again), journals a
``restore_fallback``, and falls back to the previous verified step.
The ``checkpoint.read`` fault site (action ``corrupt``) flips payload
bytes deterministically so this whole path is chaos-testable.

**Peer replicas:** pass ``replicas=PeerReplicaStore()`` to the manager
and every published step is ALSO replicated chunk-by-chunk into buddy
ranks' memory — each chunk's buddy in a *different* failure domain
(``resilience.domains.buddy_map``), CRC-stamped.  ``restore()`` then
tries the peer replica first and falls back to disk, so a
device-loss/partition recovery runs at interconnect speed and a whole
host's shards survive its loss with zero disk reads (witnessed by the
``checkpoint.disk_reads`` vs ``checkpoint.restore_source`` counters).
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any

import numpy as np

import jax

from .. import telemetry as _tm
from ..darray import DArray, DData, distribute

__all__ = ["save", "load", "CheckpointManager", "CheckpointIntegrityError",
           "PeerReplicaStore", "PeerReplicaUnavailable"]

_META = "dartpu_meta.json"
_ARRS = "arrays.npz"
_ORBAX = "orbax_store"


class CheckpointIntegrityError(RuntimeError):
    """A checkpoint payload array failed its recorded CRC32 check —
    bytes on disk (or the read path) are corrupt.  ``path`` is the
    checkpoint directory, ``keys`` the failing payload keys."""

    def __init__(self, path, keys: list):
        self.path = str(path)
        self.keys = list(keys)
        super().__init__(
            f"checkpoint {self.path} failed integrity verification: "
            f"payload CRC32 mismatch on {self.keys}")


def _crc_map(arrays: dict) -> dict:
    """Per-payload CRC32 over the at-rest host bytes — the integrity
    metadata stored next to the tree (one pass per array; checkpoint IO
    dominates)."""
    return {k: int(zlib.crc32(np.ascontiguousarray(v).tobytes()))
            for k, v in arrays.items()}


def _verify_integrity(path, meta_doc: dict, arrays: dict) -> None:
    """Check every payload array against the CRC32s recorded at save
    time.  Pre-integrity checkpoints (no ``integrity`` section) pass
    unverified; a key recorded but missing from the payload counts as a
    mismatch (a vanished shard is corruption, not absence)."""
    integ = meta_doc.get("integrity") if isinstance(meta_doc, dict) else None
    if not integ or not isinstance(integ.get("crc32"), dict):
        return
    bad = []
    for key, want in integ["crc32"].items():
        arr = arrays.get(key)
        if arr is None or int(zlib.crc32(
                np.ascontiguousarray(arr).tobytes())) != int(want):
            bad.append(key)
    if bad:
        _tm.count("checkpoint.integrity_failures")
        if _tm.enabled():
            # cold path: a corrupt checkpoint is exceptional by definition
            _tm.event("checkpoint", "integrity_failure", path=str(path),
                      keys=",".join(sorted(bad)[:8]))
        raise CheckpointIntegrityError(path, sorted(bad))


def _encode(tree, arrays: dict, copy: bool = False):
    """Recursively replace array-ish leaves with tagged placeholders.

    ``copy=True`` decouples plain numpy leaves from caller-owned buffers
    (async checkpointing); device-sourced leaves (DArray/jax.Array) already
    materialize fresh host arrays and are never re-copied."""
    if isinstance(tree, DArray):
        key = f"a{len(arrays)}"
        # probe the at-rest physical buffer (`_data`), NOT `.garray` —
        # for padded layouts garray runs the compiled unpad program, and
        # the addressability answer is the same
        if getattr(tree._data.sharding, "is_fully_addressable", True):
            arrays[key] = np.asarray(tree)
        else:
            # multi-controller: the array spans processes — assemble via
            # the DCN gather (every process calls save in SPMD style,
            # like the reference's master-side checkpoint gather)
            from ..parallel.multihost import gather_global
            arrays[key] = gather_global(tree)
        return {"__dartpu__": "DArray", "key": key,
                "procs": [int(p) for p in tree.pids.flat],
                "dist": list(tree.pids.shape),
                "cuts": [list(c) for c in tree.cuts]}
    if isinstance(tree, DData):
        parts = tree.gather()
        enc_parts = [_encode(p, arrays, copy) for p in parts]
        return {"__dartpu__": "DData", "parts": enc_parts,
                "pids": [int(p) for p in tree.pids]}
    if isinstance(tree, (jax.Array, np.ndarray)):
        key = f"a{len(arrays)}"
        host = np.asarray(tree)
        if copy and host is tree:   # numpy leaf aliasing caller memory
            host = host.copy()
        entry = {"__dartpu__": "ndarray", "key": key,
                 "jax": isinstance(tree, jax.Array)}
        import ml_dtypes
        if host.dtype.kind == "V" and hasattr(ml_dtypes, host.dtype.name):
            # ml_dtypes (bfloat16, fp8, ...) don't survive npz round-trips;
            # store raw bytes + the dtype name and re-view at load.
            # (structured void dtypes fall through — npz handles those.)
            entry["mldtype"] = host.dtype.name
            entry["shape"] = list(host.shape)
            host = np.frombuffer(host.tobytes(), dtype=np.uint8)
        arrays[key] = host
        return entry
    if isinstance(tree, dict):
        if all(isinstance(k, str) for k in tree) and \
                not any(k in ("__dartpu__", "__dartpu_store__")
                        for k in tree):
            return {k: _encode(v, arrays, copy) for k, v in tree.items()}
        # non-string keys round-trip via an item-pair encoding (plain JSON
        # would silently stringify them)
        return {"__dartpu__": "dict",
                "items": [[_encode(k, arrays, copy),
                           _encode(v, arrays, copy)]
                          for k, v in tree.items()]}
    if isinstance(tree, (list, tuple)):
        enc = [_encode(v, arrays, copy) for v in tree]
        return {"__dartpu__": "tuple", "items": enc} \
            if isinstance(tree, tuple) else enc
    if isinstance(tree, bool) or tree is None or isinstance(tree, str):
        return tree
    if isinstance(tree, np.generic):
        # preserve the numpy scalar type (float() would corrupt int64/bool_)
        return {"__dartpu__": "npscalar", "dtype": str(tree.dtype),
                "v": tree.item()}
    if isinstance(tree, (int, float)):
        return tree
    raise TypeError(f"cannot checkpoint leaf of type {type(tree)}")


def _restore_darray(tree, arrays):
    host = arrays[tree["key"]]
    procs, dist = tree["procs"], tree["dist"]
    navail = len(jax.devices())
    if any(p >= navail for p in procs):
        import warnings
        warnings.warn(
            f"checkpoint was written on {max(procs) + 1}+ devices but only "
            f"{navail} are available; restoring with the default layout")
        return distribute(host)
    cuts = tree.get("cuts")
    if cuts is not None:
        # restore the exact (possibly uneven / non-default) chunk layout:
        # the saved host array is already assembled, so wrap it directly —
        # one device_put, no chunk split/reassemble round-trip
        from ..darray import darray_from_cuts
        return darray_from_cuts(host, procs, cuts)
    return distribute(host, procs=procs, dist=dist)


def _decode(tree, arrays):
    if isinstance(tree, dict):
        tag = tree.get("__dartpu__")
        if tag == "DArray":
            return _restore_darray(tree, arrays)
        if tag == "npscalar":
            return np.dtype(tree["dtype"]).type(tree["v"])
        if tag == "dict":
            return {_decode(k, arrays): _decode(v, arrays)
                    for k, v in tree["items"]}
        if tag == "ndarray":
            host = arrays[tree["key"]]
            if "mldtype" in tree:
                import ml_dtypes
                dt = np.dtype(getattr(ml_dtypes, tree["mldtype"]))
                host = np.frombuffer(host.tobytes(), dtype=dt).reshape(
                    tree["shape"]).copy()   # frombuffer views are read-only
            return jax.numpy.asarray(host) if tree["jax"] else host
        if tag == "DData":
            from ..darray import DData as _DData
            parts = [_decode(p, arrays) for p in tree["parts"]]
            return _DData(dict(zip(tree["pids"], parts)), tree["pids"])
        if tag == "tuple":
            return tuple(_decode(v, arrays) for v in tree["items"])
        return {k: _decode(v, arrays) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_decode(v, arrays) for v in tree]
    return tree


def _read_faults(path, store: str, arrays: dict) -> dict:
    """The ``checkpoint.read`` injection site: a fired ``corrupt`` spec
    flips payload bytes (seeded — :func:`faults.corrupt_arrays`); any
    other action runs normally (``raise``/``device_loss``/``hang`` model
    a failing storage read)."""
    from ..resilience import faults as _fl
    spec = _fl.decide("checkpoint.read", store=store, path=str(path))
    if spec is None:
        return arrays
    if spec.action == "corrupt":
        return _fl.corrupt_arrays(spec, arrays)
    _fl.act(spec, {"store": store, "path": str(path)})
    return arrays


def save(path: str | os.PathLike, tree: Any, store: str = "npz") -> None:
    """Checkpoint a pytree (DArrays keep their layout metadata).

    ``store``: "npz" (default — single self-contained file pair) or
    "orbax" (Orbax PyTree store: chunked/ocdbt on-disk format, the
    multi-host-capable tier).  The layout metadata format is identical, so
    the two stores are feature-equivalent for restores on one host.
    """
    if store not in ("npz", "orbax"):
        # validate before any side effect (no stray directories/encodes)
        raise ValueError(f"unknown store {store!r} (use 'npz' or 'orbax')")
    with _tm.span("checkpoint.save", store=store):
        # cold path: checkpoint I/O dominates the event cost
        _tm.event("checkpoint", "save_start", path=str(path),  # dalint: disable=DAL003
                  store=store)
        arrays: dict[str, np.ndarray] = {}
        with _tm.span("checkpoint.save.encode", _journal=False):
            meta = _encode(tree, arrays)
        with _tm.span("checkpoint.save.write", _journal=False):
            _write_store(Path(path), meta, arrays, store)
        _tm.count("checkpoint.saves")
        # cold path: checkpoint I/O dominates the event cost
        _tm.event("checkpoint", "save_end", path=str(path),  # dalint: disable=DAL003
                  store=store, arrays=len(arrays),
                  bytes=int(sum(a.nbytes for a in arrays.values())))
        # HBM-ledger phase boundary on the Perfetto counter track
        _tm.memory.sample("checkpoint.save")


def load(path: str | os.PathLike) -> Any:
    """Restore a checkpoint (either store); DArrays are re-distributed onto
    their saved chunk grids (default relayout with a warning when fewer
    devices are available than at save time)."""
    path = Path(path)
    with _tm.span("checkpoint.restore"):
        # the zero-disk-reads witness for peer-replica restores: every
        # on-disk load counts here, a replica fetch never reaches this
        _tm.count("checkpoint.disk_reads")
        # cold path: checkpoint I/O dominates the event cost
        _tm.event("checkpoint", "restore_start", path=str(path))  # dalint: disable=DAL003
        meta_doc = json.loads((path / _META).read_text())
        # positive new-format detection: the sentinel key can never be
        # produced by _encode (user dicts containing it are item-pair
        # encoded)
        if isinstance(meta_doc, dict) and "__dartpu_store__" in meta_doc:
            store, meta = meta_doc["__dartpu_store__"], meta_doc["tree"]
        else:                                  # pre-store-field checkpoints
            store, meta = "npz", meta_doc
        with _tm.span("checkpoint.restore.read", _journal=False):
            if store == "orbax":
                if (path / _ORBAX).exists():
                    import orbax.checkpoint as ocp
                    with ocp.PyTreeCheckpointer() as ckptr:
                        arrays = ckptr.restore((path / _ORBAX).resolve())
                else:                          # array-free checkpoint
                    arrays = {}
            else:
                with np.load(path / _ARRS) as z:
                    arrays = {k: z[k] for k in z.files}
        # chaos site: an armed plan can corrupt (or fail) the payload
        # read — byte flips applied HERE, before verification, so the
        # integrity check is what catches them, exactly like real disk
        # rot would be caught
        arrays = _read_faults(path, store, arrays)
        _verify_integrity(path, meta_doc, arrays)
        with _tm.span("checkpoint.restore.decode", _journal=False):
            out = _decode(meta, arrays)
        _tm.count("checkpoint.restores")
        # cold path: checkpoint I/O dominates the event cost
        _tm.event("checkpoint", "restore_end", path=str(path),  # dalint: disable=DAL003
                  store=store, arrays=len(arrays),
                  bytes=int(sum(a.nbytes for a in arrays.values())))
        # HBM-ledger phase boundary on the Perfetto counter track
        _tm.memory.sample("checkpoint.restore")
        return out


def _write_store(path: Path, meta, arrays, store: str) -> None:
    """Serialize one already-encoded checkpoint into ``path`` (the single
    body behind both save() and CheckpointManager publication).

    The metadata file is written LAST: its presence is the publish
    marker, so an interruption between the payload write and here leaves
    a *partial* directory that ``CheckpointManager.steps()`` ignores and
    ``restore()`` falls back past."""
    path.mkdir(parents=True, exist_ok=True)
    if store == "orbax" and arrays:
        import orbax.checkpoint as ocp
        with ocp.PyTreeCheckpointer() as ckptr:
            ckptr.save((path / _ORBAX).resolve(), arrays, force=True)
    elif store == "npz":
        np.savez(path / _ARRS, **arrays)
    # chaos site: an armed fault plan can kill the write here — payload
    # on disk, publish marker absent — the "interrupted checkpoint"
    # failure the restore fallback must survive
    from ..resilience import faults as _fl
    _fl.check("checkpoint.write", store=store)
    # (orbax with no array leaves: nothing to store; load mirrors this)
    (path / _META).write_text(
        json.dumps({"__dartpu_store__": store, "tree": meta,
                    "integrity": {"algo": "crc32",
                                  "crc32": _crc_map(arrays)}}))


class PeerReplicaUnavailable(RuntimeError):
    """No live rank holds a needed replica chunk — both its owner and
    its buddy holder are down (e.g. a partition took two domains at
    once).  The restore path falls back to disk past this."""

    def __init__(self, step: int, key: str, chunk: int,
                 owner: int, holder: int):
        self.step, self.key, self.chunk = int(step), str(key), int(chunk)
        super().__init__(
            f"peer replica for step {step} chunk {key}[{chunk}] is gone: "
            f"owner rank {owner} and holder rank {holder} are both down")


def _darray_entries(meta) -> dict:
    """Every encoded-DArray placeholder in a checkpoint tree, by payload
    key — the chunk layout (procs/dist/cuts) peer replication shards by."""
    out: dict = {}

    def walk(t):
        if isinstance(t, dict):
            if t.get("__dartpu__") == "DArray":
                out[t["key"]] = t
                return
            for v in t.values():
                walk(v)
        elif isinstance(t, list):
            for v in t:
                walk(v)
    walk(meta)
    return out


def _chunk_slices(entry: dict) -> list:
    """Per-block ``(owner_rank, index_slices)`` for one encoded DArray,
    in the block grid's row-major order — the unit peer replication
    copies, exactly the bytes that rank's device held."""
    grid = tuple(int(x) for x in entry["dist"])
    procs = [int(p) for p in entry["procs"]]
    cuts = entry["cuts"]
    out = []
    for j, owner in enumerate(procs):
        coords = np.unravel_index(j, grid) if grid else ()
        sl = tuple(slice(int(cuts[d][c]), int(cuts[d][c + 1]))
                   for d, c in enumerate(coords))
        out.append((owner, sl))
    return out


class PeerReplicaStore:
    """In-memory peer replicas of checkpoint payloads, placed by failure
    domain.

    The single-controller model of per-host RAM replication: at publish
    time every payload chunk is copied into its owner rank's *buddy*
    rank (``resilience.domains.buddy_map`` — a different failure domain
    whenever two domains are live), CRC-stamped per chunk.  A later
    :meth:`fetch` reassembles the step from chunks whose owner is still
    live ("local") or whose holder is ("peer" — the over-the-wire pull),
    so a whole domain's loss costs zero disk reads; only when BOTH sides
    of a chunk are down does the restore fall back to disk.  On a real
    multi-controller deployment the same placement map drives RDMA copies
    between hosts; the store's accounting (owner/holder/CRC per chunk) is
    identical.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # step -> {"meta": tree, "keys": {key: (shape, dtype)},
        #          "chunks": {(key, j): {owner, holder, data, crc, slices}}}
        self._steps: dict[int, dict] = {}

    # -- placement ---------------------------------------------------------

    def put(self, step: int, meta, arrays: dict,
            live_ranks=None) -> dict:
        """Replicate one encoded checkpoint into buddy memory.  Returns
        ``{"chunks": n, "bytes": n, "cross_domain": bool}``."""
        from ..resilience import domains as _dm
        if live_ranks is None:
            from ..resilience import elastic as _el
            live_ranks = _el.manager().live_ranks()
        live = sorted({int(r) for r in live_ranks})
        bmap = _dm.buddy_map(live)
        dents = _darray_entries(meta)
        chunks: dict = {}
        keys: dict = {}
        total = 0
        for key, arr in arrays.items():
            host = np.ascontiguousarray(arr)
            keys[key] = (tuple(host.shape), host.dtype.str)
            if key in dents:
                parts = _chunk_slices(dents[key])
            else:
                # plain (replicated) leaf: one chunk, conceptually owned
                # by the first live rank's host
                parts = [(live[0] if live else 0,
                          tuple(slice(0, n) for n in host.shape))]
            for j, (owner, sl) in enumerate(parts):
                data = host[sl].tobytes()
                total += len(data)
                chunks[(key, j)] = {
                    "owner": int(owner),
                    "holder": int(bmap.get(int(owner), int(owner))),
                    "data": data,
                    "crc": int(zlib.crc32(data)),
                    "slices": [(s.start, s.stop) for s in sl],
                }
        # a JSON round-trip decouples the stored tree from caller-owned
        # (and possibly later-mutated) metadata structures
        rec = {"meta": json.loads(json.dumps(meta)), "keys": keys,
               "chunks": chunks}
        with self._lock:
            self._steps[int(step)] = rec
        _tm.count("checkpoint.replications")
        if _tm.enabled():
            # cold path: one event per replicated step
            _tm.event("checkpoint", "replicate", step=int(step),
                      chunks=len(chunks), bytes=total,
                      cross_domain=_dm.is_cross_domain(bmap))
        return {"chunks": len(chunks), "bytes": total,
                "cross_domain": _dm.is_cross_domain(bmap)}

    # -- retrieval ---------------------------------------------------------

    def fetch(self, step: int, live_ranks=None):
        """Reassemble ``(meta, arrays, info)`` for ``step`` from replica
        chunks reachable through live ranks.  Raises ``KeyError`` when
        the step was never replicated, :class:`PeerReplicaUnavailable`
        when a chunk's owner AND holder are both down, and
        :class:`CheckpointIntegrityError` on a per-chunk CRC mismatch."""
        with self._lock:
            rec = self._steps.get(int(step))
            if rec is None:
                raise KeyError(f"no peer replica for step {step}")
        if live_ranks is None:
            from ..resilience import elastic as _el
            live_ranks = _el.manager().live_ranks()
        live = {int(r) for r in live_ranks}
        arrays: dict[str, np.ndarray] = {}
        for key, (shape, dstr) in rec["keys"].items():
            arrays[key] = np.empty(shape, dtype=np.dtype(dstr))
        n_local = n_peer = 0
        bad: list[str] = []
        for (key, j), ch in rec["chunks"].items():
            if ch["owner"] in live:
                n_local += 1
            elif ch["holder"] in live:
                n_peer += 1
            else:
                raise PeerReplicaUnavailable(step, key, j, ch["owner"],
                                             ch["holder"])
            if int(zlib.crc32(ch["data"])) != ch["crc"]:
                bad.append(key)
                continue
            sl = tuple(slice(a, b) for a, b in ch["slices"])
            dst = arrays[key]
            cshape = tuple(b - a for a, b in ch["slices"])
            dst[sl] = np.frombuffer(
                ch["data"], dtype=dst.dtype).reshape(cshape)
        if bad:
            _tm.count("checkpoint.integrity_failures")
            raise CheckpointIntegrityError(f"<peer replica step {step}>",
                                           sorted(set(bad)))
        if n_peer:
            _tm.count("checkpoint.peer_fetches", n=n_peer)
        info = {"local_chunks": n_local, "peer_chunks": n_peer}
        if _tm.enabled():
            # cold path: one event per replica restore
            _tm.event("checkpoint", "replica_fetch", step=int(step),
                      **info)
        return rec["meta"], arrays, info

    # -- inventory ---------------------------------------------------------

    def steps(self) -> list[int]:
        with self._lock:
            return sorted(self._steps)

    def drop(self, step: int) -> None:
        with self._lock:
            self._steps.pop(int(step), None)

    def drop_from(self, step: int) -> list[int]:
        with self._lock:
            dropped = sorted(s for s in self._steps if s >= int(step))
            for s in dropped:
                del self._steps[s]
        return dropped

    def clear(self) -> None:
        with self._lock:
            self._steps.clear()


class CheckpointManager:
    """Stepped checkpoints with async save and ``max_to_keep`` rotation.

    The reference has no checkpoint subsystem at all (SURVEY.md §5); this
    is the training-loop tier a TPU framework needs.  Usage::

        with CheckpointManager(dir, max_to_keep=3) as mgr:
            for step in range(...):
                ...
                mgr.save(step, {"params": params, "opt": opt_state})
        state = CheckpointManager(dir).restore()        # latest step

    ``save`` snapshots device state to host *synchronously* (so the train
    loop may mutate/donate its arrays immediately) and hands
    serialization + disk IO to one background thread; steps are written
    to a hidden temp directory and renamed into place, so readers never
    observe a partial checkpoint, and a crash mid-save leaves the
    previous steps intact.  Rotation deletes the oldest completed steps
    beyond ``max_to_keep`` after each successful save.
    """

    _STEP = "step_{:08d}"

    def __init__(self, directory: str | os.PathLike,
                 max_to_keep: int | None = 3, async_save: bool = True,
                 keep_quarantined: int | None = 4,
                 replicas: PeerReplicaStore | None = None):
        if max_to_keep is not None and max_to_keep < 1:
            raise ValueError(f"max_to_keep must be >= 1, got {max_to_keep}")
        if keep_quarantined is not None and keep_quarantined < 0:
            raise ValueError(f"keep_quarantined must be >= 0, got "
                             f"{keep_quarantined}")
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.max_to_keep = max_to_keep
        # quarantined (corrupt) step dirs kept for forensics; older ones
        # are reaped during save so they cannot accumulate forever
        # (None = keep all)
        self.keep_quarantined = keep_quarantined
        # peer replica tier: replicate each published step into buddy
        # memory and restore from there first (None = disk only)
        self._replicas = replicas
        self._async = bool(async_save)
        self._pool = (ThreadPoolExecutor(max_workers=1,
                                         thread_name_prefix="ckpt")
                      if self._async else None)
        self._pending: dict[int, Any] = {}   # step -> in-flight future
        self._lock = threading.Lock()

    # -- inventory ---------------------------------------------------------

    def steps(self) -> list[int]:
        """Completed (published) step numbers, ascending."""
        out = []
        for p in self.directory.iterdir():
            name = p.name
            if p.is_dir() and name.startswith("step_") and \
                    name[5:].isdigit() and (p / _META).exists():
                out.append(int(name[5:]))
        return sorted(out)

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    def _step_dir(self, step: int) -> Path:
        return self.directory / self._STEP.format(step)

    # -- save --------------------------------------------------------------

    def save(self, step: int, tree: Any, store: str = "npz") -> None:
        """Checkpoint ``tree`` as ``step``.  Device→host transfer happens
        before this returns; IO happens in the background (async mode)."""
        if store not in ("npz", "orbax"):
            raise ValueError(f"unknown store {store!r} (use 'npz'/'orbax')")
        with self._lock:
            self._reap(wait=False)  # dalint: disable=DAL008 — wait=False reaps only done() futures; result() returns immediately
            # pending/reserved steps count as existing: a duplicate racing
            # an in-flight (or concurrently-encoding) save must get this
            # ValueError, not a later os.replace failure from the
            # background thread — so the step is RESERVED here, inside the
            # same lock section as the check
            if step in self.steps() or step in self._pending:
                raise ValueError(f"step {step} already exists in "
                                 f"{self.directory}")
            self._pending[step] = None          # reservation
        try:
            arrays: dict[str, np.ndarray] = {}
            # copy=True decouples plain numpy leaves from caller-owned
            # buffers (device leaves already materialize fresh host arrays)
            meta = _encode(tree, arrays, copy=True)
            if self._pool is None:
                self._publish(step, meta, arrays, store)
                with self._lock:
                    self._pending.pop(step, None)
                return
            with self._lock:
                self._pending[step] = self._pool.submit(
                    self._publish, step, meta, arrays, store)
        except BaseException:
            with self._lock:
                self._pending.pop(step, None)
            raise

    def _publish(self, step: int, meta, arrays, store: str) -> None:
        # peer replication FIRST (it is memory-speed; the disk write
        # dominates), so a crash mid-write still leaves the in-memory
        # replica restorable.  Best-effort: a replication failure must
        # never lose the durable tier.
        if self._replicas is not None:
            try:
                self._replicas.put(step, meta, arrays)
            except Exception as e:  # noqa: BLE001 — disk tier still publishes
                _tm.count("checkpoint.replication_failures")
                if _tm.enabled():
                    # cold path: a failed replication is exceptional
                    _tm.event("checkpoint", "replication_failure",
                              step=step,
                              error=f"{type(e).__name__}: {str(e)[:200]}")
        final = self._step_dir(step)
        tmp = self.directory / f".tmp_{self._STEP.format(step)}"
        if tmp.exists():
            shutil.rmtree(tmp)
        _write_store(tmp, meta, arrays, store)
        os.replace(tmp, final)
        # event from the background save thread — the journal is
        # thread-safe, and the publish time is the phase worth seeing
        _tm.count("checkpoint.saves")
        # cold path: the atomic-publish rename dominates the event cost
        _tm.event("checkpoint", "publish", step=step, store=store,  # dalint: disable=DAL003
                  arrays=len(arrays),
                  bytes=int(sum(a.nbytes for a in arrays.values())))
        self._rotate()
        self._reap_quarantine()

    def _rotate(self) -> None:
        if self.max_to_keep is None:
            return
        done = self.steps()
        for s in done[:max(0, len(done) - self.max_to_keep)]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
            if self._replicas is not None:
                self._replicas.drop(s)
        if self._replicas is not None:
            # replica-only steps (disk write failed) rotate on the same
            # census, or the memory tier would grow unboundedly
            reps = self._replicas.steps()
            for s in reps[:max(0, len(reps) - self.max_to_keep)]:
                self._replicas.drop(s)

    def _reap_quarantine(self) -> None:
        """Bound the ``.quarantine_step_*`` forensic stash: keep the
        newest ``keep_quarantined`` (by step, which the zero-padded name
        sorts), reap the rest oldest-first, journaling each reap."""
        if self.keep_quarantined is None:
            return
        quarantined = sorted(
            p for p in self.directory.iterdir()
            if p.is_dir() and p.name.startswith(".quarantine_step_"))
        for p in quarantined[:max(0,
                                  len(quarantined) - self.keep_quarantined)]:
            shutil.rmtree(p, ignore_errors=True)
            _tm.count("checkpoint.quarantine_reaps")
            if _tm.enabled():
                # cold path: reaping is rarer than quarantining
                _tm.event("checkpoint", "quarantine_reap", path=p.name)

    def _reap(self, wait: bool) -> None:
        still, first_exc = {}, None
        for step, fut in self._pending.items():
            if fut is None:          # reserved by a save() mid-encode
                still[step] = fut
            elif fut.done() or wait:
                try:
                    fut.result()
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    # the failed future must still leave _pending, or the
                    # manager wedges: every later call would re-raise this
                    # and the step could never be retried
                    if first_exc is None:
                        first_exc = e
            else:
                still[step] = fut
        self._pending = still
        if first_exc is not None:
            raise first_exc

    # -- restore / lifecycle ----------------------------------------------

    def _restore_replica(self, step: int):
        """Try the peer-replica tier for one step.  Returns the decoded
        tree, or None when no replica exists / the replica cannot serve
        (chunk owners+holders all down, CRC mismatch) — the caller falls
        back to disk.  A CRC-bad replica is evicted like a quarantined
        disk step (the bytes are provably wrong forever)."""
        if self._replicas is None:
            return None
        try:
            meta, arrays, info = self._replicas.fetch(step)
            out = _decode(meta, arrays)
        except KeyError:
            return None                      # never replicated: not a fault
        except Exception as e:  # noqa: BLE001 — disk tier is the fallback
            _tm.count("checkpoint.replica_fallbacks")
            if _tm.enabled():
                # cold path: an unservable replica is exceptional
                _tm.event("checkpoint", "replica_fallback", step=step,
                          error=f"{type(e).__name__}: {str(e)[:200]}")
            if isinstance(e, CheckpointIntegrityError):
                self._replicas.drop(step)
            return None
        _tm.count("checkpoint.restore_source", source="peer")
        _tm.count("checkpoint.restores")
        if _tm.enabled():
            # cold path: one event per restore
            _tm.event("checkpoint", "restore_peer", step=step, **info)
        return out

    def restore(self, step: int | None = None) -> Any:
        """Load ``step``; with no step given, the latest *restorable*
        one.  With a peer-replica store attached the replica tier is
        tried FIRST (memory/interconnect speed, zero disk reads —
        ``checkpoint.restore_source`` records which tier served); disk
        is the fallback.  A partially-published step directory — no
        publish marker (``steps()`` already skips those), or a marker
        whose payload is missing/corrupt (a crash or fault mid-write) —
        is skipped with a journaled fallback to the previous complete
        step instead of raising mid-restore; an explicitly requested
        ``step`` stays strict on the disk tier."""
        self.wait()
        if step is not None:
            out = self._restore_replica(step)
            if out is not None:
                return out
            d = self._step_dir(step)
            if not (d / _META).exists():
                raise FileNotFoundError(f"no checkpoint for step {step} in "
                                        f"{self.directory}")
            out = load(d)
            _tm.count("checkpoint.restore_source", source="disk")
            if _tm.enabled():
                # cold path: one event per restore — the disk-tier twin
                # of restore_peer, so incident reconstruction names which
                # tier actually served
                _tm.event("checkpoint", "restore_disk", step=step)
            return out
        done = self.steps()
        rep_steps = self._replicas.steps() if self._replicas is not None \
            else []
        candidates = sorted(set(done) | set(rep_steps))
        if not candidates:
            raise FileNotFoundError(
                f"no completed checkpoints in {self.directory}")
        last_exc: BaseException | None = None
        for s in reversed(candidates):
            out = self._restore_replica(s)
            if out is not None:
                return out
            if s not in done:
                continue                     # replica-only step: no disk dir
            try:
                out = load(self._step_dir(s))
                _tm.count("checkpoint.restore_source", source="disk")
                if _tm.enabled():
                    # cold path: one event per restore (see above)
                    _tm.event("checkpoint", "restore_disk", step=s)
                return out
            except Exception as e:  # noqa: BLE001 — fall back, then re-raise
                last_exc = e
                _tm.count("checkpoint.restore_fallbacks")
                if _tm.enabled():
                    # cold path: a partial/corrupt step is exceptional
                    _tm.event("checkpoint", "restore_fallback",
                              step=s, error=f"{type(e).__name__}: "
                                            f"{str(e)[:200]}")
                if isinstance(e, CheckpointIntegrityError):
                    # bytes on disk are provably bad: quarantine the step
                    # so no later restore (or rotation census) ever
                    # trusts it again — partial steps merely fall back,
                    # corrupt ones are evicted
                    self._quarantine(s)
        raise FileNotFoundError(
            f"no restorable checkpoint in {self.directory}: every "
            f"completed step failed to load") from last_exc

    def _quarantine(self, step: int) -> None:
        """Move a corrupt step directory to a hidden ``.quarantine_*``
        name: it stops counting as a completed step (``steps()`` only
        sees ``step_*``) but stays on disk for forensics."""
        src = self._step_dir(step)
        dst = self.directory / f".quarantine_{src.name}"
        try:
            if dst.exists():
                shutil.rmtree(dst)
            os.replace(src, dst)
        except OSError:
            # a quarantine that cannot rename still must not block the
            # fallback restore; the step will fail integrity again next
            # time and re-enter here
            return
        _tm.count("checkpoint.quarantines")
        if _tm.enabled():
            # cold path: quarantining a corrupt step is exceptional
            _tm.event("checkpoint", "quarantine", step=step,
                      path=str(dst))

    def discard_from(self, step: int) -> list[int]:
        """Delete every published step ``>= step`` (and drain pending
        saves first).  The timeline-rewind primitive: a trainer that
        restored step ``S`` and is about to recompute forward must
        discard the now-stale later steps, or a future restore could
        resurrect state from the abandoned timeline (e.g. a pre-shrink
        device layout).  Returns the discarded step numbers."""
        self.wait()
        dropped = [s for s in self.steps() if s >= step]
        for s in dropped:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
        if self._replicas is not None:
            # the memory tier rewinds with the disk tier, or a future
            # peer-first restore would resurrect the abandoned timeline
            dropped = sorted(set(dropped)
                             | set(self._replicas.drop_from(step)))
        if dropped and _tm.enabled():
            # cold path: a timeline rewind is a recovery-path event
            _tm.event("checkpoint", "discard_from", step=step,
                      dropped=len(dropped))
        return dropped

    def wait(self) -> None:
        """Block until every pending async save has been published (and
        re-raise the first background failure, if any)."""
        with self._lock:
            self._reap(wait=True)  # dalint: disable=DAL008 — wait() IS the quiesce API: holding the lock while IO drains is its contract (no save may interleave)

    def close(self) -> None:
        self.wait()
        if self._pool is not None:
            self._pool.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
