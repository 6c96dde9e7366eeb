"""HBM memory ledger: who holds how many device bytes, and since when.

PR 4's reshard planner promises *bounded staging memory* and its
``_BufShare`` co-ownership makes "who owns these bytes" non-trivial; the
lifecycle registry (``core.registry()`` / ``d_closeall()``) proves arrays
are *closed* but says nothing about the resource those invariants
protect.  This module is the accounting layer between the two: every
DArray's device buffer is tracked from creation through rebind/reshard/
mutation to ``close()``/finalizer, with

- **per-device live-byte gauges and peak watermarks** — physical bytes
  (sum over addressable shards, so replication and blocked padding cost
  what they actually cost in HBM), not logical array sizes;
- **shared-ownership awareness** — a buffer co-owned through a
  ``_BufShare`` token is counted ONCE and released only when the last
  owner closes, mirroring the runtime semantics exactly;
- **allocation-site attribution** — the creating span plus a truncated
  stack per entry (``DA_TPU_TELEMETRY_MEMSTACK=0`` turns the stack
  capture off; ``DA_TPU_TELEMETRY=0`` turns the whole ledger off and
  every hook collapses to a single boolean check);
- **staging accounting** — :func:`staging` brackets transient buffers
  (the reshard planner's per-chunk staging pieces), so the
  ``DA_TPU_RESHARD_CHUNK_MB`` bound is *observed*, not assumed;
- **:func:`leak_census`** — diffs the ledger against
  ``jax.live_arrays()`` and classifies bytes as ledger-tracked /
  untracked-foreign / deleted-but-registered.

Surfaced as the ``memory`` section of :func:`core.report`, as
``da_tpu_hbm_*`` gauges in ``to_prometheus``, as a counter ("C") track in
``to_perfetto``, and via ``python -m distributedarrays_tpu.telemetry mem``.

Like the rest of the telemetry core this module imports nothing from the
rest of the package (stdlib only; ``leak_census`` imports jax lazily),
so any layer can import it without cycles.
"""

from __future__ import annotations

import itertools
import os
import sys
import time
import weakref

from . import core

__all__ = [
    "track", "untrack", "share", "sample",
    "live_bytes", "live_bytes_by_device", "peak_bytes", "reset_peak",
    "tracked_count",
    "staging", "staging_peak", "snapshot", "entries", "leak_census",
]

_STACK_DEPTH = 5


def _stack_enabled() -> bool:
    v = os.environ.get("DA_TPU_TELEMETRY_MEMSTACK")
    return v is None or v.strip().lower() not in core._FALSY


# read once at import, like core._ENABLED: every DArray construction
# passes here
_STACK_ENABLED: bool = _stack_enabled()


class _Entry:
    """One tracked device buffer.  ``owners`` is the set of DArray ids
    co-owning it (>1 after a ``_BufShare`` join); bytes are freed when
    the LAST owner leaves."""

    __slots__ = ("eid", "owners", "nbytes", "per_dev", "site", "span",
                 "stack", "buf_ref", "buf_id", "t")

    def to_dict(self) -> dict:
        # the stack is stored as raw (file, line, function) triples (no
        # line-text lookup, no string formatting on the allocation path)
        # and only rendered here, when someone actually inspects the entry
        stack = None
        if self.stack:
            stack = [f"{os.path.basename(filename)}:{lineno}:{name}"
                     for filename, lineno, name in reversed(self.stack)]
        return {"owners": [list(o) if isinstance(o, tuple) else o
                           for o in sorted(self.owners)],
                "nbytes": self.nbytes,
                "per_device": {str(k): v for k, v in self.per_dev.items()},
                "site": self.site, "span": self.span, "stack": stack,
                "age_s": round(time.monotonic() - self.t, 3)}


_ids = itertools.count(1)          # CPython-atomic
_entries: dict[int, _Entry] = {}   # eid -> entry
_by_owner: dict = {}               # owner id -> eid
_by_buf: dict[int, int] = {}       # id(buf) -> eid (weakref-validated)
_live_total = 0
_peak_total = 0
_live_dev: dict = {}               # device id -> live bytes
_peak_dev: dict = {}               # device id -> peak bytes
_staging_live = 0
_staging_peak = 0
_staging_peak_tag: dict[str, int] = {}


def _shard_bytes(buf) -> tuple[dict, int]:
    """Physical per-device byte map of a (possibly sharded, possibly
    replicated) device buffer — duck-typed so this module never imports
    jax.  Falls back to the logical size on one pseudo-device when shard
    introspection is unavailable."""
    per: dict = {}
    total = 0
    try:
        shards = buf.addressable_shards
    except Exception:
        shards = None
    if shards:
        try:
            for s in shards:
                dev = getattr(getattr(s, "device", None), "id", -1)
                nb = int(getattr(getattr(s, "data", None), "nbytes", 0) or 0)
                per[dev] = per.get(dev, 0) + nb
                total += nb
            return per, total
        except Exception:
            per, total = {}, 0
    nb = core.nbytes_of(buf)
    return ({-1: nb} if nb else {}), nb


def _capture_site():
    sp = core._CURRENT_SPAN.get()
    span = sp.name if sp is not None else None
    stack = None
    if _STACK_ENABLED:
        # a plain walk up the frames: every DArray construction passes
        # here, and traceback.StackSummary costs ten times as much for
        # the same three facts a frame; rendered lazily in _Entry.to_dict
        stack = []
        frame = sys._getframe(2)
        while frame is not None and len(stack) < _STACK_DEPTH:
            code = frame.f_code
            stack.append((code.co_filename, frame.f_lineno, code.co_name))
            frame = frame.f_back
    return span, stack


def _add_locked(per: dict, total: int) -> None:
    global _live_total, _peak_total
    _live_total += total
    if _live_total > _peak_total:
        _peak_total = _live_total
    for dev, nb in per.items():
        v = _live_dev.get(dev, 0) + nb
        _live_dev[dev] = v
        if v > _peak_dev.get(dev, 0):
            _peak_dev[dev] = v


def _sub_locked(per: dict, total: int) -> None:
    global _live_total
    _live_total -= total
    for dev, nb in per.items():
        v = _live_dev.get(dev, 0) - nb
        if v <= 0:
            _live_dev.pop(dev, None)
        else:
            _live_dev[dev] = v


def _drop_owner_locked(owner):
    """Remove ``owner`` from its entry; returns the freed entry (bytes
    subtracted) when the owner was the last holder, else None."""
    eid = _by_owner.pop(owner, None)
    if eid is None:
        return None
    e = _entries.get(eid)
    if e is None:
        return None
    e.owners.discard(owner)
    if e.owners:
        return None
    del _entries[eid]
    if _by_buf.get(e.buf_id) == eid:
        del _by_buf[e.buf_id]
    _sub_locked(e.per_dev, e.nbytes)
    return e


# ---------------------------------------------------------------------------
# lifecycle hooks (called from darray.py)
# ---------------------------------------------------------------------------


def track(owner, buf, *, site: str | None = None) -> None:
    """Attribute ``buf``'s device bytes to DArray ``owner``.  Re-tracking
    an owner (rebind) releases its previous entry first.  If ``buf`` is
    already a tracked entry's buffer (identity-checked through the
    entry's weakref), the owner JOINS that entry instead of allocating a
    duplicate — so handing a buffer from one DArray to another (aligned
    ``samedist``, ``map_localparts_into``) never double-counts it, not
    even transiently: the peak watermark only ever sees real HBM."""
    if not core._ENABLED:
        return
    per, total = _shard_bytes(buf)
    span, stack = _capture_site()
    try:
        ref = weakref.ref(buf)
    except TypeError:
        ref = None
    with core._LOCK:
        jeid = _by_buf.get(id(buf))
        je = _entries.get(jeid) if jeid is not None else None
        if je is not None and (je.buf_ref is None
                               or je.buf_ref() is not buf):
            je = None                # stale id: a dead buffer's address
        if je is not None:
            if _by_owner.get(owner) != jeid:
                _drop_owner_locked(owner)
                je.owners.add(owner)
                _by_owner[owner] = jeid
            live = _live_total
        else:
            e = _Entry()
            e.eid = next(_ids)
            e.owners = {owner}
            e.nbytes = total
            e.per_dev = per
            e.site = site
            e.span = span
            e.stack = stack
            e.buf_ref = ref
            e.buf_id = id(buf)
            e.t = time.monotonic()
            _drop_owner_locked(owner)
            _entries[e.eid] = e
            _by_owner[owner] = e.eid
            if ref is not None:
                _by_buf[id(buf)] = e.eid
            live = _live_total + total
            _add_locked(per, total)
    if je is not None:
        core.event("hbm", "share", owner=str(owner), bytes=total,
                   live=live, site=site)
    else:
        core.event("hbm", "alloc", owner=str(owner), bytes=total,
                   live=live, site=site)


def untrack(owner) -> None:
    """Owner released its buffer (close / finalizer / wrapper release).
    Frees the entry's bytes only when ``owner`` was the last holder.
    Always runs (even with telemetry disabled) so the ledger can drain
    after a mid-run ``disable()`` — a no-op dict probe when empty."""
    if not _by_owner:
        return
    with core._LOCK:
        freed = _drop_owner_locked(owner)
        live = _live_total
    if freed is not None and core._ENABLED:
        core.event("hbm", "free", owner=str(owner), bytes=freed.nbytes,
                   live=live, site=freed.site)


def share(src_owner, dst_owner) -> None:
    """``dst_owner`` now co-owns ``src_owner``'s buffer (a ``_BufShare``
    group formed).  ``dst_owner``'s own entry — the double-count from its
    constructor tracking the shared buffer — is dissolved; the group's
    bytes stay counted once, on the shared entry."""
    if not core._ENABLED and not _by_owner:
        return
    with core._LOCK:
        seid = _by_owner.get(src_owner)
        if seid is None:
            return                       # source untracked: nothing to join
        if _by_owner.get(dst_owner) != seid:
            _drop_owner_locked(dst_owner)
        e = _entries.get(seid)
        if e is not None:
            e.owners.add(dst_owner)
            _by_owner[dst_owner] = seid


def sample(tag: str) -> None:
    """Journal one ``hbm``/``sample`` point (current live bytes) — used
    at phase boundaries (checkpoint save/restore) so the Perfetto HBM
    counter track shows them even when no alloc/free lands exactly
    there."""
    if not core._ENABLED:
        return
    with core._LOCK:
        live = _live_total
    core.event("hbm", "sample", tag=tag, live=live)


# ---------------------------------------------------------------------------
# staging (transient buffers: reshard chunks, checkpoint encode)
# ---------------------------------------------------------------------------


class staging:
    """Context manager bracketing a transient allocation of ``nbytes``
    (estimated, per device): feeds the staging live gauge and per-tag
    peak watermarks, so chunked-reshard staging is *observed* against
    its ``DA_TPU_RESHARD_CHUNK_MB`` budget."""

    __slots__ = ("_tag", "_nbytes", "_on")

    def __init__(self, tag: str, nbytes: int):
        self._tag = tag
        self._nbytes = int(nbytes)
        self._on = False

    def __enter__(self):
        if not core._ENABLED:            # the single-boolean disabled path
            return self
        self._on = True
        global _staging_live, _staging_peak
        with core._LOCK:
            _staging_live += self._nbytes
            if _staging_live > _staging_peak:
                _staging_peak = _staging_live
            tp = _staging_peak_tag.get(self._tag, 0)
            if _staging_live > tp:
                _staging_peak_tag[self._tag] = _staging_live
            live = _staging_live
        core.event("hbm", "staging", tag=self._tag, bytes=self._nbytes,
                   staging_live=live)
        return self

    def __exit__(self, *exc):
        if self._on:
            global _staging_live
            with core._LOCK:
                _staging_live -= self._nbytes
        return False


def staging_peak(tag: str | None = None) -> int:
    with core._LOCK:
        if tag is None:
            return _staging_peak
        return _staging_peak_tag.get(tag, 0)


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------


def live_bytes(device=None) -> int:
    with core._LOCK:
        if device is None:
            return _live_total
        return _live_dev.get(device, 0)


def live_bytes_by_device() -> dict:
    """Per-device live-byte map (device id -> bytes) — the elastic
    manager's witness that a shrunk device's HBM actually drained."""
    with core._LOCK:
        return dict(_live_dev)


def peak_bytes(device=None) -> int:
    with core._LOCK:
        if device is None:
            return _peak_total
        return _peak_dev.get(device, 0)


def reset_peak() -> None:
    """Reset every peak watermark (total, per-device, staging) to the
    current live level — the per-bench-config watermark reset."""
    global _peak_total, _staging_peak
    with core._LOCK:
        _peak_total = _live_total
        _peak_dev.clear()
        _peak_dev.update(_live_dev)
        _staging_peak = _staging_live
        _staging_peak_tag.clear()


def tracked_count() -> int:
    with core._LOCK:
        return len(_entries)


def entries(limit: int | None = None) -> list[dict]:
    """Snapshot of the tracked entries (largest first), for bundles and
    debugging."""
    with core._LOCK:
        es = sorted(_entries.values(), key=lambda e: -e.nbytes)
        if limit is not None:
            es = es[:limit]
        return [e.to_dict() for e in es]


def snapshot() -> dict:
    """The ``memory`` section of :func:`core.report`."""
    with core._LOCK:
        sites: dict[str, dict] = {}
        for e in _entries.values():
            key = e.span or e.site or "?"
            s = sites.setdefault(key, {"bytes": 0, "count": 0})
            s["bytes"] += e.nbytes
            s["count"] += 1
        return {
            "live_bytes": _live_total,
            "peak_bytes": _peak_total,
            "tracked_arrays": len(_entries),
            "owners": len(_by_owner),
            "by_device": {str(d): {"live_bytes": _live_dev.get(d, 0),
                                   "peak_bytes": _peak_dev.get(d, 0)}
                          for d in sorted(set(_live_dev) | set(_peak_dev),
                                          key=str)},
            "staging": {"live_bytes": _staging_live,
                        "peak_bytes": _staging_peak,
                        "peak_by_tag": dict(sorted(
                            _staging_peak_tag.items()))},
            "top_sites": sorted(
                ([k, v["bytes"], v["count"]] for k, v in sites.items()),
                key=lambda kv: -kv[1])[:10],
        }


def leak_census() -> dict:
    """Diff the ledger against ``jax.live_arrays()``.

    - ``ledger_tracked`` — live jax buffers the ledger knows about;
    - ``untracked_foreign`` — live jax buffers with no ledger entry
      (raw jnp temporaries, jit constants, user arrays);
    - ``deleted_but_registered`` — ledger entries whose buffer is gone
      (deleted or collected) without the owner releasing — the
      lifecycle-hygiene violations this census exists to catch.
    """
    with core._LOCK:
        es = list(_entries.values())
    live_tracked_ids = set()
    stale_bytes = stale_count = 0
    for e in es:
        buf = e.buf_ref() if e.buf_ref is not None else None
        deleted = buf is None
        if buf is not None:
            try:
                deleted = bool(buf.is_deleted())
            except Exception:
                deleted = False
        if deleted:
            stale_bytes += e.nbytes
            stale_count += 1
        else:
            live_tracked_ids.add(id(buf))
    tracked_b = tracked_n = foreign_b = foreign_n = 0
    arrays_seen = None
    try:
        import jax
        arrays_seen = [a for a in jax.live_arrays()
                       if not getattr(a, "is_deleted", lambda: False)()]
    except Exception:
        arrays_seen = None
    if arrays_seen is not None:
        for a in arrays_seen:
            _, nb = _shard_bytes(a)
            if id(a) in live_tracked_ids:
                tracked_b += nb
                tracked_n += 1
            else:
                foreign_b += nb
                foreign_n += 1
    return {
        "ledger_tracked": {"bytes": tracked_b, "count": tracked_n},
        "untracked_foreign": {"bytes": foreign_b, "count": foreign_n},
        "deleted_but_registered": {"bytes": stale_bytes,
                                   "count": stale_count},
        "jax_live_arrays": None if arrays_seen is None
        else len(arrays_seen),
    }


def _reset() -> None:
    global _live_total, _peak_total, _staging_live, _staging_peak
    with core._LOCK:
        _entries.clear()
        _by_owner.clear()
        _by_buf.clear()
        _live_dev.clear()
        _peak_dev.clear()
        _staging_peak_tag.clear()
        _live_total = _peak_total = 0
        _staging_live = _staging_peak = 0


core.register_report_section("memory", snapshot)
core.register_reset_hook(_reset)
