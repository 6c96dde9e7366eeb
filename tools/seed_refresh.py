#!/usr/bin/env python
"""Merge the live autotune cache's HARDWARE winners into the tracked
seed registry (AUTOTUNE_SEED.json).

The live cache (AUTOTUNE_CACHE.json, gitignored) accumulates every
winner the bench sweeps measure; the seed ships the hardware-measured
subset so a fresh checkout dispatches to silicon-tuned configs out of
the box (VERDICT round-4 weak 3).  Keys are device-fenced strings
(``...|platform|device_kind``) — only entries whose platform segment is
a real accelerator are promoted; cpu/interpret winners must never ship
(they would be inert under the fence, but shipping them would bloat the
registry and invite confusion).

GEMM winners are additionally filtered through the SAME validity
predicate ``_resolve_block`` applies at dispatch (block well-formedness,
shape divisibility, Mosaic alignment, per-kernel scoped-VMEM estimate —
``ops.pallas_gemm.entry_valid_for_seed``): a winner measured before a
VMEM-estimator fix would otherwise ship as a dead seed entry that every
dispatch silently rejects back to the heuristic (ADVICE round-5).

Usage: python tools/seed_refresh.py [--dry-run]
Prints a per-kernel diff of what changed (and what was rejected); exits
1 on --dry-run if a merge WOULD change the seed (CI-able).
"""

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
CACHE = REPO / "AUTOTUNE_CACHE.json"
SEED = REPO / "AUTOTUNE_SEED.json"

sys.path.insert(0, str(REPO))

# platform fence segment values that count as real hardware — the same
# allowlist tests/test_autotune_seed.py enforces on the shipped file
# (cpu/interpret winners must never ship), cross-pinned by that test
_HW_PLATFORMS = ("tpu", "gpu")


def _is_hardware_key(key: str) -> bool:
    parts = key.split("|")
    return len(parts) >= 2 and parts[-2] in _HW_PLATFORMS


# kernels whose promotion is filtered through the dispatch validity
# predicate (ops/pallas_gemm.entry_valid_for_seed — the same checks
# _resolve_block applies).  Gated here too so non-GEMM kernels promote
# without importing the package at all: the tool must stay runnable from
# a bare checkout/sandbox (tests/test_autotune_seed.py rc contract).
# Cross-pinned against the predicate's own kernel set by
# tests/test_autotune_seed.py.
_GEMM_KERNELS = ("pallas_matmul", "pallas_matmul_int8")


def _dispatch_valid(kernel: str, key: str, val):
    """``entry_valid_for_seed``'s verdict (None = kernel not GEMM-owned,
    no filtering).  The import is deferred so ``--help`` and non-GEMM
    merges stay jax-free; when GEMM entries ARE present but the package
    cannot import (jax-less box), exit with the rc-2 diagnostic rather
    than a traceback — promoting unvalidated GEMM winners is exactly
    what this filter exists to stop."""
    if kernel not in _GEMM_KERNELS:
        return None
    try:
        from distributedarrays_tpu.ops.pallas_gemm import (
            entry_valid_for_seed)
    except ImportError as e:
        print(f"cannot validate GEMM entries ({e}); run seed_refresh "
              "from the repo environment (jax required)")
        raise SystemExit(2) from None
    return entry_valid_for_seed(kernel, key, val)


def main() -> int:
    dry = "--dry-run" in sys.argv
    try:
        cache = json.loads(CACHE.read_text())
    except OSError:
        print("no live cache; nothing to merge")
        return 0
    except ValueError as e:
        # a corrupt cache must be a clean diagnostic, not a traceback —
        # CI tells 'seed stale' (rc 1) from 'tool crashed' by the output
        print(f"live cache unreadable ({e}); refusing to merge")
        return 2
    try:
        seed = json.loads(SEED.read_text()) if SEED.exists() else {}
    except ValueError as e:
        print(f"seed unreadable ({e}); fix or delete {SEED.name} first")
        return 2
    changed, rejected = [], []
    # prune entries ALREADY shipped in the seed that dispatch would
    # reject — the ADVICE round-5 case is precisely a pre-VMEM-fix
    # winner committed before the predicate existed; filtering only the
    # promotion path would leave it dead in the tracked file forever
    # (and --dry-run would keep reporting the seed current)
    pruned = []
    for kernel in sorted(seed):
        entries = seed[kernel]
        if not isinstance(entries, dict):
            continue
        for key in sorted(entries):
            if _dispatch_valid(kernel, key, entries[key]) is False:
                pruned.append((kernel, key, entries.pop(key)))
        if not entries:
            del seed[kernel]
    for kernel, entries in sorted(cache.items()):
        if not isinstance(entries, dict):
            continue
        for key, val in sorted(entries.items()):
            if not _is_hardware_key(key):
                continue
            if _dispatch_valid(kernel, key, val) is False:
                rejected.append((kernel, key, val))
                continue
            cur = seed.get(kernel, {}).get(key)
            if cur != val:
                changed.append((kernel, key, cur, val))
                seed.setdefault(kernel, {})[key] = val
    for kernel, key, val in rejected:
        print(f"REJECTED (fails dispatch validity — alignment/VMEM): "
              f"{kernel} | {key}: {val}")
    for kernel, key, val in pruned:
        print(f"PRUNED from seed (fails dispatch validity): "
              f"{kernel} | {key}: {val}")
    for kernel, key, old, new in changed:
        print(f"{kernel} | {key}: {old} -> {new}")
    if not changed and not pruned:
        print("seed already current")
        return 0
    if dry:
        print(f"--dry-run: {len(changed)} entries would change, "
              f"{len(pruned)} would be pruned")
        return 1
    # atomic replace, same pattern as autotune.save(): an interrupt
    # mid-write must not leave a truncated tracked file
    tmp = SEED.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(seed, indent=2, sort_keys=True) + "\n")
    tmp.replace(SEED)
    print(f"wrote {SEED.name}: {len(changed)} entries updated, "
          f"{len(pruned)} pruned")
    return 0


if __name__ == "__main__":
    sys.exit(main())
