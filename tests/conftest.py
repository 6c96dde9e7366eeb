"""Test harness: 8 virtual CPU devices, scalar-indexing ban, leak checks.

Mirrors the reference harness (/root/reference/test/runtests.jl):
- real multi-worker processes via addprocs (runtests.jl:10-13) → here an
  8-device CPU mesh via --xla_force_host_platform_device_count, the JAX
  moral equivalent for exercising true multi-device sharding in CI;
- global allowscalar(false) so accidental scalar fallbacks throw
  (runtests.jl:5-7);
- leak checking between suites (runtests.jl:28-37): every test must leave
  the DArray registry empty or close what it made.
"""

import os

# DAT_TEST_TPU=1 runs the suite on whatever real devices JAX sees (tests
# needing >1 device will fail on a 1-chip host — intended for real slices);
# default is the virtual 8-device CPU mesh, the reference's addprocs analog.
_ON_REAL = os.environ.get("DAT_TEST_TPU") == "1"

if not _ON_REAL:
    # the CPU-mesh bootstrap lives in ONE place, shared with
    # examples/_setup.py and chip_smoke.py — see _cpu_harness.py
    import sys as _sys
    from pathlib import Path as _Path
    _sys.path.insert(0, str(_Path(__file__).resolve().parents[1]))
    import _cpu_harness
    _cpu_harness.force_cpu_mesh()

import gc

import numpy as np
import pytest

import jax  # noqa: F401  (config already forced by _cpu_harness)

import distributedarrays_tpu as dat


@pytest.fixture(autouse=True)
def _seed_and_leakcheck(request):
    dat.seed(1234)
    yield
    # After the test body returns, its locals are collectable: any DArray the
    # test didn't explicitly keep must vanish from the registry on gc (the
    # finalizer discipline the reference asserts in test/darray.jl:1079-1086).
    # Whatever legitimately remains (fixture-held refs) is then reaped with
    # d_closeall like the reference does between testsets (test/darray.jl:314).
    # A young-generation pass reaps the typical test's droppings; the full
    # (gen-2) collect — tens of ms per call across ~950 tests — runs only
    # when something survived it, so the growth gate below keeps its exact
    # meaning at a fraction of the wall cost.
    gc.collect(1)
    leaked = dat.live_ids()
    if leaked:
        gc.collect()
        leaked = dat.live_ids()
    dat.d_closeall()
    assert dat.live_ids() == []
    # real leak check lives in test_leaks.py; here we only flag runaway growth
    assert len(leaked) < 64, f"suspicious registry growth: {len(leaked)} live"
    # HBM-ledger leak gate: with the registry drained the ledger must be
    # empty too — a nonzero residue means some lifecycle path swapped or
    # dropped a buffer without telling the ledger.  Opt out (tests that
    # leak on purpose) with @pytest.mark.intentional_leak.
    if "intentional_leak" not in request.keywords:
        from distributedarrays_tpu.telemetry import memory as _tmem
        residue = _tmem.live_bytes()
        assert residue == 0, (
            f"HBM ledger not drained after d_closeall: {residue} bytes "
            f"across {_tmem.tracked_count()} entries — "
            f"{_tmem.entries(limit=5)}")


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def pytest_configure(config):
    if not _ON_REAL:
        assert len(jax.devices()) == 8, (
            f"test harness expects 8 virtual devices, got {jax.devices()}")
    config.addinivalue_line(
        "markers", "slow: long-running test (property fuzz, training "
        "convergence, subprocess clusters); run with --runslow or "
        "DAT_TEST_SLOW=1 — CI always runs them")
    config.addinivalue_line(
        "markers", "intentional_leak: test leaves device buffers "
        "unaccounted on purpose; skips the per-test HBM-ledger drain "
        "assertion")


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="include tests marked slow (default loop skips them to stay "
             "under ~5 minutes; CI sets DAT_TEST_SLOW=1 for the full run)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or \
            os.environ.get("DAT_TEST_SLOW") == "1":
        return
    skip = pytest.mark.skip(reason="slow; use --runslow / DAT_TEST_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
