"""Static-quality gates, mirroring the reference's Aqua.jl /
ExplicitImports.jl discipline (test/aqua.jl:4-6, test/explicit_imports.jl:
5-64): export hygiene, import-time side effects, API stability.

The star-import / export-hygiene checks run through the ``analysis`` rule
engine (DAL005) — the ad-hoc AST walks this file used to carry moved into
``distributedarrays_tpu.analysis.rules``; this file asserts the package is
clean under them, plus the dalint self-lint gate over the whole lint
surface (package, examples/)."""

import importlib
import pkgutil
import subprocess
import sys
from pathlib import Path

import pytest

import distributedarrays_tpu as dat
from distributedarrays_tpu.analysis import RULES, lint_paths

PKG_ROOT = Path(dat.__file__).resolve().parent
REPO_ROOT = PKG_ROOT.parent


def _all_modules():
    errors = []
    mods = list(pkgutil.walk_packages([str(PKG_ROOT)],
                                      prefix="distributedarrays_tpu.",
                                      onerror=errors.append))
    assert not errors, f"subpackage import failures: {errors}"
    # sanity floor: every known subpackage must have been walked
    names = [m.name for m in mods]
    for sub in ("ops", "parallel", "models", "utils"):
        assert any(n.startswith(f"distributedarrays_tpu.{sub}.")
                   for n in names), f"subpackage {sub} not walked"
    return names


def test_every_export_exists():
    # reference Aqua checks undefined exports.  Static half: the DAL005
    # rule engine proves every literal __all__ entry is bound in its
    # module; dynamic half: every export must also resolve at runtime
    # (catches bindings behind dead conditionals the AST pass accepts)
    hygiene = [f for f in lint_paths([PKG_ROOT], select=["DAL005"])
               if not f.suppressed and "__all__" in f.message]
    assert hygiene == [], [f.format() for f in hygiene]
    for name in _all_modules():
        mod = importlib.import_module(name)
        for sym in getattr(mod, "__all__", []):
            assert hasattr(mod, sym), f"{name}.__all__ lists missing {sym!r}"


def test_package_namespace_complete():
    # everything the README/docs surface references must exist at top level
    for sym in ["DArray", "SubDArray", "DData", "distribute", "dzeros",
                "dones", "dfill", "drand", "drandn", "drandint", "dsample",
                "darray", "darray_like", "from_chunks", "ddata", "gather",
                "localpart", "localindices", "locate", "makelocal",
                "allowscalar", "close", "d_closeall", "procs", "dmap",
                "dmap_into", "djit", "dsum", "dmean", "dstd", "dsort",
                "dnnz", "ddot", "dnorm", "matmul", "mul_into", "axpy_",
                "samedist", "mapslices", "ppeval", "copyto_", "dcat",
                "dfetch", "parallel"]:
        assert hasattr(dat, sym), f"top-level export {sym!r} missing"


def test_no_star_imports():
    # ExplicitImports.jl analog, via the DAL005 rule: no `from x import *`
    # anywhere in the package
    stars = [f for f in lint_paths([PKG_ROOT], select=["DAL005"])
             if not f.suppressed and "star import" in f.message]
    assert stars == [], [f.format() for f in stars]


def test_dalint_self_clean():
    # the package gates itself: zero unsuppressed findings across the
    # whole lint surface (suppressions carry their justification inline).
    # lint_paths runs EVERY registered rule, so this also arms the PR 9
    # DAL008/DAL009 lock analyses — a new blocking-under-lock site or
    # lock-order cycle fails here before CI
    targets = [PKG_ROOT, REPO_ROOT / "examples"]
    active = [f for f in lint_paths(targets) if not f.suppressed]
    assert active == [], "\n".join(f.format() for f in active)
    assert {"DAL008", "DAL009"} <= set(RULES), "lock rules must be armed"


def test_dalint_no_rotted_suppressions():
    # every `# dalint: disable=` comment must still silence something:
    # the unused-suppression satellite (DAL100) as a standing gate, so
    # justified suppressions cannot rot when the code around them heals
    from distributedarrays_tpu.analysis.engine import (lint_file,
                                                       unused_suppressions)
    from distributedarrays_tpu.analysis.engine import iter_python_files
    targets = [PKG_ROOT, REPO_ROOT / "examples"]
    stale = []
    for f in iter_python_files(targets):
        per_file = lint_file(f)
        src = Path(f).read_text()
        stale.extend(x for x in unused_suppressions(src, str(f), per_file)
                     if not x.suppressed)
    assert stale == [], "\n".join(f.format() for f in stale)


def test_import_has_no_backend_side_effect():
    # importing the package must not initialize a JAX backend (users must
    # be able to configure jax.config afterwards); regression for the
    # import-time RNG key finding
    code = (
        "import jax\n"
        "import distributedarrays_tpu\n"
        "try:\n"
        "    import jax._src.xla_bridge as xb\n"
        "    backends = getattr(xb, '_backends', None)\n"
        "except ImportError:\n"
        "    backends = None\n"
        "if backends is None:\n"
        "    print('clean (probe unavailable on this jax version)')\n"
        "else:\n"
        "    assert not backends, f'backends initialized: {backends}'\n"
        "    print('clean')\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=str(PKG_ROOT.parent))
    assert r.returncode == 0 and "clean" in r.stdout, r.stderr[-500:]
