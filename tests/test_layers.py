"""The package's import graph, written down: one case a subpackage.

Static (AST) only: for every file of a subpackage, module level and inside
functions alike, the set of sibling subpackages it imports must EQUAL what
this file lists for it.  ``DOWN`` is the layering as meant, lowest first:

    telemetry < utils < parallel < ops < models < resilience < serve
              < train < solvers          (analysis: a tool beside them)

``DEBT`` names each arrow that points up, with the ROADMAP item that owns
it.  No code was moved to make this pass: it records the graph, fails on a
new up-arrow, and fails when a debt is paid so its line is taken out here.
The root modules (``layout``, ``core``, ``darray``) are the array type
itself and every layer may import them; the root's own eager imports are
ROADMAP Queue 3 item 6.

Inside ``models/``, one case a module holds the seam between the training
models: they share ``models/common.py`` and import nothing of each other.
"""

import ast
from pathlib import Path

import pytest

PKG = Path(__file__).resolve().parents[1] / "distributedarrays_tpu"
ROOT_MODULES = {"layout", "core", "darray"}

DOWN = {
    "telemetry": set(),
    "utils": {"telemetry"},
    "parallel": {"telemetry", "utils"},
    "ops": {"telemetry", "utils", "parallel"},
    "models": {"telemetry", "utils", "parallel", "ops"},
    "resilience": {"telemetry", "parallel"},
    "serve": {"telemetry", "models", "resilience"},
    "train": {"telemetry", "utils", "parallel", "ops", "models",
              "resilience"},
    "solvers": {"telemetry", "parallel", "ops", "models", "resilience",
                "serve"},
    "analysis": {"telemetry", "ops"},
}

DEBT = {
    "telemetry": {
        "parallel": "debt: Queue 3 item 5 (stream.py and agg.py look up the "
                    "aggregator's address through parallel.multihost)",
    },
    "utils": {
        "parallel": "debt: Queue 3 item 14 (checkpoint.py gathers through "
                    "parallel.multihost)",
        "resilience": "debt: Queue 3 item 14 (checkpoint.py calls the "
                      "fault sites and the elastic device manager: safety "
                      "code, so the file moves up, the calls stay)",
    },
    "parallel": {
        "ops": "debt: Queue 3 item 4 (reshard.py runs the ring kernels of "
               "ops.pallas_collectives; ops <-> parallel)",
        "resilience": "debt: Queue 3 item 14 (reshard.py, multihost.py and "
                      "spmd_mode.py call the fault sites and failure "
                      "domains: safety code)",
        "analysis": "debt: Queue 3 item 10 (spmd_mode.py's divergence "
                    "guard lives in analysis.divergence)",
    },
    "resilience": {
        "analysis": "debt: Queue 3 item 14 (recovery.py classifies "
                    "analysis.divergence's CollectiveDivergenceError; "
                    "moves with the guard)",
    },
}


def _subpackages():
    return sorted(p.name for p in PKG.iterdir()
                  if p.is_dir() and (p / "__init__.py").exists())


def _imports_of(path: Path, pkg_parts: list[str]) -> set[str]:
    """First-level names under ``distributedarrays_tpu`` that ``path``
    imports, anywhere in the file."""
    out: set[str] = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            if node.level:
                base = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                mod = base + (node.module.split(".") if node.module else [])
            elif (node.module or "").split(".")[0] == PKG.name:
                mod = node.module.split(".")[1:]
            else:
                continue
            # ``from .. import telemetry``: the names are the targets
            out.update([mod[0]] if mod else [a.name for a in node.names])
        elif isinstance(node, ast.Import):
            for a in node.names:
                parts = a.name.split(".")
                if parts[0] == PKG.name and len(parts) > 1:
                    out.add(parts[1])
    return out


def _graph(sub: str) -> dict[str, list[str]]:
    seen: dict[str, list[str]] = {}
    for f in sorted((PKG / sub).rglob("*.py")):
        rel = f.relative_to(PKG).parts
        for name in _imports_of(f, list(rel[:-1])):
            if name != sub and name not in ROOT_MODULES:
                seen.setdefault(name, []).append("/".join(rel))
    return seen


def test_every_subpackage_has_a_case():
    assert _subpackages() == sorted(DOWN)
    assert set(DEBT) <= set(DOWN)


@pytest.mark.parametrize("sub", sorted(DOWN))
def test_subpackage_imports_only_what_is_listed(sub):
    seen = _graph(sub)
    listed = DOWN[sub] | set(DEBT.get(sub, {}))
    new = {k: v for k, v in seen.items() if k not in listed}
    assert not new, (
        f"{sub} imports {sorted(new)} (from {new}): a new arrow. Move the "
        f"code down, or list it here with the debt that owns it")
    gone = listed - set(seen)
    assert not gone, (
        f"{sub} no longer imports {sorted(gone)}: take it out of this "
        f"file's lists so that it cannot come back unseen")
    assert not DOWN[sub] & set(DEBT.get(sub, {}))


def test_telemetry_is_a_leaf_but_for_the_live_plane():
    # the one arrow out of telemetry, and only from the two files named
    assert _graph("telemetry") == {
        "parallel": ["telemetry/agg.py", "telemetry/stream.py"]}


# The training models and their float32 references.  No module of models/
# imports a model's code but its own reference (a reference reads its
# model's Config) and sp_transformer (GPT-2's Config, init_params and
# norm): what two models share lives in models/common.py.  A model, a
# reference and common import no private name of the package; the older
# modules' private imports (kmeans, ring_attention, stencil, ulysses) are
# ROADMAP Queue 3 debt.
MODELS = {"transformer", "sp_transformer", "sambay", "mla_moe",
          "mamba2_hybrid", "olmo_hybrid"}
MAY_IMPORT = {"sp_transformer": {"transformer"}}


def _from_the_package(path: Path):
    """(module of models/ or None, name) of every name ``path`` imports
    from the package (``from . import common`` names the module)."""
    out = []
    for node in ast.walk(ast.parse(path.read_text())):
        if not isinstance(node, ast.ImportFrom):
            continue
        if node.level:
            parts = ["models"] if node.level == 1 else []
            parts += node.module.split(".") if node.module else []
        elif (node.module or "").split(".")[0] == PKG.name:
            parts = node.module.split(".")[1:]
        else:
            continue
        for a in node.names:
            full = parts + [a.name]
            out.append((full[1] if full[0] == "models" else None, a.name))
    return out


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (PKG / "models").glob("*.py") if p.stem != "__init__"))
def test_a_model_imports_no_other_model_and_no_private_name(name):
    imported = _from_the_package(PKG / "models" / f"{name}.py")
    own = {name.removesuffix("_reference")} - {name}
    models = {m for m, _ in imported
              if m in MODELS or (m or "").endswith("_reference")}
    assert models <= own | MAY_IMPORT.get(name, set()), (
        f"models/{name}.py imports {sorted(models)}: a model imports no "
        f"other model's code; move what they share into models/common.py")
    if name.removesuffix("_reference") in MODELS | {"common"}:
        private = [(m, n) for m, n in imported if n.startswith("_")]
        assert not private, f"models/{name}.py imports private {private}"
