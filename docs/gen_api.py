"""Generate docs/api.md from the package's public surface.

Run from the repo root:  python docs/gen_api.py
"""

import importlib
import inspect
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

MODULES = [
    "distributedarrays_tpu",
    "distributedarrays_tpu.layout",
    "distributedarrays_tpu.core",
    "distributedarrays_tpu.darray",
    "distributedarrays_tpu.ops.broadcast",
    "distributedarrays_tpu.ops.mapreduce",
    "distributedarrays_tpu.ops.linalg",
    "distributedarrays_tpu.ops.sort",
    "distributedarrays_tpu.ops.sparse",
    "distributedarrays_tpu.ops.fft",
    "distributedarrays_tpu.ops.conv",
    "distributedarrays_tpu.ops.pallas_gemm",
    "distributedarrays_tpu.ops.pallas_attention",
    "distributedarrays_tpu.ops.pallas_selective_scan",
    "distributedarrays_tpu.ops.pallas_ssd",
    "distributedarrays_tpu.ops.pallas_gated_delta",
    "distributedarrays_tpu.ops.pallas_stencil",
    "distributedarrays_tpu.ops.pallas_collectives",
    "distributedarrays_tpu.ops.ring_schedules",
    "distributedarrays_tpu.ops.collective_matmul",
    "distributedarrays_tpu.parallel.spmd_mode",
    "distributedarrays_tpu.parallel.collectives",
    "distributedarrays_tpu.parallel.reshard",
    "distributedarrays_tpu.parallel.multihost",
    "distributedarrays_tpu.models.stencil",
    "distributedarrays_tpu.models.ring_attention",
    "distributedarrays_tpu.models.ulysses",
    "distributedarrays_tpu.models.pipeline",
    "distributedarrays_tpu.models.moe",
    "distributedarrays_tpu.models.kmeans",
    "distributedarrays_tpu.models.montecarlo",
    "distributedarrays_tpu.models.mlp",
    "distributedarrays_tpu.models.common",
    "distributedarrays_tpu.models.transformer",
    "distributedarrays_tpu.models.sp_transformer",
    "distributedarrays_tpu.models.sambay",
    "distributedarrays_tpu.models.mamba2_hybrid",
    "distributedarrays_tpu.models.olmo_hybrid",
    "distributedarrays_tpu.train.trainer",
    "distributedarrays_tpu.train.optim",
    "distributedarrays_tpu.train.tasks",
    "distributedarrays_tpu.telemetry",
    "distributedarrays_tpu.telemetry.tracing",
    "distributedarrays_tpu.telemetry.memory",
    "distributedarrays_tpu.telemetry.flight",
    "distributedarrays_tpu.telemetry.export",
    "distributedarrays_tpu.telemetry.summarize",
    "distributedarrays_tpu.telemetry.cluster",
    "distributedarrays_tpu.telemetry.alerts",
    "distributedarrays_tpu.telemetry.stream",
    "distributedarrays_tpu.telemetry.agg",
    "distributedarrays_tpu.analysis",
    "distributedarrays_tpu.analysis.divergence",
    "distributedarrays_tpu.analysis.protocol",
    "distributedarrays_tpu.analysis.locks",
    "distributedarrays_tpu.resilience",
    "distributedarrays_tpu.resilience.domains",
    "distributedarrays_tpu.resilience.faults",
    "distributedarrays_tpu.resilience.elastic",
    "distributedarrays_tpu.resilience.recovery",
    "distributedarrays_tpu.serve",
    "distributedarrays_tpu.serve.server",
    "distributedarrays_tpu.serve.admission",
    "distributedarrays_tpu.serve.batching",
    "distributedarrays_tpu.serve.errors",
    "distributedarrays_tpu.serve.kvcache",
    "distributedarrays_tpu.serve.decode",
    "distributedarrays_tpu.serve.aio",
    "distributedarrays_tpu.solvers",
    "distributedarrays_tpu.solvers.operators",
    "distributedarrays_tpu.solvers.krylov",
    "distributedarrays_tpu.solvers.multigrid",
    "distributedarrays_tpu.solvers.service",
    "distributedarrays_tpu.utils.checkpoint",
    "distributedarrays_tpu.utils.autotune",
    "distributedarrays_tpu.utils.profiling",
    "distributedarrays_tpu.utils.debug",
    "distributedarrays_tpu.utils.native",
]


def first_para(doc):
    if not doc:
        return ""
    return inspect.cleandoc(doc).split("\n\n")[0].replace("\n", " ")


def fmt_sig(obj, drop_self=False):
    try:
        sig = inspect.signature(obj)
    except (ValueError, TypeError):
        return "(...)"
    params = list(sig.parameters.values())
    if drop_self and params and params[0].name in ("self", "cls"):
        params = params[1:]
    sig = sig.replace(parameters=params)
    # `from __future__ import annotations` stringizes annotations; unquote
    return str(sig).replace('"', "").replace("'", "")


def describe(mod):
    out = [f"## `{mod.__name__}`\n"]
    if mod.__doc__:
        out.append(first_para(mod.__doc__) + "\n")
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod)
                 if not n.startswith("_") and
                 getattr(getattr(mod, n), "__module__", None) == mod.__name__]
    for name in names:
        obj = getattr(mod, name, None)
        if obj is None:
            continue
        if inspect.isclass(obj):
            out.append(f"### class `{name}`\n\n{first_para(obj.__doc__)}\n")
            for mname, m in inspect.getmembers(obj):
                if mname.startswith("_") or not callable(m):
                    continue
                out.append(f"- `{name}.{mname}{fmt_sig(m, drop_self=True)}` — "
                           f"{first_para(m.__doc__)}")
        elif callable(obj):
            out.append(f"- **`{name}{fmt_sig(obj)}`** — "
                       f"{first_para(obj.__doc__)}")
    return "\n".join(out) + "\n"


def main():
    parts = ["# API reference\n\nGenerated by `python docs/gen_api.py`; "
             "one-line summaries — see docstrings for the full contracts "
             "and reference citations.\n"]
    for name in MODULES:
        parts.append(describe(importlib.import_module(name)))
    Path(__file__).with_name("api.md").write_text("\n".join(parts))
    print(f"wrote docs/api.md ({sum(len(p) for p in parts)} chars)")


if __name__ == "__main__":
    main()
