from . import broadcast, conv, fft, linalg, mapreduce, sort, sparse  # noqa: F401

_LAZY = ("pallas_attention", "pallas_gemm", "pallas_collectives",
         "pallas_stencil", "pallas_selective_scan", "collective_matmul")


def __getattr__(name):
    # Pallas kernel modules load lazily: importing the package should not
    # pay the jax.experimental.pallas import cost unless a kernel is used.
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
