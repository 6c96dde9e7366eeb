"""Device: what the largest live registered program needs of the chip while
it runs, its scratch included: ``telemetry.programs.memory(p)["total"]``
(argument + output - alias + temp + generated code of the executable's
``memory_analysis()``) in GB.  ``peak_hbm_gb`` counts live buffers and not a
program's scratch.  No registry, or no live program, gives nothing."""


def read(run):
    try:
        from distributedarrays_tpu.telemetry import programs
    except ImportError:              # a program from before the registry
        return None
    totals = [programs.memory(p)["total"] for p in programs.live()]
    return max(totals) / 1e9 if totals else None
