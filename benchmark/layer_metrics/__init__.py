"""One file a per-layer metric: ``read(run)`` takes the metric from the
harness's spans, the program's counters or the reduced trace, and returns
its value, or ``None`` where it finds nothing to read (the harness then
leaves the metric out of the line; a share of a roofline or of a peak is
never reported as 0).  The harness finds a reader by the metric's name in
``BENCHMARK.json``."""
