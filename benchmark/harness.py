"""The harness: finds a cell's files by the names in ``BENCHMARK.json``, sets
the cell up, measures a window of closed-loop steps, optionally traces a
short window, frees the program's state, and decides ``correct`` by the
plain reference.

Driven by data.  A cell is an entry of ``workloads`` in ``BENCHMARK.json``
naming a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the traffic file names its driver
(``drivers/<driver>.py``); the cell's limits are ``limits/<cell>.json``; a
per-layer metric is ``layer_metrics/<metric>.py``.  A later PR adds any of
them as new files and entries and edits nothing that is here.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time
import traceback
from pathlib import Path
from types import SimpleNamespace

WATCHED = ("fallback.hits", "reshard.collective_fallbacks")
DISPATCH = "pallas_collectives.dispatch"
OK_PATHS = ("path=rdma", "path=compiled")
EXIT_NO_CHIP = 3
EXIT_REHEARSAL = 4


class BenchError(RuntimeError):
    """The run cannot be a measurement (no chip, wrong files, ...)."""


def _parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", choices=("cpu",), default=None,
                    help="rehearsal on virtual CPU devices: names the CPU "
                         "truthfully, prints no metric, exits non-zero")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: also write the raw trace's summary "
                         "and its first steps as JSON into DIR (for reading "
                         "a trace by hand; the driver never passes it)")
    ap.add_argument("--size", choices=("tiny",), default=None,
                    help="rehearsal sizes (only with --platform cpu)")
    return ap.parse_args(argv)


def load_cell(root: Path, name: str):
    """(cell, config entry, config, traffic, limits, benchmark) by name."""
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root.parent / entry["file"]).read_text())
    traffic = json.loads(
        (root / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((root / "limits" / f"{name}.json").read_text())
    return cell, entry, config, traffic, limits, bench


def metrics_for(bench, kind, cell_name):
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


class CompileClock:
    """Backend compilations (cache retrievals are not compilations) and
    persistent-cache hits, from ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.requests, self.hits, self.seconds = 0, 0, 0.0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def fallback_hits(on_tpu):
    """Every counter that records a path quietly giving way: the fallbacks,
    and on the chip any ring dispatch that is neither rdma nor compiled."""
    from distributedarrays_tpu import telemetry as tm
    total = 0
    for key, val in tm.report()["counters"].items():
        name = key.split("{", 1)[0]
        if name in WATCHED or (on_tpu and name == DISPATCH
                               and not any(p in key for p in OK_PATHS)):
            total += int(val)
    return total


def use_virtual_cpu(chips):
    """Before JAX is first used: hold it to ``chips`` virtual CPU devices
    (more where ``XLA_FLAGS`` already asks for a count)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={chips}").strip()


def enable_cache():
    """The persistent compile cache, through the program's own switch (which
    takes ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), for
    every program however small or quick to compile."""
    import jax
    from distributedarrays_tpu.utils.compile_cache import \
        enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def percentile(values, pct):
    """Nearest-rank percentile of all ``values``."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(pct / 100.0 * len(s)) - 1))]


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def run_window(driver, seconds, span=None, annotate=None):
    """Closed loop: the next step starts when the previous step's scalar is
    on the host.  Returns every step's time, the time inside the calls
    before the blocking read, the whole window, and the failed steps."""
    step_s, dispatch_s, failed = [], [], 0
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if annotate is None:
            t_d, ok = driver.step()
        else:
            with annotate("bench.step"):
                t_d, ok = driver.step(span)
        t1 = time.perf_counter()
        step_s.append(t1 - t0)
        dispatch_s.append(t_d - t0)
        failed += not ok
        if t1 - start >= seconds:
            return step_s, dispatch_s, t1 - start, failed


def main(argv, t0, root: Path) -> int:
    args = _parse(argv)
    rehearsal = args.platform == "cpu"
    try:
        return _run(args, t0, Path(root), rehearsal)
    except BenchError as e:
        say(f"benchmark: {e}")
        return EXIT_NO_CHIP
    except Exception:                      # noqa: BLE001: no result line
        traceback.print_exc(file=sys.stderr)
        return 1


def _run(args, t0, root, rehearsal):
    if args.size and not rehearsal:
        raise BenchError("--size tiny is for --platform cpu rehearsals only")
    cell, entry, config, traffic, limits, bench = load_cell(root,
                                                            args.workload)
    chips = int(cell["chips"])
    for p in (str(root.parent), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    if rehearsal:
        use_virtual_cpu(chips)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    else:
        say(f"compile cache: {enable_cache()}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = devs[0].platform == "tpu"
    if not on_tpu and not rehearsal:
        raise BenchError(f"no accelerator: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips or (on_tpu and len(devs) != chips):
        raise BenchError(f"cell {cell['name']} needs {chips} chip(s), JAX "
                         f"reports {len(devs)}")
    import counts
    peaks = counts.load_peaks(devs[0].device_kind) if on_tpu else None
    from distributedarrays_tpu.utils import autotune
    if os.path.exists(autotune.default_cache_path()):
        raise BenchError(f"a live autotune cache at "
                         f"{autotune.default_cache_path()} would steer "
                         f"dispatch: only AUTOTUNE_SEED.json may")
    clock = CompileClock()

    def mark(what):
        say(f"set-up {time.perf_counter() - t0:8.3f} s  {what}")

    mark("JAX up, devices seen")
    ctx = SimpleNamespace(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, devices=devs[:chips],
                          on_tpu=on_tpu, tiny=args.size == "tiny",
                          root=root, mark=mark)
    driver = importlib.import_module(
        f"drivers.{traffic['driver']}").Driver(ctx)
    driver.setup()
    compiles_setup = clock.requests - clock.hits
    c_before = clock.requests
    setup_s = time.perf_counter() - t0

    # -- the measured window --------------------------------------------------
    from distributedarrays_tpu import telemetry as tm
    comm0 = tm.comm_bytes("reshard")
    step_s, dispatch_s, window_s, failed = run_window(driver, args.seconds)
    comm_per_step = (tm.comm_bytes("reshard") - comm0) / len(step_s)
    compiles_in_window = clock.requests - c_before
    steps = len(step_s)
    say(f"window: {steps} steps in {window_s:.4f} s; step_p95_ms is the "
        f"95th percentile of {steps} step times; setup {setup_s:.3f} s "
        f"({compiles_setup} compiled, {clock.hits} from the cache); "
        f"compilations inside the window: {compiles_in_window}")

    med = statistics.median(step_s)
    slow = sorted(range(steps), key=lambda i: -step_s[i])[:5]
    say(f"slowest steps (index: ms): "
        f"{[(i, round(1e3 * step_s[i], 3)) for i in slow]}; median "
        f"{1e3 * med:.3f} ms; time above the median in all steps "
        f"{1e3 * sum(max(0.0, t - med) for t in step_s):.1f} ms")

    # -- the traced window (a run of its own: --trace 1) ------------------------
    traced = None
    if args.trace:
        traced = _traced_window(driver, traffic, root, args.keep_trace)

    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:chips]) if on_tpu else 0
    hits = fallback_hits(on_tpu)

    # -- free the program's state, then the reference ---------------------------
    outputs = driver.finish()
    t_ref = time.perf_counter()
    numbers = driver.compare(outputs, driver.reference())
    driver.release(outputs)
    ref_s = time.perf_counter() - t_ref
    # a rehearsal at tiny sizes is held to limits of its own (set from CPU
    # readings at those sizes; the cell's limits are for the timed sizes)
    lim = limits["tiny_limits" if ctx.tiny else "limits"]
    compared = {k: [v, lim.get(k)] for k, v in numbers.items()}
    unbounded = [k for k, (_, lim) in compared.items() if lim is None]
    correct = (not unbounded and failed == 0 and all(
        v <= top for v, top in compared.values()))      # NaN fails
    say(f"reference took {ref_s:.2f} s; numbers with no limit: {unbounded}")
    if getattr(driver, "worst_leaves", None):
        say(f"worst leaves: {driver.worst_leaves}")

    values = {
        "step_ms": 1e3 * window_s / steps,
        "step_p95_ms": 1e3 * percentile(step_s, 95),
        "setup_s": setup_s,
    }
    if driver.tokens_per_step:
        values["tokens_per_s"] = driver.tokens_per_step * steps / window_s
    if args.trace:
        run = SimpleNamespace(
            cell=cell, chips=chips, peaks=peaks, driver=driver,
            cost=driver.cost(), trace=traced, steps=steps,
            step_s=step_s, dispatch_s=dispatch_s, window_s=window_s,
            memory_peak_bytes=mem_peak, fallback_hits=hits,
            reshard_bytes_per_step=comm_per_step, values=values,
            notes=[])
        metrics, readers = {}, []
        for m in metrics_for(bench, "per_layer", cell["name"]):
            reader = importlib.import_module(f"layer_metrics.{m['name']}")
            readers.append(m["name"])
            # no run on a CPU reads a device metric
            val = reader.read(run) if on_tpu else None
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        for note in run.notes:
            say(note)
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_for(bench, "end_to_end", cell["name"])}

    if traced and traced.get("reduced"):
        device["busy_s"] = traced["reduced"]["busy_s_mean"]
        device["window_s"] = traced["reduced"]["window_s"]
    device["memory_peak_bytes"] = int(mem_peak)
    line = {"correct": bool(correct), "attempted": steps, "failed": failed,
            "metrics": metrics, "device": device}
    if traced and traced.get("reduced"):
        line["breakdown"] = {
            "device_ops": traced["reduced"]["device_ops"],
            "idle_gaps": traced["reduced"]["idle_gaps"]}
    line["compiles_in_window"] = compiles_in_window
    line["reference_s"] = ref_s
    line["compared"] = compared
    for k, (v, lim) in compared.items():
        say(f"compared {k} = {v!r} (limit {lim!r})"
            f"{'' if lim is not None and v <= lim else '  <-- FAILS'}")
    if rehearsal:
        # a rehearsal is no measurement: it names the CPU, prints what it
        # counted and compared, no metric, and exits non-zero
        line["metrics"] = {}
        line["rehearsal"] = {"steps": steps,
                             "cost": dataclasses.asdict(driver.cost()),
                             "readers_found": readers if args.trace else []}
        print(json.dumps(line), flush=True)
        return EXIT_REHEARSAL
    print(json.dumps(line), flush=True)
    return 0


def _traced_window(driver, traffic, root, keep):
    """A short traced window after the measured one, its steps bracketed in
    ``bench.step`` spans; the trace is read and deleted."""
    import jax
    import trace_reduce
    tdir = root.parent / ".bench_trace"
    shutil.rmtree(tdir, ignore_errors=True)
    seconds = float(traffic.get("trace_seconds", 2.0))
    annotate = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the bench.* spans are all the host says
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        step_s, dispatch_s, window_s, _ = run_window(
            driver, seconds, span=annotate, annotate=annotate)
    finally:
        jax.profiler.stop_trace()
    out = {"steps": len(step_s), "window_s": window_s, "reduced": None}
    try:
        path = trace_reduce.find_xplane(tdir)
        trace = trace_reduce.load_xplane(path)
        out["reduced"] = trace_reduce.reduce_trace(trace)
        if keep:
            Path(keep).mkdir(parents=True, exist_ok=True)
            (Path(keep) / "raw_summary.json").write_text(
                json.dumps(trace_reduce.summarize_raw(path)))
            import gzip
            with gzip.open(Path(keep) / "trace_head.json.gz", "wt") as f:
                json.dump(trace_reduce.head(trace, steps=3), f)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return out
