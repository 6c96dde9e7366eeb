#!/usr/bin/env python3
"""Where the limits come from: the readings of many seeds in one process.

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 3 --seconds 2 --out chiprun_out/calib_<name>.json

For each seed: the cell set up and driven as a run drives it (the timed
path at the timed sizes, a short window), then what it produced compared
with the plain reference: the LOWER readings.  For the first
``--control-seeds`` seeds also the control (the reference computed in the
nearest precision below the configuration's, put in the program's place)
and, for a training cell, the planted fault "half of the batch left out":
the UPPER readings.  Prints one JSON line a seed and a summary; a limit is
then set by hand between the two, in ``limits/<cell>.json``, with the
readings beside it.  The benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", choices=("cpu",), default=None)
    ap.add_argument("--size", choices=("tiny",), default=None)
    args = ap.parse_args(argv)
    here = Path(__file__).resolve().parent
    sys.path.insert(0, str(here))
    sys.path.insert(1, str(here.parent))
    import harness
    cell, entry, config, traffic, limits, bench = harness.load_cell(
        here, args.workload)
    chips = int(cell["chips"])
    if args.platform == "cpu":
        harness.use_virtual_cpu(chips)
    import importlib
    import jax
    import jax.numpy as jnp
    if args.platform != "cpu":
        harness.enable_cache()
    devs = jax.devices()
    if args.platform != "cpu" and (devs[0].platform != "tpu"
                                   or len(devs) != chips):
        print(f"calibrate: needs {chips} TPU chip(s)", file=sys.stderr)
        return 3
    mod = importlib.import_module(f"drivers.{traffic['driver']}")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    rows, lower, upper = [], {}, {}
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        ctx = SimpleNamespace(cell=cell, config=config, traffic=traffic,
                              seed=seed, devices=devs[:chips],
                              on_tpu=devs[0].platform == "tpu",
                              tiny=args.size == "tiny", root=here,
                              mark=lambda what: None)
        drv = mod.Driver(ctx)
        drv.setup()
        step_s, _, window_s, failed = harness.run_window(drv, args.seconds)
        outputs = drv.finish()
        ref = drv.reference()
        row = {"seed": seed, "steps": len(step_s),
               "step_ms": 1e3 * window_s / len(step_s), "failed": failed,
               "program": drv.compare(outputs, ref)}
        if hasattr(drv, "worst_leaves"):
            row["worst_leaves"] = drv.worst_leaves
        drv.release(outputs)
        if n < args.control_seeds:
            lowp = jnp.dtype(drv.control_lowp).type
            row["control"] = drv.compare(drv.control_outputs(lowp), ref)
            if hasattr(drv, "batch"):
                half = {"readings": drv.reference(rows=drv.batch // 2),
                        "nonfinite": 0}
                row["fault_half_batch"] = drv.compare(half, ref)
        row["seconds"] = time.perf_counter() - t0
        for k, v in row["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        for kind in ("control", "fault_half_batch"):
            for k, v in row.get(kind, {}).items():
                d = upper.setdefault(kind, {})
                d[k] = min(d.get(k, float("inf")), v)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": seeds,
               "lower_max_over_seeds": lower, "upper_min_over_seeds": upper}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows, **summary},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
