"""Drive the rest of a run without the harness's look for a chip."""

import json
import time
from pathlib import Path

import harness

BENCH = Path(__file__).resolve().parent.parent


def rehearse(capsys, workload, seed=7, seconds=0.2, trace=0):
    """One tiny CPU rehearsal in this process: (exit code, last line)."""
    capsys.readouterr()
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--platform", "cpu", "--size", "tiny"],
                      t0=time.perf_counter(), root=BENCH)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)
