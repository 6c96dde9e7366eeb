#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips.  Everything is in ``harness.py``;
this file only notes the time the process started before anything heavy is
imported, so that ``setup_s`` counts the imports too.
"""

import sys
import time

T0 = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    here = Path(__file__).resolve().parent
    sys.path.insert(0, str(here))
    import harness
    sys.exit(harness.main(sys.argv[1:], t0=T0, root=here))
