"""One traced benchmark rehearsal at a time across test processes.

``benchmark/harness.py``'s traced window writes the profiler's trace into
the one directory ``<repo>/.bench_trace`` and deletes it when read, so two
traced rehearsals in two xdist workers delete each other's trace.  A test
that runs ``harness.main([... "--trace", "1" ...])`` holds this lock round
the call."""

import contextlib
import fcntl
from pathlib import Path

LOCK = Path(__file__).resolve().parent.parent / ".bench_trace.lock"


@contextlib.contextmanager
def traced_rehearsal():
    with open(LOCK, "a") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)
