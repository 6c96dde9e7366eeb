#!/usr/bin/env python
"""Scripted telemetry workload for the journal, trace and live-plane gates.

Runs a small but representative slice of the framework — h2d distribute,
a distributed GEMM, an RDMA-armed (interpret-mode) single-axis reshard
NEXT TO its XLA twin, a serve round trip over an SPMD endpoint, a
solver, a mapreduce, and a d2h gather — with the journal enabled:

    python tools/perf_workload.py /tmp/journal.jsonl
    python -m distributedarrays_tpu.telemetry trace /tmp/journal.jsonl

Shared by the CI observability leg (Perfetto export, live-plane streaming
gate) and the trace-id tests, so the workload cannot drift between them.
"""

import os
import sys

if len(sys.argv) != 2:
    print("usage: perf_workload.py JOURNAL_PATH", file=sys.stderr)
    sys.exit(2)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["DA_TPU_TELEMETRY"] = "1"
os.environ["DA_TPU_TELEMETRY_JOURNAL"] = sys.argv[1]
os.environ.setdefault("DA_TPU_RDMA", "0")     # armed per-phase below

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import _cpu_harness  # noqa: E402

_cpu_harness.force_cpu_mesh()

import numpy as np  # noqa: E402

import distributedarrays_tpu as dat  # noqa: E402
from distributedarrays_tpu.parallel import spmd_mode as sm  # noqa: E402
from distributedarrays_tpu.serve import Server, ServeConfig  # noqa: E402

# -- h2d + distributed GEMM -------------------------------------------------
A = dat.distribute(np.arange(64 * 64, dtype=np.float32).reshape(64, 64))
B = dat.distribute(np.ones((64, 64), dtype=np.float32))
C = A @ B

# -- the RDMA-armed (interpret) reshard vs its XLA twin ---------------------
# an eligible single-axis repartition: (8,1) -> (1,8) lowers to the
# planner's compiled all_to_all; DA_TPU_RDMA flips which ring runs and
# the reshard span carries dispatch=rdma|xla
src = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
for dispatch in ("interpret", "0"):
    os.environ["DA_TPU_RDMA"] = dispatch
    E = dat.distribute(src, dist=(8, 1))
    F = dat.dzeros((64, 64), dist=(1, 8))
    dat.copyto_(F, E)
    assert np.array_equal(dat.gather(F), src), dispatch
    E.close()
    F.close()
os.environ["DA_TPU_RDMA"] = "0"

# -- serve round trip: trace ids submit -> dispatch -> rank steps -----------
srv = Server(ServeConfig(max_batch=4, flush_s=0.002))


def endpoint(payloads):
    out = []
    for p in payloads:
        ranks = sm.spmd(lambda: sm.myid(), pids=[0, 1])
        out.append(float(np.sum(p)) + float(sum(ranks)))
    return out


srv.register("echo", endpoint)
futs = [srv.submit("echo", np.full((8, 8), i, dtype=np.float32),
                   tenant=f"t{i % 2}") for i in range(4)]
results = [f.result(timeout=60) for f in futs]
srv.close()

# -- solver: sparse + stencil SpMV under CG (solver.spmv spans) -------------
from distributedarrays_tpu import solvers  # noqa: E402

sop = solvers.StencilOperator((32, 32))
procs, pdist = sop.vector_layout()
rhs = np.random.default_rng(5).standard_normal((32, 32)).astype(np.float32)
bsol = dat.distribute(rhs, procs=procs, dist=list(pdist))
sres = solvers.cg(sop, bsol, tol=1e-3, maxiter=500)
assert sres.converged, sres.outcome
sres.x.close()
bsol.close()

band = (2.5 * np.eye(96) - np.eye(96, k=1) - np.eye(96, k=-1)).astype(
    np.float32)
bop = solvers.SparseOperator(band)
procs, pdist = bop.vector_layout()
vb = dat.distribute(np.ones(96, dtype=np.float32), procs=procs,
                    dist=list(pdist))
y = bop.apply(vb)
y.close()
vb.close()

# -- mapreduce + gather -----------------------------------------------------
total = dat.dreduce("sum", A)
g = dat.gather(C)

for d in (A, B, C):
    d.close()
dat.d_closeall()
print("perf-workload-ok", len(results), float(np.asarray(total)))
