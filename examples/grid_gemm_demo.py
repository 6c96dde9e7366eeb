"""2-D-grid distributed GEMM: the owned tile schedules.

The reference's tile-grid ``mul!`` (linalg.jl:189-253) ships A-row and
B-column tiles to every destination rank; BASELINE config 3 is exactly
that shape (16384² on a 2×2 block layout).  The TPU-native answers are
compiled collective schedules run as ONE shard_map program each:

- ``cannon_matmul`` — square ``(g, g)`` grids: Cannon pre-skew (one
  two-axis ppermute per operand), then a double panel ring with every
  ICI hop pipelined behind the local MXU matmul.
- ``cannon_matmul_int8`` — the same ring with int8 panels + per-panel
  scales riding it (4× less ICI traffic), per-hop Pallas int8 kernel,
  f32 accumulation.
- ``summa_matmul`` — arbitrary ``(r, c)`` grids, where Cannon's skewed
  ring misaligns: masked-psum SUMMA panels over lcm(r, c) statically
  unrolled contraction steps, O(one panel) peak memory.

Dispatch from plain ``A @ B`` promotes to these only by measurement
(``tune_matmul_impl_summa``) — this demo calls them directly
and checks the dense oracle.  Runs on the virtual CPU mesh.
"""

import _setup  # noqa: F401

import numpy as np
from jax.sharding import PartitionSpec as P

from distributedarrays_tpu import layout as L
from distributedarrays_tpu.ops.collective_matmul import (
    cannon_matmul, cannon_matmul_int8, summa_matmul)
from distributedarrays_tpu.parallel import collectives as C

rng = np.random.default_rng(0)

# --- square 2x2 grid: Cannon double ring (BASELINE config 3's layout) ---
g = 2
mesh = L.mesh_for(range(g * g), (g, g))
M, K, N = 256, 128, 192
a = rng.standard_normal((M, K)).astype(np.float32)
b = rng.standard_normal((K, N)).astype(np.float32)

cannon = C.run_spmd(lambda al, bl: cannon_matmul(al, bl, "d0", "d1"), mesh,
                    in_specs=(P("d0", "d1"), P("d0", "d1")),
                    out_specs=P("d0", "d1"))
got = np.asarray(cannon(a, b))
print("cannon 2x2 max|err|:", np.abs(got - a @ b).max())
assert np.allclose(got, a @ b, rtol=1e-4, atol=1e-4)

# --- the same ring with int8 panels (quantization-tolerant workloads) ---
cannon8 = C.run_spmd(
    lambda al, bl: cannon_matmul_int8(al, bl, "d0", "d1"), mesh,
    in_specs=(P("d0", "d1"), P("d0", "d1")), out_specs=P("d0", "d1"))
got8 = np.asarray(cannon8(a, b))
rel = np.abs(got8 - a @ b).max() / np.abs(a @ b).max()
print("cannon int8 2x2 rel err:", f"{rel:.2e}", "(quantization-bounded)")
assert rel < 2e-2

# --- rectangular 4x2 grid: SUMMA panels (Cannon refuses r != c) ---
mesh42 = L.mesh_for(range(8), (4, 2))
M2, K2, N2 = 256, 256, 128
a2 = rng.standard_normal((M2, K2)).astype(np.float32)
b2 = rng.standard_normal((K2, N2)).astype(np.float32)
summa = C.run_spmd(lambda al, bl: summa_matmul(al, bl, "d0", "d1"), mesh42,
                   in_specs=(P("d0", "d1"), P("d0", "d1")),
                   out_specs=P("d0", "d1"))
got2 = np.asarray(summa(a2, b2))
print("summa 4x2 max|err|:", np.abs(got2 - a2 @ b2).max())
assert np.allclose(got2, a2 @ b2, rtol=1e-4, atol=1e-4)

print("grid GEMM demo OK")
