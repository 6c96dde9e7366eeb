"""Overlapped tensor parallelism + int8 quantized GEMM demo.

Two round-3 performance features on one page:

1. ``ops.collective_matmul`` — the Megatron sequence-parallel FFN
   (``tp_ffn``): ring all-gather GEMM in, GEMM + ring reduce-scatter
   out, each ICI hop pipelined behind the MXU.  Run as ONE shard_map
   program over a tp axis and verified against the dense oracle.
2. ``ops.pallas_gemm.quantized_matmul`` — float in/out, int8 on the
   MXU: dynamic per-row/per-column symmetric quantization, exact int32
   accumulation, dequant fused into the tile flush.  On e-class TPUs
   the int8 MXU rate is 2x bf16, so this path can beat the chip's bf16
   peak (not measured: no benchmark cell runs it).
"""

import _setup  # noqa: F401

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributedarrays_tpu.ops.collective_matmul import tp_ffn
from distributedarrays_tpu.ops.pallas_gemm import quantized_matmul
from distributedarrays_tpu.parallel import collectives as C

# ---- 1. sequence-parallel FFN over a 4-rank tp axis ----------------------
p = min(4, len(jax.devices()))
mesh = C.spmd_mesh(p)
S, E, F = 32 * p, 64, 64 * p
rng = np.random.default_rng(0)
x = rng.standard_normal((S, E)).astype(np.float32)
w1 = rng.standard_normal((E, F)).astype(np.float32) * 0.1
w2 = rng.standard_normal((F, E)).astype(np.float32) * 0.1

ffn = C.run_spmd(lambda a, b, c: tp_ffn(a, b, c, "p"), mesh,
                 in_specs=(P("p", None), P(None, "p"), P("p", None)),
                 out_specs=P("p", None))
y = np.asarray(ffn(x, w1, w2))
want = np.asarray(jax.nn.gelu(jnp.asarray(x @ w1))) @ w2
err = np.abs(y - want).max() / np.abs(want).max()
print(f"tp_ffn over {p} ranks: sequence shard {S // p}x{E}, "
      f"intermediate {S}x{F // p} (1/{p} of full), rel err {err:.2e}")
assert err < 1e-4

# and it trains: gradients flow through both ring loops
g1, g2 = jax.jit(jax.grad(lambda b, c: jnp.sum(ffn(x, b, c) ** 2),
                          (0, 1)))(jnp.asarray(w1), jnp.asarray(w2))
print(f"grad norms through the rings: |dW1|={float(jnp.abs(g1).max()):.3f} "
      f"|dW2|={float(jnp.abs(g2).max()):.3f}")

# ---- 2. int8 quantized GEMM ----------------------------------------------
N = 512
a = rng.standard_normal((N, N)).astype(np.float32)
b = rng.standard_normal((N, N)).astype(np.float32)
c8 = np.asarray(quantized_matmul(a, b))
rel = np.abs(c8 - a @ b).max() / np.abs(a @ b).max()
print(f"int8 GEMM {N}x{N}: rel err {rel:.2e} "
      "(quantization noise; int32 accumulation is exact)")
assert rel < 2e-2

# ---- 3. square 2-D-grid GEMM: the Cannon double panel ring ---------------
# The reference's tile-grid mul! shape (both operands block-distributed
# over one (g,g) grid).  Float panels ride two overlapped ppermute rings;
# the int8 variant ships int8 panels + per-panel scales (4x less wire).
if len(jax.devices()) >= 4:
    import distributedarrays_tpu as dat
    from distributedarrays_tpu.ops import linalg as la
    from distributedarrays_tpu.utils import autotune

    M = 64
    A2 = rng.standard_normal((M, M)).astype(np.float32)
    B2 = rng.standard_normal((M, M)).astype(np.float32)
    ga = dat.distribute(A2, procs=range(4), dist=(2, 2))
    gb = dat.distribute(B2, procs=range(4), dist=(2, 2))
    # promotion is by measurement (tune_matmul_impl_summa);
    # force the registry here so the demo exercises the owned schedule
    autotune.record("matmul_impl_dist",
                    la._impl_key(M, M, M, "2x2", ga.dtype, gb.dtype),
                    "summa")
    gc = ga @ gb
    err2 = np.abs(np.asarray(gc) - A2 @ B2).max() / np.abs(A2 @ B2).max()
    print(f"Cannon 2x2-grid GEMM {M}x{M}: rel err {err2:.2e}")
    assert err2 < 1e-4
    qc = dat.dmatmul_int8(ga, gb)
    err8 = np.abs(np.asarray(qc) - A2 @ B2).max() / np.abs(A2 @ B2).max()
    print(f"Cannon 2x2-grid int8 GEMM {M}x{M}: rel err {err8:.2e}")
    assert err8 < 3e-2
    autotune.clear()
    dat.d_closeall()
print("OK")
