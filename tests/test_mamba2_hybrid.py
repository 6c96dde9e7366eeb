"""The Mamba-2 hybrid (``models/mamba2_hybrid.py``) and its SSD kernel pair
(``ops/pallas_ssd.py``) against plain references at tiny sizes on the CPU
(kernels in interpret mode): the kernels against the recurrence taken one
position after the other, the model against its package reference and the
benchmark's, the step's scopes against the phases the readers place."""

import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _models
from distributedarrays_tpu.models import mamba2_hybrid as M
from distributedarrays_tpu.models import mamba2_hybrid_reference as MR
from distributedarrays_tpu.ops.pallas_ssd import ssd, ssd_plan

REPO = _models.REPO
LAYERS, DIMS = _models.GRANITE_LAYERS, _models.GRANITE_DIMS
MULT = _models.GRANITE_MULT
TINY = _models.mamba2_hybrid
_config, _weights, _tokens = TINY.config, TINY.weights, TINY.tokens


# ---------------------------------------------------------------------------
# the SSD kernels against the recurrence
# ---------------------------------------------------------------------------


SSD_CASES = {
    # L, H, P, G, N, chunk, dt from .. to
    "padded_small_dt": (44, 4, 16, 1, 16, 16, 1e-3, 1e-1),
    "groups_dt_near_one": (64, 4, 16, 2, 8, 16, 0.5, 1.0),
    "chunk_of_8_four_groups": (40, 8, 8, 4, 16, 8, 1e-3, 1.0),
    "one_chunk_two_blocks": (32, 16, 8, 1, 8, 32, 1e-3, 1e-1),
}


def _ssd_case(L, H, P, G, N, lo, hi, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    dt = jnp.exp(jax.random.uniform(ks[1], (L, H), minval=np.log(lo),
                                    maxval=np.log(hi)))
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), maxval=np.log(16.0)))
    return (jax.random.normal(ks[0], (L, H, P)), dt, a,
            jax.random.normal(ks[3], (L, G, N)),
            jax.random.normal(ks[4], (L, G, N))), \
        jax.random.normal(ks[5], (L, H, P))


def _ssd_results(case):
    """(kernel, recurrence) results of a case: the forward, then the five
    gradients of a weighted sum, computed once for the six tests of it."""
    L, H, P, G, N, chunk, lo, hi = SSD_CASES[case]
    args, w = _ssd_case(L, H, P, G, N, lo, hi)
    kernel = lambda *a: ssd(*a, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        return _models.against(("ssd", case), (kernel, MR.ssd_scan), args, w)


@pytest.mark.parametrize("arg", ["forward", "x", "dt", "A", "B", "C"])
@pytest.mark.parametrize("case", list(SSD_CASES))
def test_ssd_kernels_match_the_sequential_recurrence(case, arg):
    n = ["forward", "x", "dt", "A", "B", "C"].index(arg)
    got, want = (r[n] for r in _ssd_results(case))
    assert got.shape == want.shape
    # float32 throughout at this size; the kernels sum in another order
    # (by chunks, the running sums as products of split parts): read
    # 2.2e-6 at most
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))


def test_ssd_in_bfloat16_stays_near_the_float32_recurrence():
    # the training type: the large products take bf16 operands, the states
    # and the sums stay float32
    args, w = _ssd_case(64, 4, 16, 1, 16, 1e-3, 1e-1)
    x, dt, a, b, c = args
    got = ssd(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
              c.astype(jnp.bfloat16), chunk=16)
    assert got.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = MR.ssd_scan(*args)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2 * float(
        jnp.max(jnp.abs(want)))


def test_ssd_plan_and_its_gauge():
    from distributedarrays_tpu import telemetry as tm
    plan = ssd_plan(8192, 64, 64, 1, 128)
    assert (plan["chunk"], plan["chunks"], plan["head_block"]) == (256, 32, 8)
    assert plan["checkpoint_bytes"] == 32 * 64 * 64 * 128 * 4
    assert plan["vmem_bytes"] % 2**20 == 0 and plan["vmem_bytes"] < 16 * 2**20
    assert ssd_plan(44, 4, 16, 1, 16, 16)["padded"] == 48
    # a head block never straddles two groups
    assert ssd_plan(64, 12, 8, 4, 8, 16)["head_block"] == 3
    with pytest.raises(ValueError):
        ssd_plan(64, 6, 8, 4, 8)
    args, _ = _ssd_case(32, 4, 16, 1, 16, 1e-3, 1e-1)
    ssd(*args, chunk=16)
    read = lambda what: tm.gauge_value("pallas.ssd.plan", L=32, H=4, P=16,
                                       G=1, N=16, what=what)
    assert (read("chunk"), read("chunks"), read("head_block")) == (16, 2, 4)
    assert read("checkpoint_bytes") == 2 * 4 * 16 * 16 * 4
    assert read("vmem_bytes") > 0


# ---------------------------------------------------------------------------
# the model against the references
# ---------------------------------------------------------------------------


def test_loss_and_every_leaf_gradient_match_refs_granite():
    import refs_granite as R
    params, tok = _weights(), _tokens()
    loss, g = TINY.result("value_and_grad")
    dims = dict(DIMS, eps=1e-5, kinds=tuple(k for _, k in LAYERS), **MULT)
    loss0, g0 = _models.row_reference(R, params, tok, dims)
    assert abs(float(loss) - loss0) < 2e-5 * abs(loss0)
    # float32 on both sides at this size: read 1.4e-6 at most
    worst = _models.worst_leaf(R, g, g0, params)
    assert worst[0] < 5e-4, worst


@pytest.mark.parametrize("batch", [1, 2])
def test_package_reference_agrees_with_the_program(batch):
    # with two rows the batch folds into the kernels' heads
    loss, g = TINY.result("value_and_grad", batch=batch)
    loss0, g0 = TINY.result("value_and_grad", "reference", batch=batch)
    assert abs(float(loss) - float(loss0)) < 2e-5 * abs(float(loss0))
    worst = max((v, k) for k, v in _models.gaps(g, g0).items())
    assert worst[0] < 5e-4, worst
    logits = TINY.result("forward", batch=batch)
    logits0 = TINY.result("forward", "reference", batch=batch)
    assert np.allclose(logits, logits0, atol=2e-5)


def test_bf16_training_step_runs_and_moves_the_weights():
    import optax
    cfg = _config(dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                    _weights())
    before = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    step, init = M.make_optax_train_step(cfg, optax.adamw(1e-3))
    params, state, loss = step(params, init(params), _tokens())
    assert np.isfinite(float(loss))
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))),
        params, before)
    assert moved["embed"] > 0
    assert moved["layers"][0]["in_proj"] > 0 and moved["layers"][1]["wqkv"] > 0
    # (dt_bias lies near log(dt), some -2 to -7, where one step of 1e-3
    # is under half a bf16 ulp: it moves on neither side)
    assert moved["layers"][0]["A_log"] > 0 and moved["layers"][2]["conv_b"] > 0


def test_the_multipliers_act_where_the_configuration_says():
    # each multiplier changes the logits; the published ones are not the
    # identity, so none may be dropped silently
    params, tok = _weights(), _tokens()[:, :-1]
    base = TINY.result("forward")
    forward = _models.jitted(M.forward)
    for key, value in dict(embedding_mult=1.0, residual_mult=1.0,
                           attention_mult=0.25, logits_scaling=1.0).items():
        cfg = _config(**{key: value})
        assert float(jnp.max(jnp.abs(forward(params, tok, cfg) - base))) \
            > 1e-3, key


def test_granite_parameters_at_the_cell_widths():
    import counts_granite as C
    cut = tuple((i, "attention" if i == 5 else "mamba") for i in range(10))
    cfg = M.Config(vocab=12544, dim=2048, ffn=8192, heads=32, kv_heads=8,
                   head_dim=64, ssm_heads=64, ssm_head_dim=64, d_state=128,
                   n_groups=1, layers=cut, attention_mult=0.015625)
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    m = dict(DIMS, dim=2048, ffn=8192, heads=32, kv_heads=8, head_dim=64,
             ssm_heads=64, ssm_head_dim=64, d_inner=4096, d_state=128,
             n_groups=1, chunk=256)
    assert n == C.granite_params(m, [k for _, k in cut], 12544) == 772_160_448
    per = {k: C.layer_params(k, m) for k in M.KINDS}
    assert per == {"mamba": 76_182_976, "attention": 60_821_504}
    layer0 = shapes["layers"][0]
    assert sum(x.size for k, x in layer0.items()
               if k not in ("w1", "w2", "norm1", "norm2")) == 25_847_232


# ---------------------------------------------------------------------------
# the step's scopes, and what importing the package loads
# ---------------------------------------------------------------------------


def test_declared_scopes_are_phases_the_readers_place():
    from layer_metrics.phases import GROUPS
    placed = {phase: g for g, phases in GROUPS.items() for phase in phases}
    assert {s: placed.get(s) for s in M.SCOPES} == {
        "embed": "head_loss", "block/mamba": "ssm", "block/attn": "attn",
        "block/mlp": "mlp", "head_loss": "head_loss",
        "optimizer": "optimizer"}


@pytest.mark.parametrize("scope", ["embed", "block/mamba", "block/attn",
                                   "block/mlp", "head_loss", "optimizer",
                                   "ssd_fwd", "ssd_bwd", "flash_fwd",
                                   "flash_bwd_dkv"])
def test_step_carries_its_scopes_and_kernel_names(scope, step_text):
    names = set(re.findall(r'loc\("([^"]*)"', step_text))
    if scope.startswith("block/"):
        leaf = scope.split("/")[1]
        hits = [n for n in names if f"block/{leaf}/" in n
                or f"jvp(block)/{leaf}/" in n]
        # a layer is computed forward, again in the backward, and backward
        assert any("rematted_computation" in n for n in hits)
        assert any(n.startswith("jit(step)/jvp(") for n in hits)
    else:
        hits = [n for n in names if scope in n]
    assert hits, scope


@pytest.fixture(scope="module")
def step_text():
    return _models.step_text("mamba2_hybrid")


def test_importing_the_package_loads_neither_the_model_nor_the_kernels():
    code = textwrap.dedent("""
        import sys
        import distributedarrays_tpu
        import distributedarrays_tpu.ops
        for name in ("distributedarrays_tpu.models.mamba2_hybrid",
                     "distributedarrays_tpu.models.mamba2_hybrid_reference",
                     "distributedarrays_tpu.ops.pallas_ssd"):
            assert name not in sys.modules, name
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-2000:]
