"""The Olmo hybrid (``models/olmo_hybrid.py``) and its gated delta-rule kernel
pair (``ops/pallas_gated_delta.py``) against plain references at tiny sizes
on the CPU (kernels in interpret mode): the kernels against the recurrence
taken one position after the other, the model against its package
reference and the benchmark's, the step's scopes against the phases the
readers place."""

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
from distributedarrays_tpu.models import olmo_hybrid as M
from distributedarrays_tpu.models import olmo_hybrid_reference as MR
from distributedarrays_tpu.ops.pallas_gated_delta import (
    gated_delta, gated_delta_plan)

REPO = _models.REPO
LAYERS, DIMS = _models.OLMO_LAYERS, _models.OLMO_DIMS
TINY = _models.olmo_hybrid
_config, _weights, _tokens = TINY.config, TINY.weights, TINY.tokens


# ---------------------------------------------------------------------------
# the delta-rule kernels against the recurrence
# ---------------------------------------------------------------------------


GDN_CASES = {
    # L, H, dk, dv, chunk, -g from .. to, beta from .. to
    "padded_mild_gate": (44, 2, 16, 24, 16, 0.0, 0.1, 0.0, 1.0),
    "one_chunk": (16, 2, 16, 16, 16, 0.0, 0.1, 0.0, 2.0),
    "many_chunks_alpha_near_one": (96, 4, 8, 16, 8, 0.0, 1e-3, 0.0, 2.0),
    "alpha_near_zero": (50, 4, 8, 16, 8, 3.0, 8.0, 0.0, 2.0),
    "beta_near_two": (64, 2, 16, 32, 16, 0.0, 0.05, 1.9, 2.0),
    # two head blocks of 5, 13 chunks (the saved solves 8 to a row, the
    # last row part-filled) and a padded tail of 8 positions
    "head_blocks_padded_solve_rows": (200, 10, 8, 16, 16, 0.0, 0.1, 0.0, 2.0),
}


def _gdn_case(L, H, dk, dv, glo, ghi, blo, bhi, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (L, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (L, H, dk)))
    v = jax.random.normal(ks[2], (L, H, dv))
    beta = jax.random.uniform(ks[3], (L, H), minval=blo, maxval=bhi)
    g = -jax.random.uniform(ks[4], (L, H), minval=glo, maxval=ghi)
    return (q, k, v, beta, g), jax.random.normal(ks[5], (L, H, dv))


def _gdn_results(case):
    """(kernel, recurrence) results of a case: the forward, then the five
    gradients of a weighted sum, computed once for the six tests of it."""
    L, H, dk, dv, chunk, glo, ghi, blo, bhi = GDN_CASES[case]
    args, w = _gdn_case(L, H, dk, dv, glo, ghi, blo, bhi)
    kernel = lambda *a: gated_delta(*a, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        return _models.against(("gdn", case), (kernel, MR.delta_rule),
                               args, w)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("arg", ["forward", "q", "k", "v", "beta", "g"])
@pytest.mark.parametrize("case", list(GDN_CASES))
def test_gdn_kernels_match_the_sequential_recurrence(case, arg):
    n = ["forward", "q", "k", "v", "beta", "g"].index(arg)
    got, want = (r[n] for r in _gdn_results(case))
    assert got.shape == want.shape
    # float32 throughout at this size; the kernels sum in another order (by
    # chunks, the solve by doubling): read 7e-7 at most
    assert _rel(got, want) < 2e-5


def test_a_kernel_that_clamps_beta_to_one_fails_the_comparison():
    # eigenvalues of the transition in (-1, 0) are the published
    # configuration's (linear_allow_neg_eigval): a kernel held to beta <= 1
    # is wrong there by far more than the comparison lets through
    L, H, dk, dv, chunk, glo, ghi, blo, bhi = GDN_CASES["beta_near_two"]
    (q, k, v, beta, g), _ = _gdn_case(L, H, dk, dv, glo, ghi, blo, bhi)
    with jax.default_matmul_precision("highest"):
        want = MR.delta_rule(q, k, v, beta, g)
    clamped = gated_delta(q, k, v, jnp.minimum(beta, 1.0), g, chunk=chunk)
    assert _rel(clamped, want) > 100 * 2e-5
    assert _rel(gated_delta(q, k, v, beta, g, chunk=chunk), want) < 2e-5


def test_gdn_in_bfloat16_stays_near_the_float32_recurrence():
    # the training type: the products with a width take bf16 operands, the
    # solve, the states and the sums stay float32
    args, _ = _gdn_case(64, 2, 16, 32, 0.0, 0.1, 0.0, 2.0)
    q, k, v, beta, g = args
    bf = lambda t: t.astype(jnp.bfloat16)
    got = gated_delta(bf(q), bf(k), bf(v), beta, g, chunk=16)
    assert got.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = MR.delta_rule(*args)
    assert _rel(got, want) < 3e-2


def test_gdn_plan_and_its_gauge():
    from distributedarrays_tpu import telemetry as tm
    plan = gated_delta_plan(8192, 30, 96, 192)
    assert (plan["chunk"], plan["chunks"], plan["head_block"]) == (64, 128, 5)
    assert plan["checkpoint_bytes"] == 128 * 30 * 192 * 96 * 4
    assert plan["vmem_bytes"] % 2**20 == 0 and plan["vmem_bytes"] < 16 * 2**20
    assert gated_delta_plan(44, 2, 16, 24, 16)["padded"] == 48
    assert gated_delta_plan(64, 7, 8, 8, 8)["head_block"] == 1
    with pytest.raises(ValueError):
        gated_delta_plan(64, 2, 8, 8, 12)
    args, _ = _gdn_case(32, 2, 16, 24, 0.0, 0.1, 0.0, 1.0)
    gated_delta(*args, chunk=16)
    read = lambda what: tm.gauge_value("pallas.gated_delta.plan", L=32, H=2,
                                       dk=16, dv=24, what=what)
    assert (read("chunk"), read("chunks"), read("head_block")) == (16, 2, 2)
    assert read("checkpoint_bytes") == 2 * 2 * 24 * 16 * 4
    # the two chunks' solves (16 x 16) share one 128-lane row a head
    assert read("solve_bytes") == 2 * 1 * 16 * 128 * 4
    assert read("vmem_bytes") > 0
    # at the cell's shapes two chunks of 64 share a row: no lane is padding
    assert plan["solve_bytes"] == 128 * 30 * 64 * 64 * 4 == 62_914_560


def _kernel_operands(args, chunk):
    """q, k, v, beta and the running sums as ``gated_delta`` hands them to
    the kernels (heads first; no padding needed at these lengths)."""
    q, k, v, beta, g = args
    L, H, _ = q.shape
    heads = lambda t: jnp.transpose(t, (1, 0, 2))
    rows = lambda t: t.T.reshape(H, L // chunk, chunk)
    return (heads(q), heads(k), heads(v), rows(beta),
            jnp.cumsum(rows(g), axis=-1))


def test_the_saved_solve_is_the_inverse_of_each_chunk():
    # the solve kernel writes each chunk's X = (I + A)^-1 for the
    # recurrence and the backward to read; against a float64 solve of
    # every chunk of every head
    from distributedarrays_tpu.ops import pallas_gated_delta as GD
    chunk = 16
    args, _ = _gdn_case(160, 10, 8, 16, 0.0, 0.5, 0.0, 2.0)
    ops = _kernel_operands(args, chunk)
    solve = GD._calls(ops[0], ops[2], ops[4], True)[0]
    saved = np.asarray(solve(*ops[1:2], *ops[3:]), np.float64)
    k = np.asarray(ops[1], np.float64)
    beta, b = (np.asarray(t, np.float64) for t in ops[3:])
    H, nc = beta.shape[:2]
    pack = saved.shape[3] // chunk
    worst = 0.0
    for h in range(H):
        for c in range(nc):
            kc = k[h, c * chunk:(c + 1) * chunk]
            decay = np.exp(np.tril(b[h, c][:, None] - b[h, c][None, :]))
            a = np.tril(beta[h, c][:, None] * (kc @ kc.T) * decay, -1)
            want = np.linalg.inv(np.eye(chunk) + a)
            got = saved[h, c // pack, :,
                        (c % pack) * chunk:(c % pack + 1) * chunk]
            worst = max(worst, np.abs(got - want).max() / np.abs(want).max())
    assert worst < 2e-5, worst


def _kernels(jaxpr):
    """[(kernel name, dot_generals at HIGHEST in its body)] of every
    ``pallas_call`` reached from ``jaxpr``, sorted: the solve and the
    recurrence share the name ``gdn_fwd`` and differ in their products."""
    found = []

    def products(jx):
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general" and eqn.params.get(
                    "precision") == (jax.lax.Precision.HIGHEST,) * 2:
                n += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += products(sub)
        return n

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              products(eqn.params["jaxpr"])))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return sorted(found)


# at the default chunk of 64 and one head a grid step: the solve kernel
# takes the two chunks of a row of X, each six doubling levels of two
# float32 products; the recurrence reads X and holds none; the backward
# holds only the two of dA = -X^T (dT beta) X^T
SOLVE, RECURRENCE, BACKWARD = ("gdn_fwd", 24), ("gdn_fwd", 0), ("gdn_bwd", 2)


def test_the_backward_reads_the_solve_and_does_not_solve_again():
    args, w = _gdn_case(64, 1, 8, 8, 0.0, 0.1, 0.0, 2.0)
    grad = jax.grad(lambda *a: jnp.sum(gated_delta(*a) * w),
                    argnums=(0, 1, 2, 3, 4))
    assert _kernels(jax.make_jaxpr(grad)(*args).jaxpr) == sorted(
        [SOLVE, RECURRENCE, BACKWARD])


@pytest.mark.parametrize("policy", ["the_model_keeps_the_solve",
                                    "a_policy_without_the_solve"])
def test_a_recomputed_layer_solves_once(policy, monkeypatch):
    # two linear-attention layers under jax.checkpoint: with _KEEP the
    # solve runs once a layer (in the forward) and the recurrence twice
    # (forward, and again in the backward); a policy that forgets
    # "gdn_solve" solves twice a layer
    if policy == "a_policy_without_the_solve":
        monkeypatch.setattr(M, "_KEEP", jax.checkpoint_policies
                            .save_only_these_names("mlp_up"))
    cfg = M.Config(vocab=96, dim=64, ffn=96, heads=4, head_dim=16,
                   lin_heads=1, key_dim=16, value_dim=32, loss_rows=16,
                   layers=((0, "linear_attention"), (1, "linear_attention")))
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 73), jnp.int32)
    found = _kernels(jax.make_jaxpr(
        lambda p, t: jax.grad(M.loss_fn)(p, t, cfg))(params, tokens).jaxpr)
    solves = 2 if policy == "the_model_keeps_the_solve" else 4
    assert found == sorted([SOLVE] * solves + [RECURRENCE] * 4
                           + [BACKWARD] * 2)


# ---------------------------------------------------------------------------
# the model against the references
# ---------------------------------------------------------------------------


def test_loss_and_every_leaf_gradient_match_refs_olmo_hybrid():
    import refs_olmo_hybrid as R
    params, tok = _weights(), _tokens()
    loss, g = TINY.result("value_and_grad")
    dims = dict(DIMS, eps=1e-6, kinds=tuple(k for _, k in LAYERS))
    loss0, g0 = _models.row_reference(R, params, tok, dims)
    assert abs(float(loss) - loss0) < 2e-5 * abs(loss0)
    # float32 on both sides at this size: read 1.3e-5 at most
    worst = _models.worst_leaf(R, g, g0, params)
    assert worst[0] < 5e-4, worst


@pytest.mark.parametrize("batch", [1, 2])
def test_package_reference_agrees_with_the_program(batch):
    # with two rows the batch folds into the kernels' heads
    loss, g = TINY.result("value_and_grad", batch=batch)
    loss0, g0 = TINY.result("value_and_grad", "reference", batch=batch)
    assert abs(float(loss) - float(loss0)) < 2e-5 * abs(float(loss0))
    # float32 on both sides: read 1.2e-5 at most (A_log, dt_bias)
    worst = max((v, k) for k, v in _models.gaps(g, g0).items())
    assert worst[0] < 5e-4, worst
    logits = TINY.result("forward", batch=batch)
    logits0 = TINY.result("forward", "reference", batch=batch)
    assert np.allclose(logits, logits0, atol=2e-5)


@pytest.mark.parametrize("leaf", [
    "post_mix_norm", "post_mlp_norm", "o_norm", "q_norm", "k_norm"])
def test_each_norm_acts_and_its_gradient_matches(leaf):
    # the post-norms, the gated head norm and the QK-norm each change the
    # logits when their scale moves (channel by channel: the post-norm
    # after the mixer takes a uniform scale of o_norm out again), and their
    # gradients agree with the package reference's
    cfg, params, tok = _config(), _weights(), _tokens()
    n = next(i for i, p in enumerate(params["layers"]) if leaf in p)
    moved = jax.tree_util.tree_map(lambda x: x, params)
    moved["layers"][n] = dict(params["layers"][n])
    scale = params["layers"][n][leaf]
    moved["layers"][n][leaf] = scale * (
        1.0 + 0.5 * jax.random.normal(jax.random.key(5), scale.shape))
    base = TINY.result("forward")
    forward = _models.jitted(M.forward)
    assert float(jnp.max(jnp.abs(forward(moved, tok[:, :-1], cfg) - base))) \
        > 1e-3, leaf
    got = TINY.result("value_and_grad")[1]["layers"][n][leaf]
    want = TINY.result("value_and_grad", "reference")[1]["layers"][n][leaf]
    assert float(jnp.linalg.norm(want)) > 0
    assert float(jnp.linalg.norm(got - want)) < 5e-4 * float(
        jnp.linalg.norm(want))


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
    assert moved["embed"] > 0 and moved["head"] > 0
    assert moved["layers"][0]["w_in"] > 0 and moved["layers"][1]["w_qkv"] > 0
    assert moved["layers"][0]["w_ab"] > 0 and moved["layers"][2]["conv_w"] > 0
    assert moved["layers"][1]["w_o"] > 0 and moved["layers"][0]["w_o"] > 0
    # (the norm scales lie near 1, A_log near log of 0..16 and dt_bias near
    # log(dt), some -2 to -7, where one step of 1e-3 is under half a bf16
    # ulp: they move on neither side)


def test_olmo_hybrid_parameters_at_the_cell_widths():
    import counts_olmo_hybrid as C
    cut = tuple((i, "full_attention" if i == 3 else "linear_attention")
                for i in range(4))
    cfg = M.Config(vocab=12544, dim=3840, ffn=11008, heads=30, head_dim=128,
                   lin_heads=30, key_dim=96, value_dim=192, layers=cut)
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    m = dict(DIMS, dim=3840, ffn=11008, heads=30, head_dim=128, lin_heads=30,
             key_dim=96, value_dim=192)
    assert n == C.olmo_hybrid_params(m, [k for _, k in cut], 12544) \
        == 928_862_196
    per = {k: C.layer_params(k, m) for k in M.KINDS}
    assert per == {"linear_attention": 215_570_172,
                   "full_attention": 185_809_920}
    layer0 = shapes["layers"][0]
    assert sum(x.size for k, x in layer0.items()
               if k not in ("w1", "w2", "post_mix_norm", "post_mlp_norm")) \
        == 88_750_332


# ---------------------------------------------------------------------------
# the step's scopes, and what importing the package loads
# ---------------------------------------------------------------------------


def test_declared_scopes_are_phases_the_readers_place():
    # block/linear is in no group of phases.py: its own reader
    # (phase_linear_ms) reads it from the split by phase, and
    # phase_unscoped_ms is not reported for this model's cell
    from layer_metrics.phases import GROUPS
    from layer_metrics import phase_linear_ms
    placed = {phase: g for g, phases in GROUPS.items() for phase in phases}
    assert {s: placed.get(s) for s in M.SCOPES} == {
        "embed": "head_loss", "block/linear": None, "block/attn": "attn",
        "block/mlp": "mlp", "head_loss": "head_loss",
        "optimizer": "optimizer"}
    assert phase_linear_ms.PHASE in M.SCOPES


@pytest.mark.parametrize("scope", ["embed", "block/linear", "block/attn",
                                   "block/mlp", "head_loss", "optimizer",
                                   "gdn_fwd", "gdn_bwd", "flash_fwd",
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
    return _models.step_text("olmo_hybrid")


def test_importing_the_package_loads_neither_the_model_nor_the_kernels():
    code = textwrap.dedent("""
        import sys
        import distributedarrays_tpu
        import distributedarrays_tpu.ops
        for name in ("distributedarrays_tpu.models.olmo_hybrid",
                     "distributedarrays_tpu.models.olmo_hybrid_reference",
                     "distributedarrays_tpu.ops.pallas_gated_delta"):
            assert name not in sys.modules, name
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-2000:]
