"""The latent-attention expert model (``models/mla_moe.py``), its expert
layer with its grouped products (``models/moe.held_experts_ffn``) and the
flash kernels at width 256, against plain references at tiny sizes on the
CPU (kernels in interpret mode)."""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _models
from distributedarrays_tpu import telemetry as tm
from distributedarrays_tpu.models import mla_moe as M
from distributedarrays_tpu.models import mla_moe_reference as MR
from distributedarrays_tpu.models import moe as E
from distributedarrays_tpu.ops.pallas_attention import flash_attention

REPO = _models.REPO
DIMS, LAYERS = _models.MLA_DIMS, _models.MLA_LAYERS
TINY = _models.mla_moe
_cfg = TINY.config


def _rel(a, b):
    nb = float(jnp.linalg.norm(b))
    return float(jnp.linalg.norm(a - b)) / nb if nb else \
        float(jnp.linalg.norm(a))


@pytest.fixture(scope="module")
def tiny():
    return _cfg(), TINY.weights(), TINY.tokens()


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------


def test_logits_of_trunk_and_mtp_match_the_reference(tiny):
    cfg, params, tokens = tiny
    forward = jax.jit(M.forward, static_argnums=(2, 3))
    main, mtp = forward(params, tokens[:, :-1], cfg, True)
    for b in range(tokens.shape[0]):
        a, c = MR.forward(params, tokens[b, :-1], cfg, mtp=True)
        # float32 both sides; the order of the sums differs
        assert float(jnp.abs(main[b] - a).max()) < 5e-5
        assert float(jnp.abs(mtp[b] - c).max()) < 5e-5
    alone = forward(params, tokens[:, :-2], cfg, False)
    assert float(jnp.abs(alone - main).max()) < 1e-6


def test_both_losses_and_every_leaf_gradient_match_the_reference(tiny):
    cfg, params, tokens = tiny
    got = _models.jitted(M.loss_parts)(params, tokens, cfg)
    want = _models.jitted(MR.loss_parts)(params, tokens, cfg)
    for a, b in zip(got, want):
        assert abs(float(a) - float(b)) < 2e-6 * float(b)
    loss, grads = _models.jitted(M.loss_fn, jax.value_and_grad)(
        params, tokens, cfg)
    ref_loss, ref_grads = _models.jitted(MR.loss_and_grads)(
        params, tokens, cfg)
    assert abs(float(loss) - float(ref_loss)) < 2e-6 * float(ref_loss)
    assert abs(float(loss) - float(got[0] + cfg.mtp_lambda * got[1])) < 1e-6
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:          # selects only: no gradient
            assert not g.any() and not r.any(), name
            continue
        assert float(jnp.linalg.norm(r)) > 0, name
        # float32 on both sides: a leaf's gradient to 2e-5 of its norm
        assert _rel(g, r) < 2e-5, (name, _rel(g, r))


def test_without_the_mtp_module_rows_are_one_id_shorter(tiny):
    _, _, tokens = tiny
    cfg = _cfg(mtp=None)
    params = TINY.weights(mtp=None)
    assert "mtp" not in params
    main, mtp = _models.jitted(M.loss_parts)(params, tokens[:, :-1], cfg)
    want = _models.jitted(MR.loss_parts)(params, tokens[:, :-1], cfg)
    assert abs(float(main) - float(want[0])) < 2e-6 * float(want[0])
    assert float(mtp) == 0.0 == float(want[1])


def test_bf16_training_step_runs_and_moves_the_weights():
    import optax
    cfg = _cfg(dtype=jnp.bfloat16)
    step, init = M.make_optax_train_step(cfg, optax.adamw(1e-3))
    params = M.init_params(jax.random.key(0), cfg)
    before = jax.tree_util.tree_map(jnp.copy, params)
    tokens = jax.random.randint(jax.random.key(1), (1, 50), 0, cfg.vocab)
    state = init(params)
    for _ in range(2):
        params, state, loss = step(params, state, tokens)
    loss = np.asarray(loss)
    assert loss.shape == (3,) and np.isfinite(loss).all()
    assert abs(loss[0] - (loss[1] + cfg.mtp_lambda * loss[2])) < 1e-5
    moved = [k for k in ("wqa", "wkvb", "ew1", "ew2", "sw1", "router")
             if float(jnp.abs(params["layers"][1][k].astype(jnp.float32)
                              - before["layers"][1][k].astype(jnp.float32)
                              ).max()) > 0]
    assert moved == ["wqa", "wkvb", "ew1", "ew2", "sw1", "router"]
    assert not params["layers"][1]["router_bias"].any()
    assert float(jnp.abs(params["mtp"]["eh_proj"].astype(jnp.float32)
                         - before["mtp"]["eh_proj"].astype(jnp.float32)
                         ).max()) > 0


def test_published_layout_and_config_validation():
    layout = M.published_layers(47)
    assert layout[0] == (0, "dense") and layout[1] == (1, "moe")
    assert sum(k == "moe" for _, k in layout) == 46
    with pytest.raises(ValueError):
        M.Config(n_experts=16, held=(12, 8))
    with pytest.raises(ValueError):
        M.Config(rope=7)
    with pytest.raises(ValueError):
        M.Config(layers=((0, "mamba"),))
    assert _cfg() == _cfg() and hash(_cfg()) == hash(_cfg())
    assert _cfg() != _cfg(held=(0, 4))


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------


def test_rope_is_a_complex_rotation_of_column_pairs():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 33, 3, 8)).astype(np.float32)
    got = np.asarray(M.rope(jnp.asarray(x), 1e6))
    z = x[..., 0::2].astype(np.float64) + 1j * x[..., 1::2]
    ang = (np.arange(33, dtype=np.float64)[:, None, None]
           * 1e6 ** (-np.arange(0, 8, 2) / 8.0))
    want = z * np.exp(1j * ang)
    assert np.abs(got[..., 0::2] - want.real).max() < 1e-5
    assert np.abs(got[..., 1::2] - want.imag).max() < 1e-5
    # position 0 stays; the turn depends on the distance alone
    assert np.array_equal(got[:, 0], x[:, 0])
    q, k = x[0, :, 0], x[0, :, 1]
    rq, rk = got[0, :, 0], got[0, :, 1]
    shifted = np.asarray(M.rope(jnp.asarray(
        np.concatenate([np.zeros((1, 5, 3, 8), np.float32), x[:1]], 1)),
        1e6))[0, 5:]
    assert np.allclose(rq[7] @ rk[3], shifted[7, 0] @ shifted[3, 1],
                       atol=1e-4)
    assert q.shape == rq.shape


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def _layer_parts(seed=0, T=40, D=16, F=24, n=16, skew=None):
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((T, D)).astype(np.float32)
    router = rng.standard_normal((D, n)).astype(np.float32)
    if skew is not None:
        # positive tokens, and a large positive column for each favoured
        # expert: its score saturates, every other expert's stays near 1/2
        u, router = np.abs(u), router * 0.01
        router[:, skew] = 10.0
    p = {"router": router, "router_bias": np.zeros(n, np.float32),
         "ew1": (rng.standard_normal((n, D, 2 * F)) / 4).astype(np.float32),
         "ew2": (rng.standard_normal((n, F, D)) / 5).astype(np.float32),
         "sw1": (rng.standard_normal((D, 2 * F)) / 4).astype(np.float32),
         "sw2": (rng.standard_normal((F, D)) / 5).astype(np.float32)}
    return u, p


def _held(u, p, held, k=4):
    first, count = held
    return E.held_experts_ffn(
        u, p["router"], p["router_bias"], p["ew1"][first:first + count],
        p["ew2"][first:first + count], held=held, k=k, scale=1.8)


def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer():
    u, p = _layer_parts()
    whole = M.Config(**{**DIMS, "dim": 16, "moe_ffn": 24}, held=(0, 16))
    want = MR.expert_layer(jnp.asarray(u), jax.tree_util.tree_map(
        jnp.asarray, p), whole)
    total = MR._gated(jnp.asarray(u), p["sw1"], p["sw2"])
    for first in range(0, 16, 2):
        total = total + _held(u, p, (first, 2))
    assert float(jnp.abs(total - want).max()) < 1e-5
    # and a share of the program is the share of the reference
    cut = M.Config(**{**DIMS, "dim": 16, "moe_ffn": 24}, held=(6, 2))
    share = dict(p, ew1=p["ew1"][6:8], ew2=p["ew2"][6:8])
    assert float(jnp.abs(
        _held(u, p, (6, 2)) - MR.expert_layer(jnp.asarray(u), share, cut,
                                              shared=False)).max()) < 1e-5


@pytest.mark.parametrize("held,rows", [((0, 4), 160), ((8, 4), 0)],
                         ids=["every_slot_held", "no_slot_held"])
def test_no_token_is_dropped_at_any_imbalance(held, rows):
    # a router that sends every token's four slots to experts 0..3
    u, p = _layer_parts(skew=[0, 1, 2, 3])
    idx, w = E.route_sigmoid_topk(jnp.asarray(u), p["router"],
                                  p["router_bias"], 4, 1.8)
    assert sorted(np.unique(np.asarray(idx))) == [0, 1, 2, 3]
    assert np.allclose(np.asarray(w).sum(-1), 1.8, atol=1e-5)
    lay = E.held_layout(idx, held, 16)
    assert int(lay["sizes"].sum()) == rows == int(lay["row_ok"].sum())
    assert int(lay["slot_ok"].sum()) == rows
    got = _held(u, p, held)
    if not rows:
        assert not np.asarray(got).any()
        return
    # every token's four experts are held: the whole weighted sum is here
    cfg = M.Config(**{**DIMS, "dim": 16, "moe_ffn": 24}, held=(0, 16))
    want = MR.expert_layer(jnp.asarray(u), jax.tree_util.tree_map(
        jnp.asarray, p), cfg, shared=False)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # the layout is a partial permutation: each held slot has its own row
    rows_of = np.asarray(lay["slot_row"])[np.asarray(lay["slot_ok"])]
    assert len(set(rows_of.tolist())) == rows
    back = np.asarray(lay["row_slot"])[np.asarray(lay["row_ok"])]
    assert sorted(back.tolist()) == sorted(
        np.flatnonzero(np.asarray(lay["slot_ok"]).reshape(-1)).tolist())


def test_expert_layer_gradients_match_the_dense_form():
    u, p = _layer_parts(seed=3)
    cfg = M.Config(**{**DIMS, "dim": 16, "moe_ffn": 24}, held=(4, 4))
    ct = np.random.default_rng(1).standard_normal(u.shape).astype(np.float32)
    share = dict(p, ew1=p["ew1"][4:8], ew2=p["ew2"][4:8])

    def prog(u, q):
        return jnp.sum(E.held_experts_ffn(
            u, q["router"], q["router_bias"], q["ew1"], q["ew2"],
            held=(4, 4), k=4, scale=1.8) * ct)

    def dense(u, q):
        return jnp.sum(MR.expert_layer(u, q, cfg, shared=False) * ct)

    got = jax.jit(jax.grad(prog, argnums=(0, 1)))(jnp.asarray(u), share)
    want = jax.jit(jax.grad(dense, argnums=(0, 1)))(jnp.asarray(u), share)
    assert _rel(got[0], want[0]) < 1e-5 and float(
        jnp.linalg.norm(want[0])) > 0
    for k in ("router", "ew1", "ew2"):
        assert float(jnp.linalg.norm(want[1][k])) > 0, k
        assert _rel(got[1][k], want[1][k]) < 1e-5, k
    assert not np.asarray(got[1]["router_bias"]).any()


def test_recomputed_expert_layer_keeps_its_products_with_their_routing(
        tiny, capsys):
    # the FFN half of an expert layer, computed again in the backward,
    # keeps the two grouped products (their time follows the held rows)
    # and, with them, the choice of experts they were computed under: on
    # the chip a choice made again turned near-ties, and the kept rows
    # were read under a layout they were not written by (PERF.md, PR 35)
    import functools
    cfg, params, tokens = tiny
    T, k = tokens.shape[0] * 48, cfg.top_k
    h = jax.random.normal(jax.random.key(5), (tokens.shape[0], 48, cfg.dim))
    ffn = jax.checkpoint(
        functools.partial(M._ffn_half, kind="moe", cfg=cfg), policy=M._KEEP)
    jax.ad_checkpoint.print_saved_residuals(ffn, h, params["layers"][1])
    kept = [ln for ln in capsys.readouterr().out.splitlines()
            if "from the argument" not in ln]
    shapes = sorted(ln.split()[0] for ln in kept)
    assert shapes == sorted([
        f"i32[{T},{k}]",                                    # route_idx
        f"f32[{T * k},{2 * cfg.moe_ffn}]",                  # experts_up
        f"f32[{T * k},{cfg.dim}]",                          # experts_down
        f"f32[{tokens.shape[0]},48,{2 * cfg.moe_ffn}]",     # ffn_up
    ]), kept
    assert any("named 'route_idx'" in ln for ln in kept), kept


def test_routing_stats_agree_with_the_reference_routing(tiny):
    cfg, params, tokens = tiny
    stats = M.routing_stats(params, tokens[:1, :-1], cfg)
    assert len(stats) == 3                     # two expert layers and MTP
    for s in stats:
        counts = np.asarray(s["counts"])
        assert counts.sum() == 48 * cfg.top_k
        lo, n = cfg.held
        assert int(s["held_rows"]) == counts[lo:lo + n].sum()
        assert s["chosen"].shape == (48, cfg.top_k)
    # layer 1's chosen experts by the reference's arithmetic
    p32 = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
    x = p32["embed"][tokens[0, :-2]]
    x = MR._layer(x, p32["layers"][0], "dense", cfg)
    p1 = p32["layers"][1]
    h = x + MR._mla(MR._rms(x, p1["ln1"], cfg.eps), p1, cfg)
    idx, _ = MR.chosen_experts(MR._rms(h, p1["ln2"], cfg.eps), p1, cfg)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.asarray(stats[0]["chosen"]), -1))


# ---------------------------------------------------------------------------
# the grouped products over the buffer, at chosen loads
# ---------------------------------------------------------------------------

# how many of 40 tokens send a slot to each of the four held experts
LOADS = {"uneven": [5, 0, 17, 8], "first_empty": [0, 3, 9, 1],
         "all_empty": [0, 0, 0, 0], "one_full": [0, 0, 40, 0],
         "even": [8, 8, 8, 8], "every_slot": [40, 40, 40, 40]}


def _chosen(load, held=(4, 4), T=40, k=4, n=16, seed=0):
    """(idx, w): the first ``load[j]`` tokens choose held expert ``j``; a
    token's other slots go to experts that are not held."""
    rng = np.random.default_rng(seed)
    idx = np.zeros((T, k), np.int32)
    others = [e for e in range(n) if not held[0] <= e < held[0] + held[1]]
    for t in range(T):
        mine = [held[0] + j for j, c in enumerate(load) if t < c]
        idx[t] = (mine + list(rng.permutation(others)))[:k]
        rng.shuffle(idx[t])
    w = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(w)


def _dense_apply(u, idx, w, w1, w2, held):
    y = jnp.zeros_like(u)
    for j in range(held[1]):
        w_e = jnp.sum(jnp.where(idx == held[0] + j, w, 0.0), axis=-1)
        y = y + w_e[:, None] * MR._gated(u, w1[j], w2[j])
    return y


@pytest.mark.parametrize("case", list(LOADS))
def test_grouped_products_match_jnp_on_uneven_and_empty_groups(case):
    u, p = _layer_parts(seed=2)
    idx, w = _chosen(LOADS[case])
    w1, w2 = jnp.asarray(p["ew1"][4:8]), jnp.asarray(p["ew2"][4:8])
    lay = E.held_layout(idx, (4, 4))
    assert lay["sizes"].tolist() == LOADS[case]
    assert int(lay["row_ok"].sum()) == sum(LOADS[case])
    got = E.held_experts_apply(jnp.asarray(u), idx, w, w1, w2, held=(4, 4))
    want = _dense_apply(jnp.asarray(u), idx, w, w1, w2, (4, 4))
    assert float(jnp.abs(got - want).max()) < 1e-5
    if not sum(LOADS[case]):
        assert not np.asarray(got).any()


@pytest.mark.parametrize("case", list(LOADS))
def test_grouped_products_dx_and_dw_match_jnp(case):
    u, p = _layer_parts(seed=3)
    idx, w = _chosen(LOADS[case], seed=1)
    ct = np.random.default_rng(2).standard_normal(u.shape).astype(np.float32)
    args = (jnp.asarray(u), w, jnp.asarray(p["ew1"][4:8]),
            jnp.asarray(p["ew2"][4:8]))
    got = jax.grad(lambda u, w, a, b: jnp.sum(E.held_experts_apply(
        u, idx, w, a, b, held=(4, 4)) * ct), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda u, w, a, b: jnp.sum(_dense_apply(
        u, idx, w, a, b, (4, 4)) * ct), argnums=(0, 1, 2, 3))(*args)
    for g, r in zip(got, want):
        assert float(jnp.abs(g - r).max()) < 2e-5
    for j, n in enumerate(LOADS[case]):
        if n == 0:                      # an empty group's dW is zeros
            assert not np.asarray(got[2][j]).any()
            assert not np.asarray(got[3][j]).any()


def test_layout_gauge_says_row_bound_groups_and_expected_rows():
    tm.reset()
    idx, _ = _chosen(LOADS["uneven"])
    lay = E.held_layout(idx, (4, 4), 16)
    assert lay["slot_row"].shape == (40, 4) and lay["row_slot"].shape == (160,)
    gauges = {k: v for k, v in tm.report()["gauges"].items()
              if k.startswith("moe.held_experts.plan")}
    plan = {k.split("what=")[1].split(",")[0].rstrip("}"): v
            for k, v in gauges.items()}
    assert plan == {"rows": 160, "groups": 4, "rows_expected": 40}


# ---------------------------------------------------------------------------
# the flash kernels at the latent heads' width
# ---------------------------------------------------------------------------


def _dense_attention(q, k, v):
    S, _, D = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.float32(np.sqrt(D))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_at_width_256_matches_dense_attention(direction):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((256, 2, 256)), jnp.float32)
               for _ in range(3))
    ct = jnp.asarray(rng.standard_normal((256, 2, 256)), jnp.float32)
    got, want = _models.against(
        "flash_256", (lambda *a: flash_attention(*a, causal=True),
                      _dense_attention), (q, k, v), ct)
    if direction == "forward":
        assert float(jnp.abs(got[0] - want[0]).max()) < 2e-5
        return
    for g, r in zip(got[1:], want[1:]):
        assert _rel(g, r) < 2e-5


# ---------------------------------------------------------------------------
# what importing the package loads
# ---------------------------------------------------------------------------


def test_importing_the_package_or_a_sibling_model_loads_nothing_new():
    code = textwrap.dedent("""
        import sys
        import distributedarrays_tpu
        import distributedarrays_tpu.ops
        new = ("distributedarrays_tpu.models.mla_moe",
               "distributedarrays_tpu.models.mla_moe_reference",
               "distributedarrays_tpu.models.moe")
        for name in new + ("jax.experimental.pallas",):
            assert name not in sys.modules, name
        import distributedarrays_tpu.models.transformer
        import distributedarrays_tpu.models.sambay
        for name in new:
            assert name not in sys.modules, name
        print("clean")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True,
                         env={**__import__("os").environ,
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]
