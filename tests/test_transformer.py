"""Flagship transformer tests: flash-kernel attention with custom-VJP
gradients, Megatron tp layout, dp×tp training."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributedarrays_tpu.models import transformer as T
from distributedarrays_tpu.parallel.collectives import shard_map_compat
from distributedarrays_tpu.models.mlp import make_mesh
from distributedarrays_tpu.ops.pallas_attention import (_dense_attention_shd,
                                                        flash_attention)


def _axes(x):
    """Normalized sharding spec (XLA may drop trailing Nones)."""
    s = tuple(x.sharding.spec)
    return s + (None,) * (x.ndim - len(s))


def test_flash_custom_vjp_exact(rng):
    # gradients through the kernel == gradients of the dense formulation
    S, H, D = 64, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
               for _ in range(3))

    def via_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=32, block_k=32) ** 2)

    def via_dense(q, k, v):
        return jnp.sum(_dense_attention_shd(q, k, v, True,
                                            float(1 / np.sqrt(D))) ** 2)

    gf = jax.grad(via_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(via_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert float(jnp.abs(a - b).max()) < 1e-5


@pytest.fixture(scope="module")
def trained():
    cfg = T.Config(vocab=32, dim=64, heads=4, layers=2, max_seq=32)
    mesh = make_mesh(8)
    params = T.shard_params(T.init_params(jax.random.key(0), cfg), mesh)
    start = jax.random.randint(jax.random.key(1), (16, 1), 0, 32)
    tokens = ((start + jnp.arange(32)[None]) % 32).astype(jnp.int32)
    tokens = jax.device_put(
        tokens, jax.NamedSharding(mesh, P("dp", None)))
    losses = []
    for _ in range(60):
        params, loss = T.train_step(params, tokens, jnp.float32(0.05), cfg)
        losses.append(float(loss))
    return cfg, mesh, params, tokens, losses


@pytest.mark.slow
def test_transformer_learns_counting(trained):
    cfg, mesh, params, tokens, losses = trained
    assert losses[-1] < 0.3 * losses[0], losses[::10]


@pytest.mark.slow
def test_transformer_predictions(trained):
    # after training, argmax next-token should mostly be (t+1) % vocab
    cfg, mesh, params, tokens, _ = trained
    logits = T.forward(params, tokens[:, :-1], cfg)
    pred = np.asarray(jnp.argmax(logits, axis=-1))
    want = np.asarray(tokens[:, 1:])
    acc = (pred == want).mean()
    assert acc > 0.8, acc


@pytest.mark.slow
def test_transformer_sharding_layout(trained):
    cfg, mesh, params, _, _ = trained
    b = params["blocks"][0]

    assert _axes(b["qkv"]) == (None, "tp")      # column-parallel
    assert _axes(b["proj"]) == ("tp", None)     # row-parallel
    assert _axes(b["w1"]) == (None, "tp")
    assert _axes(b["w2"]) == ("tp", None)


def test_config_validation():
    with pytest.raises(ValueError, match="divisible"):
        T.Config(dim=65, heads=4)
    # value-hashable: equal configs share one jit compilation key
    assert T.Config() == T.Config()
    assert hash(T.Config()) == hash(T.Config())


# ---------------------------------------------------------------------------
# round-3: sequence-parallel transformer (models/sp_transformer.py) — ring
# flash attention + tp_ffn composed into one shard_map training program
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sp_setup():
    from distributedarrays_tpu.models import sp_transformer as SPT
    from distributedarrays_tpu.parallel import collectives as C
    p = 4
    mesh = C.spmd_mesh(p)
    cfg = SPT.SPConfig(vocab=64, dim=32, heads=4, layers=2, max_seq=32,
                       dtype=jnp.float32, block_q=8, block_k=8,
                       interpret=True)
    params = SPT.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab,
                                dtype=jnp.int32)
    return SPT, C, p, mesh, cfg, params, tokens


def _sp_dense_forward(cfg, params, tokens):
    """Dense single-device oracle for the sp forward."""
    B, S = tokens.shape
    E, H = cfg.dim, cfg.heads
    D = E // H
    x = params["embed"][tokens] + params["pos"][:S][None]
    for blk in params["blocks"]:
        h = T.norm(x, blk["ln1"])
        q, k, v = jnp.split(h @ blk["qkv"], 3, axis=-1)

        def heads_(t):
            return jnp.transpose(t.reshape(B, S, H, D), (0, 2, 1, 3))

        s = jnp.einsum("bhqd,bhkd->bhqk", heads_(q), heads_(k)) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None],
                      s, -jnp.inf)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), heads_(v))
        x = x + jnp.transpose(o, (0, 2, 1, 3)).reshape(B, S, E) @ blk["proj"]
        h2 = T.norm(x, blk["ln2"])
        x = x + jax.nn.gelu(h2 @ blk["w1"]) @ blk["w2"]
    return (T.norm(x, params["ln_f"]) @ params["head"]).astype(
        jnp.float32)


def test_sp_transformer_forward_matches_dense(sp_setup):
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    fwd = jax.jit(shard_map_compat(
        lambda pr, t: SPT.forward_local(pr, t, cfg, "p"),
        mesh=mesh, in_specs=(SPT.param_specs(cfg, "p"), P(None, "p")),
        out_specs=P(None, "p"), check=False))
    got = np.asarray(fwd(params, tokens))
    want = np.asarray(_sp_dense_forward(cfg, params, tokens))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4


def test_sp_transformer_loss_matches_dense_ce(sp_setup):
    # the cross-rank target shift + end mask must equal the dense
    # next-token CE (which simply drops the final position)
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    # dense CE first: the train step DONATES params (buffers are gone after)
    logp = jax.nn.log_softmax(_sp_dense_forward(cfg, params, tokens), -1)
    ll = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)
    want = float(-jnp.mean(ll))
    step = SPT.make_train_step(mesh, cfg)
    params = jax.tree_util.tree_map(jnp.copy, params)  # keep fixture alive
    _, loss = step(params, tokens, jnp.float32(0.0))
    assert abs(float(loss) - want) / want < 1e-4


@pytest.mark.slow
def test_sp_transformer_trains(sp_setup):
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    step = SPT.make_train_step(mesh, cfg)
    params = SPT.init_params(jax.random.key(2), cfg)
    losses = []
    for _ in range(8):
        params, l = step(params, tokens, jnp.float32(0.5))
        losses.append(float(l))
    assert losses[-1] < 0.7 * losses[0], losses
    assert all(np.isfinite(l) for l in losses)


def test_sp_transformer_picks_up_later_banked_tune(sp_setup, monkeypatch):
    # the train-step factories must resolve None hop knobs OUTSIDE their
    # cached jits (ADVICE round-4): a tune banked AFTER the first step
    # call must change the dispatched program, not be pinned at first
    # trace.  Resolution is spied at _resolve_cfg's registry consumer.
    from distributedarrays_tpu.utils import autotune
    from distributedarrays_tpu.models import ring_attention as RA
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    autotune.clear()
    tcfg = SPT.SPConfig(vocab=64, dim=32, heads=4, layers=2, max_seq=32,
                        dtype=jnp.float32, interpret=True)  # knobs None
    step = SPT.make_train_step(mesh, tcfg)
    prm = SPT.init_params(jax.random.key(3), tcfg)
    seen = []
    real = RA.tuned_hop_blocks_for

    def spy(shape, dtype, causal, bq, bk):
        out = real(shape, dtype, causal, bq, bk)
        seen.append(out)
        return out

    monkeypatch.setattr(RA, "tuned_hop_blocks_for", spy)
    prm, l0 = step(prm, tokens, jnp.float32(0.1))
    assert seen and seen[-1][:2] == (512, 512)   # default, nothing banked
    # bank a tune for the per-rank hop shape this model sees:
    # (s_loc, b*heads, head_dim) under causal=True
    B, S = tokens.shape
    key = autotune.device_key_for(S // p, B * tcfg.heads,
                                  tcfg.dim // tcfg.heads,
                                  jnp.dtype(tcfg.dtype), True)
    autotune.record("ring_flash", key, (4, 4))
    seen.clear()
    prm, l1 = step(prm, tokens, jnp.float32(0.1))
    assert seen and seen[-1][:2] == (4, 4), \
        "a tune banked after step 1 must reach the next step's dispatch"
    assert np.isfinite(float(l1))
    autotune.clear()


def test_sp_transformer_max_seq_guard(sp_setup):
    # position reads past the table would CLAMP silently; must raise
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    small = SPT.SPConfig(vocab=64, dim=32, heads=4, layers=2, max_seq=16,
                         dtype=jnp.float32, block_q=8, block_k=8,
                         interpret=True)
    sp = SPT.init_params(jax.random.key(0), small)
    with pytest.raises(ValueError, match="max_seq"):
        shard_map_compat(
            lambda pr, t: SPT.forward_local(pr, t, small, "p"),
            mesh=mesh, in_specs=(SPT.param_specs(small, "p"), P(None, "p")),
            out_specs=P(None, "p"), check=False)(sp, tokens)


def test_sp_transformer_zigzag_matches_dense(sp_setup):
    # load-balanced layout: tokens permuted by zigzag_order; logits
    # unpermute back to natural order and must match the dense oracle,
    # and the zigzag-aware CE shift must equal the dense next-token CE
    from distributedarrays_tpu.models.ring_attention import zigzag_order
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    zcfg = SPT.SPConfig(vocab=64, dim=32, heads=4, layers=2, max_seq=32,
                        dtype=jnp.float32, block_q=8, block_k=8,
                        interpret=True, zigzag=True)
    perm = np.asarray(zigzag_order(32, p))
    zz_tokens = jnp.asarray(np.asarray(tokens)[:, perm])
    fwd = jax.jit(shard_map_compat(
        lambda pr, t: SPT.forward_local(pr, t, zcfg, "p"),
        mesh=mesh, in_specs=(SPT.param_specs(zcfg, "p"), P(None, "p")),
        out_specs=P(None, "p"), check=False))
    got = np.asarray(fwd(params, zz_tokens))[:, np.argsort(perm)]
    want = np.asarray(_sp_dense_forward(zcfg, params, tokens))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4

    logp = jax.nn.log_softmax(jnp.asarray(want), -1)
    ll = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)
    want_loss = float(-jnp.mean(ll))
    step = SPT.make_train_step(mesh, zcfg)
    pc = jax.tree_util.tree_map(jnp.copy, params)
    _, loss = step(pc, zz_tokens, jnp.float32(0.0))
    assert abs(float(loss) - want_loss) / want_loss < 1e-4


@pytest.mark.slow
def test_sp_transformer_zigzag_trains(sp_setup):
    from distributedarrays_tpu.models.ring_attention import zigzag_order
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    zcfg = SPT.SPConfig(vocab=64, dim=32, heads=4, layers=2, max_seq=32,
                        dtype=jnp.float32, block_q=4, block_k=4,
                        interpret=True, zigzag=True)
    perm = np.asarray(zigzag_order(32, p))
    zz_tokens = jnp.asarray(np.asarray(tokens)[:, perm])
    step = SPT.make_train_step(mesh, zcfg)
    prm = SPT.init_params(jax.random.key(3), zcfg)
    losses = []
    for _ in range(8):
        prm, l = step(prm, zz_tokens, jnp.float32(0.5))
        losses.append(float(l))
    assert losses[-1] < 0.7 * losses[0], losses
    assert all(np.isfinite(v) for v in losses)


@pytest.mark.slow
def test_sp_transformer_checkpoint_roundtrip(sp_setup, tmp_path):
    # training state (incl. the tp-sharded FFN weights produced by the
    # donated train step) must survive save/load and continue identically
    from distributedarrays_tpu.utils import load, save
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    step = SPT.make_train_step(mesh, cfg)
    prm = SPT.init_params(jax.random.key(4), cfg)
    for _ in range(2):
        prm, _ = step(prm, tokens, jnp.float32(0.1))
    save(tmp_path / "sp_ckpt", {"params": prm})
    back = load(tmp_path / "sp_ckpt")["params"]
    prm_l, loss_cont = step(jax.tree_util.tree_map(jnp.copy, prm),
                            tokens, jnp.float32(0.1))
    _, loss_restored = step(back, tokens, jnp.float32(0.1))
    assert float(loss_cont) == pytest.approx(float(loss_restored),
                                             rel=1e-6)


def test_sp_transformer_update_matches_dense_sgd(sp_setup):
    # one train step == dense value_and_grad SGD step, and every
    # REPLICATED param's device copies stay bit-identical after the
    # update (regression: check=False means the train step must
    # psum replicated-param grads itself; without it the copies diverge
    # and shard 0 hides it)
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    lr = 0.1

    def dense_loss(pp):
        logp = jax.nn.log_softmax(_sp_dense_forward(cfg, pp, tokens), -1)
        ll = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)
        return -jnp.mean(ll)

    g = jax.grad(dense_loss)(params)
    want = jax.tree_util.tree_map(lambda a, b: a - lr * b, params, g)

    step = SPT.make_train_step(mesh, cfg)
    got, _ = step(jax.tree_util.tree_map(jnp.copy, params), tokens,
                  jnp.float32(lr))
    for (k, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_flatten_with_path(want)[0]):
        scale = max(float(jnp.abs(b).max()), 1e-6)
        err = float(jnp.abs(a - b).max()) / scale
        assert err < 1e-4, (jax.tree_util.keystr(k), err)
    # replicated leaves: all device copies agree bit-exactly
    for k, a in jax.tree_util.tree_flatten_with_path(got)[0]:
        spec = tuple(a.sharding.spec) if hasattr(a.sharding, "spec") else ()
        if all(s is None for s in spec):
            vals = [np.asarray(s.data) for s in a.addressable_shards]
            for v in vals[1:]:
                np.testing.assert_array_equal(vals[0], v,
                                              err_msg=jax.tree_util.keystr(k))


@pytest.mark.slow
def test_sp_transformer_optax_adamw(sp_setup):
    # real-optimizer training path: grads from the shard_map program,
    # Adam moments laid out by GSPMD to match each param (sharded FFN
    # moments stay sharded)
    optax = pytest.importorskip("optax")
    SPT, C, p, mesh, cfg, params, tokens = sp_setup
    tx = optax.adamw(3e-3)
    step, init = SPT.make_optax_train_step(mesh, cfg, tx)
    prm = SPT.init_params(jax.random.key(5), cfg)
    state = init(prm)
    losses = []
    for _ in range(10):
        prm, state, l = step(prm, state, tokens)
        losses.append(float(l))
    assert losses[-1] < 0.8 * losses[0], losses
    assert all(np.isfinite(v) for v in losses)
    # Adam mu for the column-sharded w1 must be sharded like w1 (and f32)
    mu_w1 = state[0].mu["blocks"][0]["w1"]
    assert _axes(mu_w1) == _axes(prm["blocks"][0]["w1"])
    assert mu_w1.dtype == jnp.float32


@pytest.mark.slow
def test_transformer_optax_adamw_sharded_moments():
    # GSPMD flagship with a real optimizer at the DEFAULT bf16 dtype:
    # the fp32 master-precision path must keep Adam-scale updates from
    # rounding away in bf16, moments must inherit the Megatron tp
    # sharding of their params, and training must converge
    optax = pytest.importorskip("optax")
    cfg = T.Config(vocab=32, dim=64, heads=4, layers=2, max_seq=32)
    assert cfg.dtype == jnp.bfloat16
    mesh = make_mesh(8)
    params = T.shard_params(T.init_params(jax.random.key(0), cfg), mesh)
    start = jax.random.randint(jax.random.key(1), (8, 1), 0, 32)
    tokens = ((start + jnp.arange(32)[None]) % 32).astype(jnp.int32)
    tokens = jax.device_put(tokens, jax.NamedSharding(mesh, P("dp", None)))
    tx = optax.adamw(3e-3)
    step, init = T.make_optax_train_step(cfg, tx)
    state = init(params)
    losses = []
    for _ in range(10):
        params, state, loss = step(params, state, tokens)
        losses.append(float(loss))
    assert losses[-1] < 0.8 * losses[0], losses
    assert params["blocks"][0]["w1"].dtype == jnp.bfloat16
    mu_w1 = state[0].mu["blocks"][0]["w1"]
    assert mu_w1.dtype == jnp.float32
    assert _axes(mu_w1) == _axes(params["blocks"][0]["w1"]) == (None, "tp")


# ---------------------------------------------------------------------------
# round-4: KV-cache autoregressive generation
# ---------------------------------------------------------------------------


def test_generate_greedy_matches_forward():
    # greedy decode must be self-consistent with the full forward: for
    # every generated position, forward(seq)'s argmax at t equals seq[t+1]
    cfg = T.Config(vocab=64, dim=32, heads=4, layers=2, max_seq=48,
                   dtype=jnp.float32)
    params = T.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab,
                                dtype=jnp.int32)
    seq = T.generate(params, prompt, 12, cfg)
    assert seq.shape == (2, 20)
    np.testing.assert_array_equal(np.asarray(seq[:, :8]),
                                  np.asarray(prompt))
    logits = T.forward(params, seq, cfg)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    for t in range(7, 19):        # generated region
        np.testing.assert_array_equal(np.asarray(seq[:, t + 1]),
                                      greedy[:, t], err_msg=str(t))


def test_generate_sampling_and_validation():
    cfg = T.Config(vocab=32, dim=16, heads=2, layers=1, max_seq=16,
                   dtype=jnp.float32)
    params = T.init_params(jax.random.key(2), cfg)
    prompt = jnp.zeros((1, 4), jnp.int32)
    s1 = T.generate(params, prompt, 8, cfg, temperature=1.0,
                    key=jax.random.key(3))
    s2 = T.generate(params, prompt, 8, cfg, temperature=1.0,
                    key=jax.random.key(4))
    assert s1.shape == s2.shape == (1, 12)
    assert (np.asarray(s1) != np.asarray(s2)).any()   # different keys
    with pytest.raises(ValueError, match="max_seq"):
        T.generate(params, prompt, 100, cfg)
    with pytest.raises(ValueError, match="PRNG"):
        T.generate(params, prompt, 4, cfg, temperature=0.5)
