"""The SambaY model (``models/sambay.py``), its scan kernel and the flash
kernels' window / grouped / wide-value calls, against plain references at
tiny sizes on the CPU (kernels in interpret mode)."""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _models
from distributedarrays_tpu.models import sambay as M
from distributedarrays_tpu.ops.pallas_attention import (
    _count_steps, flash_attention)
from distributedarrays_tpu.ops.pallas_selective_scan import (
    selective_scan, selective_scan_plan)

REPO = _models.REPO
CUT = _models.sambay_cut()
TWELVE = CUT + tuple((i + 10, k) for i, k in CUT)    # every kind twice
DIMS = _models.SAMBAY_DIMS
TINY = _models.sambay
_config, _weights, _tokens = TINY.config, TINY.weights, TINY.tokens


# ---------------------------------------------------------------------------
# the model against the benchmark's reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layers", [CUT, TWELVE], ids=["cut6", "twelve"])
def test_loss_and_every_leaf_gradient_match_refs_sambay(layers):
    import refs_sambay as R
    cfg, params = _config(layers), _weights(layers)
    tok = _tokens(batch=2 if layers is CUT else 1, seq=40)
    loss, g = _models.jitted(M.loss_fn, jax.value_and_grad)(params, tok, cfg)
    with jax.default_matmul_precision("highest"):
        loss0, g0 = R.ref_loss_and_grads(
            params, tok, dict(DIMS, eps=1e-5, layers=layers))
    assert abs(float(loss) - loss0) < 2e-5 * abs(loss0)
    worst = _models.worst_leaf(R, g, g0, params)
    assert worst[0] < 5e-4, worst
    # a key bias has no gradient (a shift of a row's scores): both read ~0
    want = R.leaf_norm_dict(g0)
    floor = 1e-3 * float(np.median(list(want.values())))
    assert all(want[k] < floor for k in want if k.endswith(".bk"))


def test_package_reference_agrees_with_the_program():
    loss, g = TINY.result("value_and_grad")
    loss0, g0 = TINY.result("value_and_grad", "reference")
    assert abs(float(loss) - float(loss0)) < 2e-5 * abs(float(loss0))
    worst = max((v, k) for k, v in _models.gaps(g, g0).items())
    assert worst[0] < 5e-4, worst
    assert np.allclose(TINY.result("forward"),
                       TINY.result("forward", "reference"), atol=2e-4)


def test_bf16_training_step_runs_and_moves_the_weights():
    import optax
    cfg = _config(CUT, jnp.bfloat16)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                    _weights(CUT))
    before = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    step, init = M.make_optax_train_step(cfg, optax.adamw(1e-3))
    params, state, loss = step(params, init(params), _tokens())
    assert np.isfinite(float(loss))
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))),
        params, before)
    assert moved["embed"] > 0 and moved["layers"][0]["in_proj"] > 0


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------


def test_published_layout_counts():
    kinds = [k for _, k in M.layer_kinds(32, 2)]
    assert [kinds.count(k) for k in M.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:20] == ["mamba", "full", "gmu", "cross"]
    assert kinds[18:] == ["gmu", "cross"] * 7


def test_cut_keeps_published_order_and_indices():
    assert CUT == ((14, "mamba"), (15, "window"), (16, "mamba"),
                   (17, "full"), (18, "gmu"), (19, "cross"))
    cfg = _config(CUT)
    assert cfg.layers == CUT
    assert M.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    # the benchmark's own rule gives the same layout
    from drivers.train_step_sambay import published_layout
    assert tuple(sorted(published_layout(32, 2).items())) == \
        M.layer_kinds(32, 2)
    with pytest.raises(ValueError, match="needs a mamba"):
        _config(((0, "gmu"),))
    with pytest.raises(ValueError, match="needs a full"):
        _config(((0, "mamba"), (1, "cross")))


def test_lambda_init_follows_the_published_index():
    # the same layer at another published index is another function
    cfg_a = _config(((1, "full"),))
    cfg_b = _config(((17, "full"),))
    params, tok = _weights(((1, "full"),)), _tokens()
    a, b = (float(_models.jitted(M.loss_fn)(params, tok, c))
            for c in (cfg_a, cfg_b))
    assert abs(a - b) > 1e-4


# ---------------------------------------------------------------------------
# what a layer publishes: its gradient is the sum over the readers
# ---------------------------------------------------------------------------

READERS = ((16, "mamba"), (17, "full"), (18, "gmu"), (19, "cross"),
           (20, "gmu"), (21, "cross"))


@pytest.mark.parametrize("what", ["m", "kv"])
def test_gradient_of_what_is_published_is_the_sum_over_readers(
        monkeypatch, what):
    cfg, params, tok = _config(READERS), _weights(READERS), _tokens()
    real_mamba, real_gmu, real_attn = M._mamba, M._gmu, M._attention
    tap_shape = (1, 32, 256) if what == "m" else (1, 32, 4, 16)

    def grad_through(readers):
        """d loss / d tap, the tap added to what is published, with only
        the readers named letting a gradient through."""
        def loss(tap):
            seen = []

            def reads(kind):
                seen.append(kind)
                return seen.count(kind) - 1 in readers

            def mamba(u, p, c):
                mix, m = real_mamba(u, p, c)
                return mix, (m + tap if what == "m" else m)

            def gmu(u, p, m_star, c):
                if what == "m" and not reads("gmu"):
                    m_star = jax.lax.stop_gradient(m_star)
                return real_gmu(u, p, m_star, c)

            def attn(u, p, index, c, kind, kv_star):
                if what == "kv" and kind == "cross" and not reads("cross"):
                    kv_star = jax.lax.stop_gradient(kv_star)
                mix, (k, v) = real_attn(u, p, index, c, kind, kv_star)
                if what == "kv" and kind == "full":
                    k = k + tap
                return mix, (k, v)

            monkeypatch.setattr(M, "_mamba", mamba)
            monkeypatch.setattr(M, "_gmu", gmu)
            monkeypatch.setattr(M, "_attention", attn)
            return M.loss_fn(params, tok, cfg)

        return jax.jit(jax.grad(loss))(jnp.zeros(tap_shape, jnp.float32))

    both, first, second = (grad_through(r) for r in ({0, 1}, {0}, {1}))
    norm = lambda t: float(jnp.linalg.norm(t))
    assert norm(first) > 0.05 * norm(both) and norm(second) > 0.05 * norm(both)
    assert norm(both - first - second) < 1e-4 * norm(both)
    # a reader removed changes it
    assert norm(both - first) > 0.05 * norm(both)


# ---------------------------------------------------------------------------
# the scan kernel against the sequential scan
# ---------------------------------------------------------------------------


def _scan_ref(x, dt, A, B, C):
    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt[:, None] * A) * h + (dtt * xt)[:, None] * bt[None, :]
        return h, h @ ct
    return jax.lax.scan(step, jnp.zeros(A.shape), (x, dt, B, C))[1]


def _scan_case(L=44, E=256, N=16):
    ks = jax.random.split(jax.random.key(0), 6)
    return (jax.random.normal(ks[0], (L, E)),
            jax.nn.softplus(jax.random.normal(ks[1], (L, E)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (E, N))),
            jax.random.normal(ks[3], (L, N)),
            jax.random.normal(ks[4], (L, N))), jax.random.normal(ks[5],
                                                                 (L, E))


@pytest.mark.parametrize("arg", ["forward", "x", "dt", "A", "B", "C"])
def test_scan_kernel_matches_the_sequential_scan(arg):
    # 44 positions in chunks of 16: three chunks, the last one padded
    args, w = _scan_case()
    kernel = lambda *a: selective_scan(*a, chunk=16, block_e=128)
    n = ["forward", "x", "dt", "A", "B", "C"].index(arg)
    got, want = (r[n] for r in _models.against(
        "selective_scan", (kernel, _scan_ref), args, w))
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))


def test_scan_plan_and_its_gauge():
    from distributedarrays_tpu import telemetry as tm
    plan = selective_scan_plan(8192, 5120, 16)
    assert plan["padded"] == 8192 and 5120 % plan["block_e"] == 0
    assert plan["checkpoint_bytes"] == plan["chunks"] * 16 * 5120 * 4
    assert selective_scan_plan(44, 256, 16, 16)["padded"] == 48
    with pytest.raises(ValueError):
        selective_scan_plan(64, 100, 16)
    args, _ = _scan_case(L=32, E=128)
    selective_scan(*args, chunk=16)
    read = lambda what: tm.gauge_value("pallas.selective_scan.plan", L=32,
                                       E=128, N=16, what=what)
    assert (read("chunk"), read("chunks"), read("block_e")) == (16, 2, 128)
    assert read("checkpoint_bytes") == 2 * 16 * 128 * 4


# ---------------------------------------------------------------------------
# flash attention: window, grouped heads, a wide value head, cross
# ---------------------------------------------------------------------------


def _dense(q, k, v, window):
    S, H, D = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    live = j <= i
    if window is not None:
        live = live & (j > i - window)
    return jnp.einsum("hqk,khd->qhd",
                      jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), -1),
                      v)


FLASH_CASES = {
    # S, H, Hk, Hv, D, Dv, window, block
    "window_off_the_tile": (256, 4, 4, 4, 32, 32, 100, None),
    "window_over_blocks": (256, 2, 2, 2, 32, 32, 100, 64),
    "window_with_whole_blocks": (512, 2, 2, 2, 32, 32, 300, 64),
    "window_beyond_the_sequence": (256, 4, 2, 1, 32, 64, 1000, None),
    "grouped": (256, 4, 2, 2, 32, 32, None, 64),
    "wide_value": (256, 2, 2, 2, 32, 64, None, 128),
    "grouped_wide_windowed": (512, 4, 2, 1, 32, 64, 200, 128),
    "window_of_one": (384, 2, 2, 2, 32, 32, 1, 128),
}


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_flash_variants_match_masked_dense_attention(case, direction):
    S, H, Hk, Hv, D, Dv, window, blk = FLASH_CASES[case]
    ks = jax.random.split(jax.random.key(S + H), 4)
    q = jax.random.normal(ks[0], (S, H, D))
    k = jax.random.normal(ks[1], (S, Hk, D))
    v = jax.random.normal(ks[2], (S, Hv, Dv))
    w = jax.random.normal(ks[3], (S, H, Dv))
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=blk, block_k=blk)
    dense = lambda q, k, v: _dense(q, k, v, window)
    ours, ref = _models.against(("flash", case), (flash, dense), (q, k, v), w)
    n = slice(0, 1) if direction == "forward" else slice(1, 4)
    for got, want in zip(ours[n], ref[n]):
        assert got.shape == want.shape
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_cross_attention_reads_another_layers_keys_and_values():
    # k, v made from other activations than q: gradients reach both sides
    S, H, D = 128, 4, 32
    ks = jax.random.split(jax.random.key(9), 3)
    x, y = jax.random.normal(ks[0], (S, 64)), jax.random.normal(ks[1], (S, 64))
    wq = jax.random.normal(ks[2], (64, H * D)) / 8
    wk = wq[:, :2 * D] * 0.5

    def f(attn, x, y):
        q = (x @ wq).reshape(S, H, D)
        k = (y @ wk).reshape(S, 2, D)
        v = (y[:, :64]).reshape(S, 1, 64)
        return jnp.sum(jnp.sin(attn(q, k, v)))

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
    got = jax.jit(jax.grad(lambda x, y: f(flash, x, y), (0, 1)))(x, y)
    want = jax.jit(jax.grad(lambda x, y: f(lambda *a: _dense(*a, None), x, y),
                            (0, 1)))(x, y)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5


@pytest.mark.parametrize("sweep", ["k", "q"])
@pytest.mark.parametrize("s,b,t,window", [(1024, 256, 128, 300),
                                          (1024, 512, 256, 512),
                                          (512, 128, 128, 100),
                                          (512, 512, 128, 129)])
def test_windowed_step_counts_match_the_mask(s, b, t, window, sweep):
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    live = (j <= i) & (j > i - window)
    tiles = live.reshape(s // t, t, s // t, t)
    some, all_ = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    blocks = live.reshape(s // b, b, s // b, b).any(axis=(1, 3))
    assert _count_steps(s, b, b, t, t, True, sweep, window) == {
        "unmasked": int(all_.sum()), "masked": int((some & ~all_).sum()),
        "dead": int((~blocks).sum())}


def test_window_field_of_the_plan_gauge_and_validation():
    from distributedarrays_tpu import telemetry as tm
    q = jax.ShapeDtypeStruct((512, 4, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((512, 2, 32), jnp.float32)
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=100, block_q=128, block_k=128), q, kv, kv)
    read = lambda what: tm.gauge_value(
        "pallas.flash_attention.plan", kernel="flash_fwd", s=512, d=32,
        causal=True, what=what, window=100)
    assert read("bq") == 128 and read("dead") == 16 - 7
    assert read("masked") + read("unmasked") > 0
    x = jnp.zeros((64, 4, 16))
    with pytest.raises(ValueError, match="window needs causal"):
        flash_attention(x, x, x, window=8)
    with pytest.raises(ValueError, match="share"):
        flash_attention(x, x[:, :3], x, causal=True)


# ---------------------------------------------------------------------------
# the token ids, and what importing the package loads
# ---------------------------------------------------------------------------


def test_token_ids_stay_inside_the_vocabulary_slice():
    import datagen_sambay as G
    rows = np.asarray(G.token_rows(jax.random.key(5), 4, 1, 4097, 25008))
    assert rows.shape == (4, 1, 4097) and rows.dtype == np.int32
    assert rows.min() >= 0 and rows.max() < 25008
    assert rows.max() > 24000          # and they use the slice
    assert len({r.tobytes() for r in rows.reshape(4, -1)}) == 4


def test_importing_the_package_loads_neither_the_model_nor_pallas():
    code = textwrap.dedent("""
        import sys
        import distributedarrays_tpu
        import distributedarrays_tpu.ops
        for name in ("distributedarrays_tpu.models.sambay",
                     "distributedarrays_tpu.models.sambay_reference",
                     "distributedarrays_tpu.ops.pallas_selective_scan",
                     "distributedarrays_tpu.models.mla_moe",
                     "distributedarrays_tpu.models.mla_moe_reference",
                     "distributedarrays_tpu.models.moe",
                     "jax.experimental.pallas"):
            assert name not in sys.modules, name
        print("clean")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True,
                         env={**__import__("os").environ,
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]
