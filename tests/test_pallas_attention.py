"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh),
oracle = the dense attention from models/ring_attention."""

import numpy as np
import pytest

import distributedarrays_tpu  # noqa: F401  (package init)
from distributedarrays_tpu.models.ring_attention import reference_attention
from distributedarrays_tpu.ops.pallas_attention import flash_attention


@pytest.fixture
def qkv(rng):
    S, H, D = 128, 2, 16
    mk = lambda: rng.standard_normal((S, H, D)).astype(np.float32)
    return mk(), mk(), mk()


def test_flash_full(qkv):
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, block_q=32, block_k=32))
    want = reference_attention(q, k, v)
    assert np.abs(got - want).max() < 1e-5


def test_flash_causal(qkv):
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, causal=True,
                                     block_q=32, block_k=32))
    want = reference_attention(q, k, v, causal=True)
    assert np.abs(got - want).max() < 1e-5


def test_flash_uneven_blocks(qkv):
    # bq != bk exercises the grid bookkeeping
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, causal=True,
                                     block_q=64, block_k=32))
    want = reference_attention(q, k, v, causal=True)
    assert np.abs(got - want).max() < 1e-5


def test_flash_validation(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="share"):
        flash_attention(q, k[:64], v)


def test_flash_block_fitting(qkv):
    # a non-dividing block request is fitted (halved until it divides),
    # not rejected — every sequence length works with the defaults
    q, k, v = qkv
    got = np.asarray(flash_attention(q, k, v, causal=True, block_q=48))
    want = reference_attention(q, k, v, causal=True)
    assert np.abs(got - want).max() < 1e-5


def test_flash_head_fold(qkv):
    # hfold > 1: heads ride the grid step as a batched dot (the lane-
    # occupancy lever for small head_dim); numerics identical
    q, k, v = qkv
    want = reference_attention(q, k, v)
    for hf in (2, 3):   # 3 is clipped to a divisor of H=2 -> 2
        got = np.asarray(flash_attention(q, k, v, block_q=32, block_k=32,
                                         head_fold=hf))
        assert np.abs(got - want).max() < 1e-5, hf
    got_c = np.asarray(flash_attention(q, k, v, causal=True, block_q=32,
                                       block_k=32, head_fold=2))
    want_c = reference_attention(q, k, v, causal=True)
    assert np.abs(got_c - want_c).max() < 1e-5


def test_flash_head_fold_grads(qkv):
    import jax
    import jax.numpy as jnp
    q, k, v = qkv

    def loss(fold):
        def f(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, causal=True,
                                           block_q=32, block_k=32,
                                           head_fold=fold) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g1 = loss(1)
    g2 = loss(2)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_autotune_three_tuple_entry(qkv):
    # a (bq, bk, hfold) registry entry drives dispatch; malformed entries
    # degrade to the defaults
    from distributedarrays_tpu.utils import autotune
    q, k, v = qkv
    want = reference_attention(q, k, v)
    key = autotune.device_key_for(128, 2, 16, q.dtype, False)
    autotune.clear()
    autotune.record("flash_attention", key, (32, 32, 2))
    got = np.asarray(flash_attention(q, k, v))
    assert np.abs(got - want).max() < 1e-5
    autotune.record("flash_attention", key, ("bogus",))
    got = np.asarray(flash_attention(q, k, v))   # degrades, still correct
    assert np.abs(got - want).max() < 1e-5
    autotune.clear()


# ---------------------------------------------------------------------------
# every body the kernels have (unmasked tile, diagonal tile, clamped dead
# step, fused and two-pass backward), against the dense differentiation rule
# ---------------------------------------------------------------------------


def _attention_pad(S):
    # models/transformer._attention's padding rule
    bs = next(b for b in (512, 256, 128, 64, 32)
              if b == 32 or (-(-S // b) * b - S) * 8 <= S)
    return -(-S // bs) * bs


def _exact_cases():
    # default choice of blocks, fold alternating so that every shape meets
    # both dtypes and both folds meet both dtypes
    shapes = [(d, s, causal) for d in (64, 128)
              for s in (128, 1000, 1024, 1536) for causal in (True, False)]
    for j, (d, s, causal) in enumerate(shapes):
        for t, dtype in enumerate(("float32", "bfloat16")):
            fold = 1 + (j + t) % 2
            yield pytest.param(
                s, d, causal, dtype, fold, None, None, True,
                id=f"s{s}-d{d}-{'causal' if causal else 'full'}-{dtype}"
                   f"-fold{fold}")
    # named blocks at the benchmark cell's S and D: the two-pass backward,
    # dead steps behind the clamp (several blocks a head), and blocks of
    # two sizes (the diagonal crosses a step anywhere: traced sweep bounds)
    for dtype, causal, fold, bq, bk, fused in [
            ("bfloat16", True, 2, None, None, False),
            ("float32", True, 1, 512, 512, False),
            ("float32", True, 1, 256, 512, False),
            ("float32", True, 1, 512, 256, True),
            ("bfloat16", False, 1, 512, 512, False),
            ("bfloat16", True, 2, 256, 256, True)]:
        yield pytest.param(
            1024, 64, causal, dtype, fold, bq, bk, fused,
            id=f"s1024-d64-{'causal' if causal else 'full'}-{dtype}"
               f"-fold{fold}-b{bq}x{bk}-{'fused' if fused else 'twopass'}")
    # the latent cell's head width, small S: the fused sweep through the
    # very pallas_call that names its VMEM limit (the default limit's room
    # patched to nothing, so that every dQ asks for the raised one)
    for s, dtype, causal, fold, bq, bk in [
            (512, "bfloat16", True, 1, None, None),
            (512, "float32", True, 2, 256, 256),
            (1024, "bfloat16", True, 1, 512, 512),
            (384, "float32", False, 1, 128, 128)]:
        yield pytest.param(
            s, 256, causal, dtype, fold, bq, bk, "raised",
            id=f"s{s}-d256-{'causal' if causal else 'full'}-{dtype}"
               f"-fold{fold}-b{bq}x{bk}-raised")


@pytest.mark.parametrize("S,D,causal,dtype,fold,bq,bk,fused",
                         list(_exact_cases()))
def test_flash_exact_output_and_grads(rng, monkeypatch, S, D, causal, dtype,
                                      fold, bq, bk, fused):
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.ops import pallas_attention as PA
    if fused is not True:
        # nothing fits the default limit; two passes: nor the raised one
        monkeypatch.setattr(PA, "_FUSED_DQ_BYTES", 0)
        if not fused:
            monkeypatch.setattr(PA, "_FUSED_VMEM_CAP", 0)
        PA._build_bwd.cache_clear()
    # a causal S that is no multiple of a block is padded as _attention
    # pads it (keys at positions >= S are hidden from every real row)
    Spad = _attention_pad(S) if causal else S
    q, k, v, w = (jnp.asarray(rng.standard_normal((S, fold, D)), dtype)
                  for _ in range(4))
    scale = 1.0 / np.sqrt(D)
    f32 = lambda x: x.astype(jnp.float32)

    def flash(q, k, v):
        pad = lambda x: jnp.pad(x, ((0, Spad - S), (0, 0), (0, 0)))
        return PA.flash_attention(pad(q), pad(k), pad(v), causal=causal,
                                  block_q=bq, block_k=bk,
                                  head_fold=fold)[:S]

    def dense(q, k, v):
        return PA._dense_attention_shd(q, k, v, causal, scale)

    tol_o, tol_g = (2e-5, 1e-4) if dtype == "float32" else (2e-2, 3e-2)
    assert float(jnp.abs(f32(flash(q, k, v))
                         - f32(dense(q, k, v))).max()) < tol_o
    loss = lambda f: (lambda q, k, v: jnp.sum(f32(f(q, k, v)) * f32(w)))
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), (0, 1, 2))(q, k, v)
    if fused is not True:
        if fused == "raised":
            # the program that ran is the one sweep, and names its limit
            limit = tm.gauge_value(
                "pallas.flash_attention.plan", kernel="flash_bwd_dkv",
                s=Spad, d=D, causal=causal, what="vmem_limit")
            assert 0 < limit <= PA._FUSED_VMEM_CAP
            assert f"vmem_limit_bytes={int(limit)}" in str(jax.make_jaxpr(
                jax.grad(loss(flash), (0, 1, 2)))(q, k, v))
        PA._build_bwd.cache_clear()
    for name, a, b in zip("qkv", got, want):
        gap = float(jnp.abs(f32(a) - f32(b)).max() / jnp.abs(f32(b)).max())
        assert gap < tol_g, (name, gap)


@pytest.mark.parametrize("bq,bk", [(256, 256), (128, 256), (64, 32)],
                         ids=["aligned", "two_sizes", "small"])
def test_flash_hop_bwd_offsets_match_dense_gradient(rng, bq, bk):
    # the ring's backward hop with non-zero traced offsets: the
    # contributions of the four (q half, k half) pairs (one of them wholly
    # above the diagonal) add up to the dense causal gradient
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu.ops.pallas_attention import (
        _LANE, _dense_attention_shd, flash_attention_hop_bwd)
    S, H, D = 512, 2, 64
    half = S // 2
    q, k, v, g = (jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
                  for _ in range(4))
    scale = 1.0 / np.sqrt(D)
    dense = lambda q, k, v: _dense_attention_shd(q, k, v, True, scale)
    o, vjp = jax.vjp(dense, q, k, v)
    want = vjp(g)
    hf = lambda x: jnp.transpose(x, (1, 0, 2))
    # final logsumexp and D rows of the whole sequence, lane-replicated
    s = jnp.einsum("qhd,khd->hqk", q * scale, k)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    lanes = lambda x: jnp.broadcast_to(x[:, :, None], (H, S, _LANE))
    lse = lanes(jax.nn.logsumexp(s, axis=-1))
    dd = lanes(jnp.einsum("shd,shd->hs", g, o))
    got = [jnp.zeros((H, S, D), jnp.float32) for _ in range(3)]
    hop = jax.jit(lambda qoff, koff, *a: flash_attention_hop_bwd(
        *a, qoff, koff, causal=True, block_q=bq, block_k=bk))
    for qoff in (0, half):
        for koff in (0, half):
            rq, rk = slice(qoff, qoff + half), slice(koff, koff + half)
            dq, dk, dv = hop(qoff, koff, hf(q)[:, rq], hf(k)[:, rk],
                             hf(v)[:, rk], hf(g)[:, rq], lse[:, rq],
                             dd[:, rq])
            got[0] = got[0].at[:, rq].add(dq)
            got[1] = got[1].at[:, rk].add(dk)
            got[2] = got[2].at[:, rk].add(dv)
    for name, a, b in zip("qkv", got, want):
        gap = float(jnp.abs(hf(a) - b).max() / jnp.abs(b).max())
        assert gap < 1e-4, (name, gap)


# ---------------------------------------------------------------------------
# the plan: which blocks a program visits, and the gauge that says so
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sweep", ["k", "q"])
@pytest.mark.parametrize("s,bq,bk", [(1024, 1024, 1024), (1024, 512, 512),
                                     (1536, 512, 512), (1024, 256, 512),
                                     (2048, 1024, 256), (128, 128, 128)])
def test_flash_step_counts_match_the_mask(s, bq, bk, sweep):
    from distributedarrays_tpu.ops.pallas_attention import (
        _count_steps, _tiles)
    tq, tk = _tiles(bq, bk)
    live = np.tril(np.ones((s, s), bool))
    tiles = live.reshape(s // tq, tq, s // tk, tk)
    some, all_ = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    blocks = live.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3))
    got = _count_steps(s, bq, bk, tq, tk, True, sweep)
    assert got == {"unmasked": int(all_.sum()),
                   "masked": int((some & ~all_).sum()),
                   "dead": int((~blocks).sum())}
    full = _count_steps(s, bq, bk, tq, tk, False, sweep)
    assert full == {"unmasked": (s // tq) * (s // tk), "masked": 0,
                    "dead": 0}


def test_flash_plan_gauge_at_the_benchmark_cell_shape():
    # gpt2m_train calls flash_attention on (1024, 8 x 16, 64) bf16 causal
    # with nothing named: no grid step is dead, under half of the tiles
    # are masked, and the backward is one program
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.ops import pallas_attention as PA
    PA._build.cache_clear()
    PA._build_bwd.cache_clear()
    q = jax.ShapeDtypeStruct((1024, 128, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(
        lambda q, k, v: jnp.sum(PA.flash_attention(q, k, v, causal=True)
                                .astype(jnp.float32)), (0, 1, 2)), q, q, q)

    def read(kernel, what):
        return tm.gauge_value("pallas.flash_attention.plan", kernel=kernel,
                              s=1024, d=64, causal=True, what=what)

    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert read(kernel, "dead") == 0
        masked, unmasked = read(kernel, "masked"), read(kernel, "unmasked")
        assert 0 < masked < (masked + unmasked) / 2
        assert [read(kernel, w) for w in ("bq", "bk", "fold")] == [
            1024, 1024, 1]
    # one backward program: a head's dQ fits VMEM
    assert PA._fused_backward(1024, 64, jnp.bfloat16, 1, False) == (
        True, None)
    # the blocks the caller used to force leave one dead step a head
    jax.eval_shape(lambda q: PA.flash_attention(
        q, q, q, causal=True, block_q=512, block_k=512), q)
    assert read("flash_fwd", "dead") == 1
    assert read("flash_fwd", "bq") == 512
    PA._build.cache_clear()


_MIB = 1024 * 1024


@pytest.mark.parametrize("s,d,dtype,fold,traced,want", [
    (1024, 64, "bfloat16", 1, False, "fused"),      # gpt2m_train
    (8192, 128, "bfloat16", 1, False, "fused"),     # phi4mf: exactly 8 MiB
    (2048, 256, "bfloat16", 2, False, "fused"),
    (8192, 256, "bfloat16", 1, False, "raised"),    # glm47f_train_s8k
    (16384, 128, "bfloat16", 1, False, "raised"),   # dQ of a head: 16 MiB
    (8192, 64, "float32", 1, False, "raised"),      # 64 lanes pad to 128
    (8192, 128, "bfloat16", 2, False, "raised"),    # two heads a step
    (16384, 256, "bfloat16", 1, False, "raised"),   # twice the cell's dQ
    (32768, 256, "bfloat16", 1, False, "two"),      # past the cap
    (65536, 128, "float32", 1, False, "two"),
    (256, 64, "float32", 1, True, "two"),           # the ring hop
    (8192, 256, "bfloat16", 1, True, "two"),
])
def test_flash_backward_form_follows_from_the_shapes(s, d, dtype, fold,
                                                     traced, want):
    from distributedarrays_tpu.ops.pallas_attention import (
        _FUSED_VMEM_CAP, _fused_backward, _fused_vmem_bytes)
    fused, limit = _fused_backward(s, d, dtype, fold, traced)
    assert fused is (want != "two")
    if want != "raised":
        assert limit is None       # no limit named: the parent's program
    else:
        need = _fused_vmem_bytes(s, d, d, 1024, 1024, dtype, dtype, dtype,
                                 fold)
        assert need < limit <= _FUSED_VMEM_CAP and limit % _MIB == 0


def test_flash_backward_vmem_limit_at_the_latent_cell_shape():
    # glm47f_train_s8k's backward, (8192, 20 heads, 256) bf16 with blocks
    # of 1024: the resident dQ alone is 16 MiB (float32 scratch and the
    # output block in two buffers), the blocks 8 more; the chip's compiler
    # takes the kernel from 26 MiB up and refuses it at 24 (compiled for a
    # described v5e:2x2, PR 36), so the reckoned limit has to clear that
    from distributedarrays_tpu.ops.pallas_attention import (
        _FUSED_VMEM_CAP, _fused_backward, _fused_vmem_bytes)
    need = _fused_vmem_bytes(8192, 256, 256, 1024, 1024, "bfloat16",
                             "bfloat16", "bfloat16", 1)
    assert 26 * _MIB <= need <= 32 * _MIB
    fused, limit = _fused_backward(8192, 256, "bfloat16", 1, False, 256,
                                   1024, 1024, "bfloat16", "bfloat16")
    assert fused and need < limit <= 40 * _MIB < _FUSED_VMEM_CAP
    # float32 dK, dV (grouped heads) and smaller blocks move the need, and
    # the limit with it
    wide = _fused_backward(8192, 256, "bfloat16", 1, False, 256, 1024, 1024,
                           "bfloat16", "float32")[1]
    small = _fused_backward(8192, 256, "bfloat16", 1, False, 256, 512, 512,
                            "bfloat16", "bfloat16")[1]
    assert small < limit < wide


def _backward_jaxpr(q, k, v, **kw):
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu.ops import pallas_attention as PA
    PA._build_bwd.cache_clear()
    return str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(PA.flash_attention(q, k, v, causal=True, **kw)
                                .astype(jnp.float32)), (0, 1, 2)))(q, k, v))


@pytest.mark.parametrize("cell", ["gpt2m_train", "phi4mf_full",
                                  "phi4mf_window"])
def test_flash_backward_programs_of_the_fused_cells_name_no_limit(cell):
    # what was one sweep under the default limit keeps its pallas_call to
    # the letter: no compiler_params, so neither cell's program changes
    import jax
    import jax.numpy as jnp
    sds = lambda s, h, d: jax.ShapeDtypeStruct((s, h, d), jnp.bfloat16)
    if cell == "gpt2m_train":
        txt = _backward_jaxpr(*[sds(1024, 128, 64)] * 3)
    else:
        txt = _backward_jaxpr(sds(8192, 40, 64), sds(8192, 20, 64),
                              sds(8192, 10, 128),
                              window=512 if cell == "phi4mf_window" else None)
    assert "flash_bwd_dkv" in txt and "flash_bwd_dq" not in txt
    assert "vmem_limit_bytes" not in txt
    assert txt.count("compiler_params=FrozenDict({})") == 2   # fwd, bwd


def test_flash_backward_at_the_latent_cell_shape_is_one_sweep():
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu.ops.pallas_attention import _fused_backward
    q = jax.ShapeDtypeStruct((8192, 20, 256), jnp.bfloat16)
    txt = _backward_jaxpr(q, q, q)
    limit = _fused_backward(8192, 256, "bfloat16", 1, False)[1]
    assert "flash_bwd_dkv" in txt and "flash_bwd_dq" not in txt
    assert f"vmem_limit_bytes={limit}" in txt
    # lse and D reach the one kernel as rows: nothing replicated over lanes
    assert "f32[20,8192,128]" not in txt


def test_flash_plan_gauge_says_the_raised_limit():
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.ops.pallas_attention import _fused_backward

    def read(kernel, s, d, what):
        return tm.gauge_value("pallas.flash_attention.plan", kernel=kernel,
                              s=s, d=d, causal=True, what=what)

    q = jax.ShapeDtypeStruct((8192, 20, 256), jnp.bfloat16)
    _backward_jaxpr(q, q, q)
    assert read("flash_bwd_dkv", 8192, 256, "vmem_limit") == _fused_backward(
        8192, 256, "bfloat16", 1, False)[1]
    assert read("flash_bwd_dkv", 8192, 256, "bq") == 1024
    # one sweep: no second program was planned
    assert read("flash_bwd_dq", 8192, 256, "bq") is None
    # under the default limit the gauge's keys are what they were
    q = jax.ShapeDtypeStruct((1024, 128, 64), jnp.bfloat16)
    _backward_jaxpr(q, q, q)
    assert read("flash_bwd_dkv", 1024, 64, "bq") == 1024
    assert read("flash_bwd_dkv", 1024, 64, "vmem_limit") is None


def test_flash_default_blocks_and_fold_follow_from_the_shapes():
    from distributedarrays_tpu.ops.pallas_attention import (
        tuned_flash_config)
    # nothing named, no registry entry (the CPU's device key has none)
    assert tuned_flash_config(1024, 128, 64, "bfloat16", True) == (
        1024, 1024, 1)
    assert tuned_flash_config(8192, 32, 128, "bfloat16", True) == (
        1024, 1024, 1)
    # a named block is kept and takes fold 1 with it, a named fold wins
    assert tuned_flash_config(1024, 128, 64, "bfloat16", True,
                              block_q=512) == (512, 1024, 1)
    assert tuned_flash_config(1024, 128, 64, "bfloat16", True,
                              head_fold=4) == (1024, 1024, 4)


# ---------------------------------------------------------------------------
# the caller's (B, S, heads, D) layout, read in place (two heads of 64 a
# 128-lane block) or through head-major copies, against the dense rule
# and against the head-major path the kernels took before
# ---------------------------------------------------------------------------

# B, S, query heads, k heads, v heads, D, value width, window, blocks,
# backward form, the gauge's lane_heads
_LAYOUT_CASES = {
    "b1_d64_even": (1, 256, 4, 4, 4, 64, 64, None, 128, "fused", 2),
    "b3_d64_even": (3, 256, 2, 2, 2, 64, 64, None, 128, "fused", 2),
    "b3_d64_odd": (3, 256, 3, 3, 3, 64, 64, None, 128, "fused", 0),
    "b1_d64_odd": (1, 128, 1, 1, 1, 64, 64, None, None, "fused", 0),
    "b3_d128": (3, 256, 2, 2, 2, 128, 128, None, 128, "fused", 0),
    "b1_d256": (1, 256, 2, 2, 2, 256, 256, None, 128, "fused", 0),
    "b3_g2": (3, 256, 4, 2, 2, 64, 64, None, 128, "fused", 2),
    "b3_g4": (3, 256, 8, 2, 2, 64, 64, None, 128, "fused", 2),
    "b3_g4_twopass": (3, 256, 8, 2, 2, 64, 64, None, 128, "two", 2),
    "b3_g2_v128": (3, 256, 4, 2, 1, 64, 128, None, 128, "fused", 0),
    "b1_g2_v128_window": (1, 512, 4, 2, 1, 64, 128, 100, 128, "fused", 0),
    "b3_window": (3, 512, 2, 2, 2, 64, 64, 100, 128, "fused", 2),
    "b1_g4_window_twopass": (1, 512, 8, 2, 2, 64, 64, 200, 128, "two", 2),
    "b3_d64_full": (3, 256, 2, 2, 2, 64, 64, "full", 128, "fused", 2),
}


def _dense_4d(q, k, v, causal, window, scale):
    """(B, S, H, D) attention by the dense rule: ``_dense_attention_shd``
    a row of the batch, or a masked softmax where a window is kept; k and
    v heads repeated over the query heads they serve."""
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu.ops.pallas_attention import (
        _dense_attention_shd)
    H = q.shape[2]
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    if window is None:
        return jax.vmap(lambda q, k, v: _dense_attention_shd(
            q, k, v, causal, scale))(q, k, v)
    S = q.shape[1]
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    live = (j <= i) & (i - j < window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


@pytest.mark.parametrize("case", list(_LAYOUT_CASES))
def test_flash_in_the_callers_layout_matches_dense_and_head_major(
        case, monkeypatch):
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.ops import pallas_attention as PA
    B, S, H, Hk, Hv, D, Dv, window, blk, form, lanes = _LAYOUT_CASES[case]
    causal = window != "full"
    window = None if window == "full" else window
    if form == "two":
        # nothing fits either limit: the dQ pass runs as its own kernel
        monkeypatch.setattr(PA, "_FUSED_DQ_BYTES", 0)
        monkeypatch.setattr(PA, "_FUSED_VMEM_CAP", 0)
    PA._build.cache_clear()
    PA._build_bwd.cache_clear()
    ks = jax.random.split(jax.random.key(B * S + H + D + Dv), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hk, D))
    v = jax.random.normal(ks[2], (B, S, Hv, Dv))
    w = jax.random.normal(ks[3], (B, S, H, Dv))
    scale = 1.0 / np.sqrt(D)

    def flash(q, k, v):
        return PA.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=blk, block_k=blk)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v)

    got, got_g = flash(q, k, v), grads(flash)
    assert got.shape == (B, S, H, Dv)
    assert tm.gauge_value("pallas.flash_attention.plan", kernel="flash_fwd",
                          s=S, d=D, causal=causal, what="lane_heads",
                          **({} if window is None else {"window": window})
                          ) == lanes
    want = _dense_4d(q, k, v, causal, window, scale)
    want_g = grads(lambda *a: _dense_4d(*a, causal, window, scale))
    # the head-major path: every call through (B x H, S, D) copies
    monkeypatch.setattr(PA, "_lane_heads", lambda *a: 0)
    major, major_g = flash(q, k, v), grads(flash)
    PA._build_bwd.cache_clear()
    for ref, ref_g, tol in ((want, want_g, 1e-4), (major, major_g, 1e-5)):
        assert float(jnp.abs(got - ref).max()) < 2e-5
        for name, a, b in zip("qkv", got_g, ref_g):
            gap = float(jnp.abs(a - b).max() / jnp.abs(b).max())
            assert gap < tol, (name, gap)


# B, S, heads, D, window, blocks, backward form, the gauge's lane_heads
_PACKED_CASES = {
    "b3_d64": (3, 256, 4, 64, None, 128, "fused", 2),
    "b1_d64_window": (1, 512, 2, 64, 100, 128, "fused", 2),
    "b2_d64_full": (2, 256, 2, 64, "full", 128, "fused", 2),
    "b2_d64_twopass": (2, 256, 2, 64, None, 128, "two", 2),
    "b2_d32_head_major": (2, 128, 2, 32, None, None, "fused", 0),
    "row_d64": (None, 256, 2, 64, None, 128, "fused", 2),
}


@pytest.mark.parametrize("case", list(_PACKED_CASES))
def test_flash_reads_packed_qkv_and_writes_its_gradient_packed(
        case, monkeypatch):
    # q, k, v as one (B, S, 3, H, D) projection: the same attention and the
    # same gradients as the three arrays apart, the gradient one array
    import jax
    import jax.numpy as jnp
    from distributedarrays_tpu import telemetry as tm
    from distributedarrays_tpu.ops import pallas_attention as PA
    B, S, H, D, window, blk, form, lanes = _PACKED_CASES[case]
    causal = window != "full"
    window = None if window == "full" else window
    if form == "two":
        # no one sweep: the packed form gives way to q, k, v apart
        monkeypatch.setattr(PA, "_FUSED_DQ_BYTES", 0)
        monkeypatch.setattr(PA, "_FUSED_VMEM_CAP", 0)
    PA._build.cache_clear()
    PA._build_bwd.cache_clear()
    ks = jax.random.split(jax.random.key(S + H + D), 2)
    shape = (S, 3, H, D) if B is None else (B, S, 3, H, D)
    qkv = jax.random.normal(ks[0], shape)
    w = jax.random.normal(ks[1], shape[:-3] + (H, D))
    kw = dict(causal=causal, window=window, block_q=blk, block_k=blk)

    def packed(x):
        return PA.flash_attention(x, None, None, **kw)

    def apart(x):
        return PA.flash_attention(*(x[..., n, :, :] for n in range(3)), **kw)

    def dense(x):
        x4 = x if B is not None else x[None]
        o = _dense_4d(*(x4[:, :, n] for n in range(3)), causal, window,
                      1.0 / np.sqrt(D))
        return o if B is not None else o[0]

    loss = lambda f: (lambda x: jnp.sum(f(x) * w))
    got, got_g = packed(qkv), jax.grad(loss(packed))(qkv)
    assert got.shape == w.shape and got_g.shape == qkv.shape
    assert tm.gauge_value("pallas.flash_attention.plan", kernel="flash_fwd",
                          s=S, d=D, causal=causal, what="lane_heads",
                          **({} if window is None else {"window": window})
                          ) == lanes
    for ref, tol in ((dense, 1e-4), (apart, 1e-5)):
        assert float(jnp.abs(got - ref(qkv)).max()) < 2e-5
        want_g = jax.grad(loss(ref))(qkv)
        for n in range(3):
            a, b = got_g[..., n, :, :], want_g[..., n, :, :]
            gap = float(jnp.abs(a - b).max() / jnp.abs(b).max())
            assert gap < tol, ("qkv"[n], gap)
    PA._build_bwd.cache_clear()
