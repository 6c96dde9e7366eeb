"""The plain reference of the SambaY decoder-hybrid-decoder (the
``phi4_mini_flash`` configuration): its layer equations written
straightforwardly, independent of the program.

float32 ``jax.numpy`` with ``HIGHEST``-precision products, no kernel,
nothing imported from the program.  The selective scan is a sequential
``lax.scan`` over the positions, attention is masked dense scores, the loss
and the gradients come from ``jax.vjp``.  So that three steps at the cell's
size fit one chip beside the optimizer's moments, the work goes layer by
layer (a layer's input is kept, the layer is differentiated on its own),
the scan goes in blocks of channels and attention and the loss in blocks
of rows, each block differentiated on its own: the same equations, no
other arithmetic.  ``lowp`` makes the CONTROL: every matmul operand
rounded to that type (``refs.q``).

Every layer: ``h = x + Mixer(LN1(x))``, ``y = h + MLP(LN2(h))``;
``MLP(u) = (SiLU(g) * v) W2`` with ``[g, v] = u W1``; LayerNorm with scale
and bias; no positional term; the head is the embedding, transposed.

- mamba: ``[xs, z] = u W_in``; ``xc = SiLU(conv(xs) + b)`` (causal,
  depthwise, ``out[t] = sum_k w[k] xs[t - K + 1 + k]``); ``[d, B, C] = xc
  W_x``; ``Delta = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t =
  exp(Delta_t A) h_{t-1} + (Delta_t xc_t) (x) B_t``; ``m_t = h_t C_t +
  D_skip xc_t``; ``out = (m SiLU(z)) W_out``.  The layer publishes ``m``.
- gmu: ``out = (m* SiLU(u W_g)) W_o``, ``m*`` of the nearest Mamba layer
  before it.
- window, full, cross: differential attention.  Query heads pair up (even,
  odd) as ``q1, q2``, K/V heads likewise as ``k1, k2`` and ``v1, v2``;
  ``V = [v1, v2]``; query pair ``j`` uses K/V pair ``j // (pairs of q /
  pairs of kv)``; ``O_i = softmax(mask(q_i k_i^T / sqrt(hd))) V``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 index)``; ``O = RMSNorm(O_1 - lambda
  O_2) * subln * (1 - lambda_init)``; ``out = O W_o + b_o``; q, k and v
  each have a bias (kept as three leaves: the key bias has no gradient).  Mask: causal,
  and for a window layer ``j > i - window``.  A full layer publishes its
  ``k, v``; a cross layer has only ``W_q`` and ``W_o`` and uses those.

Departures from the published description: none in the equations; what the
published config does not state (the Mamba sizes, the pairing, the
sub-norm) is listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from refs import HP, adamw_reference, q

__all__ = ["ref_loss_and_grads", "ref_adamw", "ref_train_step",
           "subtree_norms", "leaf_norm_dict"]

F32 = jnp.float32


def _f32(tree):
    """Gradients are wanted in float32 whatever type stores the leaf."""
    return jax.tree_util.tree_map(lambda t: t.astype(F32), tree)


def _mm(a, b, lowp):
    return jnp.dot(q(a, lowp), q(b, lowp), precision=HP)


def _layernorm(x, s, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True)
                              + eps) * s + b


def _blocks(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    b = min(n, cap)
    while n % b:
        b -= 1
    return b


def _scan_ref(xc, delta, a_t, bm, cm):
    """The recurrence, one position after the other, in blocks of channels
    (each channel's recurrence is its own).  The state is kept as (N,
    channels), channels last, so that the saved states are not padded."""
    S, E = xc.shape
    eb = _blocks(E, 1280)

    @jax.checkpoint
    def block(args):
        x, d, a = args                                  # (S, eb), (N, eb)

        def step(h, inp):
            xt, dt, bt, ct = inp
            h = jnp.exp(dt[None, :] * a) * h + (dt * xt)[None, :] * bt[:, None]
            return h, jnp.sum(h * ct[:, None], axis=0)

        return jax.lax.scan(step, jnp.zeros_like(a), (x, d, bm, cm))[1]

    split = lambda t: jnp.moveaxis(t.reshape(t.shape[0], E // eb, eb), 1, 0)
    y = jax.lax.map(block, (split(xc), split(delta), split(a_t)))
    return jnp.moveaxis(y, 0, 1).reshape(S, E)


def _mamba(u, p, dims, lowp):
    N, R, K = dims["d_state"], dims["dt_rank"], dims["d_conv"]
    S = u.shape[0]
    xs, z = jnp.split(_mm(u, p["in_proj"], lowp), 2, axis=-1)
    xp = jnp.pad(xs, ((K - 1, 0), (0, 0)))
    conv = sum(p["conv_w"][k] * xp[k:k + S] for k in range(K))
    xc = jax.nn.silu(conv + p["conv_b"])
    d, bm, cm = jnp.split(_mm(xc, p["x_proj"], lowp), [R, R + N], axis=-1)
    delta = jax.nn.softplus(_mm(d, p["dt_w"], lowp) + p["dt_b"])
    a_t = -jnp.exp(p["A_log"]).T
    m = _scan_ref(xc, delta, a_t, bm, cm) + p["D_skip"] * xc
    return _mm(m * jax.nn.silu(z), p["out_proj"], lowp), m


def _dense_rows(q1, q2, k1, k2, v, window, lowp):
    """``O_1, O_2`` (S, pairs, 2 hd) by masked dense scores, a block of rows
    at a time."""
    S, P, hd = q1.shape
    rb = _blocks(S, 512)
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(args):
        qa, qb, r0 = args                               # (rb, P, hd)
        rows = r0 + jnp.arange(rb)[:, None]
        live = cols <= rows
        if window is not None:
            live = live & (cols > rows - window)

        def one(qq, kk):
            s = jnp.einsum("qhd,khd->hqk", q(qq, lowp), q(kk, lowp),
                           precision=HP) / np.float32(math.sqrt(hd))
            pr = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", q(pr, lowp), q(v, lowp),
                              precision=HP)

        return one(qa, k1), one(qb, k2)

    cut = lambda t: t.reshape(S // rb, rb, P, hd)
    o1, o2 = jax.lax.map(block, (cut(q1), cut(q2),
                                 jnp.arange(0, S, rb, dtype=jnp.int32)))
    return o1.reshape(S, P, -1), o2.reshape(S, P, -1)


def _attention(u, p, index, kind, kv_star, dims, lowp):
    H, KV, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    S = u.shape[0]
    if kind == "cross":
        qh = _mm(u, p["wq"], lowp) + p["bq"]
        k, v = kv_star
    else:
        qh, k, v = jnp.split(_mm(u, p["wqkv"], lowp),
                             [H * hd, (H + KV) * hd], axis=-1)
        qh, k, v = qh + p["bq"], k + p["bk"], v + p["bv"]
    qh = qh.reshape(S, H // 2, 2, hd)
    kh = k.reshape(S, KV // 2, 2, hd)
    vv = v.reshape(S, KV // 2, 2 * hd)
    rep = (H // 2) // (KV // 2)
    spread = lambda t: jnp.repeat(t, rep, axis=1)
    o1, o2 = _dense_rows(qh[:, :, 0], qh[:, :, 1], spread(kh[:, :, 0]),
                         spread(kh[:, :, 1]), spread(vv),
                         dims["window"] if kind == "window" else None, lowp)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"]))
           - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0)
    d = o1 - lam * o2
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                          + dims["eps"])
    d = d * p["subln"] * (1.0 - lam0)
    return _mm(d.reshape(S, H * hd), p["wo"], lowp) + p["bo"], (k, v)


def _layer(p, x, m_star, kv_star, *, index, kind, dims, lowp):
    """(y, m or None, (k, v) or None) of one layer on one row (S, D)."""
    p = _f32(p)
    eps = dims["eps"]
    u = _layernorm(x, p["ln1_s"], p["ln1_b"], eps)
    m = kv = None
    if kind == "mamba":
        mix, m = _mamba(u, p, dims, lowp)
    elif kind == "gmu":
        mix = _mm(m_star * jax.nn.silu(_mm(u, p["wg"], lowp)), p["wo"], lowp)
    else:
        mix, kv = _attention(u, p, index, kind, kv_star, dims, lowp)
        kv = kv if kind == "full" else None
    h = x + mix
    g, v = jnp.split(_mm(_layernorm(h, p["ln2_s"], p["ln2_b"], eps),
                         p["w1"], lowp), 2, axis=-1)
    return h + _mm(jax.nn.silu(g) * v, p["w2"], lowp), m, kv


def _head_nll(embed, s, b, x, tgt, *, eps, lowp):
    """Summed cross-entropy of the row: final LayerNorm, the tied head, a
    block of rows at a time."""
    embed = embed.astype(F32)
    xn = _layernorm(x, s.astype(F32), b.astype(F32), eps)
    S, D = xn.shape
    rb = _blocks(S, 1024)

    @jax.checkpoint
    def block(args):
        xr, tr = args
        logp = jax.nn.log_softmax(_mm(xr, embed.T, lowp), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tr[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block, (xn.reshape(S // rb, rb, D),
                                       tgt.reshape(S // rb, rb))))


@functools.lru_cache(maxsize=None)
def _programs(index, kind, dims_key, lowp):
    """(forward, backward) of one layer, jitted; the backward differentiates
    the layer on its own from its kept inputs."""
    dims = dict(dims_key)
    f = functools.partial(_layer, index=index, kind=kind, dims=dims,
                          lowp=lowp)
    bwd = lambda p, x, ms, kvs, ct: jax.vjp(f, _f32(p), x, ms, kvs)[1](ct)
    return jax.jit(f), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _head_programs(eps, lowp):
    f = functools.partial(_head_nll, eps=eps, lowp=lowp)
    vg = jax.value_and_grad(f, argnums=(0, 1, 2, 3))
    return jax.jit(lambda e, s, b, x, tgt: vg(_f32(e), _f32(s), _f32(b), x,
                                              tgt))


_embed_rows = jax.jit(lambda embed, tok: embed[tok].astype(F32))
_embed_grad = jax.jit(lambda g_embed, tok, gx: g_embed.at[tok].add(gx),
                      donate_argnums=(0,))
_tree_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                    donate_argnums=(0,))
_tree_scale = jax.jit(lambda a, s: jax.tree_util.tree_map(
    lambda t: t * s, a), donate_argnums=(0,))
_zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))


def _row_nll_and_grads(params, row, dims, lowp, sink=None):
    """Summed cross-entropy of one row of token ids (S + 1,) and its
    gradients (float32, the program's tree), layer by layer.  With a
    ``sink`` each layer's gradients are handed to ``sink(n, grads)`` as
    soon as they exist and not kept (``n`` None: the leaves outside the
    layers, last), so that the whole tree's never live together."""
    layers = dims["layers"]
    key = tuple(sorted((k, v) for k, v in dims.items()))
    tok, tgt = row[:-1], row[1:]
    x = _embed_rows(params["embed"], tok)
    kept, m_star, kv_star, m_from, kv_from = [], None, None, None, None
    for n, ((index, kind), p) in enumerate(zip(layers, params["layers"])):
        ms = m_star if kind == "gmu" else None
        kvs = kv_star if kind == "cross" else None
        kept.append((x, ms, kvs, m_from if kind == "gmu" else None,
                     kv_from if kind == "cross" else None))
        x, m, kv = _programs(index, kind, key, lowp)[0](p, x, ms, kvs)
        if m is not None:
            m_star, m_from = m, n
        if kv is not None:
            kv_star, kv_from = kv, n
    nll, (g_embed, g_s, g_b, gx) = _head_programs(dims["eps"], lowp)(
        params["embed"], params["ln_f_s"], params["ln_f_b"], x, tgt)
    grads = {"ln_f_s": g_s, "ln_f_b": g_b,
             "layers": [None] * len(layers)}
    owed = {}                 # publisher's layer -> cotangent of what it published
    for n in reversed(range(len(layers))):
        index, kind = layers[n]
        x_in, ms, kvs, m_src, kv_src = kept.pop()
        # what a publisher owes to its readers; zeros where it has none
        g_m = g_kv = None
        if kind == "mamba":
            g_m = owed.pop(("m", n), None)
            if g_m is None:
                g_m = jnp.zeros((x_in.shape[0], dims["d_inner"]), F32)
        elif kind == "full":
            g_kv = owed.pop(("kv", n), None) or _zeros(kv_star)
        gp, gx, gms, gkvs = _programs(index, kind, key, lowp)[1](
            params["layers"][n], x_in, ms, kvs, (gx, g_m, g_kv))
        grads["layers"][n] = gp if sink is None else sink(n, gp)
        for name, src, g in (("m", m_src, gms), ("kv", kv_src, gkvs)):
            if src is not None:
                have = owed.get((name, src))
                owed[(name, src)] = g if have is None else _tree_add(have, g)
    grads["embed"] = _embed_grad(g_embed, tok, gx)
    if sink is not None:
        sink(None, {k: grads.pop(k) for k in ("embed", "ln_f_s", "ln_f_b")})
    return float(nll), grads


def ref_loss_and_grads(params, tokens, dims, lowp=None):
    """Mean loss and its float32 gradients over a batch (B, S + 1), a row
    at a time.  ``params`` is the program's tree in any float type;
    ``dims`` holds heads, kv_heads, head_dim, window, d_state, d_conv,
    dt_rank, d_inner, eps and ``layers`` ((published index, kind), ...)."""
    total, grads, n = 0.0, None, 0
    for row in tokens:
        nll, g = _row_nll_and_grads(params, row, dims, lowp)
        total += nll
        n += row.shape[0] - 1
        grads = g if grads is None else _tree_add(grads, g)
    return total / n, _tree_scale(grads, np.float32(1.0 / n))


_up = jax.jit(_f32)


def ref_adamw(p, mu, nu, g, t, hyper):
    """``refs.adamw_reference`` on one subtree (a layer, or the leaves
    outside the layers): float32 arithmetic, the new parameters rounded to
    and kept in the stored type."""
    store = jax.tree_util.tree_leaves(p)[0].dtype
    new, mu, nu = adamw_reference(_up(p), mu, nu, g, t, hyper)
    return jax.tree_util.tree_map(lambda x: x.astype(store), new), mu, nu


def ref_train_step(params, mu, nu, row, t, hyper, dims, lowp=None,
                   on_grads=None):
    """One training step on one row (S + 1,), in place on the dicts
    ``params``, ``mu``, ``nu``: the loss, and each layer's AdamW update as
    soon as its gradients exist (a layer's parameters are not read again
    once it has been differentiated, so the whole tree of gradients never
    lives).  ``on_grads(name, grads)`` sees each subtree of the mean
    gradient before it is used."""
    n_pos = np.float32(1.0 / (row.shape[0] - 1))
    top = ("embed", "ln_f_s", "ln_f_b")

    def sink(n, g):
        g = _tree_scale(g, n_pos)
        if on_grads is not None:
            on_grads(n, g)
        if n is None:
            new = ref_adamw({k: params[k] for k in top},
                            {k: mu[k] for k in top}, {k: nu[k] for k in top},
                            g, t, hyper)
            for k in top:
                params[k], mu[k], nu[k] = new[0][k], new[1][k], new[2][k]
        else:
            params["layers"][n], mu["layers"][n], nu["layers"][n] = \
                ref_adamw(params["layers"][n], mu["layers"][n],
                          nu["layers"][n], g, t, hyper)

    nll, _ = _row_nll_and_grads(params, row, dims, lowp, sink)
    return nll * float(n_pos)


@jax.jit
def _norm(x, y=None):
    d = x.astype(F32) if y is None else x.astype(F32) - y.astype(F32)
    return jnp.sqrt(jnp.sum(jnp.square(d)))


def subtree_norms(n, a, b=None):
    """{leaf name: norm of ``a - b`` (of ``a``)} of one subtree: layer
    ``n``'s dict, or with ``n`` None the leaves outside the layers."""
    prefix = "" if n is None else f"layers.{n}."
    return {prefix + k: float(_norm(v) if b is None else _norm(v, b[k]))
            for k, v in a.items()}


def leaf_norm_dict(a, b=None):
    """{leaf name: Euclidean norm of ``a - b`` (of ``a``)} in float32, a
    leaf at a time, over the program's tree."""
    top = lambda t: {k: v for k, v in t.items() if k != "layers"}
    out = subtree_norms(None, top(a), None if b is None else top(b))
    for i, layer in enumerate(a["layers"]):
        out.update(subtree_norms(i, layer,
                                 None if b is None else b["layers"][i]))
    return out
