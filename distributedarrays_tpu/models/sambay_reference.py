"""The plain reference of ``models/sambay.py``: the layer equations in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernel, no recomputation, the scan one position after the other,
attention as masked dense scores.  Differentiable by ``jax.grad`` as it
stands; meant for small sizes (the scores and the scan's states are held
whole).  Takes the program's parameter tree and ``Config``.

Departures from the published description: none in the equations.  What
the published configuration does not state (the Mamba sizes, how the heads
pair up, the sub-norm) is taken as ``models/sambay.py`` takes it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .sambay import Config, lambda_init

__all__ = ["forward", "loss_fn"]


def _ln(x, s, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def _mamba(u, p, cfg):
    S = u.shape[0]
    N, R, K = cfg.d_state, cfg.dt_rank, cfg.d_conv
    xs, z = jnp.split(u @ p["in_proj"], 2, axis=-1)
    xp = jnp.pad(xs, ((K - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(p["conv_w"][k] * xp[k:k + S] for k in range(K))
                     + p["conv_b"])
    d, bm, cm = jnp.split(xc @ p["x_proj"], [R, R + N], axis=-1)
    delta = jax.nn.softplus(d @ p["dt_w"] + p["dt_b"])
    a = -jnp.exp(p["A_log"])                                   # (E, N)

    def step(h, inp):
        xt, dt, bt, ct = inp
        h = jnp.exp(dt[:, None] * a) * h + (dt * xt)[:, None] * bt[None, :]
        return h, h @ ct

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (xc, delta, bm, cm))
    m = y + p["D_skip"] * xc
    return (m * jax.nn.silu(z)) @ p["out_proj"], m


def _attention(u, p, index, kind, kv_star, cfg):
    S = u.shape[0]
    H, KV, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    if kind == "cross":
        q = u @ p["wq"] + p["bq"]
        k, v = kv_star
    else:
        q, k, v = jnp.split(u @ p["wqkv"], [H * hd, (H + KV) * hd], axis=-1)
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(S, H // 2, 2, hd)
    kk = k.reshape(S, KV // 2, 2, hd)
    vv = jnp.repeat(v.reshape(S, KV // 2, 2 * hd), H // KV, axis=1)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    live = j <= i
    if kind == "window":
        live = live & (j > i - cfg.window)

    def one(part):
        kp = jnp.repeat(kk[:, :, part], H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q[:, :, part], kp) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, vv)

    lam0 = lambda_init(index)
    lam = (jnp.exp(p["lq1"] @ p["lk1"]) - jnp.exp(p["lq2"] @ p["lk2"])
           + lam0)
    d = one(0) - lam * one(1)
    d = d / jnp.sqrt(jnp.mean(d * d, axis=-1, keepdims=True) + cfg.eps)
    d = d * p["subln"] * (1.0 - lam0)
    return d.reshape(S, H * hd) @ p["wo"] + p["bo"], (k, v)


def _row(params, tok, cfg: Config):
    """The final hidden states (S, D) of one row of token ids."""
    x = params["embed"][tok]
    m_star = kv_star = None
    for (index, kind), p in zip(cfg.layers, params["layers"]):
        u = _ln(x, p["ln1_s"], p["ln1_b"], cfg.eps)
        if kind == "mamba":
            mix, m_star = _mamba(u, p, cfg)
        elif kind == "gmu":
            mix = (m_star * jax.nn.silu(u @ p["wg"])) @ p["wo"]
        else:
            mix, kv = _attention(u, p, index, kind, kv_star, cfg)
            kv_star = kv if kind == "full" else kv_star
        h = x + mix
        g, v = jnp.split(_ln(h, p["ln2_s"], p["ln2_b"], cfg.eps) @ p["w1"],
                         2, axis=-1)
        x = h + (jax.nn.silu(g) * v) @ p["w2"]
    return _ln(x, params["ln_f_s"], params["ln_f_b"], cfg.eps)


def forward(params, tokens, cfg: Config):
    """Logits (B, S, vocab) for token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
        x = jnp.stack([_row(p, row, cfg) for row in tokens])
        return x @ p["embed"].T


def loss_fn(params, tokens, cfg: Config):
    """Mean next-token cross-entropy of ``tokens`` (B, S + 1)."""
    logp = jax.nn.log_softmax(forward(params, tokens[:, :-1], cfg), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
