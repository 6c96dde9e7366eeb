"""The plain reference of ``models/olmo_hybrid.py``: the layer equations in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernel, no recomputation.  The delta rule is the recurrence by its
definition, one position after the other (``lax.scan``), never the chunked
decomposition the kernels use; attention is the explicit causal softmax.
Differentiable by ``jax.grad`` as it stands; meant for small sizes (the
scores are held whole).  Takes the program's parameter tree and
``Config``.

Departures from the published modelling code: none in the equations.
What the configuration does not state is taken as the program takes it
(``assumed`` in the benchmark's configuration file): the post-norm block
and the QK-norm over the whole projection, the convolution without bias,
the L2 norm's eps, the q scale ``dk^-1/2`` and the norm-then-gate order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .olmo_hybrid import Config

__all__ = ["delta_rule", "forward", "loss_fn"]


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * s


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, beta, g):
    """``o`` (S, H, dv) of ``S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) +
    beta_t v_t k_t^T``, ``o_t = S_t q_t``, one position after the other:
    ``q, k`` (S, H, dk), ``v`` (S, H, dv), ``beta, g`` (S, H)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, inp):
        qt, kt, vt, bt, gt = inp
        s = jnp.exp(gt)[:, None, None] * s
        s = s + bt[:, None, None] * jnp.einsum(
            "hv,hk->hvk", vt - jnp.einsum("hvk,hk->hv", s, kt), kt)
        return s, jnp.einsum("hvk,hk->hv", s, qt)

    s0 = jnp.zeros((H, dv, dk), q.dtype)
    return jax.lax.scan(step, s0, (q, k, v, beta, g))[1]


def _linear(u, p, cfg):
    S = u.shape[0]
    H, dk, dv, K = cfg.lin_heads, cfg.key_dim, cfg.value_dim, cfg.d_conv
    qkv, gate = jnp.split(u @ p["w_in"], [2 * H * dk + H * dv], axis=-1)
    xp = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][k] * xp[k:k + S] for k in range(K)))
    q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
    q = _l2(q.reshape(S, H, dk)) * dk ** -0.5
    k = _l2(k.reshape(S, H, dk))
    a, b = jnp.split(u @ p["w_ab"], 2, axis=-1)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = delta_rule(q, k, v.reshape(S, H, dv), 2.0 * jax.nn.sigmoid(b), g)
    o = _rms(o, p["o_norm"], cfg.eps).reshape(S, H * dv)
    return (o * jax.nn.silu(gate)) @ p["w_o"]


def _attention(u, p, cfg):
    S = u.shape[0]
    H, hd = cfg.heads, cfg.head_dim
    q, k, v = jnp.split(u @ p["w_qkv"], 3, axis=-1)
    q = _rms(q, p["q_norm"], cfg.eps).reshape(S, H, hd)
    k = _rms(k, p["k_norm"], cfg.eps).reshape(S, H, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    live = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    pr = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, v.reshape(S, H, hd))
    return o.reshape(S, H * hd) @ p["w_o"]


def _row(params, tok, cfg: Config):
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    params = f32(params)
    x = params["embed"][tok]
    for (_, kind), p in zip(cfg.layers, params["layers"]):
        mix = _linear if kind == "linear_attention" else _attention
        h = x + _rms(mix(x, p, cfg), p["post_mix_norm"], cfg.eps)
        g, v = jnp.split(h @ p["w1"], 2, axis=-1)
        x = h + _rms((jax.nn.silu(g) * v) @ p["w2"], p["post_mlp_norm"],
                     cfg.eps)
    x = _rms(x, params["norm_f"], cfg.eps)
    return x @ params["head"].T


def forward(params, tokens, cfg: Config):
    """Logits (B, S, vocab) in float32 for token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_row(params, t, cfg) for t in tokens])


def loss_fn(params, tokens, cfg: Config):
    """Mean next-token cross-entropy of ``tokens`` (B, S + 1)."""
    logp = jax.nn.log_softmax(forward(params, tokens[:, :-1], cfg), axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
