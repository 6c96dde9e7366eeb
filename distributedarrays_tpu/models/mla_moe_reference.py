"""The plain reference of ``models/mla_moe.py``: the layer equations of the
DeepSeek-V3 family's block as GLM-4.7-Flash (``glm4_moe_lite``) has it,
written straightforwardly.

float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernel, no sort, no recomputation, nothing of the program but its parameter
tree: attention is masked dense scores, every held expert is applied to
every token and weighed by a mask, the loss and the gradients come from
``jax.grad``.

Every layer: ``h = x + MLA(RMS1(x))``, ``y = h + FFN(RMS2(h))``; ``RMS(x) =
x / sqrt(mean(x^2) + eps) * scale``; final RMSNorm, then the head (untied).

- MLA: ``cq = RMS(u Wqa)``; ``q = cq Wqb``, a head ``[q_nope, q_rot]``;
  ``[ckv, kr] = u Wkva``; ``ckv = RMS(ckv)``; a head ``[k_nope, v] = ckv
  Wkvb``; ``RoPE`` on ``q_rot`` and ``kr``: the complex number ``x[2i] + i
  x[2i+1]`` of position ``s`` times ``exp(i s theta^(-2i/R))``; ``k =
  [k_nope, kr]`` with the one ``kr`` for all heads; ``o = softmax(causal(q
  k^T / sqrt(nope + rope))) v``; ``out = concat(o) Wo``.
- dense FFN: ``(SiLU(u Wg) * (u Wu)) Wd`` with ``W1 = [Wg, Wu]``.
- expert layer: ``s = sigmoid(u Wr)``; the ``top_k`` experts with the
  largest ``s + b`` (ties to the lower index; no gradient reaches ``b``);
  ``w_e = scale * s_e / (sum of the chosen s + 1e-20)``; ``y = sum over the
  chosen AND HELD e of w_e E_e(u) + E_shared(u)``.
- MTP: ``h'_i = [RMS_e(Emb(t_{i+1})), RMS_h(x_i)] Weh`` on the trunk's
  output before the final norm, one expert block, a norm of its own, the
  same embedding and head, cross-entropy against ``t_{i+2}``; ``loss =
  L_main + lambda L_mtp``.

Departures from the published description: the deployment's cut alone (the
absent experts' part of the sum is left out, the vocabulary is the slice
held); the selection bias is not updated from the load (``noaux_tc``'s
rule is no part of the published modelling code).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["forward", "loss_parts", "loss_fn", "loss_and_grads",
           "expert_layer", "chosen_experts"]

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x (S, heads, R): pairs (2i, 2i + 1) as complex numbers, turned."""
    S, _, R = x.shape
    ang = (jnp.arange(S, dtype=F32)[:, None]
           * F32(theta) ** (-jnp.arange(0, R, 2, dtype=F32) / R))[:, None, :]
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(
        jax.lax.complex(jnp.zeros_like(ang), ang))
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def _mla(u, p, cfg):
    S = u.shape[0]
    H, N, R, V = cfg.heads, cfg.nope, cfg.rope, cfg.v_dim
    q = (_rms(u @ p["wqa"], p["q_norm"], cfg.eps) @ p["wqb"]
         ).reshape(S, H, N + R)
    kva = u @ p["wkva"]
    ckv = _rms(kva[:, :cfg.kv_rank], p["kv_norm"], cfg.eps)
    kr = _rope(kva[:, cfg.kv_rank:].reshape(S, 1, R), cfg.rope_theta)
    kv = (ckv @ p["wkvb"]).reshape(S, H, N + V)
    q = jnp.concatenate([q[..., :N], _rope(q[..., N:], cfg.rope_theta)], -1)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(kr, (S, H, R))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / F32(math.sqrt(N + R))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), kv[..., N:])
    return o.reshape(S, H * V) @ p["wo"]


def _gated(u, w1, w2):
    g, v = jnp.split(u @ w1, 2, axis=-1)
    return (jax.nn.silu(g) * v) @ w2


def chosen_experts(u, p, cfg):
    """``(idx, w)`` (S, top_k): the chosen experts and their weights."""
    s = jax.nn.sigmoid(u @ p["router"])
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]),
                           cfg.top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, cfg.route_scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def expert_layer(u, p, cfg, shared: bool = True):
    """The expert layer's result for ``u`` (S, D): each held expert on
    every token, weighed by the weight the token gave it (0 where it was
    not chosen), and the shared expert."""
    idx, w = chosen_experts(u, p, cfg)
    y = _gated(u, p["sw1"], p["sw2"]) if shared else jnp.zeros_like(u)
    for j in range(cfg.held[1]):
        w_e = jnp.sum(jnp.where(idx == cfg.held[0] + j, w, 0.0), axis=-1)
        y = y + w_e[:, None] * _gated(u, p["ew1"][j], p["ew2"][j])
    return y


def _layer(x, p, kind, cfg):
    h = x + _mla(_rms(x, p["ln1"], cfg.eps), p, cfg)
    u = _rms(h, p["ln2"], cfg.eps)
    if kind == "dense":
        return h + _gated(u, p["w1"], p["w2"])
    return h + expert_layer(u, p, cfg)


def _trunk(params, tok, cfg):
    x = params["embed"][tok]
    for (_, kind), p in zip(cfg.layers, params["layers"]):
        x = _layer(x, p, kind, cfg)
    return x


def _mtp(params, x, tok_next, cfg):
    m = params["mtp"]
    both = jnp.concatenate([_rms(params["embed"][tok_next], m["enorm"],
                                 cfg.eps), _rms(x, m["hnorm"], cfg.eps)], -1)
    return _rms(_layer(both @ m["eh_proj"], m["block"], "moe", cfg),
                m["norm"], cfg.eps)


def _f32(params):
    return jax.tree_util.tree_map(lambda t: t.astype(F32), params)


def forward(params, tokens, cfg, mtp: bool = False):
    """Logits of one row of ids (S,) ((S + 1,) with ``mtp``, then the MTP
    module's logits come second), float32."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        x = _trunk(params, tokens[:-1] if mtp else tokens, cfg)
        main = _rms(x, params["norm_f"], cfg.eps) @ params["head"].T
        if not mtp:
            return main
        return main, _mtp(params, x, tokens[1:], cfg) @ params["head"].T


def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss_parts(params, tokens, cfg):
    """``(L_main, L_mtp)`` over a batch of rows (B, S + 2) ((B, S + 1) and
    0 without the MTP module), a row at a time."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        main = mtp = 0.0
        for row in tokens:
            if cfg.mtp is None:
                main += _nll(forward(params, row[:-1], cfg), row[1:])
                continue
            a, b = forward(params, row[:-1], cfg, mtp=True)
            main += _nll(a, row[1:-1])
            mtp += _nll(b, row[2:])
        n = tokens.shape[0]
        return main / n, jnp.asarray(mtp / n, F32)


def loss_fn(params, tokens, cfg):
    main, mtp = loss_parts(params, tokens, cfg)
    return main + cfg.mtp_lambda * mtp


def loss_and_grads(params, tokens, cfg):
    """The loss and its float32 gradients, the program's tree."""
    return jax.value_and_grad(loss_fn)(_f32(params), tokens, cfg)
