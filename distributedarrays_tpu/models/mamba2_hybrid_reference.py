"""The plain reference of ``models/mamba2_hybrid.py``: the layer equations in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernel, no recomputation.  The state-space part is the recurrence by
its definition, one position after the other (``lax.scan``), never the
chunked decomposition the kernels use; attention is the explicit causal
softmax.  Differentiable by ``jax.grad`` as it stands; meant for small
sizes (the scores are held whole).  Takes the program's parameter tree and
``Config``.

Departures from the published modelling code (``GraniteMoeHybrid`` with
its Mamba-2 mixer): none in the equations.  Left out as they do nothing
in this configuration: the routed experts (``num_local_experts`` 0), the
dt clamp (the published ``time_step_limit`` is (0, inf)), rotary
positions (``position_embedding_type`` ``nope``), the cache and padding
masks.  What the configuration does not state is taken as the program
takes it (``assumed`` in the benchmark's configuration file): the split
order of the input projection and of xBC, the gated norm over all
``d_inner`` channels after the gate, heads grouped consecutively.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .mamba2_hybrid import Config

__all__ = ["ssd_scan", "forward", "loss_fn"]


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * s


def ssd_scan(x, dt, a, bm, cm):
    """``y`` (S, H, P) of ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = h_t C_t`` one position after the other: ``x`` (S, H, P), ``dt``
    (S, H), ``a`` (H,), ``bm, cm`` (S, G, N), head ``h`` reading group
    ``h // (H / G)``."""
    H, G = x.shape[1], bm.shape[1]
    bh, ch = (jnp.repeat(t, H // G, axis=1) for t in (bm, cm))

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (jnp.exp(dtt * a)[:, None, None] * h
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, ct)

    h0 = jnp.zeros((H, x.shape[2], bm.shape[2]), x.dtype)
    return jax.lax.scan(step, h0, (x, dt, bh, ch))[1]


def _mamba(u, p, cfg):
    S = u.shape[0]
    E, Hs, P = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim
    G, N, K = cfg.n_groups, cfg.d_state, cfg.d_conv
    z, xbc, dt = jnp.split(u @ p["in_proj"], [E, 2 * E + 2 * G * N], axis=-1)
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_w"][k] * xp[k:k + S] for k in range(K))
                      + p["conv_b"])
    x, bm, cm = jnp.split(xbc, [E, E + G * N], axis=-1)
    x = x.reshape(S, Hs, P)
    y = ssd_scan(x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                 bm.reshape(S, G, N), cm.reshape(S, G, N))
    y = (y + p["D_skip"][:, None] * x).reshape(S, E)
    return _rms(y * jax.nn.silu(z), p["norm_gated"], cfg.eps) @ p["out_proj"]


def _attention(u, p, cfg):
    S = u.shape[0]
    H, KV, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    q, k, v = jnp.split(u @ p["wqkv"], [H * hd, (H + KV) * hd], axis=-1)
    q = q.reshape(S, H, hd)
    k, v = (jnp.repeat(t.reshape(S, KV, hd), H // KV, axis=1) for t in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) * cfg.attention_mult
    live = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    pr = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", pr, v).reshape(S, H * hd) @ p["wo"]


def _row(params, tok, cfg: Config):
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    params = f32(params)
    r = cfg.residual_mult
    x = params["embed"][tok] * cfg.embedding_mult
    for (_, kind), p in zip(cfg.layers, params["layers"]):
        u = _rms(x, p["norm1"], cfg.eps)
        h = x + r * (_mamba(u, p, cfg) if kind == "mamba"
                     else _attention(u, p, cfg))
        g, v = jnp.split(_rms(h, p["norm2"], cfg.eps) @ p["w1"], 2, axis=-1)
        x = h + r * ((jax.nn.silu(g) * v) @ p["w2"])
    x = _rms(x, params["norm_f"], cfg.eps)
    return x @ params["embed"].T / cfg.logits_scaling


def forward(params, tokens, cfg: Config):
    """Logits (B, S, vocab) in float32 for token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_row(params, t, cfg) for t in tokens])


def loss_fn(params, tokens, cfg: Config):
    """Mean next-token cross-entropy of ``tokens`` (B, S + 1)."""
    logp = jax.nn.log_softmax(forward(params, tokens[:, :-1], cfg), axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
