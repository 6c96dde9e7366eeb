"""A hybrid of gated delta-rule linear attention and full attention in the
manner of AllenAI's Olmo Hybrid (``model_type`` ``olmo_hybrid``): every
layer is post-norm, ``h = x + RMS(Mixer(x))``, ``y = h + RMS(MLP(h))``,
with no norm on a sublayer's input; the mixer is a Gated DeltaNet layer or
NoPE full attention with QK-norm, the MLP a gated SiLU one.  RMSNorm with
a learned scale; the embedding and the head are untied.

- ``linear_attention``: ``[q | k | v] = SiLU(causal depthwise conv([u W_q
  | u W_k | u W_v]))`` (no bias); per head ``q <- q / |q| dk^-1/2`` and ``k
  <- k / |k|``; ``beta = 2 sigmoid(u W_b)`` (eigenvalues reach into (-1,
  0)); ``g = -exp(A_log) softplus(u W_a + dt_bias)``; the gated delta rule
  ``S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``, ``o_t
  = S_t q_t`` by the chunked kernels (``ops.pallas_gated_delta``); ``out =
  [RMS_head(o) SiLU(u W_gate)] W_o``, the norm over each head's values
  with one scale the heads share.
- ``full_attention``: ``q = RMS(u W_q)``, ``k = RMS(u W_k)`` each over the
  whole projection, ``v = u W_v``, causal ``softmax(q k^T / sqrt(D)) v``
  through the flash kernels (``ops.pallas_attention``) with no positional
  term, then ``W_o``.

``Config.layers`` names the layers kept as (published index, kind).
Training: every layer runs under ``jax.checkpoint`` (its input and the
products ``_KEEP`` names are all that the backward keeps of it; the rest
is computed again there), the loss is taken over row blocks of the sequence
(``common.blocked_nll``), and ``make_optax_train_step`` goes through the
float32-master step of ``models.common``.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.pallas_attention import flash_attention
from ..ops.pallas_gated_delta import gated_delta
from . import common
from .common import (blocked_nll, fold, gated_silu, init_layers, init_leaf,
                     rms_norm, unfold)

__all__ = ["Config", "KINDS", "SCOPES", "leaf_shapes", "init_params",
           "forward", "loss_fn", "make_optax_train_step"]

KINDS = ("linear_attention", "full_attention")
# the phases the ``jax.named_scope``s below declare, as they nest (for the
# compiled step's phase map, ``telemetry/programs.py``)
SCOPES = ("embed", "block/linear", "block/attn", "block/mlp", "head_loss",
          "optimizer")
# What a recomputed layer keeps of its forward: the MLP's up-projection,
# named in ``_layer``, and the delta rule's chunk solves,
# named in ``pallas_gated_delta._gdn_fwd`` (62,914,560 bytes a layer at
# the cell's widths, so the recomputed forward runs the recurrence alone);
# the mixers' projections are computed again (PERF.md, section 4: the step
# then needs 14.54 GB of the chip).
_KEEP = jax.checkpoint_policies.save_only_these_names("mlp_up", "gdn_solve")
_L2_EPS = 1e-6


class Config:
    """Widths (the published ones by default) and the layers kept.
    ``layers`` is a tuple of (published index, kind)."""

    def __init__(self, vocab=256, dim=128, ffn=256, heads=4, head_dim=32,
                 lin_heads=4, key_dim=16, value_dim=32, d_conv=4, layers=None,
                 eps=1e-6, loss_rows=2048, dtype=jnp.bfloat16):
        self.vocab, self.dim, self.ffn = int(vocab), int(dim), int(ffn)
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.lin_heads = int(lin_heads)
        self.key_dim, self.value_dim = int(key_dim), int(value_dim)
        self.d_conv = int(d_conv)
        self.layers = tuple((int(i), str(k)) for i, k in (
            layers if layers is not None
            else ((0, "linear_attention"), (1, "full_attention"))))
        self.eps, self.loss_rows = float(eps), int(loss_rows)
        self.dtype = jnp.dtype(dtype)
        for _, kind in self.layers:
            if kind not in KINDS:
                raise ValueError(f"layer kind {kind!r} is not one of {KINDS}")

    def _key(self):
        return (self.vocab, self.dim, self.ffn, self.heads, self.head_dim,
                self.lin_heads, self.key_dim, self.value_dim, self.d_conv,
                self.layers, self.eps, self.loss_rows,
                str(self.dtype))

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Config) and self._key() == other._key()


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def leaf_shapes(cfg: Config, kind: str):
    """{leaf: (shape, fan_in or how it starts)} of one layer of ``kind``."""
    D, F = cfg.dim, cfg.ffn
    out = {"post_mix_norm": ((D,), "ones"), "post_mlp_norm": ((D,), "ones"),
           "w1": ((D, 2 * F), D), "w2": ((F, D), F)}
    if kind == "linear_attention":
        H = cfg.lin_heads
        qk, vw = H * cfg.key_dim, H * cfg.value_dim
        out.update(w_in=((D, 2 * qk + 2 * vw), D),
                   conv_w=((cfg.d_conv, 2 * qk + vw), cfg.d_conv),
                   w_ab=((D, 2 * H), D), A_log=((H,), "A_log"),
                   dt_bias=((H,), "dt_bias"),
                   o_norm=((cfg.value_dim,), "ones"), w_o=((vw, D), vw))
    else:
        w = cfg.heads * cfg.head_dim
        out.update(w_qkv=((D, 3 * w), D), q_norm=((w,), "ones"),
                   k_norm=((w,), "ones"), w_o=((w, D), w))
    return out


def _a_log(key, shape, dtype):
    # A uniform in (0, 16] (the Gated DeltaNet family's default)
    a = 16.0 * (1.0 - jax.random.uniform(key, shape, jnp.float32))
    return jnp.log(a).astype(dtype)


def init_params(key, cfg: Config):
    """{"embed", "head", "norm_f", "layers": [{...}]}: matrices normal with
    deviation 1/sqrt(fan_in), norm scales 1, and the Gated DeltaNet
    family's defaults for ``A_log`` and ``dt_bias``."""
    dt = cfg.dtype
    return {"embed": init_leaf(jax.random.fold_in(key, 0),
                               (cfg.vocab, cfg.dim), cfg.dim, dt),
            "head": init_leaf(jax.random.fold_in(key, 1),
                              (cfg.vocab, cfg.dim), cfg.dim, dt),
            "norm_f": jnp.ones((cfg.dim,), dt),
            "layers": init_layers(key, cfg, leaf_shapes, 2,
                                  {"A_log": _a_log})}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _linear(u, p, cfg):
    B, S, _ = u.shape
    H, dk, dv, K = cfg.lin_heads, cfg.key_dim, cfg.value_dim, cfg.d_conv
    qkv, gate = jnp.split(u @ p["w_in"], [2 * H * dk + H * dv], axis=-1)
    xp = jnp.pad(qkv.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(jnp.float32)
    qkv = jax.nn.silu(sum(w[k] * xp[:, k:k + S] for k in range(K)))
    q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
    q = _l2(q.reshape(B, S, H, dk)) * np.float32(dk ** -0.5)
    k = _l2(k.reshape(B, S, H, dk))
    a, b = jnp.split((u @ p["w_ab"]).astype(jnp.float32), 2, axis=-1)
    beta = 2.0 * jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + p["dt_bias"].astype(jnp.float32))
    o = gated_delta(fold(q.astype(u.dtype)), fold(k.astype(u.dtype)),
                    fold(v.reshape(B, S, H, dv).astype(u.dtype)),
                    fold(beta[..., None])[..., 0], fold(g[..., None])[..., 0])
    o = rms_norm(unfold(o, B), p["o_norm"], cfg.eps).reshape(B, S, H * dv)
    o = o * jax.nn.silu(gate.astype(jnp.float32))
    return o.astype(u.dtype) @ p["w_o"]


def _attention(u, p, cfg):
    B, S, _ = u.shape
    H, hd = cfg.heads, cfg.head_dim
    q, k, v = jnp.split(u @ p["w_qkv"], 3, axis=-1)
    q = rms_norm(q, p["q_norm"], cfg.eps)
    k = rms_norm(k, p["k_norm"], cfg.eps)
    o = flash_attention(q.reshape(B, S, H, hd), k.reshape(B, S, H, hd),
                        v.reshape(B, S, H, hd), causal=True,
                        scale=1.0 / math.sqrt(hd))
    return o.reshape(B, S, H * hd) @ p["w_o"]


def _layer(x, p, *, kind, cfg):
    mixer = (jax.named_scope("linear"), _linear) \
        if kind == "linear_attention" else (jax.named_scope("attn"), _attention)
    with jax.named_scope("block"), mixer[0]:
        h = x + rms_norm(mixer[1](x, p, cfg), p["post_mix_norm"], cfg.eps)
    with jax.named_scope("block"), jax.named_scope("mlp"):
        # the up-projection is kept by a recomputed layer (``_KEEP``)
        return h + rms_norm(gated_silu(h, p["w1"], p["w2"], "mlp_up"),
                            p["post_mlp_norm"], cfg.eps)


def _trunk(params, tok, cfg: Config, remat: bool):
    with jax.named_scope("embed"):
        x = params["embed"][tok]
    for (_, kind), p in zip(cfg.layers, params["layers"]):
        fn = functools.partial(_layer, kind=kind, cfg=cfg)
        if remat:
            fn = jax.checkpoint(fn, policy=_KEEP)
        x = fn(x, p)
    return rms_norm(x, params["norm_f"], cfg.eps)


def forward(params, tokens, cfg: Config):
    """Logits (B, S, vocab) in float32 for token ids (B, S): the untied
    head."""
    x = _trunk(params, tokens, cfg, remat=False)
    with jax.named_scope("head_loss"):
        return jnp.einsum("bsd,vd->bsv", x, params["head"],
                          preferred_element_type=jnp.float32)


def loss_fn(params, tokens, cfg: Config):
    """Mean next-token cross-entropy of ``tokens`` (B, S + 1) over all S
    positions, the ids and the logits over the ``cfg.vocab`` rows held
    here.  Layers are recomputed in the backward, and the logits exist one
    block of ``cfg.loss_rows`` positions at a time, in both directions."""
    tok, tgt = tokens[:, :-1], tokens[:, 1:]
    x = _trunk(params, tok, cfg, remat=True)
    with jax.named_scope("head_loss"):
        return blocked_nll(x, params["head"], tgt, cfg.loss_rows) / tgt.size


def make_optax_train_step(cfg: Config, tx):
    """``(step, init)`` as ``models.common.make_optax_train_step`` gives
    them, for this model: one jit of ``value_and_grad(loss_fn)`` and
    ``tx.update`` in float32 master precision with donated state."""
    return common.make_optax_train_step(loss_fn, cfg, tx, SCOPES)
