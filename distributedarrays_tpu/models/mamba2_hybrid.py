"""A hybrid of Mamba-2 layers and grouped-query attention in the manner of
IBM's Granite 4.0-H (``model_type`` ``granitemoehybrid`` with no routed
experts): every layer is ``h = x + r Mixer(RMS(x))``, ``y = h + r
MLP(RMS(h))``, the mixer a Mamba-2 layer or NoPE attention, the MLP a
gated SiLU one, with the muP multipliers of the published configuration:
the embedding times ``embedding_mult``, every residual branch times ``r``
(``residual_mult``), the attention scores times ``attention_mult`` and the
logits over ``logits_scaling``.  RMSNorm with a learned scale; the head is
tied to the embedding.

- ``mamba``: ``[z | xBC | dt] = u W_in``; ``xBC = SiLU(causal depthwise
  conv(xBC) + b)`` over x, B and C together; ``[x | B | C] = xBC``; ``y =
  SSD(x, softplus(dt + dt_bias), -exp(A_log), B, C) + D x`` by the chunked
  state-space-duality kernels (``ops.pallas_ssd``), one scalar decay a
  head, B and C shared by the heads of a group; ``out = RMS(y SiLU(z))
  W_out`` with the norm over all ``d_inner`` channels.
- ``attention``: ``softmax(q k^T attention_mult) v`` through the flash
  kernels (``ops.pallas_attention``), causal, no positional term, grouped
  heads (query head ``h`` reads K/V head ``h // (heads / kv_heads)``), no
  biases.

``Config.layers`` names the layers kept as (published index, kind).
Training: every layer runs under ``jax.checkpoint`` (its input and the
products ``_KEEP`` names are all that the backward keeps of it; the rest
is computed again there), the loss is taken over row blocks of the sequence
(``common.blocked_nll``), and ``make_optax_train_step`` goes through the
float32-master step of ``models.common``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas_attention import flash_attention
from ..ops.pallas_ssd import ssd
from . import common
from .common import (blocked_nll, fold, gated_silu, init_layers, init_leaf,
                     rms_norm, unfold)

__all__ = ["Config", "KINDS", "SCOPES", "leaf_shapes", "init_params",
           "forward", "loss_fn", "make_optax_train_step"]

KINDS = ("mamba", "attention")
# the phases the ``jax.named_scope``s below declare, as they nest (for the
# compiled step's phase map, ``telemetry/programs.py``)
SCOPES = ("embed", "block/mamba", "block/attn", "block/mlp", "head_loss",
          "optimizer")
# What a recomputed layer keeps of its forward: the MLP's up-projection and
# the mixer's input projection (PERF.md, section 4: the memory and time
# each costs and saves at the benchmark's size).
_KEEP = jax.checkpoint_policies.save_only_these_names("mlp_up", "mix_in")


class Config:
    """Widths, multipliers (the published ones by default) and the layers
    kept.  ``layers`` is a tuple of (published index, kind)."""

    def __init__(self, vocab=256, dim=128, ffn=256, heads=4, kv_heads=2,
                 head_dim=32, ssm_heads=4, ssm_head_dim=32, d_state=16,
                 n_groups=1, d_conv=4, chunk=256, layers=None,
                 embedding_mult=12.0, residual_mult=0.22,
                 attention_mult=0.015625, logits_scaling=8.0, eps=1e-5,
                 loss_rows=2048, dtype=jnp.bfloat16):
        self.vocab, self.dim, self.ffn = int(vocab), int(dim), int(ffn)
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        self.head_dim = int(head_dim)
        self.ssm_heads, self.ssm_head_dim = int(ssm_heads), int(ssm_head_dim)
        self.d_inner = self.ssm_heads * self.ssm_head_dim
        self.d_state, self.n_groups = int(d_state), int(n_groups)
        self.d_conv, self.chunk = int(d_conv), int(chunk)
        self.layers = tuple((int(i), str(k)) for i, k in (
            layers if layers is not None else ((0, "mamba"), (1, "attention"))))
        self.embedding_mult = float(embedding_mult)
        self.residual_mult = float(residual_mult)
        self.attention_mult = float(attention_mult)
        self.logits_scaling = float(logits_scaling)
        self.eps, self.loss_rows = float(eps), int(loss_rows)
        self.dtype = jnp.dtype(dtype)
        for _, kind in self.layers:
            if kind not in KINDS:
                raise ValueError(f"layer kind {kind!r} is not one of {KINDS}")
        if self.heads % self.kv_heads or self.ssm_heads % self.n_groups:
            raise ValueError("kv_heads must divide heads and n_groups "
                             "ssm_heads")

    def _key(self):
        return (self.vocab, self.dim, self.ffn, self.heads, self.kv_heads,
                self.head_dim, self.ssm_heads, self.ssm_head_dim,
                self.d_state, self.n_groups, self.d_conv, self.chunk,
                self.layers, self.embedding_mult, self.residual_mult,
                self.attention_mult, self.logits_scaling, self.eps,
                self.loss_rows, str(self.dtype))

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Config) and self._key() == other._key()


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def leaf_shapes(cfg: Config, kind: str):
    """{leaf: (shape, fan_in or how it starts)} of one layer of ``kind``."""
    D, F, E = cfg.dim, cfg.ffn, cfg.d_inner
    out = {"norm1": ((D,), "ones"), "norm2": ((D,), "ones"),
           "w1": ((D, 2 * F), D), "w2": ((F, D), F)}
    if kind == "mamba":
        Hs, conv = cfg.ssm_heads, E + 2 * cfg.n_groups * cfg.d_state
        out.update(in_proj=((D, E + conv + Hs), D),
                   conv_w=((cfg.d_conv, conv), cfg.d_conv),
                   conv_b=((conv,), "zeros"), dt_bias=((Hs,), "dt_bias"),
                   A_log=((Hs,), "A_log"), D_skip=((Hs,), "ones"),
                   norm_gated=((E,), "ones"), out_proj=((E, D), E))
    else:
        qw, kvw = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        out.update(wqkv=((D, qw + 2 * kvw), D), wo=((qw, D), qw))
    return out


# the Mamba-2 family's A: 1..heads
_STARTS = {"A_log": lambda key, shape, dtype: jnp.log(
    jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(dtype)}


def init_params(key, cfg: Config):
    """{"embed", "norm_f", "layers": [{...}]}: matrices normal with
    deviation 1/sqrt(fan_in), norm scales 1, the convolution's bias 0, and
    the Mamba-2 family's defaults for ``A_log`` (log 1..heads), ``D_skip``
    (1) and ``dt_bias``."""
    dt = cfg.dtype
    return {"embed": init_leaf(jax.random.fold_in(key, 0),
                               (cfg.vocab, cfg.dim), cfg.dim, dt),
            "norm_f": jnp.ones((cfg.dim,), dt),
            "layers": init_layers(key, cfg, leaf_shapes, 1, _STARTS)}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def _mamba(u, p, cfg):
    B, S, _ = u.shape
    E, Hs, P = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim
    G, N, K = cfg.n_groups, cfg.d_state, cfg.d_conv
    z, xbc, dt = jnp.split(checkpoint_name(u @ p["in_proj"], "mix_in"),
                           [E, 2 * E + 2 * G * N], axis=-1)
    xp = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(w[k] * xp[:, k:k + S] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32)).astype(u.dtype)
    x, bm, cm = jnp.split(xbc, [E, E + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))      # (B, S, Hs)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = x.reshape(B, S, Hs, P)
    y = ssd(fold(xh), fold(dt[..., None])[..., 0], jnp.tile(a, B),
            fold(bm.reshape(B, S, G, N)), fold(cm.reshape(B, S, G, N)),
            chunk=cfg.chunk)
    y = unfold(y, B) + (p["D_skip"].astype(jnp.float32)[:, None]
                        * xh.astype(jnp.float32))
    g = y.reshape(B, S, E) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.eps)
    g = g * p["norm_gated"].astype(jnp.float32)
    return g.astype(u.dtype) @ p["out_proj"]


def _attention(u, p, cfg):
    B, S, _ = u.shape
    H, KV, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    q, k, v = jnp.split(checkpoint_name(u @ p["wqkv"], "mix_in"),
                        [H * hd, (H + KV) * hd], axis=-1)
    # the kernels read (B, S, heads, hd) in place, two heads of 64 a block
    o = flash_attention(q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd),
                        v.reshape(B, S, KV, hd), causal=True,
                        scale=cfg.attention_mult)
    return o.reshape(B, S, H * hd) @ p["wo"]


def _layer(x, p, *, kind, cfg):
    r = cfg.residual_mult
    mixer = (jax.named_scope("mamba"), _mamba) if kind == "mamba" else \
        (jax.named_scope("attn"), _attention)
    with jax.named_scope("block"), mixer[0]:
        h = x + r * mixer[1](rms_norm(x, p["norm1"], cfg.eps), p, cfg)
    with jax.named_scope("block"), jax.named_scope("mlp"):
        # the up-projection is kept by a recomputed layer (``_KEEP``)
        return h + r * gated_silu(rms_norm(h, p["norm2"], cfg.eps),
                                  p["w1"], p["w2"], "mlp_up")


def _trunk(params, tok, cfg: Config, remat: bool):
    with jax.named_scope("embed"):
        x = (params["embed"][tok].astype(jnp.float32)
             * cfg.embedding_mult).astype(cfg.dtype)
    for (_, kind), p in zip(cfg.layers, params["layers"]):
        fn = functools.partial(_layer, kind=kind, cfg=cfg)
        if remat:
            fn = jax.checkpoint(fn, policy=_KEEP)
        x = fn(x, p)
    return rms_norm(x, params["norm_f"], cfg.eps)


def forward(params, tokens, cfg: Config):
    """Logits (B, S, vocab) in float32 for token ids (B, S): the tied head,
    over ``logits_scaling``."""
    x = _trunk(params, tokens, cfg, remat=False)
    with jax.named_scope("head_loss"):
        return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                          preferred_element_type=jnp.float32
                          ) / cfg.logits_scaling


def loss_fn(params, tokens, cfg: Config):
    """Mean next-token cross-entropy of ``tokens`` (B, S + 1) over all S
    positions, the ids and the logits over the ``cfg.vocab`` rows held
    here.  Layers are recomputed in the backward, and the logits exist one
    block of ``cfg.loss_rows`` positions at a time, in both directions;
    ``logits_scaling`` divides the final norm's output before the head
    (exact for a power of two, as the published 8 is)."""
    tok, tgt = tokens[:, :-1], tokens[:, 1:]
    x = _trunk(params, tok, cfg, remat=True)
    with jax.named_scope("head_loss"):
        x = (x.astype(jnp.float32) / cfg.logits_scaling).astype(x.dtype)
        return blocked_nll(x, params["embed"], tgt, cfg.loss_rows) / tgt.size


def make_optax_train_step(cfg: Config, tx):
    """``(step, init)`` as ``models.common.make_optax_train_step`` gives
    them, for this model: one jit of ``value_and_grad(loss_fn)`` and
    ``tx.update`` in float32 master precision with donated state."""
    return common.make_optax_train_step(loss_fn, cfg, tx, SCOPES)
