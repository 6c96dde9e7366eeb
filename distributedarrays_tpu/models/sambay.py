"""A decoder-hybrid-decoder in the manner of SambaY (arXiv:2507.06607, the
architecture of Phi-4-mini-flash-reasoning): five kinds of layer, of which
the later ones read what two earlier ones emitted.

Every layer is ``h = x + Mixer(LN1(x))``, ``y = h + MLP(LN2(h))`` with a
LayerNorm (scale and bias) and a gated SiLU MLP; there is no positional
term; the head is tied to the embedding.  The mixers:

- ``mamba``: a selective state-space layer (``ops.pallas_selective_scan``);
  it also publishes its scan output ``m`` BEFORE the gate.
- ``gmu``: a gated memory unit, ``(m* * SiLU(u Wg)) Wo`` on the ``m`` of the
  nearest Mamba layer before it.
- ``window`` / ``full``: differential attention with grouped heads through
  the flash kernels (``ops.pallas_attention``): two score maps a head pair,
  each against a value head twice as wide, causal, ``window`` keeping the
  last ``cfg.window`` positions; a ``full`` layer publishes its keys and
  values.
- ``cross``: the same attention with only a query and an output
  projection, on the keys and values of the nearest ``full`` layer before
  it.

The gradient of what a layer publishes is the sum over its readers (plain
autodiff).  ``Config.layers`` names the layers kept as (published index,
kind): the index sets differential attention's ``lambda_init``, so a depth
cut keeps each layer's own.  ``layer_kinds`` gives the published layout.

Training: every layer runs under ``jax.checkpoint`` (its input, what it
publishes and two products, the MLP's up-projection and the mixer's input
projection, are all that the backward keeps of it; the rest of the layer
is computed again there), the loss is taken over row blocks of the
sequence so that the float32 logits are never held whole, and
``make_optax_train_step`` goes through the float32-master step of
``models.common``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas_attention import flash_attention
from ..ops.pallas_selective_scan import selective_scan
from . import common
from .common import blocked_nll, gated_silu, init_layers, init_leaf

__all__ = ["Config", "KINDS", "SCOPES", "layer_kinds", "lambda_init",
           "init_params", "forward", "loss_fn", "make_optax_train_step"]

KINDS = ("mamba", "window", "full", "gmu", "cross")
# the phases the ``jax.named_scope``s below declare, as they nest (for the
# compiled step's phase map, ``telemetry/programs.py``)
SCOPES = ("embed", *(f"block/{k}" for k in KINDS), "block/mlp", "head_loss",
          "optimizer")
# What a recomputed layer keeps of its forward: the MLP's up-projection and
# the mixer's input projection.  On the chip at the benchmark's size (PERF.md,
# PR 33) a step read 398.6 ms with nothing kept, 370.4 with the first, 362.2
# with both, for 1.4 GB more of scratch.
_KEEP = jax.checkpoint_policies.save_only_these_names("mlp_up", "mix_in")


def layer_kinds(n_layers: int, mb_per_layer: int = 2):
    """The published layout as ((index, kind), ...): a layer whose index is
    a multiple of ``mb_per_layer`` is of the Mamba kind, the others of the
    attention kind; the first half alternates ``mamba`` and ``window``; the
    second half opens with a ``mamba`` and a ``full`` layer, the two that
    publish, and then alternates ``gmu`` and ``cross``."""
    half = n_layers // 2
    out, full_seen = [], False
    for i in range(n_layers):
        ssm = i % mb_per_layer == 0
        if i < half:
            kind = "mamba" if ssm else "window"
        elif i == half and ssm:
            kind = "mamba"
        elif not ssm and not full_seen:
            kind, full_seen = "full", True
        else:
            kind = "gmu" if ssm else "cross"
        out.append((i, kind))
    return tuple(out)


def lambda_init(index: int) -> float:
    """Differential attention's fixed part of lambda at published depth
    ``index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


class Config:
    """Widths and the layers kept.  ``layers`` is a tuple of (published
    index, kind); the default is the published layout of ``n_layers``."""

    def __init__(self, vocab=256, dim=128, ffn=512, heads=4, kv_heads=2,
                 head_dim=32, window=16, d_state=16, d_conv=4, expand=2,
                 dt_rank=None, layers=None, n_layers=4, mb_per_layer=2,
                 eps=1e-5, loss_rows=2048, dtype=jnp.bfloat16):
        self.vocab, self.dim, self.ffn = int(vocab), int(dim), int(ffn)
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        self.head_dim, self.window = int(head_dim), int(window)
        self.d_state, self.d_conv = int(d_state), int(d_conv)
        self.d_inner = int(expand) * self.dim
        self.dt_rank = int(dt_rank or -(-self.dim // 16))
        self.layers = tuple((int(i), str(k)) for i, k in (
            layers if layers is not None
            else layer_kinds(n_layers, mb_per_layer)))
        self.eps, self.loss_rows = float(eps), int(loss_rows)
        self.dtype = jnp.dtype(dtype)
        if self.heads % 2 or self.kv_heads % 2 \
                or (self.heads // 2) % (self.kv_heads // 2):
            raise ValueError("differential attention pairs the heads: heads "
                             "and kv_heads even, their halves divisible")
        emitted = set()
        for _, kind in self.layers:
            if kind not in KINDS:
                raise ValueError(f"layer kind {kind!r} is not one of {KINDS}")
            need = {"gmu": "mamba", "cross": "full"}.get(kind)
            if need and need not in emitted:
                raise ValueError(f"a {kind} layer needs a {need} layer "
                                 f"before it")
            emitted.add(kind)

    def _key(self):
        return (self.vocab, self.dim, self.ffn, self.heads, self.kv_heads,
                self.head_dim, self.window, self.d_state, self.d_conv,
                self.d_inner, self.dt_rank, self.layers, self.eps,
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
    D, F, E, N, R = cfg.dim, cfg.ffn, cfg.d_inner, cfg.d_state, cfg.dt_rank
    hd = cfg.head_dim
    qw, kvw = cfg.heads * hd, cfg.kv_heads * hd
    out = {"ln1_s": ((D,), "ones"), "ln1_b": ((D,), "zeros"),
           "ln2_s": ((D,), "ones"), "ln2_b": ((D,), "zeros"),
           "w1": ((D, 2 * F), D), "w2": ((F, D), F)}
    if kind == "mamba":
        out.update(in_proj=((D, 2 * E), D), conv_w=((cfg.d_conv, E),
                                                    cfg.d_conv),
                   conv_b=((E,), "zeros"), x_proj=((E, R + 2 * N), E),
                   dt_w=((R, E), R), dt_b=((E,), "dt_bias"),
                   A_log=((E, N), "A_log"), D_skip=((E,), "ones"),
                   out_proj=((E, D), E))
    elif kind == "gmu":
        out.update(wg=((D, E), D), wo=((E, D), E))
    else:
        # the biases of q, k and v are leaves of their own: a key bias
        # shifts every score of a row alike, so its gradient is zero, and
        # an optimizer that normalizes moves it by round-off alone
        out.update(bq=((qw,), "zeros"))
        if kind == "cross":
            out.update(wq=((D, qw), D))
        else:
            out.update(wqkv=((D, qw + 2 * kvw), D), bk=((kvw,), "zeros"),
                       bv=((kvw,), "zeros"))
        out.update({k: ((hd,), "lambda") for k in ("lq1", "lk1", "lq2",
                                                   "lk2")})
        out.update(subln=((2 * hd,), "ones"), wo=((qw, D), qw),
                   bo=((D,), "zeros"))
    return out


# the Mamba family's A (log 1..d_state in every channel) and differential
# attention's lambda vectors
_STARTS = {
    "A_log": lambda key, shape, dtype: jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
        shape).astype(dtype),
    "lambda": lambda key, shape, dtype: (
        0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)}


def init_params(key, cfg: Config):
    """{"embed", "ln_f_s", "ln_f_b", "layers": [{...}]}: matrices normal
    with deviation 1/sqrt(fan_in), norm scales 1, biases 0, and the Mamba
    family's defaults for ``A_log``, ``D_skip`` and ``dt_b``."""
    dt = cfg.dtype
    return {"embed": init_leaf(jax.random.fold_in(key, 0),
                               (cfg.vocab, cfg.dim), cfg.dim, dt),
            "ln_f_s": jnp.ones((cfg.dim,), dt),
            "ln_f_b": jnp.zeros((cfg.dim,), dt),
            "layers": init_layers(key, cfg, leaf_shapes, 1, _STARTS)}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    n = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (n * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _mamba(u, p, cfg):
    """(mixer output, m): ``m`` is the scan's output with its skip term,
    before the gate, in the activations' type."""
    B, S, _ = u.shape
    N, R, K = cfg.d_state, cfg.dt_rank, cfg.d_conv
    xs, z = jnp.split(checkpoint_name(u @ p["in_proj"], "mix_in"), 2,
                      axis=-1)                               # (B, S, E) each
    xp = jnp.pad(xs.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(w[k] * xp[:, k:k + S] for k in range(K))
    xc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32)).astype(u.dtype)
    dbc = xc @ p["x_proj"]
    d, bm, cm = jnp.split(dbc, [R, R + N], axis=-1)
    delta = jax.nn.softplus((d @ p["dt_w"]).astype(jnp.float32)
                            + p["dt_b"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = jnp.stack([selective_scan(xc[b], delta[b], a, bm[b], cm[b])
                   for b in range(B)])
    m = (y + p["D_skip"].astype(jnp.float32) * xc.astype(jnp.float32)
         ).astype(u.dtype)
    gated = (m.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
             ).astype(u.dtype)
    return gated @ p["out_proj"], m


def _gmu(u, p, m_star, cfg):
    gate = jax.nn.silu(checkpoint_name(u @ p["wg"], "mix_in")
                       .astype(jnp.float32))
    return (m_star.astype(jnp.float32) * gate).astype(u.dtype) @ p["wo"]


def _diff_attention(q, k, v, p, index, cfg, window):
    """Differential attention on q (B, S, heads, hd) in the published head
    order and k (B, S, kv_heads, hd), v (B, S, kv_heads / 2, 2 hd) as the
    kernel reads them.  Query heads go pairwise (even, odd); a pair's two
    score maps use the even and the odd head of its K/V pair, and both
    weigh the pair's two value heads side by side.  In the kernel's head
    order, (K/V pair, parity, query pair within it), head ``h`` reads k
    head ``h // 2`` and the wide v head ``h // 4``."""
    B, S, H, hd = q.shape
    G = cfg.kv_heads // 2                        # K/V pairs
    r = H // 2 // G                              # query pairs a K/V pair
    qk = jnp.swapaxes(q.reshape(B, S, G, r, 2, hd), 3, 4).reshape(B, S, H, hd)
    o = flash_attention(qk, k, v, causal=True, window=window)  # (B,S,H,2hd)
    o = o.reshape(B, S, G, 2, r, 2 * hd).astype(jnp.float32)
    o1 = o[:, :, :, 0].reshape(B, S, H // 2, 2 * hd)
    o2 = o[:, :, :, 1].reshape(B, S, H // 2, 2 * hd)
    f32 = lambda name: p[name].astype(jnp.float32)
    lam0 = lambda_init(index)
    lam = (jnp.exp(jnp.sum(f32("lq1") * f32("lk1")))
           - jnp.exp(jnp.sum(f32("lq2") * f32("lk2"))) + lam0)
    d = o1 - lam * o2
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + cfg.eps)
    d = d * f32("subln") * (1.0 - lam0)
    return d.astype(q.dtype).reshape(B, S, H * hd) @ p["wo"] + p["bo"]


def _attention(u, p, index, cfg, kind, kv_star):
    """(mixer output, (k, v)): ``window`` and ``full`` layers project their
    own keys and values; a ``cross`` layer takes ``kv_star``."""
    B, S, _ = u.shape
    H, KV, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    if kind == "cross":
        q = (checkpoint_name(u @ p["wq"], "mix_in")
             + p["bq"]).reshape(B, S, H, hd)
        k, v = kv_star
    else:
        q, k, v = jnp.split(checkpoint_name(u @ p["wqkv"], "mix_in"),
                            [H * hd, (H + KV) * hd], axis=-1)
        q = (q + p["bq"]).reshape(B, S, H, hd)
        k = (k + p["bk"]).reshape(B, S, KV, hd)
        v = (v + p["bv"]).reshape(B, S, KV // 2, 2 * hd)
    window = cfg.window if kind == "window" else None
    return _diff_attention(q, k, v, p, index, cfg, window), (k, v)


def _layer(x, p, m_star, kv_star, *, index, kind, cfg):
    """One layer: (y, m or None, (k, v) or None)."""
    m = kv = None
    with jax.named_scope("block"), jax.named_scope(kind):
        u = _layernorm(x, p["ln1_s"], p["ln1_b"], cfg.eps)
        if kind == "mamba":
            mix, m = _mamba(u, p, cfg)
        elif kind == "gmu":
            mix = _gmu(u, p, m_star, cfg)
        else:
            mix, kv = _attention(u, p, index, cfg, kind, kv_star)
            kv = kv if kind == "full" else None
        h = x + mix
    with jax.named_scope("block"), jax.named_scope("mlp"):
        # the up-projection is kept by a recomputed layer (``_KEEP``)
        y = h + gated_silu(_layernorm(h, p["ln2_s"], p["ln2_b"], cfg.eps),
                           p["w1"], p["w2"], "mlp_up")
    return y, m, kv


def _trunk(params, tok, cfg: Config, remat: bool):
    with jax.named_scope("embed"):
        x = params["embed"][tok].astype(cfg.dtype)
    m_star = kv_star = None
    for (index, kind), p in zip(cfg.layers, params["layers"]):
        fn = functools.partial(_layer, index=index, kind=kind, cfg=cfg)
        if remat:
            fn = jax.checkpoint(fn, policy=_KEEP)
        x, m, kv = fn(x, p, m_star if kind == "gmu" else None,
                      kv_star if kind == "cross" else None)
        m_star = m if m is not None else m_star
        kv_star = kv if kv is not None else kv_star
    return _layernorm(x, params["ln_f_s"], params["ln_f_b"], cfg.eps)


def forward(params, tokens, cfg: Config):
    """Logits (B, S, vocab) in float32 for token ids (B, S); the head is
    the embedding, transposed."""
    x = _trunk(params, tokens, cfg, remat=False)
    with jax.named_scope("head_loss"):
        return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                          preferred_element_type=jnp.float32)


def loss_fn(params, tokens, cfg: Config):
    """Mean next-token cross-entropy of ``tokens`` (B, S + 1) over all S
    positions, the ids and the logits over the ``cfg.vocab`` rows held
    here.  Layers are recomputed in the backward, and the logits exist one
    block of ``cfg.loss_rows`` positions at a time, in both directions."""
    tok, tgt = tokens[:, :-1], tokens[:, 1:]
    x = _trunk(params, tok, cfg, remat=True)
    with jax.named_scope("head_loss"):
        return blocked_nll(x, params["embed"], tgt, cfg.loss_rows) / tgt.size


def make_optax_train_step(cfg: Config, tx):
    """``(step, init)`` as ``models.common.make_optax_train_step`` gives
    them, for this model: one jit of ``value_and_grad(loss_fn)`` and
    ``tx.update`` in float32 master precision with donated state."""
    return common.make_optax_train_step(loss_fn, cfg, tx, SCOPES)
