"""A decoder of the DeepSeek-V3 family's block (the architecture of
GLM-4.7-Flash, ``glm4_moe_lite``): multi-head latent attention with
decoupled rotary keys, gated FFNs of which all but the leading ones are
sigmoid-routed expert layers with a shared expert, and a
multi-token-prediction module, as one chip of an expert-parallel
deployment holds it.

Every layer is ``h = x + MLA(RMS1(x))``, ``y = h + FFN(RMS2(h))``; RMSNorm
with a learned scale, no bias anywhere, the head untied from the
embedding.

- ``MLA``: ``cq = RMS(u Wqa)``, ``q = cq Wqb``, a head being ``[q_nope,
  q_rot]``; ``[ckv, kr] = u Wkva``, ``ckv = RMS(ckv)``, ``[k_nope, v]`` a
  head ``= ckv Wkvb``; rotary positions on ``q_rot`` and on ``kr`` (pairs
  ``(2i, 2i + 1)``), the one ``kr`` shared by all heads; ``k = [k_nope,
  kr]``; causal softmax attention through the flash kernels
  (``ops.pallas_attention``) at the head width ``nope + rope``, the value
  head at its own; ``out = concat(o) Wo``.
- ``dense``: ``(SiLU(u Wg) * (u Wu)) Wd``.
- ``moe``: ``models.moe.held_experts_ffn`` over the experts held here
  (``Config.held``) plus the shared expert every token passes.  What the
  absent experts would add is left out: the partial result goes on.
- MTP: ``h'_i = [RMS_e(Emb(t_{i+1})), RMS_h(x_i)] Weh`` with ``x`` the
  trunk's output before the final norm, one ``moe`` block on ``h'``, a norm
  of its own, the SAME embedding and head, cross-entropy against
  ``t_{i+2}``; ``loss = L_main + mtp_lambda * L_mtp``.

``Config.layers`` names the layers kept as (published index, kind);
``Config.held`` the experts held (first, count) of ``n_experts`` published;
``Config.vocab`` the rows of the published vocabulary held (ids, logits and
both losses are over the slice).

Training: the FFN half of every layer runs under ``jax.checkpoint`` (PERF.md
says what was read for the choice), the losses are taken over row blocks of
the sequence (``common.blocked_nll``), and ``make_optax_train_step`` goes
through the float32-master step of ``models.common``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.pallas_attention import flash_attention
from .common import (blocked_nll, gated_silu, init_layer, init_layers,
                     init_leaf, optax_f32_step, rms_norm)
from .moe import held_experts_ffn, route_sigmoid_topk

__all__ = ["Config", "KINDS", "SCOPES", "published_layers", "rope",
           "init_params", "forward", "loss_parts", "loss_fn",
           "routing_stats", "make_optax_train_step"]

KINDS = ("dense", "moe")
# the phases the ``jax.named_scope``s here and in ``moe.held_experts_ffn``
# declare, as they nest (for the compiled step's phase map,
# ``telemetry/programs.py``); ``mtp`` holds a block and a ``head_loss``
SCOPES = ("embed", "block/mla", "block/mlp", "block/moe/route",
          "block/moe/experts", "block/moe/shared", "mtp", "head_loss",
          "optimizer")
# What the recomputed FFN half of a layer keeps of its forward: the
# up-projection of the dense FFN and of the shared expert (0.6 GB in all at
# the benchmark's size) and the results of the routed experts' two grouped
# products (``models/moe.py``; tokens x top_k rows each, 1.7 GB in all): a
# grouped product's time follows the rows the held experts are sent, which
# under a collapsed routing is the seed's draw, so computing it again made
# the step's time swing by a third more from seed to seed (PERF.md, PR 35).
# With them goes the choice of experts they were computed under
# (``route_idx``): chosen again, a near-tie may turn, and the kept rows
# would be read under a layout they were not written by.
_KEEP = jax.checkpoint_policies.save_only_these_names(
    "ffn_up", "route_idx", "experts_up", "experts_down")


def published_layers(n_layers: int, first_dense: int = 1):
    """The published layout as ((index, kind), ...): ``first_dense`` dense
    layers, then expert layers."""
    return tuple((i, "dense" if i < first_dense else "moe")
                 for i in range(n_layers))


class Config:
    """Widths, the layers kept and the share held.  ``layers`` is a tuple
    of (published index, kind), ``published_layers(n)`` for an uncut
    trunk; ``mtp`` the published index of the MTP module's block (None: no
    module, rows of S + 1 ids)."""

    def __init__(self, vocab=256, dim=128, heads=4, q_rank=48, kv_rank=32,
                 nope=24, rope=8, v_dim=32, ffn=256, moe_ffn=64,
                 n_experts=16, held=None, top_k=4, route_scale=1.8,
                 layers=published_layers(3), mtp=None, mtp_lambda=0.3,
                 rope_theta=1e6, eps=1e-5, loss_rows=2048,
                 dtype=jnp.bfloat16):
        self.vocab = int(vocab)
        self.dim, self.heads = int(dim), int(heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.nope, self.rope, self.v_dim = int(nope), int(rope), int(v_dim)
        self.ffn, self.moe_ffn = int(ffn), int(moe_ffn)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.held = tuple(int(h) for h in (held or (0, n_experts)))
        self.route_scale = float(route_scale)
        self.layers = tuple((int(i), str(k)) for i, k in layers)
        self.mtp = None if mtp is None else int(mtp)
        self.mtp_lambda, self.rope_theta = float(mtp_lambda), float(rope_theta)
        self.eps, self.loss_rows = float(eps), int(loss_rows)
        self.dtype = jnp.dtype(dtype)
        if self.rope % 2:
            raise ValueError("rotary positions pair the key's columns: "
                             "rope even")
        first, count = self.held
        if (first < 0 or count < 1 or first + count > self.n_experts
                or not 1 <= self.top_k <= self.n_experts):
            raise ValueError(f"held experts {self.held} and top_k "
                             f"{self.top_k} do not fit {self.n_experts} "
                             f"experts")
        for _, kind in self.layers:
            if kind not in KINDS:
                raise ValueError(f"layer kind {kind!r} is not one of {KINDS}")

    def _key(self):
        return (self.vocab, self.dim, self.heads, self.q_rank, self.kv_rank,
                self.nope, self.rope, self.v_dim, self.ffn, self.moe_ffn,
                self.n_experts, self.held, self.top_k, self.route_scale,
                self.layers, self.mtp, self.mtp_lambda, self.rope_theta,
                self.eps, self.loss_rows, str(self.dtype))

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Config) and self._key() == other._key()


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def leaf_shapes(cfg: Config, kind: str):
    """{leaf: (shape, fan_in or how it starts)} of one layer of ``kind``."""
    D, H, F, Fe = cfg.dim, cfg.heads, cfg.ffn, cfg.moe_ffn
    out = {"ln1": ((D,), "ones"), "ln2": ((D,), "ones"),
           "wqa": ((D, cfg.q_rank), D), "q_norm": ((cfg.q_rank,), "ones"),
           "wqb": ((cfg.q_rank, H * (cfg.nope + cfg.rope)), cfg.q_rank),
           "wkva": ((D, cfg.kv_rank + cfg.rope), D),
           "kv_norm": ((cfg.kv_rank,), "ones"),
           "wkvb": ((cfg.kv_rank, H * (cfg.nope + cfg.v_dim)), cfg.kv_rank),
           "wo": ((H * cfg.v_dim, D), H * cfg.v_dim)}
    if kind == "dense":
        out.update(w1=((D, 2 * F), D), w2=((F, D), F))
    else:
        n = cfg.held[1]
        out.update(router=((D, cfg.n_experts), D),
                   router_bias=((cfg.n_experts,), "zeros"),
                   ew1=((n, D, 2 * Fe), D), ew2=((n, Fe, D), Fe),
                   sw1=((D, 2 * Fe), D), sw2=((Fe, D), Fe))
    return out


def init_params(key, cfg: Config):
    """{"embed", "head", "norm_f", "layers": [{...}], "mtp": {...}}:
    matrices normal with deviation 1/sqrt(fan_in), norm scales 1, the
    selection bias 0."""
    D, dt = cfg.dim, cfg.dtype
    params = {
        "embed": init_leaf(jax.random.fold_in(key, 0), (cfg.vocab, D), D, dt),
        "head": init_leaf(jax.random.fold_in(key, 1), (cfg.vocab, D), D, dt),
        "norm_f": jnp.ones((D,), dt),
        "layers": init_layers(key, cfg, leaf_shapes, 2)}
    if cfg.mtp is not None:
        mk = jax.random.fold_in(key, len(cfg.layers) + 2)
        params["mtp"] = {
            "enorm": jnp.ones((D,), dt), "hnorm": jnp.ones((D,), dt),
            "eh_proj": init_leaf(jax.random.fold_in(mk, 0), (2 * D, D),
                                 2 * D, dt),
            "block": init_layer(jax.random.fold_in(mk, 1),
                                leaf_shapes(cfg, "moe"), dt),
            "norm": jnp.ones((D,), dt)}
    return params


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def rope(x, theta: float):
    """Rotary positions on ``x`` (..., S, heads, R): the pair of columns
    ``(2i, 2i + 1)`` of position ``s`` turns by ``s * theta**(-2i / R)``."""
    S, R = x.shape[-3], x.shape[-1]
    inv = jnp.float32(theta) ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)                    # (S, 1, R/2)
    pair = x.astype(jnp.float32).reshape(*x.shape[:-1], R // 2, 2)
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _mla(u, p, cfg):
    B, S, _ = u.shape
    H, N, R, V = cfg.heads, cfg.nope, cfg.rope, cfg.v_dim
    cq = rms_norm(u @ p["wqa"], p["q_norm"], cfg.eps)
    q = (cq @ p["wqb"]).reshape(B, S, H, N + R)
    kva = u @ p["wkva"]
    ckv = rms_norm(kva[..., :cfg.kv_rank], p["kv_norm"], cfg.eps)
    kr = rope(kva[..., cfg.kv_rank:].reshape(B, S, 1, R), cfg.rope_theta)
    kv = (ckv @ p["wkvb"]).reshape(B, S, H, N + V)
    q = jnp.concatenate([q[..., :N], rope(q[..., N:], cfg.rope_theta)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(kr, (B, S, H, R))],
                        axis=-1)
    # the scale is 1 / sqrt(nope + rope), the kernel's own default; a head
    # of 256 reaches the kernels through flash_attention's head-major copy,
    # which XLA makes inside the concatenations above
    o = flash_attention(q, k, kv[..., N:], causal=True)
    return o.reshape(B, S, H * V) @ p["wo"]


# the dense FFN and the shared expert; the up-projection is kept by the
# recomputed FFN half (``_KEEP``)
_gated = functools.partial(gated_silu, name="ffn_up")


def _ffn_half(h, p, *, kind, cfg):
    """``h + FFN(RMS2(h))`` of a layer of ``kind``."""
    u = rms_norm(h, p["ln2"], cfg.eps)
    if kind == "dense":
        with jax.named_scope("block"), jax.named_scope("mlp"):
            return h + _gated(u, p["w1"], p["w2"])
    B, S, D = u.shape
    with jax.named_scope("block"), jax.named_scope("moe"):
        routed = held_experts_ffn(
            u.reshape(B * S, D), p["router"], p["router_bias"], p["ew1"],
            p["ew2"], held=cfg.held, k=cfg.top_k,
            scale=cfg.route_scale).reshape(B, S, D)
        with jax.named_scope("shared"):
            return h + routed + _gated(u, p["sw1"], p["sw2"])


def _layer(x, p, *, kind, cfg, remat, note=None):
    """One layer; ``note(h, p)`` sees an expert layer's input (before its
    norm) and parameters (``routing_stats``)."""
    with jax.named_scope("block"), jax.named_scope("mla"):
        h = x + _mla(rms_norm(x, p["ln1"], cfg.eps), p, cfg)
    if note is not None and kind == "moe":
        note(h, p)
    ffn = functools.partial(_ffn_half, kind=kind, cfg=cfg)
    return (jax.checkpoint(ffn, policy=_KEEP) if remat else ffn)(h, p)


def _trunk(params, tok, cfg: Config, remat: bool, note=None):
    """The layers' output BEFORE the final norm (what MTP reads)."""
    with jax.named_scope("embed"):
        x = params["embed"][tok].astype(cfg.dtype)
    for (_, kind), p in zip(cfg.layers, params["layers"]):
        x = _layer(x, p, kind=kind, cfg=cfg, remat=remat, note=note)
    return x


def _mtp_trunk(params, x, tok_next, cfg: Config, remat: bool, note=None):
    """The MTP module on the trunk's output ``x`` (B, S, D) and the ids one
    position on, up to its own norm."""
    m = params["mtp"]
    with jax.named_scope("mtp"):
        e = params["embed"][tok_next].astype(cfg.dtype)
        both = jnp.concatenate([rms_norm(e, m["enorm"], cfg.eps),
                                rms_norm(x, m["hnorm"], cfg.eps)], axis=-1)
        h = _layer(both @ m["eh_proj"], m["block"], kind="moe", cfg=cfg,
                   remat=remat, note=note)
        return rms_norm(h, m["norm"], cfg.eps)


def _logits(x, head):
    with jax.named_scope("head_loss"):
        return jnp.einsum("bsd,vd->bsv", x, head,
                          preferred_element_type=jnp.float32)


def forward(params, tokens, cfg: Config, mtp: bool = False):
    """Logits (B, S, vocab) in float32 for token ids (B, S).  With ``mtp``
    the ids are (B, S + 1) and the MTP module's logits (position ``i``
    predicts ``t_{i+2}``) come second."""
    tok = tokens[:, :-1] if mtp else tokens
    x = _trunk(params, tok, cfg, remat=False)
    main = _logits(rms_norm(x, params["norm_f"], cfg.eps), params["head"])
    if not mtp:
        return main
    return main, _logits(_mtp_trunk(params, x, tokens[:, 1:], cfg, False),
                         params["head"])


def loss_parts(params, tokens, cfg: Config):
    """``(L_main, L_mtp)``: the mean next-token cross-entropy of ``tokens``
    (B, S + 2) over the S positions of the trunk, and the MTP module's
    against the token after the next; ids and logits over the ``cfg.vocab``
    rows held here.  Without a module the rows are (B, S + 1) and ``L_mtp``
    is 0.  The logits exist one block of ``cfg.loss_rows`` positions at a
    time, in both directions."""
    ahead = 1 if cfg.mtp is None else 2
    S = tokens.shape[1] - ahead
    x = _trunk(params, tokens[:, :S], cfg, remat=True)
    rows = x.shape[0] * S
    with jax.named_scope("head_loss"):
        main = blocked_nll(rms_norm(x, params["norm_f"], cfg.eps),
                           params["head"], tokens[:, 1:S + 1],
                           cfg.loss_rows) / rows
    if cfg.mtp is None:
        return main, jnp.zeros((), jnp.float32)
    h = _mtp_trunk(params, x, tokens[:, 1:S + 1], cfg, True)
    with jax.named_scope("mtp"), jax.named_scope("head_loss"):
        return main, blocked_nll(h, params["head"], tokens[:, 2:],
                                 cfg.loss_rows) / rows


def loss_fn(params, tokens, cfg: Config):
    """``L_main + cfg.mtp_lambda * L_mtp`` of ``loss_parts``."""
    main, mtp = loss_parts(params, tokens, cfg)
    return main + cfg.mtp_lambda * mtp


@functools.partial(jax.jit, static_argnames=("cfg",))
def routing_stats(params, tokens, cfg: Config):
    """What the program's own arithmetic routes where, outside any timed
    path: for token ids (B, S) (or (B, S + 1) with the MTP module) a list,
    one entry an expert layer in order (the MTP block last), of
    ``{"chosen": (B * S, top_k) int32, "counts": (n_experts,) int32,
    "held_rows": () int32}``: every token's chosen experts, how many
    token-slots each published expert got, and how many of them fall to
    the experts held here."""
    out = []

    def note(h, p):
        u = rms_norm(h, p["ln2"], cfg.eps).reshape(-1, cfg.dim)
        idx, _ = route_sigmoid_topk(u, p["router"], p["router_bias"],
                                    cfg.top_k, cfg.route_scale)
        counts = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(
            cfg.n_experts)[None, :], axis=0, dtype=jnp.int32)
        first, n = cfg.held
        out.append({"chosen": idx, "counts": counts,
                    "held_rows": jnp.sum(counts[first:first + n])})

    tok = tokens if cfg.mtp is None else tokens[:, :-1]
    x = _trunk(params, tok, cfg, False, note)
    if cfg.mtp is not None:
        _mtp_trunk(params, x, tokens[:, 1:], cfg, False, note)
    return out


def make_optax_train_step(cfg: Config, tx):
    """``(step, init)`` as ``models.common.make_optax_train_step`` gives
    them, for this model: one jit of ``value_and_grad(loss_fn)`` and
    ``tx.update`` in float32 master precision with donated state.  The
    step's third result is the float32 vector ``[loss, L_main, L_mtp]``."""
    def grad_fn(params, tokens):
        def f(p):
            main, mtp = loss_parts(p, tokens, cfg)
            loss = main + cfg.mtp_lambda * mtp
            return loss, jnp.stack([loss, main, mtp])

        (_, parts), g = jax.value_and_grad(f, has_aux=True)(params)
        return parts, g

    return optax_f32_step(tx, grad_fn, SCOPES)
