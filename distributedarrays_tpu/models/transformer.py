"""Flagship model: a GPT-style transformer stack on the framework's kernels.

Composes the pieces this framework provides into one trainable model:

- attention = the Pallas flash kernel (ops/pallas_attention.py), which
  reads q, k, v and writes its result as the (B, S, heads·D) products lie
  (the batch a grid axis) — one kernel call, no vmap, no layout copy, no
  O(S²) score matrix;
- FFN and QKV/projection weights laid out Megatron-style over the ``tp``
  mesh axis (column-parallel up, row-parallel down) so GSPMD inserts the
  contraction psums;
- batch data-parallel over ``dp``; gradients all-reduce over dp
  automatically;
- one jitted train step (cross-entropy on next-token, SGD, donated
  params).

Used by ``__graft_entry__.entry()`` as the flagship forward and by the
multichip dry-run as the dp×tp training step.  For sequence lengths beyond
one chip's HBM, swap the attention call for ``models.ring_attention`` /
``models.ulysses`` — same (S, H, D) contract.
"""

from __future__ import annotations

import functools
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.pallas_attention import flash_attention
from . import common
from .mlp import make_mesh

__all__ = ["init_params", "forward", "loss_fn", "train_step",
           "make_optax_train_step", "generate", "shard_params", "make_mesh",
           "norm", "Config", "SCOPES"]

# the phases this module's ``jax.named_scope``s declare, as they nest:
# what ``telemetry.programs`` places the compiled step's instructions by
SCOPES = ("embed", "block/attn", "block/mlp", "head_loss", "optimizer")


class Config:
    def __init__(self, vocab=256, dim=128, heads=4, layers=2, ffn_mult=4,
                 max_seq=128, dtype=jnp.bfloat16):
        if dim % heads:
            raise ValueError(f"dim {dim} must be divisible by heads {heads}")
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.layers, self.ffn_mult, self.max_seq = layers, ffn_mult, max_seq
        self.dtype = dtype

    def _key(self):
        return (self.vocab, self.dim, self.heads, self.layers,
                self.ffn_mult, self.max_seq, str(self.dtype))

    # value-hashable so jit's static_argnames reuses one compilation per
    # configuration, not per Config instance
    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Config) and self._key() == other._key()


def init_params(key, cfg: Config):
    E, F, H = cfg.dim, cfg.dim * cfg.ffn_mult, cfg.heads
    dt = cfg.dtype

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, dt) * jnp.asarray(
            np.sqrt(1.0 / fan_in), dt)

    keys = iter(jax.random.split(key, 3 + 4 * cfg.layers))
    params = {
        "embed": dense(next(keys), (cfg.vocab, E), E),
        "pos": dense(next(keys), (cfg.max_seq, E), E),
        "ln_f": jnp.ones((E,), dt),
        "head": dense(next(keys), (E, cfg.vocab), E),
        "blocks": [],
    }
    for _ in range(cfg.layers):
        params["blocks"].append({
            "ln1": jnp.ones((E,), dt),
            "qkv": dense(next(keys), (E, 3 * E), E),
            "proj": dense(next(keys), (E, E), E),
            "ln2": jnp.ones((E,), dt),
            "w1": dense(next(keys), (E, F), E),
            "w2": dense(next(keys), (F, E), F),
        })
    return params


def shard_params(params, mesh: Mesh):
    """Megatron layout: qkv/w1 column-parallel (split output features over
    tp), proj/w2 row-parallel (split input features); embeddings and norms
    replicated."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))  # dalint: disable=DAL007 — initial host→mesh parameter placement, no source layout

    out = {
        "embed": put(params["embed"], P(None, None)),
        "pos": put(params["pos"], P(None, None)),
        "ln_f": put(params["ln_f"], P(None)),
        "head": put(params["head"], P(None, "tp")),
        "blocks": [],
    }
    for b in params["blocks"]:
        out["blocks"].append({
            "ln1": put(b["ln1"], P(None)),
            "qkv": put(b["qkv"], P(None, "tp")),
            "proj": put(b["proj"], P("tp", None)),
            "ln2": put(b["ln2"], P(None)),
            "w1": put(b["w1"], P(None, "tp")),
            "w2": put(b["w2"], P("tp", None)),
        })
    return out


def norm(x, scale):
    """This model's RMS norm, eps 1e-6 (``sp_transformer`` shares it)."""
    return common.rms_norm(x, scale, 1e-6)


def _attention(x, blk, heads):
    B, S, E = x.shape
    D = E // heads
    qkv = x @ blk["qkv"]                     # (B, S, 3E): q's, k's, v's

    # pad the sequence to a healthy block multiple (tiny or odd S would
    # force degenerate flash blocks); padded KEYS sit at positions >= S so
    # the causal mask hides them from every real query row, and padded
    # query rows are sliced away below.  Pick the largest block whose
    # padding waste stays under ~1/8 of S — a flat 512 would pad S=513 to
    # 1024 and near-double the attention work
    bs = next(b for b in (512, 256, 128, 64, 32)
              if b == 32 or (-(-S // b) * b - S) * 8 <= S)
    Spad = -(-S // bs) * bs
    qkv = jnp.pad(qkv, ((0, 0), (0, Spad - S), (0, 0)))

    # (B, S, 3E) viewed as (B, S, 3, heads, D): the kernels read q, k and v
    # from the one product as it lies and write its gradient as one array
    # (no slice or layout copy on either side); blocks, the heads a grid
    # step takes and the form of the backward are flash_attention's own
    # choice from the shapes: the padding above only keeps Spad a multiple
    # of what it will pick
    o = flash_attention(qkv.reshape(B, Spad, 3, heads, D), None, None,
                        causal=True)
    return o.reshape(B, Spad, E)[:, :S] @ blk["proj"]


def forward(params, tokens, cfg: Config):
    """tokens: (B, S) int32 → logits (B, S, vocab)."""
    B, S = tokens.shape
    if S > cfg.max_seq:
        raise ValueError(f"sequence length {S} exceeds max_seq {cfg.max_seq}")
    # the scopes name the phases in a profiler trace (docs/telemetry.md);
    # they are metadata only, the arithmetic is what it was
    with jax.named_scope("embed"):
        x = params["embed"][tokens] + params["pos"][:S][None]
    for blk in params["blocks"]:
        with jax.named_scope("block"):
            with jax.named_scope("attn"):
                x = x + _attention(norm(x, blk["ln1"]), blk, cfg.heads)
            with jax.named_scope("mlp"):
                h = norm(x, blk["ln2"])
                x = x + jax.nn.gelu(h @ blk["w1"]) @ blk["w2"]
    with jax.named_scope("head_loss"):
        return (norm(x, params["ln_f"])
                @ params["head"]).astype(jnp.float32)


def loss_fn(params, tokens, cfg: Config):
    """Next-token cross-entropy."""
    logits = forward(params, tokens[:, :-1], cfg)
    with jax.named_scope("head_loss"):
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(ll)


def _decode_attn(h, blk, heads, kc, vc, i, t, max_seq):
    """One decode position through layer ``i``'s attention with the
    stacked (L, B, max_seq, H, D) KV caches updated in place at ``t``.
    Full-cache einsum with a position mask — the standard static-shape
    decode step (small, memory-bound; the flash kernel is for prefill/
    training shapes)."""
    B, _, E = h.shape
    D = E // heads
    qkv = h @ blk["qkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, heads, D).astype(jnp.float32)
    upd = lambda c, val: jax.lax.dynamic_update_slice(
        c, val.reshape(1, B, 1, heads, D).astype(c.dtype), (i, 0, t, 0, 0))
    kc, vc = upd(kc, k), upd(vc, v)
    s = jnp.einsum("bhd,bkhd->bhk", q / np.sqrt(D),
                   kc[i].astype(jnp.float32))
    mask = jnp.arange(max_seq) <= t
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bkhd->bhd", p, vc[i].astype(jnp.float32))
    return (o.reshape(B, 1, E).astype(h.dtype) @ blk["proj"]), kc, vc


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_new", "temperature"))
def generate(params, prompt, n_new: int, cfg: Config,
             temperature: float = 0.0, key=None):
    """Autoregressive generation: ``n_new`` tokens appended to ``prompt``
    (B, S0) int32, returned as (B, S0 + n_new).

    The ENTIRE decode — prompt prefill (teacher-forced through the same
    step) and generation — is one ``lax.scan`` under jit with per-layer
    KV caches as the carry: static shapes, no per-token dispatch, no
    Python in the loop.  ``temperature`` 0 = greedy argmax; > 0 samples
    categorically (``key`` required).  Parameters keep their shardings,
    so the tp/dp layouts of ``shard_params`` decode unchanged.
    """
    B, S0 = prompt.shape
    total = S0 + n_new
    if total > cfg.max_seq:
        raise ValueError(f"prompt {S0} + n_new {n_new} exceeds max_seq "
                         f"{cfg.max_seq}")
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    H, D = cfg.heads, cfg.dim // cfg.heads
    Lb = cfg.layers
    kc = jnp.zeros((Lb, B, cfg.max_seq, H, D), cfg.dtype)
    vc = jnp.zeros_like(kc)
    keys = (jax.random.split(key, max(total - 1, 1)) if key is not None
            else jnp.zeros((max(total - 1, 1), 2), jnp.uint32))

    def step(carry, inputs):
        kc, vc, tok = carry
        t, kt = inputs
        x = (params["embed"][tok][:, None]
             + params["pos"][t][None, None]).astype(cfg.dtype)
        for i, blk in enumerate(params["blocks"]):
            a, kc, vc = _decode_attn(norm(x, blk["ln1"]), blk, H,
                                     kc, vc, i, t, cfg.max_seq)
            x = x + a
            h2 = norm(x, blk["ln2"])
            x = x + jax.nn.gelu(h2 @ blk["w1"]) @ blk["w2"]
        logits = (norm(x[:, 0], params["ln_f"])
                  @ params["head"]).astype(jnp.float32)      # (B, V)
        if temperature > 0.0:
            nxt = jax.random.categorical(kt, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(prompt.dtype)
        # teacher-force while still inside the prompt (index capped at
        # S0-1, so it never reads past the prompt)
        nxt = jnp.where(t + 1 < S0, prompt[:, jnp.minimum(t + 1, S0 - 1)],
                        nxt)
        return (kc, vc, nxt), nxt

    ts = jnp.arange(total - 1)
    (_, _, _), toks = jax.lax.scan(step, (kc, vc, prompt[:, 0]),
                                   (ts, keys[: total - 1]))
    # toks[t] is the token at position t+1
    return jnp.concatenate([prompt[:, :1], jnp.swapaxes(toks, 0, 1)],
                           axis=1)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def train_step(params, tokens, lr, cfg: Config):
    """One SGD step: value_and_grad of ``loss_fn`` + an fp32 update
    (bf16 params upcast for the arithmetic, downcast after) with donated
    buffers; GSPMD inserts the tp psums and dp grad all-reduce."""
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    new = jax.tree_util.tree_map(
        lambda p, gg: (p.astype(jnp.float32) - lr * gg.astype(jnp.float32))
        .astype(p.dtype), params, g)
    return new, loss


def make_optax_train_step(cfg: Config, tx):
    """Training with any optax optimizer under the GSPMD model: one jit
    of value_and_grad + ``tx.update`` in fp32 master precision; XLA lays
    the optimizer state out to match each param's sharding
    (Megatron-sharded qkv/proj/w1/w2 moments stay tp-sharded).  Returns
    ``(step, init)``: ``state = init(params)``, then
    ``step(params, opt_state, tokens) -> (params, opt_state, loss)``."""
    return common.make_optax_train_step(loss_fn, cfg, tx, SCOPES)
