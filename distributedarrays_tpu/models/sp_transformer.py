"""Sequence-parallel transformer: long-context training as ONE shard_map
program per step.

Where ``models/transformer.py`` is the GSPMD flagship (XLA infers the
collectives from shardings), this model is the explicit-SPMD composition
of the framework's round-3 pieces — activations stay sequence-sharded
``(s_loc, e)`` end to end, so the full sequence never materializes on any
chip:

- attention: ``ring_flash_attention_kernel`` (context parallelism — K/V
  blocks ride the ppermute ring through Pallas flash hops, differentiable
  FA2 ring backward);
- FFN: ``tp_ffn`` (ring all-gather GEMM -> gelu -> GEMM + reduce-scatter,
  Megatron sequence-parallel layout, both hops pipelined behind the MXU);
- loss: next-token cross-entropy with the shift crossing rank boundaries
  via one ``pshift`` (each rank fetches its right neighbor's first
  token), masked at the global sequence end, averaged with ``psum``.

Batch folds into the head axis for attention (exact — causality is
per-head) and into the row axis for the FFN (exact — the AG->RS ring
returns each rank's rows to it), so one kernel call covers the batch.

The reference's long-context substrate is its SPMD ring programs
(/root/reference/test/spmd.jl:90-101); this is that substrate promoted to
a trainable model family.  See tests/test_transformer.py for the
dense-oracle gradient tests and ``__graft_entry__.dryrun_multichip`` for
the multi-device training leg.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.collective_matmul import tp_ffn
from ..parallel import collectives as C
from ..parallel.collectives import axis_size as _axis_size
from .ring_attention import (ring_flash_attention_kernel,
                             zigzag_ring_flash_attention_kernel)
from .common import fold, optax_f32_init, optax_f32_step, unfold
from .transformer import Config, norm
from .transformer import init_params as _transformer_init_params

__all__ = ["SPConfig", "init_params", "param_specs", "forward_local",
           "loss_local", "make_train_step", "make_grad_fn",
           "make_optax_train_step"]


class SPConfig(Config):
    """transformer.Config plus the shard_map knobs: ``block_q``/``block_k``
    feed the Pallas flash hops; ``interpret`` forces interpreter mode
    (auto: on for non-TPU backends); ``zigzag`` switches to the
    load-balanced causal layout (rank i holds sequence-chunk pair
    ``(i, 2P-1-i)`` — feed tokens permuted by ``zigzag_order``)."""

    def __init__(self, vocab=256, dim=128, heads=4, layers=2, ffn_mult=4,
                 max_seq=128, dtype=jnp.bfloat16, block_q=None, block_k=None,
                 interpret=None, zigzag=False, head_fold=None):
        # block_q/block_k/head_fold None = take the autotune registry's
        # tuned hop config, falling back to the kernel's 512²/1 default.  The train-step factories
        # resolve the Nones OUTSIDE their cached jits (``_resolve_cfg``)
        # so a tune banked after the first step is picked up, not
        # silently pinned at first trace (ADVICE round-4).
        super().__init__(vocab, dim, heads, layers, ffn_mult, max_seq,
                         dtype)
        self.block_q, self.block_k = block_q, block_k
        self.head_fold = head_fold
        self.interpret = interpret
        self.zigzag = bool(zigzag)

    def _key(self):
        return super()._key() + (self.block_q, self.block_k, self.head_fold,
                                 self.interpret, self.zigzag)


def init_params(key, cfg: SPConfig):
    """Identical pytree to ``transformer.init_params`` (same family, same
    init scheme); ``param_specs`` shards the FFN weights over the sp axis,
    the rest replicated."""
    return _transformer_init_params(key, cfg)


def param_specs(cfg: SPConfig, axis: str = "p"):
    """PartitionSpec pytree mirroring ``init_params``: w1 column-sharded,
    w2 row-sharded over the sp axis (the Megatron layout ``tp_ffn``
    expects), everything else replicated."""
    blk = {"ln1": P(None), "qkv": P(None, None), "proj": P(None, None),
           "ln2": P(None), "w1": P(None, axis), "w2": P(axis, None)}
    return {"embed": P(None, None), "pos": P(None, None), "ln_f": P(None),
            "head": P(None, None), "blocks": [dict(blk)] * cfg.layers}


def forward_local(params, tokens_loc, cfg: SPConfig, axis: str):
    """Per-rank forward inside shard_map.  ``tokens_loc``: ``(b, s_loc)``
    — this rank's sequence chunk: contiguous by default, or the
    ``(i, 2p-1-i)`` chunk pair when ``cfg.zigzag`` (shard tokens
    pre-permuted by ``ring_attention.zigzag_order``).  Returns ``(b,
    s_loc, vocab)`` f32 logits for the rank's positions (same layout as
    the input chunk)."""
    Bt, S_loc = tokens_loc.shape
    H = cfg.heads
    E = cfg.dim
    D = E // H
    p = _axis_size(axis)                  # static at trace time
    if S_loc * p > cfg.max_seq:
        # dynamic_slice would CLAMP out-of-table position reads (silently
        # reusing earlier ranks' embeddings); fail loudly instead, like
        # the dense transformer.forward does
        raise ValueError(
            f"global sequence length {S_loc * p} exceeds max_seq "
            f"{cfg.max_seq}")
    me = lax.axis_index(axis)

    if cfg.zigzag:
        # rank's positions are the chunk pair (me, 2p-1-me), C2 each
        if S_loc % 2:
            raise ValueError(
                f"zigzag needs an even per-rank length, got {S_loc}")
        C2 = S_loc // 2
        ar = jnp.arange(C2)
        idx = jnp.concatenate([me * C2 + ar, (2 * p - 1 - me) * C2 + ar])
        pos = jnp.take(params["pos"], idx, axis=0)
    else:
        pos = lax.dynamic_slice_in_dim(params["pos"], me * S_loc, S_loc, 0)
    x = params["embed"][tokens_loc] + pos[None]          # (b, s_loc, e)

    for blk in params["blocks"]:
        h = norm(x, blk["ln1"])
        qkv = h @ blk["qkv"]                             # (b, s_loc, 3e)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        # (b, s_loc, e) -> (s_loc, b*h, d): batch folds into heads
        heads = lambda t: fold(t.reshape(Bt, S_loc, H, D))
        if cfg.zigzag:
            o = zigzag_ring_flash_attention_kernel(
                heads(q), heads(k), heads(v), axis,
                block_q=cfg.block_q, block_k=cfg.block_k,
                head_fold=cfg.head_fold, interpret=cfg.interpret)
        else:
            o = ring_flash_attention_kernel(
                heads(q), heads(k), heads(v), axis, causal=True,
                block_q=cfg.block_q, block_k=cfg.block_k,
                head_fold=cfg.head_fold, interpret=cfg.interpret)
        o = unfold(o, Bt).reshape(Bt, S_loc, E)
        x = x + o @ blk["proj"]

        h2 = norm(x, blk["ln2"])
        # batch folds into rows: the AG->RS ring returns each rank's rows
        f = tp_ffn(h2.reshape(Bt * S_loc, E), blk["w1"], blk["w2"], axis)
        x = x + f.reshape(Bt, S_loc, E)

    return (norm(x, params["ln_f"]) @ params["head"]).astype(jnp.float32)


def _loss_partial(params, tokens_loc, cfg: SPConfig, axis: str):
    """This rank's share of the next-token CE: local masked total over
    the GLOBAL valid count.  Summing (psum) over ranks gives the global
    mean loss.  Chunk-tail targets live on statically known neighbor
    ranks, so the shift is one ``pshift`` per chunk; the final global
    position has no target and is masked.

    Contiguous layout: rank i's tail target is rank i+1's first token;
    rank p-1's tail is the global end (masked).  Zigzag layout (chunk
    pair ``(i, 2p-1-i)``): chunk i's successor i+1 is rank i+1's FIRST
    chunk (rank p-1's: its own second chunk), and chunk ``2p-1-i``'s
    successor ``2p-i`` is rank i-1's SECOND chunk (rank 0's: the global
    end, masked)."""
    p = _axis_size(axis)
    me = lax.axis_index(axis)
    Bt, S_loc = tokens_loc.shape

    logits = forward_local(params, tokens_loc, cfg, axis)
    if cfg.zigzag:
        C2 = S_loc // 2
        ta, tb = tokens_loc[:, :C2], tokens_loc[:, C2:]
        nxt_a = C.pshift(ta[:, :1], axis, -1)        # rank i+1's chunk-a head
        nxt_a = jnp.where(me == p - 1, tb[:, :1], nxt_a)
        nxt_b = C.pshift(tb[:, :1], axis, 1)         # rank i-1's chunk-b head
        targets = jnp.concatenate([ta[:, 1:], nxt_a, tb[:, 1:], nxt_b],
                                  axis=1)
        end_rank = 0                                 # chunk 2p-1 sits on rank 0
    else:
        nxt_first = C.pshift(tokens_loc[:, :1], axis, -1)
        targets = jnp.concatenate([tokens_loc[:, 1:], nxt_first], axis=1)
        end_rank = p - 1
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    valid = jnp.ones((Bt, S_loc), jnp.float32)
    valid = valid.at[:, -1].set(jnp.where(me == end_rank, 0.0, 1.0))
    # count is data-independent of params; the psum carries no gradient
    count = lax.psum(jnp.sum(valid), axis)
    return jnp.sum(-ll * valid) / count


def loss_local(params, tokens_loc, cfg: SPConfig, axis: str):
    """Global mean next-token CE (psum'd — identical on every rank).
    For training use ``_loss_partial`` under ``value_and_grad`` and psum
    the value afterwards: differentiating THROUGH this psum scales every
    gradient by the axis size (psum's SPMD transpose is another psum)."""
    return lax.psum(_loss_partial(params, tokens_loc, cfg, axis), axis)


def _resolve_cfg(cfg: SPConfig, mesh, axis: str, tokens_shape) -> SPConfig:
    """Resolve ``None`` hop knobs against the autotune registry OUTSIDE
    any cached jit: returns an SPConfig whose block_q/block_k/head_fold
    are concrete, suitable as a program-cache key.  Resolving at trace
    time inside a cached step would pin the registry's state at first
    trace — a tune banked after step 1 would be silently ignored for the
    life of the program (ADVICE round-4; same contract as
    ``tuned_flash_config`` / models/ulysses.py)."""
    if (cfg.block_q is not None and cfg.block_k is not None
            and cfg.head_fold is not None):
        return cfg
    from .ring_attention import tuned_hop_blocks_for
    B, S = tokens_shape
    p = mesh.shape[axis]
    # forward_local's fold: q is (s_loc, b*heads, head_dim) in cfg.dtype;
    # both ring layouts tune under causal=True
    shape = (S // p, B * cfg.heads, cfg.dim // cfg.heads)
    bq, bk, hf = tuned_hop_blocks_for(shape, jnp.dtype(cfg.dtype), True,
                                      cfg.block_q, cfg.block_k)
    if cfg.head_fold is not None:
        hf = cfg.head_fold
    return SPConfig(cfg.vocab, cfg.dim, cfg.heads, cfg.layers,
                    cfg.ffn_mult, cfg.max_seq, cfg.dtype,
                    block_q=int(bq), block_k=int(bk),
                    interpret=cfg.interpret, zigzag=cfg.zigzag,
                    head_fold=int(hf))


def make_grad_fn(mesh, cfg: SPConfig, axis: str = "p"):
    """The (loss, grads) program shared by both train steps: tokens
    sharded ``(b, s/p)``, replicated-param grads psum'd EXPLICITLY
    (check=False disables shard_map's automatic replication
    accounting), FFN-shard grads staying sharded.  The returned callable
    resolves ``None`` hop knobs per call (``_resolve_cfg``) and
    dispatches to a shard_map program cached on the RESOLVED config, so
    later-banked tunes take effect."""
    def grad_fn(params, tokens):
        rcfg = _resolve_cfg(cfg, mesh, axis, tokens.shape)
        return _grad_program(mesh, rcfg, axis)(params, tokens)

    return grad_fn


@functools.lru_cache(maxsize=32)
def _grad_program(mesh, cfg: SPConfig, axis: str):
    """The shard_map (loss, grads) program for a RESOLVED config (cfg is
    value-hashable; one program per configuration)."""
    specs = param_specs(cfg, axis)

    def local(params, tokens_loc):
        # differentiate the PARTIAL loss: grads of the psum'd mean would
        # come back scaled by the axis size (psum transposes to psum)
        part, g = jax.value_and_grad(_loss_partial)(params, tokens_loc,
                                                    cfg, axis)
        loss = lax.psum(part, axis)
        # check=False puts replication maintenance on us: each rank's
        # grad for a REPLICATED param is only its partial (its own token
        # shard's contribution) — without this psum the per-rank param
        # copies silently diverge after the first update (caught by the
        # checkpoint round-trip test: save() reads shard 0).  Sharded
        # params (w1/w2) already receive their cross-rank contributions
        # through the ring collectives' transposes.
        g = jax.tree_util.tree_map(
            lambda spec, gg: (lax.psum(gg, axis)
                              if all(s is None for s in spec) else gg),
            specs, g)
        return loss, g

    return C.shard_map_compat(local, mesh=mesh,
                         in_specs=(specs, P(None, axis)),
                         out_specs=(P(), specs), check=False)


def make_optax_train_step(mesh, cfg: SPConfig, tx, axis: str = "p"):
    """Training with any optax optimizer: the (loss, grads) shard_map
    program composed with ``tx.update`` under ONE jit, in fp32 master
    precision (bf16 params/grads upcast for the optimizer arithmetic —
    see ``common.optax_f32_step``) — GSPMD lays the optimizer
    state out to match each param (Adam moments for the tp-sharded FFN
    weights stay sharded, replicated params' moments replicated).  Hop
    knobs left ``None`` resolve per call against the autotune registry,
    outside the jitted-step cache (``_resolve_cfg``).  Returns ``(step,
    init)``: ``state = init(params)``, then ``step(params, opt_state,
    tokens) -> (params, opt_state, loss)``.

    Example::

        tx = optax.adamw(1e-3)
        step, init = make_optax_train_step(mesh, cfg, tx)
        state = init(params)
        params, state, loss = step(params, state, tokens)
    """
    built = {}

    def step(params, opt_state, tokens):
        rcfg = _resolve_cfg(cfg, mesh, axis, tokens.shape)
        if rcfg not in built:
            built[rcfg] = optax_f32_step(
                tx, lambda p, t: _grad_program(mesh, rcfg, axis)(p, t))[0]
        return built[rcfg](params, opt_state, tokens)

    def init(params):
        # block-knob independent; the fp32-master policy is common's
        return optax_f32_init(tx, params)

    return step, init


def make_train_step(mesh, cfg: SPConfig, axis: str = "p"):
    """One jitted SGD train step over ``mesh``: the gradient program plus
    the SGD update under one jit (use ``make_optax_train_step`` for a
    real optimizer).  Hop knobs left ``None`` resolve per call against
    the autotune registry, outside the jitted-step cache.  Returns
    ``step(params, tokens, lr) -> (params, loss)``."""
    def step(params, tokens, lr):
        rcfg = _resolve_cfg(cfg, mesh, axis, tokens.shape)
        return _sgd_step(mesh, rcfg, axis)(params, tokens, lr)

    return step


@functools.lru_cache(maxsize=32)
def _sgd_step(mesh, cfg: SPConfig, axis: str):
    grad_fn = _grad_program(mesh, cfg, axis)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(params, tokens, lr):
        loss, g = grad_fn(params, tokens)
        new = jax.tree_util.tree_map(
            lambda pp, gg: (pp.astype(jnp.float32)
                            - lr * gg.astype(jnp.float32)).astype(pp.dtype),
            params, g)
        return new, loss

    return step
