"""What the training models share: the float32-master optax step, the
blocked cross-entropy, and the layer pieces more than one model is built
of.

Every model that trains through optax (``transformer``, ``sambay``,
``mla_moe``, ``mamba2_hybrid``, ``olmo_hybrid``, and ``sp_transformer``'s
shard_map program) compiles its step through ``optax_f32_step``.  No model
imports another model's code: a piece two of them use lives here.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import programs

__all__ = ["rms_norm", "gated_silu", "fold", "unfold", "init_leaf",
           "init_layer", "init_layers", "blocked_nll", "as_f32",
           "optax_f32_init", "optax_f32_step", "make_optax_train_step"]


# ---------------------------------------------------------------------------
# layer pieces
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps):
    """RMSNorm over the last axis with a learned ``scale``, computed in
    float32 and returned in ``x``'s type."""
    x32 = x.astype(jnp.float32)
    n = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (n * scale.astype(jnp.float32)).astype(x.dtype)


def gated_silu(u, w1, w2, name):
    """``(SiLU(g) * v) w2`` with ``[g | v] = u w1``, the gate's product in
    float32.  The up-projection carries the checkpoint name ``name``, which
    a model's recompute policy (its ``_KEEP``) may keep."""
    g, v = jnp.split(checkpoint_name(u @ w1, name), 2, axis=-1)
    return (jax.nn.silu(g.astype(jnp.float32)) * v.astype(jnp.float32)
            ).astype(u.dtype) @ w2


def fold(t):
    """(B, S, H, W) -> (S, B * H, W): the batch folds into the head axis,
    so one kernel call covers it (a group never straddles two rows)."""
    B, S, H, W = t.shape
    return jnp.transpose(t, (1, 0, 2, 3)).reshape(S, B * H, W)


def unfold(t, B):
    """The inverse of ``fold`` for a batch of ``B`` rows."""
    S, BH, W = t.shape
    return jnp.transpose(t.reshape(S, B, BH // B, W), (1, 0, 2, 3))


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_leaf(key, shape, how, dtype, starts=None):
    """One leaf.  ``how`` is a fan-in (normal with deviation
    1/sqrt(fan_in)), ``"ones"``, ``"zeros"``, ``"dt_bias"`` (softplus of
    it log-uniform in [1e-3, 1e-1], the Mamba family's default), or a name
    in ``starts``, the model's own ``{name: fn(key, shape, dtype)}``."""
    if starts and how in starts:
        return starts[how](key, shape, dtype)
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "zeros":
        return jnp.zeros(shape, dtype)
    if how == "dt_bias":
        # dt_bias = dt + log(-expm1(-dt)), so that softplus(dt_bias) = dt
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            * np.float32(1.0 / np.sqrt(how))).astype(dtype)


def init_layer(key, shapes, dtype, starts=None):
    """The leaves of one layer, ``shapes`` being ``{leaf: (shape, how)}``:
    leaf ``j`` in name order starts from ``fold_in(key, j)``."""
    return {name: init_leaf(jax.random.fold_in(key, j), shape, how, dtype,
                            starts)
            for j, (name, (shape, how)) in enumerate(sorted(shapes.items()))}


def init_layers(key, cfg, leaf_shapes, first, starts=None):
    """One ``init_layer`` a layer of ``cfg.layers``: layer ``n`` from
    ``fold_in(key, first + n)`` with the leaves ``leaf_shapes(cfg, kind)``
    gives its kind."""
    return [init_layer(jax.random.fold_in(key, n + first),
                       leaf_shapes(cfg, kind), cfg.dtype, starts)
            for n, (_, kind) in enumerate(cfg.layers)]


# ---------------------------------------------------------------------------
# the loss and the step
# ---------------------------------------------------------------------------


def blocked_nll(x, table, targets, loss_rows: int):
    """The summed cross-entropy of ``x`` (B, S, D) against ``targets``
    (B, S) under the head ``table`` (vocab, D), float32: the logits exist
    one block of at most ``loss_rows`` positions at a time, in both
    directions (a block's are computed again in the backward), so a long
    row's float32 logits are never held whole."""
    B, S, D = x.shape
    rows = B * S
    blk = min(loss_rows, rows)
    while rows % blk:
        blk -= 1
    xb = x.reshape(rows // blk, blk, D)
    tb = targets.reshape(rows // blk, blk)

    @jax.checkpoint
    def block_nll(tab, xr, tr):
        logits = jnp.einsum("sd,vd->sv", xr, tab,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    return jax.lax.scan(
        lambda acc, xt: (acc + block_nll(table, *xt), None),
        jnp.zeros((), jnp.float32), (xb, tb))[0]


def as_f32(t):
    """``t`` with every leaf cast to float32."""
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)


def optax_f32_init(tx, params):
    """Optimizer state from float32 master params: the init half of the
    float32-master policy, for every step factory."""
    return tx.init(as_f32(params))


def optax_f32_step(tx, grad_fn, scopes=("optimizer",)):
    """``(step, init)``: an optax step with float32 master arithmetic.
    ``grad_fn(params, tokens) -> (loss, grads)``; bf16 params and grads
    are upcast before ``tx.update`` + ``apply_updates`` and downcast after
    (at bf16 resolution, about 8 mantissa bits, Adam-scale updates against
    O(0.1) weights would round to zero and training would stall).  State
    must come from ``init``.  Params and state are donated.  The step is
    the registered program ``train.optax_step`` (``telemetry/programs.py``):
    the jitted function run inside a span of that name, with ``scopes``,
    the phases the model's ``jax.named_scope``s declare, for its phase
    map."""
    import optax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, g = grad_fn(params, tokens)
        with jax.named_scope("optimizer"):
            p32 = as_f32(params)
            updates, opt_state = tx.update(as_f32(g), opt_state, p32)
            new32 = optax.apply_updates(p32, updates)
            new = jax.tree_util.tree_map(
                lambda n, p: n.astype(p.dtype), new32, params)
        return new, opt_state, loss

    def init(params):
        return optax_f32_init(tx, params)

    return programs.register("train.optax_step", step, scopes), init


def make_optax_train_step(loss_fn, cfg, tx, scopes):
    """``optax_f32_step`` of ``value_and_grad(loss_fn)(params, tokens,
    cfg)``: ``(step, init)``, ``state = init(params)``, then
    ``step(params, state, tokens) -> (params, state, loss)``."""
    def grad_fn(params, tokens):
        return jax.value_and_grad(loss_fn)(params, tokens, cfg)

    return optax_f32_step(tx, grad_fn, scopes)
