"""Plain references: the same mathematics written straightforwardly.

float32 ``jax.numpy`` with ``HIGHEST``-precision products (numpy float64
for sampled rows), no kernels, no sharding, no cache, nothing imported
from the program and nothing the program made.  Each reference takes a
``lowp`` type: ``None`` is the reference itself; a lower-precision type
makes it the CONTROL, the reference computed in the nearest precision
below the one the configuration states, which the comparison that decides
``correct`` has to fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HP = jax.lax.Precision.HIGHEST

__all__ = ["q", "chain_ref", "chain_ref_rows_f64", "gemm_ref_rows",
           "ref_loss_and_grads", "adamw_reference", "leaf_norms",
           "stack_blocks", "CHAINS"]


def q(x, lowp):
    """Round ``x`` to the precision of ``lowp`` (identity for the
    reference).  By ``lax.reduce_precision`` and not by a pair of casts:
    the TPU compiler is allowed excess precision and drops a cast down
    and back up, so a control built from casts is the reference again (read
    on the chip, PR 23: it came out bit-equal)."""
    if lowp is None:
        return x
    info = jnp.finfo(lowp)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


# ---------------------------------------------------------------------------
# array programs
# ---------------------------------------------------------------------------

# the elementwise chains a traffic file may name; each as (function of jnp
# arrays, function of numpy float64 arrays, operations per element)
CHAINS = {
    "sin_a_plus_b_times_c": (lambda a, b, c: jnp.sin(a) + b * c,
                             lambda a, b, c: np.sin(a) + b * c, 3),
}


@functools.partial(jax.jit, static_argnums=(0, 2))
def chain_ref(expr: str, blocks, lowp=None):
    """The chain over one row block.  The control rounds the inputs and
    the stored result to ``lowp``."""
    f = CHAINS[expr][0]
    return q(f(*(q(b, lowp) for b in blocks)), lowp)


def chain_ref_rows_f64(expr: str, rows):
    """The chain over sampled rows in numpy float64, on the host."""
    return CHAINS[expr][1](*(np.asarray(r, np.float64) for r in rows))


@functools.partial(jax.jit, static_argnums=(2,))
def gemm_ref_rows(a_rows, b, lowp=None):
    """Rows of ``A @ B`` in float32 at HIGHEST precision; the control rounds
    operands and the result to ``lowp``."""
    return q(jnp.dot(q(a_rows, lowp), q(b, lowp), precision=HP), lowp)


# ---------------------------------------------------------------------------
# the decoder: forward, loss, gradients, AdamW
# ---------------------------------------------------------------------------


def stack_blocks(params):
    """The program-shaped pytree with its blocks stacked along a leading
    layer axis (so the reference scans over layers), all in float32."""
    f32 = lambda t: t.astype(jnp.float32)
    blocks = {k: jnp.stack([f32(b[k]) for b in params["blocks"]])
              for k in params["blocks"][0]}
    return {"embed": f32(params["embed"]), "pos": f32(params["pos"]),
            "ln_f": f32(params["ln_f"]), "head": f32(params["head"]),
            "blocks": blocks}


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6) * scale


def _row_nll(p, row, heads, lowp):
    """Summed next-token cross-entropy of one row of token ids (S+1,):
    embedding plus learned positions; per block RMSNorm, dense causal
    softmax attention, RMSNorm, GELU FFN, both residual; final RMSNorm and
    an untied head.  float32 throughout, one layer's activations live at a
    time (scan + checkpoint)."""
    mm = lambda a, b: jnp.dot(q(a, lowp), q(b, lowp), precision=HP)
    tok, tgt = row[:-1], row[1:]
    S = tok.shape[0]
    x = p["embed"][tok] + p["pos"][:S]
    E = x.shape[-1]
    D = E // heads
    mask = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, blk):
        h = _rmsnorm(x, blk["ln1"])
        qkv = mm(h, blk["qkv"]).reshape(S, 3, heads, D)
        qh, kh, vh = (jnp.swapaxes(qkv[:, i], 0, 1) for i in range(3))
        s = jnp.einsum("hqd,hkd->hqk", q(qh, lowp), q(kh, lowp),
                       precision=HP) / np.float32(np.sqrt(D))
        s = jnp.where(mask[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,hkd->qhd", q(pr, lowp), q(vh, lowp),
                       precision=HP).reshape(S, E)
        x = x + mm(o, blk["proj"])
        h = _rmsnorm(x, blk["ln2"])
        x = x + mm(jax.nn.gelu(mm(h, blk["w1"])), blk["w2"])
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["blocks"])
    logits = mm(_rmsnorm(x, p["ln_f"]), p["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tgt[:, None], axis=-1))


_row_vg = jax.jit(jax.value_and_grad(_row_nll), static_argnums=(2, 3))
_tree_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                    donate_argnums=(0,))
_tree_scale = jax.jit(lambda a, s: jax.tree_util.tree_map(
    lambda t: t * s, a), donate_argnums=(0,))


def ref_loss_and_grads(p, tokens, heads, lowp=None):
    """Mean loss and its gradients over a batch (B, S+1), one row at a time
    so the dense score matrices of a whole batch never live together."""
    total, grads = 0.0, None
    for row in tokens:
        nll, g = _row_vg(p, row, heads, lowp)
        total += float(nll)
        grads = g if grads is None else _tree_add(grads, g)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    return total / n, _tree_scale(grads, np.float32(1.0 / n))


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def adamw_reference(p, mu, nu, g, t, hyper):
    """One AdamW update as ``optax.adamw`` defines it, in float32, the new
    parameters rounded to the type the configuration stores them in.
    ``hyper`` is (lr, b1, b2, eps, weight_decay, storage dtype name)."""
    lr, b1, b2, eps, wd, store = hyper
    store = jnp.dtype(store)

    def leaf(p, mu, nu, g):
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * g * g
        mh = mu / (1.0 - b1 ** t)
        nh = nu / (1.0 - b2 ** t)
        new = p - lr * (mh / (jnp.sqrt(nh) + eps) + wd * p)
        # rounded by q (reduce_precision): a cast down and back up is dropped
        # by the TPU compiler, and the reference would keep float32 weights
        return (new if store == jnp.float32 else q(new, store)), mu, nu

    out = jax.tree_util.tree_map(leaf, p, mu, nu, g)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    """The Euclidean norm of every leaf, as float32 scalars."""
    return jax.tree_util.tree_map(
        lambda t: jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32)))), tree)
