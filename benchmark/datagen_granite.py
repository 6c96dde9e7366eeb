"""Weights of the granite-4.0-h-micro configuration from ``--seed``.

The benchmark's own table of the model's leaves and how each starts; the
program gets the generated arrays and never the seed.  Matrices are normal
with deviation 1/sqrt(fan_in), norm scales 1, the convolution's bias 0;
the Mamba-2 family's defaults for the rest: ``A_log = log(1..heads)``,
``D_skip = 1``, ``dt_bias`` such that ``softplus(dt_bias)`` is
log-uniform in [1e-3, 1e-1].  Token rows are ``datagen_sambay.token_rows``'.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from datagen_sambay import _leaf, token_rows

__all__ = ["layer_leaves", "granite_weights", "token_rows"]


def layer_leaves(kind: str, m: dict):
    """[(leaf, shape, fan_in or the name of its start)] of a layer, in a
    fixed order.  ``m`` holds dim, ffn, heads, kv_heads, head_dim,
    ssm_heads, d_inner, d_state, n_groups, d_conv."""
    D, F, E = m["dim"], m["ffn"], m["d_inner"]
    out = [("norm1", (D,), "ones")]
    if kind == "mamba":
        Hs = m["ssm_heads"]
        conv = E + 2 * m["n_groups"] * m["d_state"]
        out += [("in_proj", (D, E + conv + Hs), D),
                ("conv_w", (m["d_conv"], conv), m["d_conv"]),
                ("conv_b", (conv,), "zeros"), ("dt_bias", (Hs,), "dt_bias"),
                ("A_log", (Hs,), "A_log"), ("D_skip", (Hs,), "ones"),
                ("norm_gated", (E,), "ones"), ("out_proj", (E, D), E)]
    else:
        qw, kvw = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
        out += [("wqkv", (D, qw + 2 * kvw), D), ("wo", (qw, D), qw)]
    out += [("norm2", (D,), "ones"), ("w1", (D, 2 * F), D), ("w2", (F, D), F)]
    return out


def _start(key, shape, how, dtype):
    if how == "A_log":        # one decay a head: log(1..heads)
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
                       ).astype(dtype)
    return _leaf(key, shape, how, dtype)


def granite_weights(key, m: dict, kinds, vocab: int, dtype=jnp.bfloat16):
    """The pytree ``models/mamba2_hybrid.py`` takes ({"embed", "norm_f",
    "layers": [{...}]}), one jitted call a layer, in the type the weights
    are trained in."""
    D = m["dim"]
    top = jax.jit(lambda k: {"embed": _leaf(k, (vocab, D), D, dtype),
                             "norm_f": jnp.ones((D,), dtype)})
    tree = dict(top(jax.random.fold_in(key, 0)), layers=[])
    for n, kind in enumerate(kinds):
        leaves = layer_leaves(kind, m)
        build = jax.jit(lambda k, leaves=leaves: {
            name: _start(jax.random.fold_in(k, j), shape, how, dtype)
            for j, (name, shape, how) in enumerate(leaves)})
        tree["layers"].append(build(jax.random.fold_in(key, n + 1)))
    return tree
