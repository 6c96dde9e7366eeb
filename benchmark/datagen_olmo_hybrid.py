"""Weights of the Olmo-Hybrid-7B configuration from ``--seed``.

The benchmark's own table of the model's leaves and how each starts; the
program gets the generated arrays and never the seed.  Matrices are normal
with deviation 1/sqrt(fan_in), norm scales 1; the Gated DeltaNet family's
defaults for the rest: ``A_log = log(A)`` with ``A`` uniform in (0, 16],
``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in [1e-3,
1e-1].  The embedding and the head are separate leaves (untied).  Token
rows are ``datagen_sambay.token_rows``'.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from datagen_sambay import _leaf, token_rows

__all__ = ["layer_leaves", "olmo_hybrid_weights", "token_rows"]


def layer_leaves(kind: str, m: dict):
    """[(leaf, shape, fan_in or the name of its start)] of a layer, in a
    fixed order.  ``m`` holds dim, ffn, heads, head_dim, lin_heads,
    key_dim, value_dim, d_conv."""
    D, F = m["dim"], m["ffn"]
    if kind == "linear_attention":
        H = m["lin_heads"]
        qk, vw = H * m["key_dim"], H * m["value_dim"]
        out = [("w_in", (D, 2 * qk + 2 * vw), D),
               ("conv_w", (m["d_conv"], 2 * qk + vw), m["d_conv"]),
               ("w_ab", (D, 2 * H), D), ("A_log", (H,), "A_log"),
               ("dt_bias", (H,), "dt_bias"),
               ("o_norm", (m["value_dim"],), "ones"), ("w_o", (vw, D), vw)]
    else:
        w = m["heads"] * m["head_dim"]
        out = [("w_qkv", (D, 3 * w), D), ("q_norm", (w,), "ones"),
               ("k_norm", (w,), "ones"), ("w_o", (w, D), w)]
    return out + [("post_mix_norm", (D,), "ones"), ("w1", (D, 2 * F), D),
                  ("w2", (F, D), F), ("post_mlp_norm", (D,), "ones")]


def _start(key, shape, how, dtype):
    if how == "A_log":        # A uniform in (0, 16] a head
        a = 16.0 * (1.0 - jax.random.uniform(key, shape, jnp.float32))
        return jnp.log(a).astype(dtype)
    return _leaf(key, shape, how, dtype)


def olmo_hybrid_weights(key, m: dict, kinds, vocab: int, dtype=jnp.bfloat16):
    """The pytree ``models/olmo_hybrid.py`` takes ({"embed", "head",
    "norm_f", "layers": [{...}]}), one jitted call a layer, in the type the
    weights are trained in."""
    D = m["dim"]
    top = jax.jit(lambda k: {
        "embed": _leaf(jax.random.fold_in(k, 0), (vocab, D), D, dtype),
        "head": _leaf(jax.random.fold_in(k, 1), (vocab, D), D, dtype),
        "norm_f": jnp.ones((D,), dtype)})
    tree = dict(top(jax.random.fold_in(key, 0)), layers=[])
    for n, kind in enumerate(kinds):
        leaves = layer_leaves(kind, m)
        build = jax.jit(lambda k, leaves=leaves: {
            name: _start(jax.random.fold_in(k, j), shape, how, dtype)
            for j, (name, shape, how) in enumerate(leaves)})
        tree["layers"].append(build(jax.random.fold_in(key, n + 1)))
    return tree
