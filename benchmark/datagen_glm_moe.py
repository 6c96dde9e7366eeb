"""Weights and token rows of the GLM-4.7-Flash configuration from ``--seed``.

The benchmark's own table of the model's leaves and how each starts; the
program gets the generated arrays and never the seed.  Matrices are normal
with deviation 1/sqrt(fan_in), norm scales 1, the router's selection bias
0.  ``m`` holds dim, heads, q_rank, kv_rank, nope, rope, v_dim, ffn,
moe_ffn, n_experts (published) and held (how many experts live here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["layer_leaves", "glm_weights", "token_rows"]


def layer_leaves(kind: str, m: dict):
    """[(leaf, shape, fan_in or the name of its start)] of a layer of
    ``kind`` ("dense" or "moe"), in a fixed order."""
    D, H, F, Fe = m["dim"], m["heads"], m["ffn"], m["moe_ffn"]
    qk, kv = m["nope"] + m["rope"], m["nope"] + m["v_dim"]
    out = [("ln1", (D,), "ones"), ("wqa", (D, m["q_rank"]), D),
           ("q_norm", (m["q_rank"],), "ones"),
           ("wqb", (m["q_rank"], H * qk), m["q_rank"]),
           ("wkva", (D, m["kv_rank"] + m["rope"]), D),
           ("kv_norm", (m["kv_rank"],), "ones"),
           ("wkvb", (m["kv_rank"], H * kv), m["kv_rank"]),
           ("wo", (H * m["v_dim"], D), H * m["v_dim"]),
           ("ln2", (D,), "ones")]
    if kind == "dense":
        return out + [("w1", (D, 2 * F), D), ("w2", (F, D), F)]
    n = m["held"]
    return out + [("router", (D, m["n_experts"]), D),
                  ("router_bias", (m["n_experts"],), "zeros"),
                  ("ew1", (n, D, 2 * Fe), D), ("ew2", (n, Fe, D), Fe),
                  ("sw1", (D, 2 * Fe), D), ("sw2", (Fe, D), Fe)]


def _leaf(key, shape, how, dtype):
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "zeros":
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            * np.float32(1.0 / np.sqrt(how))).astype(dtype)


def _layer(key, kind, m, dtype):
    leaves = layer_leaves(kind, m)
    build = jax.jit(lambda k: {
        name: _leaf(jax.random.fold_in(k, j), shape, how, dtype)
        for j, (name, shape, how) in enumerate(leaves)})
    return build(key)


def glm_weights(key, m: dict, kinds, vocab: int, mtp: bool,
                dtype=jnp.bfloat16):
    """The pytree ``models/mla_moe.py`` takes ({"embed", "head", "norm_f",
    "layers": [{...}], "mtp": {...}}), one jitted call a layer, in the
    type the weights are trained in."""
    D = m["dim"]
    top = jax.jit(lambda k: {
        "embed": _leaf(jax.random.fold_in(k, 0), (vocab, D), D, dtype),
        "head": _leaf(jax.random.fold_in(k, 1), (vocab, D), D, dtype),
        "norm_f": jnp.ones((D,), dtype)})
    tree = dict(top(jax.random.fold_in(key, 0)), layers=[
        _layer(jax.random.fold_in(key, n + 1), kind, m, dtype)
        for n, kind in enumerate(kinds)])
    if mtp:
        mk = jax.random.fold_in(key, len(kinds) + 1)
        tree["mtp"] = {
            "enorm": jnp.ones((D,), dtype), "hnorm": jnp.ones((D,), dtype),
            "eh_proj": jax.jit(lambda k: _leaf(k, (2 * D, D), 2 * D, dtype))(
                jax.random.fold_in(mk, 0)),
            "block": _layer(jax.random.fold_in(mk, 1), "moe", m, dtype),
            "norm": jnp.ones((D,), dtype)}
    return tree


def token_rows(key, pool: int, batch: int, ids_a_row: int, vocab: int):
    """``pool`` batches of ``batch`` rows of ``ids_a_row`` token ids drawn
    uniformly from the ``vocab`` rows held here, as one int32 array."""
    return jax.random.randint(key, (pool, batch, ids_a_row), 0, vocab,
                              dtype=jnp.int32)
