"""Weights and token rows of the SambaY configuration from ``--seed``.

The benchmark's own table of the model's leaves and how each starts; the
program gets the generated arrays and never the seed.  Matrices are normal
with deviation 1/sqrt(fan_in), norm scales 1, biases 0; the Mamba family's
defaults for the rest: ``A_log = log(1..N)`` in every channel, ``D_skip =
1``, ``dt_b`` such that ``softplus(dt_b)`` is log-uniform in [1e-3, 1e-1],
and differential attention's ``lq*, lk*`` normal with deviation 0.1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["layer_leaves", "sambay_weights", "token_rows"]


def layer_leaves(kind: str, m: dict):
    """[(leaf, shape, fan_in or the name of its start)] of a layer, in a
    fixed order.  ``m`` holds dim, ffn, heads, kv_heads, head_dim, d_inner,
    d_state, d_conv, dt_rank."""
    D, F, E, N, R, K = (m["dim"], m["ffn"], m["d_inner"], m["d_state"],
                        m["dt_rank"], m["d_conv"])
    hd = m["head_dim"]
    qw, kvw = m["heads"] * hd, m["kv_heads"] * hd
    out = [("ln1_s", (D,), "ones"), ("ln1_b", (D,), "zeros")]
    if kind == "mamba":
        out += [("in_proj", (D, 2 * E), D), ("conv_w", (K, E), K),
                ("conv_b", (E,), "zeros"), ("x_proj", (E, R + 2 * N), E),
                ("dt_w", (R, E), R), ("dt_b", (E,), "dt_bias"),
                ("A_log", (E, N), "A_log"), ("D_skip", (E,), "ones"),
                ("out_proj", (E, D), E)]
    elif kind == "gmu":
        out += [("wg", (D, E), D), ("wo", (E, D), E)]
    else:
        if kind == "cross":
            out += [("wq", (D, qw), D), ("bq", (qw,), "zeros")]
        else:
            out += [("wqkv", (D, qw + 2 * kvw), D), ("bq", (qw,), "zeros"),
                    ("bk", (kvw,), "zeros"), ("bv", (kvw,), "zeros")]
        out += [(k, (hd,), "lambda") for k in ("lq1", "lk1", "lq2", "lk2")]
        out += [("subln", (2 * hd,), "ones"), ("wo", (qw, D), qw),
                ("bo", (D,), "zeros")]
    out += [("ln2_s", (D,), "ones"), ("ln2_b", (D,), "zeros"),
            ("w1", (D, 2 * F), D), ("w2", (F, D), F)]
    return out


def _leaf(key, shape, how, dtype):
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "zeros":
        return jnp.zeros(shape, dtype)
    if how == "A_log":
        row = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, shape).astype(dtype)
    if how == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo)
                     + lo)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if how == "lambda":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            * np.float32(1.0 / np.sqrt(how))).astype(dtype)


def sambay_weights(key, m: dict, layers, vocab: int, dtype=jnp.bfloat16):
    """The pytree ``models/sambay.py`` takes ({"embed", "ln_f_s", "ln_f_b",
    "layers": [{...}]}), one jitted call a layer, in the type the weights
    are trained in."""
    D = m["dim"]
    top = jax.jit(lambda k: {
        "embed": _leaf(k, (vocab, D), D, dtype),
        "ln_f_s": jnp.ones((D,), dtype), "ln_f_b": jnp.zeros((D,), dtype)})
    tree = dict(top(jax.random.fold_in(key, 0)), layers=[])
    for n, (_, kind) in enumerate(layers):
        leaves = layer_leaves(kind, m)
        build = jax.jit(lambda k, leaves=leaves: {
            name: _leaf(jax.random.fold_in(k, j), shape, how, dtype)
            for j, (name, shape, how) in enumerate(leaves)})
        tree["layers"].append(build(jax.random.fold_in(key, n + 1)))
    return tree


def token_rows(key, pool: int, batch: int, seq_plus_one: int, vocab: int):
    """``pool`` batches of ``batch`` rows of ``seq_plus_one`` token ids
    drawn uniformly from the ``vocab`` rows held here, every row
    different, as one int32 array."""
    return jax.random.randint(key, (pool, batch, seq_plus_one), 0, vocab,
                              dtype=jnp.int32)
