"""Inputs and weights from ``--seed``: the one general generator.

Everything the benchmark feeds the program is made here, on the device, in
one jitted call a tensor, from a key derived from the seed and the tensor's
name.  Arrays are made in row blocks, each block from its own folded key, so
that the reference can make any block again without holding the whole
array.  The program never sees the seed: it gets the generated arrays.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["root_key", "named_key", "array_block", "array_rows",
           "make_array", "token_batches", "transformer_leaves",
           "transformer_weights"]


def root_key(seed: int):
    """A key from any whole number up to 2**62: the low 31 bits seed it, the
    rest is folded in (a seed above 2**31 does not fit an int32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def named_key(seed: int, name: str):
    return jax.random.fold_in(root_key(seed),
                              zlib.crc32(name.encode()) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# dense arrays, uniform on [0, 1) as upstream's ``drand``
# ---------------------------------------------------------------------------


def array_block(key, block: int, block_rows: int, cols: int, dtype):
    """Row block ``block`` of an array: ``block_rows`` x ``cols``."""
    return jax.random.uniform(jax.random.fold_in(key, block),
                              (block_rows, cols), dtype)


def array_rows(key, first_block: int, n_blocks: int, block_rows: int,
               cols: int, dtype):
    """Blocks ``first_block`` .. ``first_block + n_blocks`` stacked."""
    ids = first_block + jnp.arange(n_blocks)
    out = jax.vmap(lambda b: array_block(key, b, block_rows, cols, dtype))(ids)
    return out.reshape(n_blocks * block_rows, cols)


_rows_jit = jax.jit(array_rows, static_argnums=(2, 3, 4, 5))


def make_array(key, shape, dtype, block_rows: int, devices, grid):
    """The whole array on ``devices`` in the block layout ``grid`` (rank r
    owns block r of the grid in row-major order), each device making its
    own rows.  Returns a ``jax.Array`` with a ``NamedSharding``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rows, cols = int(shape[0]), int(shape[1])
    gr, gc = int(grid[0]), int(grid[1])
    if rows % (gr * block_rows) or cols % gc:
        raise ValueError(f"{shape} does not divide into grid {grid} with "
                         f"row blocks of {block_rows}")
    mesh = Mesh(np.asarray(devices[:gr * gc]).reshape(gr, gc), ("r", "c"))
    sharding = NamedSharding(mesh, P("r", "c"))
    per_dev = rows // gr // block_rows
    shards = []
    for i in range(gr):
        for j in range(gc):
            dev = mesh.devices[i, j]
            with jax.default_device(dev):
                blk = _rows_jit(jax.device_put(key, dev), i * per_dev,
                                per_dev, block_rows, cols, dtype)
            if gc > 1:
                w = cols // gc
                blk = blk[:, j * w:(j + 1) * w]
            shards.append(blk)
    return jax.make_array_from_single_device_arrays((rows, cols), sharding,
                                                    shards)


# ---------------------------------------------------------------------------
# tokens and transformer weights
# ---------------------------------------------------------------------------


def token_batches(key, n_batches: int, batch: int, seq_plus_one: int,
                  vocab: int):
    """``n_batches`` batches of ``batch`` rows of ``seq_plus_one`` token
    ids, every row different, as one int32 array."""
    return jax.random.randint(key, (n_batches, batch, seq_plus_one), 0,
                              vocab, dtype=jnp.int32)


def transformer_leaves(vocab: int, dim: int, layers: int, ffn: int,
                       positions: int):
    """(path, shape, fan_in) of every leaf, in a fixed order; fan_in None
    for a norm scale (made as ones)."""
    out = [(("embed",), (vocab, dim), dim), (("pos",), (positions, dim), dim),
           (("ln_f",), (dim,), None), (("head",), (dim, vocab), dim)]
    for i in range(layers):
        out += [(("blocks", i, "ln1"), (dim,), None),
                (("blocks", i, "qkv"), (dim, 3 * dim), dim),
                (("blocks", i, "proj"), (dim, dim), dim),
                (("blocks", i, "ln2"), (dim,), None),
                (("blocks", i, "w1"), (dim, ffn), dim),
                (("blocks", i, "w2"), (ffn, dim), ffn)]
    return out


def transformer_weights(key, vocab: int, dim: int, layers: int, ffn: int,
                        positions: int, dtype=jnp.bfloat16):
    """Every weight of the decoder in the pytree ``models/transformer.py``
    takes ({"embed", "pos", "ln_f", "head", "blocks": [{...}]}), normal with
    deviation 1/sqrt(fan_in), norm scales one, made in one jitted call in
    the type they are trained in."""
    leaves = transformer_leaves(vocab, dim, layers, ffn, positions)

    def build(key):
        tree = {"blocks": [dict() for _ in range(layers)]}
        for n, (path, shape, fan_in) in enumerate(leaves):
            if fan_in is None:
                val = jnp.ones(shape, dtype)
            else:
                val = (jax.random.normal(jax.random.fold_in(key, n), shape,
                                         jnp.float32)
                       * np.float32(1.0 / np.sqrt(fan_in))).astype(dtype)
            node = tree
            for p in path[:-1]:
                node = node[p]
            node[path[-1]] = val
        return tree

    return jax.jit(build)(key)
