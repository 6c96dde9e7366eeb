"""A whole-array reduction read back as a scalar: ``sumsq`` is
``dat.dmapreduce(jnp.square, "sum", x)``, ``sum`` ``dat.dsum``, ``mean``
``dat.dmean``, ``std`` ``dat.dstd`` (sample deviation, n - 1)."""

import numpy as np

import counts


def prepare(env, spec):
    import jax.numpy as jnp
    import distributedarrays_tpu as dat
    spec["_fn"] = {
        "sumsq": lambda x: dat.dmapreduce(jnp.square, "sum", x),
        "sum": dat.dsum, "mean": dat.dmean, "std": dat.dstd,
    }[spec["kind"]]


def run(env, spec):
    env.scalars[spec["scalar"]] = spec["_fn"](env.arrays[spec["in"]])


def out_layout(env, spec):
    return {}


def cost(env, spec):
    shape, grid = env.layout[spec["in"]]
    return counts.reduce_cost(shape, env.itemsize, chips=grid[0] * grid[1])


def ref(refenv, spec):
    arr = refenv.arrays[spec["in"]]

    def value():
        n, s1, s2 = arr.moments()
        return {"sumsq": s2, "sum": s1, "mean": s1 / n,
                "std": float(np.sqrt(max(s2 - s1 * s1 / n, 0.0) / (n - 1))),
                }[spec["kind"]]

    refenv.scalars[spec["scalar"]] = value
