"""``out = dat.djit(expr)(*inputs)``: an elementwise chain, result kept."""

import counts
import refs


def prepare(env, spec):
    """Build the program's callable once, in set-up."""
    import distributedarrays_tpu as dat
    spec["_fn"] = dat.djit(refs.CHAINS[spec["expr"]][0])


def run(env, spec):
    env.put(spec["out"], spec["_fn"](*(env.arrays[n] for n in spec["in"])))


def out_layout(env, spec):
    return {spec["out"]: env.layout[spec["in"][0]]}


def cost(env, spec):
    shape, grid = env.layout[spec["in"][0]]
    return counts.chain_cost(shape, env.itemsize, len(spec["in"]),
                             refs.CHAINS[spec["expr"]][2],
                             chips=grid[0] * grid[1])


def ref(refenv, spec):
    ins = [refenv.arrays[n] for n in spec["in"]]
    refenv.arrays[spec["out"]] = refenv.lazy(
        ins[0].shape,
        lambda r0, r1: refs.chain_ref(
            spec["expr"], tuple(a.rows(r0, r1) for a in ins), refenv.lowp),
        f64=lambda rows: refs.chain_ref_rows_f64(
            spec["expr"], [a.f64(rows) for a in ins]))
