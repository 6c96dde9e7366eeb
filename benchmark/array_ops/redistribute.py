"""``out = dat.distribute(x, procs=ranks, dist=grid)``: a DArray moved to
another block layout over the same ranks, through the reshard planner.
Pure data movement: the result is bit-equal to the source, and each shard
sits on the device the layout names."""

import counts
import refs


def prepare(env, spec):
    pass


def run(env, spec):
    import distributedarrays_tpu as dat
    out = dat.distribute(env.arrays[spec["in"]], procs=env.ranks,
                         dist=tuple(spec["grid"]))
    env.misplaced += env.count_misplaced(out, spec["grid"])
    env.put(spec["out"], out)


def out_layout(env, spec):
    shape, _ = env.layout[spec["in"]]
    return {spec["out"]: (shape, tuple(spec["grid"]))}


def cost(env, spec):
    shape, grid = env.layout[spec["in"]]
    return counts.reshard_leg_cost(shape, env.itemsize, grid, spec["grid"])


def ref(refenv, spec):
    src = refenv.arrays[spec["in"]]
    refenv.arrays[spec["out"]] = refenv.lazy(
        src.shape, lambda r0, r1: refs.q(src.rows(r0, r1), refenv.lowp))
