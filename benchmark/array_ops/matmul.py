"""``out = dat.matmul(a, b)``: dense GEMM at the MXU's default precision."""

import counts
import refs


def prepare(env, spec):
    pass


def run(env, spec):
    import distributedarrays_tpu as dat
    a, b = (env.arrays[n] for n in spec["in"])
    env.put(spec["out"], dat.matmul(a, b))


def out_layout(env, spec):
    (sa, ga), (sb, _) = (env.layout[n] for n in spec["in"])
    return {spec["out"]: ((sa[0], sb[1]), ga)}


def cost(env, spec):
    (sa, ga), (sb, _) = (env.layout[n] for n in spec["in"])
    return counts.gemm_cost(sa[0], sb[1], sa[1], env.itemsize,
                            chips=ga[0] * ga[1])


def ref(refenv, spec):
    a, b = (refenv.arrays[n] for n in spec["in"])
    refenv.arrays[spec["out"]] = refenv.lazy(
        (a.shape[0], b.shape[1]),
        lambda r0, r1: refs.gemm_ref_rows(a.rows(r0, r1), b.whole(),
                                          refenv.lowp))
