"""``x.close()``: the caller releases an array it has consumed."""

import counts


def prepare(env, spec):
    pass


def run(env, spec):
    if not env.audit:                 # the audit step keeps what it made
        env.arrays.pop(spec["name"]).close()


def out_layout(env, spec):
    return {}


def cost(env, spec):
    return counts.Cost()


def ref(refenv, spec):
    pass
