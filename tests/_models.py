"""What the model tests build, and what they compile of it, once a process.

For each training model: the tiny configuration, weights and tokens its
tests read (``olmo_hybrid``, ``mamba2_hybrid``, ``sambay``, ``mla_moe``);
one jit a function (its ``value_and_grad`` or itself), so a model is
compiled once for each shape and configuration it is called with, not
once a test; the results at the default configuration, weights
and tokens, keyed by (function, batch, dtype); and the tiny optax step of
each model, lowered and compiled once (``step_text``, ``step_program``).

A helper module, not a test file: each test stays in its own file (under
``--dist loadfile`` a file is one worker's share).  A test that
monkeypatches model code builds its own program and does not come here.
"""

import functools
import importlib
import sys
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

REPO = Path(__file__).resolve().parent.parent
if str(REPO / "benchmark") not in sys.path:
    sys.path.insert(0, str(REPO / "benchmark"))

F32 = jnp.float32


def module(name):
    return importlib.import_module(f"distributedarrays_tpu.models.{name}")


# ---------------------------------------------------------------------------
# the checks the model files share
# ---------------------------------------------------------------------------


def gaps(g, g0):
    """{leaf path: |g - g0| / max(|g0|, a thousandth of the median
    leaf's norm)}."""
    flat = jax.tree_util.tree_leaves_with_path(g)
    flat0 = jax.tree_util.tree_leaves(g0)
    scale = float(np.median([float(jnp.linalg.norm(x)) for x in flat0]))
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(a - b)) / max(
        float(jnp.linalg.norm(b)), 1e-3 * scale)
        for (p, a), b in zip(flat, flat0)}


def worst_leaf(R, g, g0, params):
    """(gap, leaf) of the leaf whose gradient ``g`` lies furthest from the
    benchmark reference's ``g0``, by ``R.leaf_norm_dict``: the norm of the
    difference over the larger of the leaf's and a thousandth of the
    median leaf's.  Every leaf of ``params`` must have its gap."""
    want, gap = R.leaf_norm_dict(g0), R.leaf_norm_dict(g, g0)
    assert set(gap) == set(R.leaf_norm_dict(params))
    floor = 1e-3 * float(np.median(list(want.values())))
    return max((gap[k] / max(want[k], floor), k) for k in gap)


def row_reference(R, params, tok, dims):
    """(loss, grads) of the benchmark reference ``R`` on the one row of
    ``tok``, in float32 at full precision: means over the row's
    positions."""
    grads = {"layers": [None] * len(dims["kinds"])}

    def keep(n, sub):
        if n is None:
            grads.update(sub)
        else:
            grads["layers"][n] = sub

    with jax.default_matmul_precision("highest"):
        nll = R._row_nll_and_grads(params, tok[0], dims, None, keep)
    n = tok.shape[1] - 1
    return nll / n, jax.tree_util.tree_map(lambda t: t / n, grads)


_AGAINST = {}


def against(key, fs, args, w):
    """``[(f(*args),) + the gradients of sum(f(*args) * w) by every
    argument, for f in fs]``: a kernel's forward and gradients beside its
    reference's, computed once for ``key`` (one jitted ``vjp`` a function,
    the cotangent ``w``) and read by one test each."""
    if key not in _AGAINST:
        def both(f):
            def run(w, *a):
                y, vjp = jax.vjp(f, *a)
                return (y,) + vjp(w)
            return jax.jit(run)(w, *args)
        _AGAINST[key] = [both(f) for f in fs]
    return _AGAINST[key]


# ---------------------------------------------------------------------------
# one jit a function
# ---------------------------------------------------------------------------


_JIT = {}


def jitted(f, transform=None):
    """``jax.jit`` of ``transform(f)`` (``f`` itself by default), the
    configuration (argument 2) static: one a process for each pair."""
    if (transform, f) not in _JIT:
        _JIT[transform, f] = jax.jit(transform(f) if transform else f,
                                     static_argnums=2)
    return _JIT[transform, f]


def _copy(tree):
    # a fresh container around the same leaves: a test may swap a leaf
    return jax.tree_util.tree_map(lambda x: x, tree)


def _moved(params, seed):
    """Every leaf moved off its start (a scale of 1 or a bias of 0 would
    hide a gradient path)."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


class Tiny:
    """One model at its tests' tiny size.  ``config``, ``weights`` and
    ``tokens`` take the model's own arguments; ``result`` is the value of
    the program's or the package reference's function at the defaults."""

    def __init__(self, name, config, weights, tokens):
        self.name = name
        self.config = config
        self._weights, self._tokens = functools.cache(weights), \
            functools.cache(tokens)
        self._results = {}

    @property
    def M(self):
        return module(self.name)

    @property
    def MR(self):
        return module(f"{self.name}_reference")

    def weights(self, *args, **kwargs):
        return _copy(self._weights(*args, **kwargs))

    def tokens(self, *args, **kwargs):
        return self._tokens(*args, **kwargs)

    def result(self, kind, which="program", batch=None, dtype=F32):
        """The ``kind`` ("value_and_grad" of the loss, or "forward") of the
        program (``which`` "program") or of the package reference
        ("reference") at the default configuration in ``dtype``, the
        default weights and the default tokens of ``batch`` rows (the
        forward reads all but the last id of each row), computed once."""
        key = (kind, which, batch, jnp.dtype(dtype))
        if key not in self._results:
            mod = self.M if which == "program" else self.MR
            tok = self.tokens() if batch is None else self.tokens(batch=batch)
            if kind == "forward":
                f, tok = jitted(mod.forward), tok[:, :-1]
            else:
                f = jitted(mod.loss_fn, jax.value_and_grad)
            self._results[key] = f(self.weights(), tok,
                                   self.config(dtype=dtype))
        return self._results[key]


# ---------------------------------------------------------------------------
# the models
# ---------------------------------------------------------------------------


OLMO_LAYERS = ((2, "linear_attention"), (3, "full_attention"),
               (4, "linear_attention"))
OLMO_DIMS = dict(dim=64, ffn=96, heads=4, head_dim=16, lin_heads=4,
                 key_dim=16, value_dim=32, d_conv=4)


def _olmo_config(layers=OLMO_LAYERS, dtype=F32):
    return module("olmo_hybrid").Config(
        vocab=96, dim=64, ffn=96, heads=4, head_dim=16, lin_heads=4,
        key_dim=16, value_dim=32, layers=layers, loss_rows=16, dtype=dtype)


def _olmo_weights(layers=OLMO_LAYERS, seed=3):
    import datagen_olmo_hybrid as G
    return _moved(G.olmo_hybrid_weights(jax.random.key(seed), OLMO_DIMS,
                                        [k for _, k in layers], 96, F32),
                  seed)


def _olmo_tokens(seed=1, batch=1, seq=72):
    import datagen_olmo_hybrid as G
    return G.token_rows(jax.random.key(seed), 1, batch, seq + 1, 96)[0]


olmo_hybrid = Tiny("olmo_hybrid", _olmo_config, _olmo_weights, _olmo_tokens)


GRANITE_LAYERS = ((4, "mamba"), (5, "attention"), (6, "mamba"))
GRANITE_DIMS = dict(dim=64, ffn=96, heads=4, kv_heads=2, head_dim=16,
                    ssm_heads=4, ssm_head_dim=16, d_inner=64, d_state=16,
                    n_groups=2, d_conv=4, chunk=16)
GRANITE_MULT = dict(embedding_mult=12.0, residual_mult=0.22,
                    attention_mult=1 / 16, logits_scaling=8.0)


def _granite_config(layers=GRANITE_LAYERS, dtype=F32, **mult):
    return module("mamba2_hybrid").Config(
        vocab=96, dim=64, ffn=96, heads=4, kv_heads=2, head_dim=16,
        ssm_heads=4, ssm_head_dim=16, d_state=16, n_groups=2, chunk=16,
        layers=layers, loss_rows=16, dtype=dtype, **{**GRANITE_MULT, **mult})


def _granite_weights(layers=GRANITE_LAYERS, seed=3):
    import datagen_granite as G
    return _moved(G.granite_weights(jax.random.key(seed), GRANITE_DIMS,
                                    [k for _, k in layers], 96, F32), seed)


def _granite_tokens(seed=1, batch=1, seq=40):
    import datagen_granite as G
    return G.token_rows(jax.random.key(seed), 1, batch, seq + 1, 96)[0]


mamba2_hybrid = Tiny("mamba2_hybrid", _granite_config, _granite_weights,
                     _granite_tokens)


SAMBAY_DIMS = dict(dim=128, ffn=256, heads=8, kv_heads=4, head_dim=16,
                   window=24, d_inner=256, d_state=16, d_conv=4, dt_rank=8)


def sambay_cut():
    """Layers 14..19 of the published 32: every kind once."""
    return tuple((i, k) for i, k in module("sambay").layer_kinds(32, 2)
                 if 14 <= i <= 19)


def _sambay_config(layers=None, dtype=F32):
    return module("sambay").Config(
        vocab=96, dim=128, ffn=256, heads=8, kv_heads=4, head_dim=16,
        window=24, layers=sambay_cut() if layers is None else layers,
        dtype=dtype, loss_rows=32)


def _sambay_weights(layers=None, seed=3):
    import datagen_sambay as G
    layers = sambay_cut() if layers is None else layers
    return _moved(G.sambay_weights(jax.random.key(seed), SAMBAY_DIMS,
                                   layers, 96, F32), seed)


def _sambay_tokens(seed=1, batch=1, seq=32):
    import datagen_sambay as G
    return G.token_rows(jax.random.key(seed), 1, batch, seq + 1, 96)[0]


sambay = Tiny("sambay", _sambay_config, _sambay_weights, _sambay_tokens)


MLA_DIMS = dict(vocab=96, dim=64, heads=4, q_rank=24, kv_rank=16, nope=24,
                rope=8, v_dim=32, ffn=128, moe_ffn=32, n_experts=16, top_k=4,
                loss_rows=32)
MLA_LAYERS = ((0, "dense"), (1, "moe"), (2, "moe"))


def _mla_config(held=(4, 4), mtp=47, dtype=F32, layers=MLA_LAYERS, **kw):
    return module("mla_moe").Config(**{**MLA_DIMS, **kw}, layers=layers,
                                    held=held, mtp=mtp, dtype=dtype)


def _mla_weights(mtp=47):
    M = module("mla_moe")
    return M.init_params(jax.random.key(0), _mla_config(mtp=mtp))


def _mla_tokens(batch=2, seq=50):
    return jax.random.randint(jax.random.key(1), (batch, seq), 0,
                              MLA_DIMS["vocab"])


mla_moe = Tiny("mla_moe", _mla_config, _mla_weights, _mla_tokens)


# ---------------------------------------------------------------------------
# the tiny optax step of each model, lowered and compiled once
# ---------------------------------------------------------------------------


@functools.cache
def _step(name):
    """(registered step, its abstract arguments) of ``name``'s tiny optax
    step: bfloat16, the tests' configuration."""
    import optax
    M = module(name)
    if name == "transformer":
        cfg, tokens = M.Config(vocab=256, dim=128, heads=4, layers=2,
                               max_seq=128, dtype=jnp.bfloat16), (2, 65)
    elif name == "sambay":
        cfg, tokens = _sambay_config(dtype=jnp.bfloat16), (1, 33)
    elif name == "mla_moe":
        cfg = _mla_config(dtype=jnp.bfloat16, layers=MLA_LAYERS[:2])
        tokens = (1, 34)
    elif name == "mamba2_hybrid":
        cfg, tokens = _granite_config(dtype=jnp.bfloat16), (1, 33)
    else:
        cfg, tokens = _olmo_config(dtype=jnp.bfloat16), (1, 33)
    step, init = M.make_optax_train_step(cfg, optax.adamw(1e-3))
    p = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    return step, (p, jax.eval_shape(init, p),
                  jax.ShapeDtypeStruct(tokens, jnp.int32))


@functools.cache
def step_text(name):
    """The lowered text, with its locations (op names), of ``name``'s
    tiny optax step."""
    step, args = _step(name)
    return step.lower(*args).as_text(debug_info=True)


@functools.cache
def step_program(name):
    """(program, scopes, phase map, compiled text) of ``name``'s tiny
    optax step, the program noted with the step's abstract arguments."""
    from distributedarrays_tpu.telemetry import programs
    step, args = _step(name)
    step.note(*args)
    return (step, step.scopes, programs.phase_map(step),
            programs.compiled(step).as_text())
