"""The training-step driver: a step is one call of the step that
``models.transformer.make_optax_train_step(cfg, optax.adamw(...))`` returns,
on a seeded batch, the loss read to the host.

Set-up builds ONE object (the compiled step with its parameters and
optimizer state), drives it from the seed through its first steps by the
window's own call and feed, takes the readings the comparison needs (each
step's loss, the first gradient's norm a leaf from Adam's first moment, the
parameters' change after the steps), and hands that same object to the
window.  After the window the state is freed and the plain reference
follows the same first steps from the same seed.
"""

from __future__ import annotations

import time

import numpy as np

import counts
import datagen
import refs


def _leaf_names(layers):
    names = ["embed", "pos", "ln_f", "head"]
    for i in range(layers):
        names += [f"blocks.{i}.{k}"
                  for k in ("ln1", "qkv", "proj", "ln2", "w1", "w2")]
    return names


def _program_leaf_dict(tree):
    """{leaf name: float} from a program-shaped tree of scalars."""
    out = {k: float(tree[k]) for k in ("embed", "pos", "ln_f", "head")}
    for i, blk in enumerate(tree["blocks"]):
        for k, v in blk.items():
            out[f"blocks.{i}.{k}"] = float(v)
    return out


def _stacked_leaf_dict(tree):
    """{leaf name: float} from the reference's stacked tree of per-layer
    norm vectors."""
    out = {k: float(tree[k]) for k in ("embed", "pos", "ln_f", "head")}
    for k, vec in tree["blocks"].items():
        for i, v in enumerate(np.asarray(vec)):
            out[f"blocks.{i}.{k}"] = float(v)
    return out


def _find_mu(opt_state):
    """Adam's first moment inside an optax state (the entry with ``mu``)."""
    import jax
    for part in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda s: hasattr(s, "mu")):
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam moments in the optimizer state")


class Driver:

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.sizes = dict(t["sizes"])
        cfg = dict(ctx.config)
        if ctx.tiny:
            self.sizes.update(t.get("tiny", {}))
            cfg.update(cfg.get("tiny", {}))
        self.model = dict(vocab=int(cfg["vocab_size"]), dim=int(cfg["n_embd"]),
                          layers=int(cfg["n_layer"]), ffn=int(cfg["n_inner"]),
                          positions=int(cfg["n_positions"]))
        self.heads = int(cfg["n_head"])
        self.store = cfg.get("torch_dtype", "bfloat16")
        self.control_lowp = {"bfloat16": "float8_e4m3fn",
                             "float32": "bfloat16"}[self.store]
        self.batch = int(self.sizes["batch"])
        self.seq = int(self.sizes["seq"])
        if self.seq > self.model["positions"]:
            raise ValueError("seq exceeds the configuration's n_positions")
        self.pool = int(self.sizes["pool"])
        self.check_steps = int(t.get("check_steps", 3))
        self.opt = dict(t["optimizer"])
        self.tokens_per_step = self.batch * self.seq
        self.losses = []
        self.i = 0
        self.readings = None
        # faults a test may plant under the timed path (never set by a run)
        self.wrap_step = None

    # -- set-up -------------------------------------------------------------

    def _weights(self):
        import jax.numpy as jnp
        return datagen.transformer_weights(
            datagen.named_key(self.ctx.seed, "weights"),
            dtype=jnp.dtype(self.store), **self.model)

    def _tokens(self):
        return datagen.token_batches(
            datagen.named_key(self.ctx.seed, "tokens"), self.pool,
            self.batch, self.seq + 1, self.model["vocab"])

    def setup(self):
        import jax
        import jax.numpy as jnp
        import optax
        from distributedarrays_tpu.models import transformer as T
        m, o = self.model, self.opt
        cfg = T.Config(vocab=m["vocab"], dim=m["dim"], heads=self.heads,
                       layers=m["layers"], ffn_mult=m["ffn"] // m["dim"],
                       max_seq=m["positions"], dtype=jnp.dtype(self.store))
        tx = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        step, init = T.make_optax_train_step(cfg, tx)
        self._step = self.wrap_step(step) if self.wrap_step else step
        self.ctx.mark("program imported, step built")
        self.params = self._weights()
        self.opt_state = init(self.params)
        toks = self._tokens()
        self.feed = [toks[i] for i in range(self.pool)]
        jax.block_until_ready((self.feed, self.params, self.opt_state))
        del toks
        self.ctx.mark("weights, optimizer state and token pool on the chip")
        # the first steps, through the window's own call and feed
        gnorm = None
        for s in range(self.check_steps):
            self.step()
            if s == 0:
                self.ctx.mark("first step done (compiled or loaded)")
                gnorm = refs.leaf_norms(_find_mu(self.opt_state))
        p0 = self._weights()
        dnorm = _diff_norms(self.params, p0, False)
        del p0
        self.ctx.mark("first steps driven, readings taken")
        scale = 1.0 / (1.0 - o["b1"])
        self.readings = {
            "loss": list(self.losses),
            "gnorm": {k: v * scale
                      for k, v in _program_leaf_dict(gnorm).items()},
            "dnorm": _program_leaf_dict(dnorm)}
        self.begin_window()

    def begin_window(self):
        self.losses = []

    # -- one step -------------------------------------------------------------

    def step(self, span=None):
        toks = self.feed[self.i % self.pool]
        self.i += 1
        if span is None:
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, toks)
            t_dispatched = time.perf_counter()
            val = float(loss)
        else:
            with span("bench.dispatch"):
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, toks)
            t_dispatched = time.perf_counter()
            with span("bench.read"):
                val = float(loss)
        self.losses.append(val)
        return t_dispatched, bool(np.isfinite(val))

    def cost(self):
        m = self.model
        flops = counts.transformer_flops_per_token(
            m["vocab"], m["dim"], m["layers"], m["ffn"], self.seq
        ) * self.tokens_per_step
        n = counts.transformer_params(m["vocab"], m["dim"], m["layers"],
                                      m["ffn"], m["positions"])
        return counts.Cost(flops=flops,
                           hbm_bytes=counts.adamw_state_bytes(n, 2))

    def attention_flops(self):
        """Required operations of the step's flash kernels, forward and
        backward, over all layers."""
        m = self.model
        d = m["dim"] // self.heads
        one = lambda bwd: counts.flash_attention_flops(
            self.batch, self.heads, self.seq, d, causal=True, backward=bwd)
        return m["layers"] * (one(False) + one(True))

    # -- after the window -----------------------------------------------------

    def finish(self):
        """The state is freed here: the comparison needs only the readings
        set-up took, and the reference needs the chip's memory."""
        self.params = self.opt_state = self.feed = None
        return {"readings": self.readings,
                "nonfinite": sum(not np.isfinite(v) for v in self.losses)}

    def release(self, outputs):
        pass

    def reference(self, lowp=None, rows=None):
        """The readings of the plain reference over the same first steps:
        float32 arithmetic, parameters kept in the stored type between
        steps, as the configuration states.  ``rows`` cuts each batch to its
        first rows (a planted fault: half the batch left out)."""
        import jax
        import jax.numpy as jnp
        o = self.opt
        p = refs.stack_blocks(self._weights())
        mu = jax.tree_util.tree_map(jnp.zeros_like, p)
        nu = jax.tree_util.tree_map(jnp.zeros_like, p)
        p_start = jax.tree_util.tree_map(jnp.copy, p)
        toks = self._tokens()
        hyper = (float(o["lr"]), float(o["b1"]), float(o["b2"]),
                 float(o["eps"]), float(o["weight_decay"]), str(self.store))
        out = {"loss": []}
        for s in range(self.check_steps):
            batch = toks[s % self.pool]
            if rows is not None:
                batch = batch[:rows]
            loss, g = refs.ref_loss_and_grads(p, batch, self.heads, lowp)
            out["loss"].append(loss)
            if s == 0:
                out["gnorm"] = _stacked_leaf_dict(_diff_norms(g, None, True))
            p, mu, nu = refs.adamw_reference(p, mu, nu, g,
                                             np.float32(s + 1), hyper)
        out["dnorm"] = _stacked_leaf_dict(_diff_norms(p, p_start, True))
        return out

    def control_outputs(self, lowp):
        return {"readings": self.reference(lowp), "nonfinite": 0}

    def compare(self, outputs, ref):
        """{name: value}: each step's loss against the reference's, and by
        the worst leaf the gap between the program's norm and the
        reference's (first gradient; parameters' change), against the
        reference's norm of that leaf or of the median leaf, whichever is
        larger.  Leaves whose reference gradient is under a thousandth of
        the median leaf's move by round-off alone and are left out of the
        change."""
        got = outputs["readings"]
        numbers = {}
        for s, (a, b) in enumerate(zip(got["loss"], ref["loss"])):
            numbers[f"loss{s + 1}_rel"] = abs(a - b) / max(abs(b), 1e-30)
        gmed = float(np.median(list(ref["gnorm"].values())))
        dmed = float(np.median(list(ref["dnorm"].values())))

        def worst(kind, med, skip=()):
            w, at = 0.0, ""
            for k, r in ref[kind].items():
                if k in skip:
                    continue
                gap = abs(got[kind][k] - r) / max(r, med, 1e-30)
                if not gap <= w:
                    w, at = gap, k
            return w, at

        numbers["grad_norm_gap"], g_at = worst("gnorm", gmed)
        still = {k for k, r in ref["gnorm"].items() if r < 1e-3 * gmed}
        numbers["dparam_norm_gap"], d_at = worst("dnorm", dmed, still)
        numbers["nonfinite_losses"] = float(outputs["nonfinite"])
        self.worst_leaves = {"grad_norm_gap": g_at, "dparam_norm_gap": d_at,
                             "left_out": sorted(still)}
        return numbers


def _diff_norms(a, b, stacked):
    """The norm of every leaf of ``a - b`` (of ``a`` where ``b`` is None) in
    float32, in one jitted call so no difference is ever held whole.  A
    stacked reference tree gives a per-layer vector for each block leaf."""
    import jax
    import jax.numpy as jnp

    def norms(a, b):
        def leaf(path, x, y=None):
            d = x.astype(jnp.float32)
            if y is not None:
                d = d - y.astype(jnp.float32)
            in_blocks = stacked and any(
                getattr(k, "key", None) == "blocks" for k in path)
            ax = tuple(range(1, d.ndim)) if in_blocks else None
            return jnp.sqrt(jnp.sum(jnp.square(d), axis=ax))
        if b is None:
            return jax.tree_util.tree_map_with_path(leaf, a)
        return jax.tree_util.tree_map_with_path(leaf, a, b)

    return jax.jit(norms)(a, b)
