"""The training-step driver of the Olmo-Hybrid-7B configuration: a step is
one call of the step that ``models.olmo_hybrid.make_optax_train_step(cfg,
optax.adamw(...))`` returns, on a seeded row of token ids, the loss read to
the host.

``drivers/train_step.py``'s driver with this model's leaves: the same
set-up (one object driven through its first steps by the window's own
call and feed), the same readings (three losses, the first gradient's norm
a leaf from Adam's first moment, the parameters' change after the steps),
the same comparison; the weights come from ``datagen_olmo_hybrid``, the
counts from ``counts_olmo_hybrid`` and the reference from
``refs_olmo_hybrid``.

The fault this driver plants itself (``reference(rows=...)``): half of the
tokens left out.
"""

from __future__ import annotations

import numpy as np

import counts
import counts_olmo_hybrid
import datagen
import datagen_olmo_hybrid
import refs
import refs_olmo_hybrid
from drivers import train_step
from drivers.train_step import _diff_norms, _find_mu
from drivers.train_step_sambay import _leaf_dict


class Driver(train_step.Driver):

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.sizes = dict(t["sizes"])
        cfg = dict(ctx.config)
        if ctx.tiny:
            self.sizes.update(t.get("tiny", {}))
            cfg.update(cfg.get("tiny", {}))
        dim, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        self.m = dict(
            dim=dim, ffn=int(cfg["intermediate_size"]), heads=heads,
            head_dim=dim // heads, lin_heads=int(cfg["linear_num_key_heads"]),
            key_dim=int(cfg["linear_key_head_dim"]),
            value_dim=int(cfg["linear_value_head_dim"]),
            d_conv=int(cfg["linear_conv_kernel_dim"]))
        if int(cfg["num_key_value_heads"]) != heads \
                or int(cfg["linear_num_value_heads"]) != self.m["lin_heads"] \
                or cfg["attention_bias"] or cfg["tie_word_embeddings"] \
                or not cfg["linear_allow_neg_eigval"] \
                or cfg["rope_parameters"].get("rope_theta") is not None:
            raise ValueError("this driver trains the NoPE MHA hybrid with an "
                             "untied head, no bias, as many value heads as "
                             "key heads and beta in (0, 2)")
        self.eps = float(cfg["rms_norm_eps"])
        self.vocab = int(cfg["vocab_size"])
        self.layers = tuple((int(i), cfg["layer_types"][int(i)])
                            for i in cfg["kept_layers"])
        if len(self.layers) != int(cfg["num_hidden_layers"]):
            raise ValueError("kept_layers and num_hidden_layers disagree")
        self.kinds = tuple(k for _, k in self.layers)
        self.store = cfg.get("torch_dtype", "bfloat16")
        self.control_lowp = {"bfloat16": "float8_e4m3fn",
                             "float32": "bfloat16"}[self.store]
        self.batch = int(self.sizes["batch"])
        if self.batch != 1:
            raise ValueError("this driver trains one row a step (one "
                             "document a row, no packing)")
        self.seq = int(self.sizes["seq"])
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
        return datagen_olmo_hybrid.olmo_hybrid_weights(
            datagen.named_key(self.ctx.seed, "weights"), self.m, self.kinds,
            self.vocab, jnp.dtype(self.store))

    def _tokens(self):
        # ids from the vocabulary slice held here, one document a row
        return datagen_olmo_hybrid.token_rows(
            datagen.named_key(self.ctx.seed, "tokens"), self.pool,
            self.batch, self.seq + 1, self.vocab)

    def setup(self):
        import jax
        import jax.numpy as jnp
        import optax
        from distributedarrays_tpu.models import olmo_hybrid as M
        m, o = self.m, self.opt
        cfg = M.Config(
            vocab=self.vocab, dim=m["dim"], ffn=m["ffn"], heads=m["heads"],
            head_dim=m["head_dim"], lin_heads=m["lin_heads"],
            key_dim=m["key_dim"], value_dim=m["value_dim"],
            d_conv=m["d_conv"], layers=self.layers,
            eps=self.eps, dtype=jnp.dtype(self.store))
        tx = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        step, init = M.make_optax_train_step(cfg, tx)
        self._step = self.wrap_step(step) if self.wrap_step else step
        self.ctx.mark("program imported, step built")
        self.params = self._weights()
        self.opt_state = init(self.params)
        toks = self._tokens()
        self.feed = [toks[i] for i in range(self.pool)]
        jax.block_until_ready((self.feed, self.params, self.opt_state))
        del toks
        self.ctx.mark("weights, optimizer state and token pool on the chip")
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
            "gnorm": {k: v * scale for k, v in _leaf_dict(gnorm).items()},
            "dnorm": _leaf_dict(dnorm)}
        self.begin_window()

    # -- what the step needs -------------------------------------------------

    def cost(self):
        flops = counts_olmo_hybrid.olmo_hybrid_flops_per_token(
            self.m, self.kinds, self.vocab, self.seq) * self.tokens_per_step
        n = counts_olmo_hybrid.olmo_hybrid_params(self.m, self.kinds,
                                                  self.vocab)
        return counts.Cost(flops=flops,
                           hbm_bytes=counts.adamw_state_bytes(n, 2))

    def attention_flops(self):
        """Required operations of the step's flash kernels, forward and
        backward, over the full-attention layers."""
        n = self.kinds.count("full_attention")
        return n * sum(counts_olmo_hybrid.attention_flops(
            self.batch, self.seq, self.m, b) for b in (False, True))

    def gdn_cost(self):
        """Required work of the step's delta-rule kernels, forward and
        backward, over the linear-attention layers."""
        n = self.kinds.count("linear_attention")
        one = counts_olmo_hybrid.gdn_cost(self.batch, self.seq, self.m, 2)
        return counts.Cost(flops=n * one.flops, hbm_bytes=n * one.hbm_bytes)

    # -- after the window -----------------------------------------------------

    def reference(self, lowp=None, rows=None):
        """The readings of the plain reference over the same first steps:
        float32 arithmetic, parameters kept in the stored type between
        steps.  ``rows`` not None plants the fault "half of the tokens
        left out": with one row a step there is no half of the batch to
        leave out, so each row is trained on its first half only
        (``calibrate.py`` passes ``batch // 2``)."""
        import jax
        import jax.numpy as jnp
        o = self.opt
        dims = dict(self.m, eps=self.eps, kinds=self.kinds)
        p = self._weights()
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t))
        mu, nu = zeros(p), zeros(p)
        toks = self._tokens()
        hyper = (float(o["lr"]), float(o["b1"]), float(o["b2"]),
                 float(o["eps"]), float(o["weight_decay"]), str(self.store))
        keep = None if rows is None else self.seq // 2
        out = {"loss": [], "gnorm": {}}
        for s in range(self.check_steps):
            first = (lambda n, g: out["gnorm"].update(
                refs_olmo_hybrid.subtree_norms(n, g))) if s == 0 else None
            row = toks[s % self.pool][0]
            # a layer's update as soon as its gradients exist: at the
            # cell's size the whole tree of float32 gradients does not fit
            # beside the moments and a layer's backward
            out["loss"].append(refs_olmo_hybrid.ref_train_step(
                p, mu, nu, row if keep is None else row[:keep + 1],
                np.float32(s + 1), hyper, dims, lowp, first))
        del mu, nu
        out["dnorm"] = refs_olmo_hybrid.leaf_norm_dict(p, self._weights())
        return out
