"""The training-step driver of the SambaY configuration: a step is one call
of the step that ``models.sambay.make_optax_train_step(cfg, optax.adamw(...))``
returns, on a seeded row of token ids, the loss read to the host.

``drivers/train_step.py``'s driver with this model's leaves: the same
set-up (one object driven through its first steps by the window's own
call and feed), the same readings (three losses, the first gradient's norm
a leaf from Adam's first moment, the parameters' change after the steps),
the same comparison; the weights come from ``datagen_sambay``, the counts
from ``counts_sambay`` and the reference from ``refs_sambay``.
"""

from __future__ import annotations

import numpy as np

import counts
import counts_sambay
import datagen
import datagen_sambay
import refs
import refs_sambay
from drivers import train_step
from drivers.train_step import _diff_norms, _find_mu


def published_layout(n_layers: int, mb_per_layer: int):
    """{published index: kind} by the modelling code's rule (the
    configuration file's ``assumed.layout``)."""
    half = n_layers // 2
    kinds = {}
    for i in range(n_layers):
        ssm = i % mb_per_layer == 0
        if i < half:
            kinds[i] = "mamba" if ssm else "window"
        elif i <= half + 1:
            kinds[i] = "mamba" if ssm else "full"
        else:
            kinds[i] = "gmu" if ssm else "cross"
    return kinds


def _leaf_dict(tree):
    """{leaf name: float} from a program-shaped tree of scalars."""
    out = {k: float(v) for k, v in tree.items() if k != "layers"}
    for i, layer in enumerate(tree["layers"]):
        for k, v in layer.items():
            out[f"layers.{i}.{k}"] = float(v)
    return out


class Driver(train_step.Driver):

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.sizes = dict(t["sizes"])
        cfg = dict(ctx.config)
        if ctx.tiny:
            self.sizes.update(t.get("tiny", {}))
            cfg.update(cfg.get("tiny", {}))
        ssm = cfg["mamba"]
        dim, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        self.m = dict(dim=dim, ffn=int(cfg["intermediate_size"]),
                      heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
                      head_dim=dim // heads,
                      window=int(cfg["sliding_window"]),
                      d_inner=int(ssm["expand"]) * dim,
                      d_state=int(ssm["d_state"]), d_conv=int(ssm["d_conv"]),
                      dt_rank=int(ssm["dt_rank"]))
        self.eps = float(cfg["layer_norm_eps"])
        self.vocab = int(cfg["vocab_size"])
        kinds = published_layout(int(cfg["published"]["num_hidden_layers"]),
                                 int(cfg["mb_per_layer"]))
        self.layers = tuple((int(i), kinds[int(i)])
                            for i in cfg["kept_layers"])
        if len(self.layers) != int(cfg["num_hidden_layers"]):
            raise ValueError("kept_layers and num_hidden_layers disagree")
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
        return datagen_sambay.sambay_weights(
            datagen.named_key(self.ctx.seed, "weights"), self.m, self.layers,
            self.vocab, jnp.dtype(self.store))

    def _tokens(self):
        # ids from the vocabulary slice held here, one document a row
        return datagen_sambay.token_rows(
            datagen.named_key(self.ctx.seed, "tokens"), self.pool,
            self.batch, self.seq + 1, self.vocab)

    def setup(self):
        import jax
        import jax.numpy as jnp
        import optax
        from distributedarrays_tpu.models import sambay as S
        m, o = self.m, self.opt
        kw = dict(vocab=self.vocab, dim=m["dim"], ffn=m["ffn"],
                  heads=m["heads"], kv_heads=m["kv_heads"],
                  head_dim=m["head_dim"], window=m["window"],
                  d_state=m["d_state"], d_conv=m["d_conv"],
                  expand=m["d_inner"] // m["dim"], dt_rank=m["dt_rank"],
                  layers=self.layers, eps=self.eps,
                  dtype=jnp.dtype(self.store))
        tx = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        step, init = S.make_optax_train_step(S.Config(**kw), tx)
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
        flops = counts_sambay.sambay_flops_per_token(
            self.m, self.layers, self.vocab, self.seq) * self.tokens_per_step
        n = counts_sambay.sambay_params(self.m, self.layers, self.vocab)
        return counts.Cost(flops=flops,
                           hbm_bytes=counts.adamw_state_bytes(n, 2))

    def attention_flops(self):
        """Required operations of the step's flash kernels, forward and
        backward, over the window, full and cross layers."""
        total = 0.0
        for _, kind in self.layers:
            if kind in ("window", "full", "cross"):
                w = self.m["window"] if kind == "window" else None
                total += sum(counts_sambay.attention_flops(
                    self.batch, self.seq, self.m, w, b) for b in (False, True))
        return total

    def scan_cost(self):
        """Required work of the step's selective scans, forward and
        backward, over the Mamba layers."""
        n = sum(kind == "mamba" for _, kind in self.layers)
        one = counts_sambay.scan_cost(self.batch, self.seq, self.m, 2)
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
        dims = dict(self.m, eps=self.eps, layers=self.layers)
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
                refs_sambay.subtree_norms(n, g))) if s == 0 else None
            row = toks[s % self.pool][0]
            # a layer's update as soon as its gradients exist: at the
            # cell's size the whole tree of float32 gradients does not fit
            # beside the moments and a Mamba layer's backward
            out["loss"].append(refs_sambay.ref_train_step(
                p, mu, nu, row if keep is None else row[:keep + 1],
                np.float32(s + 1), hyper, dims, lowp, first))
        del mu, nu
        out["dnorm"] = refs_sambay.leaf_norm_dict(p, self._weights())
        return out
