"""The training-step driver of the GLM-4.7-Flash configuration: a step is one
call of the step that ``models.mla_moe.make_optax_train_step(cfg,
optax.adamw(...))`` returns, on a seeded row of token ids, the loss read to
the host.

``drivers/train_step.py``'s driver with this model's leaves: the same
set-up (one object driven through its first steps by the window's own call
and feed), the same readings (three losses, the first gradient's norm a
leaf from Adam's first moment, the parameters' change after the steps), the
same comparison, and one number more: the share of token-slots whose chosen
experts differ from the reference's at step 1 (``route_flip_share``).  The
weights come from ``datagen_glm_moe``, the counts from ``counts_glm_moe``
and the reference from ``refs_glm_moe``.  The step hands back ``[loss,
L_main, L_mtp]``; the first is the loss that is compared, the parts are
printed beside the reference's.  After the window, outside every timed
path, ``models.mla_moe.routing_stats`` says what the held experts saw.

The fault this driver plants itself (``reference(rows=...)``): half of the
tokens left out.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import counts
import counts_glm_moe
import datagen
import datagen_glm_moe
import refs
import refs_glm_moe
from drivers import train_step
from drivers.train_step import _diff_norms, _find_mu


def _say(*a):
    print(*a, file=sys.stderr, flush=True)


def _leaf_dict(tree, prefix=""):
    """{leaf name: float} from a program-shaped tree of scalars."""
    out = {}
    for k, v in tree.items():
        if k == "layers":
            for i, layer in enumerate(v):
                out.update(_leaf_dict(layer, f"{prefix}layers.{i}."))
        elif isinstance(v, dict):
            out.update(_leaf_dict(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = float(v)
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
        first, count = (int(v) for v in cfg["held_experts"])
        if count != int(cfg["n_routed_experts"]):
            raise ValueError("held_experts and n_routed_experts disagree")
        if int(cfg["num_nextn_predict_layers"]) != 1:
            raise ValueError("this driver trains one MTP module")
        self.m = dict(
            dim=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), v_dim=int(cfg["v_head_dim"]),
            ffn=int(cfg["intermediate_size"]),
            moe_ffn=int(cfg["moe_intermediate_size"]),
            n_experts=int(cfg["published"]["n_routed_experts"]), held=count,
            top_k=int(cfg["num_experts_per_tok"]))
        self.first_held = first
        self.scale = float(cfg["routed_scaling_factor"])
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.lam = float(cfg["mtp_lambda"])
        self.vocab = int(cfg["vocab_size"])
        dense = int(cfg["first_k_dense_replace"])
        self.layers = tuple((int(i), "dense" if int(i) < dense else "moe")
                            for i in cfg["kept_layers"])
        if len(self.layers) != int(cfg["num_hidden_layers"]):
            raise ValueError("kept_layers and num_hidden_layers disagree")
        self.kinds = tuple(k for _, k in self.layers)
        self.mtp_layer = int(cfg["mtp_layer"])
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
        self.losses, self.parts = [], []
        self.i = 0
        self.readings = None
        # faults a test may plant under the timed path (never set by a run)
        self.wrap_step = None

    # -- set-up -------------------------------------------------------------

    def _weights(self):
        import jax.numpy as jnp
        return datagen_glm_moe.glm_weights(
            datagen.named_key(self.ctx.seed, "weights"), self.m, self.kinds,
            self.vocab, True, jnp.dtype(self.store))

    def _tokens(self):
        # ids from the vocabulary slice held here, one document a row
        return datagen_glm_moe.token_rows(
            datagen.named_key(self.ctx.seed, "tokens"), self.pool,
            self.batch, self.seq + 2, self.vocab)

    def _config(self):
        import jax.numpy as jnp
        from distributedarrays_tpu.models import mla_moe as M
        m = self.m
        return M.Config(
            vocab=self.vocab, dim=m["dim"], heads=m["heads"],
            q_rank=m["q_rank"],
            kv_rank=m["kv_rank"], nope=m["nope"], rope=m["rope"],
            v_dim=m["v_dim"], ffn=m["ffn"], moe_ffn=m["moe_ffn"],
            n_experts=m["n_experts"], held=(self.first_held, m["held"]),
            top_k=m["top_k"], route_scale=self.scale, layers=self.layers,
            mtp=self.mtp_layer, mtp_lambda=self.lam, rope_theta=self.theta,
            eps=self.eps, dtype=jnp.dtype(self.store))

    def setup(self):
        import jax
        import optax
        from distributedarrays_tpu.models import mla_moe as M
        o = self.opt
        tx = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        step, init = M.make_optax_train_step(self._config(), tx)
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
            "loss": list(self.losses), "parts": list(self.parts),
            "gnorm": {k: v * scale for k, v in _leaf_dict(gnorm).items()},
            "dnorm": _leaf_dict(dnorm)}
        self.begin_window()

    # -- one step -------------------------------------------------------------

    def step(self, span=None):
        """``train_step.Driver.step`` for a step whose third result is the
        vector ``[loss, L_main, L_mtp]``: one read brings all three."""
        toks = self.feed[self.i % self.pool]
        self.i += 1
        if span is None:
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, toks)
            t_dispatched = time.perf_counter()
            vals = np.asarray(loss)
        else:
            with span("bench.dispatch"):
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, toks)
            t_dispatched = time.perf_counter()
            with span("bench.read"):
                vals = np.asarray(loss)
        self.losses.append(float(vals[0]))
        self.parts.append((float(vals[1]), float(vals[2])))
        return t_dispatched, bool(np.isfinite(vals).all())

    # -- what the step needs -------------------------------------------------

    def cost(self):
        flops = counts_glm_moe.glm_flops_per_token(
            self.m, self.kinds, self.vocab, self.seq, True
        ) * self.tokens_per_step
        n = counts_glm_moe.glm_params(self.m, self.kinds, self.vocab, True)
        return counts.Cost(flops=flops,
                           hbm_bytes=counts.adamw_state_bytes(n, 2))

    def attention_flops(self):
        """Required operations of the step's flash kernels, forward and
        backward, over the kept layers and the MTP block."""
        return (len(self.kinds) + 1) * sum(counts_glm_moe.attention_flops(
            self.batch, self.seq, self.m, b) for b in (False, True))

    # -- after the window -----------------------------------------------------

    def finish(self):
        """A note of what the held experts saw: the program's own routing
        of the pool's first row on the parameters the window ended with;
        then the state is freed, and the chosen experts of the first step
        are taken from the seed's weights (all outside the timed path)."""
        from distributedarrays_tpu.models import mla_moe as M
        cfg = self._config()
        last = M.routing_stats(self.params, self.feed[0][:, :-1], cfg)
        held = [int(s["held_rows"]) for s in last]
        lo, n = self.first_held, self.m["held"]
        skew = max(float(np.max(c[lo:lo + n]) / max(np.mean(c[lo:lo + n]), 1))
                   for c in (np.asarray(s["counts"]) for s in last))
        expected = counts_glm_moe.expert_rows(self.tokens_per_step, self.m)
        _say(f"held rows a step, the pool's row 0 after the window: "
             f"{sum(held)} over the expert layers {held} (expected "
             f"{expected * len(held):.0f}, {expected:.0f} a layer); the held "
             f"experts' largest load over their mean: {skew:.3f}")
        self.params = self.opt_state = None
        stats = M.routing_stats(self._weights(), self.feed[0][:, :-1], cfg)
        self.readings["chosen"] = [np.asarray(s["chosen"]) for s in stats]
        self.feed = None
        return {"readings": self.readings,
                "nonfinite": sum(not np.isfinite(v) for v in self.losses)}

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
        m = self.m
        dims = dict(heads=m["heads"], q_rank=m["q_rank"],
                    kv_rank=m["kv_rank"], nope=m["nope"], rope=m["rope"],
                    v_dim=m["v_dim"], top_k=m["top_k"],
                    held=(self.first_held, m["held"]), eps=self.eps,
                    theta=self.theta, scale=self.scale, lam=self.lam,
                    kinds=self.kinds)
        p = self._weights()
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t))
        mu, nu = zeros(p), zeros(p)
        toks = self._tokens()
        hyper = (float(o["lr"]), float(o["b1"]), float(o["b2"]),
                 float(o["eps"]), float(o["weight_decay"]), str(self.store))
        keep = None if rows is None else self.seq // 2
        out = {"loss": [], "parts": [], "gnorm": {}, "chosen": []}
        for s in range(self.check_steps):
            first = s == 0
            row = toks[s % self.pool][0]
            # a subtree's update as soon as its gradients exist: the whole
            # tree of float32 gradients does not fit beside the moments
            loss, main, mtp = refs_glm_moe.ref_train_step(
                p, mu, nu, row if keep is None else row[:keep + 2],
                np.float32(s + 1), hyper, dims, lowp,
                (lambda n, g: out["gnorm"].update(
                    refs_glm_moe.subtree_norms(n, g))) if first else None,
                (lambda n, idx: out["chosen"].append(np.asarray(idx)))
                if first else None)
            out["loss"].append(loss)
            out["parts"].append((main, mtp))
        del mu, nu
        out["dnorm"] = refs_glm_moe.leaf_norm_dict(p, self._weights())
        return out

    def compare(self, outputs, ref):
        """``train_step.Driver.compare`` and ``route_flip_share``: over
        the expert layers of the first step (the MTP block last), the share
        of token-slots whose chosen expert is not among the reference's
        chosen for that token (a near-tie turned by rounding)."""
        numbers = super().compare(outputs, ref)
        got = outputs["readings"]
        for s, (a, b) in enumerate(zip(got["parts"], ref["parts"])):
            _say(f"loss {s + 1}: L_main {a[0]!r} (reference {b[0]!r}), "
                 f"L_mtp {a[1]!r} (reference {b[1]!r})")
        off = total = 0
        for a, b in zip(got["chosen"], ref["chosen"]):
            n = min(len(a), len(b))          # the half-tokens fault is shorter
            hit = (a[:n, :, None] == b[:n, None, :]).any(axis=-1)
            off, total = off + int((~hit).sum()), total + hit.size
        numbers["route_flip_share"] = off / max(total, 1)
        return numbers
