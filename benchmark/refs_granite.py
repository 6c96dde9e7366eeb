"""The plain reference of the granite-4.0-h-micro configuration (Mamba-2
layers beside NoPE grouped-query attention): its layer equations written
straightforwardly, independent of the program.

float32 ``jax.numpy`` with ``HIGHEST``-precision products, no kernel,
nothing imported from the program.  The state-space part is the recurrence
by its definition, one position after the other (``lax.scan``), never the
chunked decomposition the program's kernels use; attention is the
explicit causal softmax; the loss and the gradients come from ``jax.vjp``.
So that three steps at the cell's size fit one chip beside the optimizer's
moments, the work goes layer by layer (a layer's input is kept, the layer
is differentiated on its own, and its AdamW update follows as soon as its
gradients exist), the recurrence is checkpointed every 256 positions (32
states of 64 x 64 x 128 floats a layer, not 8192), attention and the loss
go in blocks of rows: the same equations, no other arithmetic.  ``lowp``
makes the CONTROL: every matmul operand, and the recurrence's x, B and C,
rounded to that type (``refs.q``).

Every layer: ``h = x + r Mixer(RMS(x))``, ``y = h + r MLP(RMS(h))`` with
``r`` the residual multiplier; ``MLP(u) = (SiLU(g) v) W2`` with ``[g, v] =
u W1``; RMSNorm with a learned scale; the embedding times its multiplier;
the head is the embedding, transposed, and the logits are divided by
``logits_scaling``.

- mamba: ``[z, xBC, dt] = u W_in``; ``xBC = SiLU(conv(xBC) + b)`` (causal,
  depthwise, ``out[t] = sum_k w[k] xBC[t - K + 1 + k]``); ``[x, B, C] =
  xBC``; per head ``dt = softplus(dt + dt_bias)``, ``a = -exp(A_log)``;
  ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D
  x_t`` (head ``h`` reads group ``h // (heads / groups)``); ``out =
  RMS(y SiLU(z)) W_out`` over all ``d_inner`` channels.
- attention: ``softmax(causal(q k^T attention_mult)) v``, query head ``h``
  on K/V head ``h // (heads / kv_heads)``, no positional term, no bias.

Departures from the published modelling code: none in the equations; what
the published config does not state (the split orders, the gated norm's
span, the grouping of heads) is listed under ``assumed`` in the
configuration file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from refs import HP, q
from refs_sambay import (_blocks, _f32, _mm, _tree_scale as _scale,
                         leaf_norm_dict, ref_adamw, subtree_norms)

__all__ = ["ssd_scan", "ref_train_step", "subtree_norms", "leaf_norm_dict"]

F32 = jnp.float32
TOP = ("embed", "norm_f")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def ssd_scan(x, dt, a, bm, cm, every: int = 256):
    """``y`` (S, H, P) of ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = h_t C_t``, one position after the other: ``x`` (S, H, P), ``dt``
    (S, H), ``a`` (H,), ``bm, cm`` (S, G, N).  The states are kept only
    every ``every`` positions; a block's are computed again in the
    backward."""
    S, H, P = x.shape
    G, N = bm.shape[1:]
    blk = _blocks(S, every)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        bt, ct = (jnp.repeat(t, H // G, axis=0) for t in (bt, ct))
        h = (jnp.exp(dtt * a)[:, None, None] * h
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, ct, precision=HP)

    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    cut = lambda t: t.reshape(S // blk, blk, *t.shape[1:])
    _, y = jax.lax.scan(block, jnp.zeros((H, P, N), F32),
                        (cut(x), cut(dt), cut(bm), cut(cm)))
    return y.reshape(S, H, P)


def _mamba(u, p, dims, lowp):
    S = u.shape[0]
    Hs, P, E = dims["ssm_heads"], dims["ssm_head_dim"], dims["d_inner"]
    G, N, K = dims["n_groups"], dims["d_state"], dims["d_conv"]
    z, xbc, dt = jnp.split(_mm(u, p["in_proj"], lowp), [E, 2 * E + 2 * G * N],
                           axis=-1)
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_w"][k] * xp[k:k + S] for k in range(K))
                      + p["conv_b"])
    x, bm, cm = jnp.split(xbc, [E, E + G * N], axis=-1)
    x = x.reshape(S, Hs, P)
    y = ssd_scan(q(x, lowp), jax.nn.softplus(dt + p["dt_bias"]),
                 -jnp.exp(p["A_log"]), q(bm, lowp).reshape(S, G, N),
                 q(cm, lowp).reshape(S, G, N))
    y = (y + p["D_skip"][:, None] * x).reshape(S, E)
    return _mm(_rms(y * jax.nn.silu(z), p["norm_gated"], dims["eps"]),
               p["out_proj"], lowp)


def _attention(u, p, dims, lowp):
    """Masked dense scores, a block of rows at a time."""
    S = u.shape[0]
    H, KV, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    qh, k, v = jnp.split(_mm(u, p["wqkv"], lowp), [H * hd, (H + KV) * hd],
                         axis=-1)
    k, v = (jnp.repeat(t.reshape(S, KV, hd), H // KV, axis=1) for t in (k, v))
    rb = _blocks(S, 512)
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(args):
        qa, r0 = args
        live = cols <= r0 + jnp.arange(rb)[:, None]
        s = jnp.einsum("qhd,khd->hqk", q(qa, lowp), q(k, lowp),
                       precision=HP) * np.float32(dims["attention_mult"])
        pr = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(pr, lowp), q(v, lowp),
                          precision=HP)

    o = jax.lax.map(block, (qh.reshape(S // rb, rb, H, hd),
                            jnp.arange(0, S, rb, dtype=jnp.int32)))
    return _mm(o.reshape(S, H * hd), p["wo"], lowp)


def _layer(p, x, *, kind, dims, lowp):
    """One layer on one row (S, D)."""
    p = _f32(p)
    eps, r = dims["eps"], np.float32(dims["residual_mult"])
    u = _rms(x, p["norm1"], eps)
    mix = (_mamba if kind == "mamba" else _attention)(u, p, dims, lowp)
    h = x + r * mix
    g, v = jnp.split(_mm(_rms(h, p["norm2"], eps), p["w1"], lowp), 2, axis=-1)
    return h + r * _mm(jax.nn.silu(g) * v, p["w2"], lowp)


def _head_nll(embed, scale, x, tgt, *, eps, logits_scaling, lowp):
    """Summed cross-entropy of the row: the final RMSNorm, the tied head
    over ``logits_scaling``, a block of rows at a time."""
    embed = embed.astype(F32)
    xn = _rms(x, scale.astype(F32), eps)
    S, D = xn.shape
    rb = _blocks(S, 1024)

    @jax.checkpoint
    def block(args):
        xr, tr = args
        logits = _mm(xr, embed.T, lowp) / np.float32(logits_scaling)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tr[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block, (xn.reshape(S // rb, rb, D),
                                       tgt.reshape(S // rb, rb))))


@functools.lru_cache(maxsize=None)
def _programs(kind, dims_key, lowp):
    """(forward, backward) of one layer, jitted; the backward
    differentiates the layer on its own from its kept input."""
    f = functools.partial(_layer, kind=kind, dims=dict(dims_key), lowp=lowp)
    bwd = lambda p, x, ct: jax.vjp(f, _f32(p), x)[1](ct)
    return jax.jit(f), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _head_program(eps, logits_scaling, lowp):
    f = functools.partial(_head_nll, eps=eps, logits_scaling=logits_scaling,
                          lowp=lowp)
    vg = jax.value_and_grad(f, argnums=(0, 1, 2))
    return jax.jit(lambda e, s, x, tgt: vg(_f32(e), _f32(s), x, tgt))


_embed_rows = jax.jit(lambda embed, tok, mult: embed[tok].astype(F32) * mult)
_embed_grad = jax.jit(lambda g_embed, tok, gx, mult:
                      g_embed.at[tok].add(gx * mult), donate_argnums=(0,))


def _row_nll_and_grads(params, row, dims, lowp, sink):
    """Summed cross-entropy of one row of token ids (S + 1,), every
    subtree of its gradient (float32, the program's tree) handed to
    ``sink(n, grads)`` as soon as it exists (``n`` None: the leaves
    outside the layers, last), so that the whole tree's never live
    together."""
    kinds = dims["kinds"]
    key = tuple(sorted((k, v) for k, v in dims.items() if k != "kinds"))
    mult = np.float32(dims["embedding_mult"])
    tok, tgt = row[:-1], row[1:]
    x = _embed_rows(params["embed"], tok, mult)
    kept = []
    for kind, p in zip(kinds, params["layers"]):
        kept.append(x)
        x = _programs(kind, key, lowp)[0](p, x)
    nll, (g_embed, g_norm, gx) = _head_program(
        dims["eps"], dims["logits_scaling"], lowp)(
            params["embed"], params["norm_f"], x, tgt)
    for n in reversed(range(len(kinds))):
        gp, gx = _programs(kinds[n], key, lowp)[1](params["layers"][n],
                                                    kept.pop(), gx)
        sink(n, gp)
    sink(None, {"embed": _embed_grad(g_embed, tok, gx, mult),
                "norm_f": g_norm})
    return float(nll)


def ref_train_step(params, mu, nu, row, t, hyper, dims, lowp=None,
                   on_grads=None):
    """One training step on one row (S + 1,), in place on the dicts
    ``params``, ``mu``, ``nu``: the loss, and each layer's AdamW update as
    soon as its gradients exist (a layer's parameters are not read again
    once it has been differentiated).  ``on_grads(n, grads)`` sees each
    subtree of the mean gradient before it is used.  ``dims`` holds the
    widths (``counts_granite``'s names), eps, the multipliers and ``kinds``
    (the kept layers' kinds in order)."""
    n_pos = np.float32(1.0 / (row.shape[0] - 1))

    def sink(n, g):
        g = _scale(g, n_pos)
        if on_grads is not None:
            on_grads(n, g)
        if n is None:
            new = ref_adamw({k: params[k] for k in TOP},
                            {k: mu[k] for k in TOP}, {k: nu[k] for k in TOP},
                            g, t, hyper)
            for k in TOP:
                params[k], mu[k], nu[k] = new[0][k], new[1][k], new[2][k]
        else:
            params["layers"][n], mu["layers"][n], nu["layers"][n] = \
                ref_adamw(params["layers"][n], mu["layers"][n],
                          nu["layers"][n], g, t, hyper)

    return _row_nll_and_grads(params, row, dims, lowp, sink) * float(n_pos)
