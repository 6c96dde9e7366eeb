"""The plain reference of the Olmo-Hybrid-7B configuration (gated delta-rule
linear attention beside NoPE full attention with QK-norm, post-norm
layers): its layer equations written straightforwardly, independent of the
program.

float32 ``jax.numpy`` with ``HIGHEST``-precision products, no kernel,
nothing imported from the program.  The delta rule is the recurrence by
its definition, one position after the other (``lax.scan``), never the
chunked decomposition the program's kernels use; attention is the explicit
causal softmax; the loss and the gradients come from ``jax.vjp``.  So that
three steps at the cell's size fit one chip beside the optimizer's
moments, the work goes layer by layer (a layer's input is kept, the layer
is differentiated on its own, and its AdamW update follows as soon as its
gradients exist), a layer's mixer, the mixer's parts and the MLP's blocks
of rows are computed again in its backward one after the other, the
recurrence is checkpointed every 256 positions and within those every 16
(32 states of 30 x 192 x 96 floats a layer, not 8192), attention and the
loss go in blocks of rows: the same equations, no other arithmetic.
``lowp`` makes the CONTROL: every matmul operand, and the recurrence's q,
k and v, rounded to that type (``refs.q``).

Every layer: ``h = x + RMS(Mixer(x))``, ``y = h + RMS(MLP(h))``; ``MLP(u)
= (SiLU(g) v) W2`` with ``[g, v] = u W1``; RMSNorm with a learned scale;
the final RMSNorm feeds the untied head.

- linear_attention: ``[q, k, v] = SiLU(conv([u W_q, u W_k, u W_v]))``
  (causal, depthwise, no bias, ``out[t] = sum_j w[j] x[t - K + 1 + j]``);
  per head ``q / |q| dk^-1/2``, ``k / |k|`` (eps 1e-6 under the root);
  ``beta = 2 sigmoid(u W_b)``, ``g = -exp(A_log) softplus(u W_a +
  dt_bias)``; ``S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t
  k_t^T``, ``o_t = S_t q_t``; ``out = [RMS_head(o) SiLU(u W_gate)] W_o``.
- full_attention: ``q = RMS(u W_q)``, ``k = RMS(u W_k)`` over the whole
  projection, ``softmax(causal(q k^T / sqrt(D))) v``, no positional term,
  then ``W_o``.

Departures from the published modelling code: none in the equations; what
the published config does not state is listed under ``assumed`` in the
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

__all__ = ["delta_scan", "ref_train_step", "subtree_norms", "leaf_norm_dict"]

F32 = jnp.float32
TOP = ("embed", "head", "norm_f")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_scan(qh, k, v, beta, g, every: int = 256, inner: int = 16):
    """``o`` (S, H, dv) of ``S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) +
    beta_t v_t k_t^T``, ``o_t = S_t q_t``, one position after the other:
    ``qh, k`` (S, H, dk), ``v`` (S, H, dv), ``beta, g`` (S, H).  The states
    are kept only every ``every`` positions and, while a block of those is
    computed again in the backward, every ``inner`` positions of it: a
    position's own intermediates live for ``inner`` positions at most."""
    S, H, dk = qh.shape
    dv = v.shape[2]
    blk = _blocks(S, every)
    ib = _blocks(blk, inner)

    def step(s, inp):
        qt, kt, vt, bt, gt = inp
        s = jnp.exp(gt)[:, None, None] * s
        old = jnp.einsum("hvk,hk->hv", s, kt, precision=HP)
        s = s + jnp.einsum("hv,hk->hvk", bt[:, None] * (vt - old), kt,
                           precision=HP)
        return s, jnp.einsum("hvk,hk->hv", s, qt, precision=HP)

    def cut(t, n):
        return t.reshape(t.shape[0] // n, n, *t.shape[1:])

    @jax.checkpoint
    def small(s, inp):
        return jax.lax.scan(step, s, inp)

    @jax.checkpoint
    def block(s, inp):
        s, o = jax.lax.scan(small, s, tuple(cut(t, ib) for t in inp))
        return s, o.reshape(blk, H, dv)

    _, o = jax.lax.scan(block, jnp.zeros((H, dv, dk), F32),
                        tuple(cut(t, blk) for t in (qh, k, v, beta, g)))
    return o.reshape(S, H, dv)


def _linear(u, p, dims, lowp):
    """The projections, the convolution and the norms before the
    recurrence and the gated norm after it are each computed again in the
    backward on their own, so that their intermediates and the
    recurrence's never live at once."""
    S = u.shape[0]
    H, dk, dv, K = (dims["lin_heads"], dims["key_dim"], dims["value_dim"],
                    dims["d_conv"])

    @jax.checkpoint
    def before(u, p):
        qkv, gate = jnp.split(_mm(u, p["w_in"], lowp), [2 * H * dk + H * dv],
                              axis=-1)
        xp = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(p["conv_w"][j] * xp[j:j + S]
                              for j in range(K)))
        qh, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
        qh = _l2(qh.reshape(S, H, dk)) * np.float32(dk ** -0.5)
        k = _l2(k.reshape(S, H, dk))
        a, b = jnp.split(_mm(u, p["w_ab"], lowp), 2, axis=-1)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
        return (q(qh, lowp), q(k, lowp), q(v.reshape(S, H, dv), lowp),
                2.0 * jax.nn.sigmoid(b), g, gate)

    @jax.checkpoint
    def after(o, gate, p):
        o = _rms(o, p["o_norm"], dims["eps"]).reshape(S, H * dv)
        return _mm(o * jax.nn.silu(gate), p["w_o"], lowp)

    *inputs, gate = before(u, p)
    return after(delta_scan(*inputs), gate, p)


def _attention(u, p, dims, lowp):
    """Masked dense scores, a block of rows at a time."""
    S = u.shape[0]
    H, hd, eps = dims["heads"], dims["head_dim"], dims["eps"]
    qh, k, v = jnp.split(_mm(u, p["w_qkv"], lowp), 3, axis=-1)
    qh = _rms(qh, p["q_norm"], eps).reshape(S, H, hd)
    k = _rms(k, p["k_norm"], eps).reshape(S, H, hd)
    v = v.reshape(S, H, hd)
    rb = _blocks(S, 512)
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(args):
        qa, r0 = args
        live = cols <= r0 + jnp.arange(rb)[:, None]
        s = jnp.einsum("qhd,khd->hqk", q(qa, lowp), q(k, lowp),
                       precision=HP) / np.float32(np.sqrt(hd))
        pr = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(pr, lowp), q(v, lowp),
                          precision=HP)

    o = jax.lax.map(block, (qh.reshape(S // rb, rb, H, hd),
                            jnp.arange(0, S, rb, dtype=jnp.int32)))
    return _mm(o.reshape(S, H * hd), p["w_o"], lowp)


def _mlp_rows(h, p, eps, lowp):
    """``h + RMS(MLP(h))`` a block of rows at a time (each row on its
    own), computed again block by block in the backward."""
    S, D = h.shape
    rb = _blocks(S, 1024)

    @jax.checkpoint
    def block(hr):
        g, v = jnp.split(_mm(hr, p["w1"], lowp), 2, axis=-1)
        return hr + _rms(_mm(jax.nn.silu(g) * v, p["w2"], lowp),
                         p["post_mlp_norm"], eps)

    return jax.lax.map(block, h.reshape(S // rb, rb, D)).reshape(S, D)


def _layer(p, x, *, kind, dims, lowp):
    """One post-norm layer on one row (S, D).  The mixer is computed again
    in the backward, after the MLP's blocks are done: the two never hold
    their intermediates at once."""
    p = _f32(p)
    eps = dims["eps"]
    mixer = jax.checkpoint(functools.partial(
        _linear if kind == "linear_attention" else _attention,
        dims=dims, lowp=lowp))
    h = x + _rms(mixer(x, p), p["post_mix_norm"], eps)
    return _mlp_rows(h, p, eps, lowp)


def _head_nll(head, scale, x, tgt, *, eps, lowp):
    """Summed cross-entropy of the row: the final RMSNorm, the untied head,
    a block of rows at a time."""
    head = head.astype(F32)
    xn = _rms(x, scale.astype(F32), eps)
    S, D = xn.shape
    rb = _blocks(S, 1024)

    @jax.checkpoint
    def block(args):
        xr, tr = args
        logp = jax.nn.log_softmax(_mm(xr, head.T, lowp), axis=-1)
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
def _head_program(eps, lowp):
    f = functools.partial(_head_nll, eps=eps, lowp=lowp)
    vg = jax.value_and_grad(f, argnums=(0, 1, 2))
    return jax.jit(lambda h, s, x, tgt: vg(_f32(h), _f32(s), x, tgt))


_embed_rows = jax.jit(lambda embed, tok: embed[tok].astype(F32))
_embed_grad = jax.jit(lambda embed, tok, gx: jnp.zeros(embed.shape, F32)
                      .at[tok].add(gx))


def _row_nll_and_grads(params, row, dims, lowp, sink):
    """Summed cross-entropy of one row of token ids (S + 1,), every
    subtree of its gradient (float32, the program's tree) handed to
    ``sink(n, grads)`` as soon as it exists (``n`` None: the leaves
    outside the layers, last), so that the whole tree's never live
    together."""
    kinds = dims["kinds"]
    key = tuple(sorted((k, v) for k, v in dims.items() if k != "kinds"))
    tok, tgt = row[:-1], row[1:]
    x = _embed_rows(params["embed"], tok)
    kept = []
    for kind, p in zip(kinds, params["layers"]):
        kept.append(x)
        x = _programs(kind, key, lowp)[0](p, x)
    nll, (g_head, g_norm, gx) = _head_program(dims["eps"], lowp)(
        params["head"], params["norm_f"], x, tgt)
    for n in reversed(range(len(kinds))):
        gp, gx = _programs(kinds[n], key, lowp)[1](params["layers"][n],
                                                    kept.pop(), gx)
        sink(n, gp)
    sink(None, {"embed": _embed_grad(params["embed"], tok, gx),
                "head": g_head, "norm_f": g_norm})
    return float(nll)


def ref_train_step(params, mu, nu, row, t, hyper, dims, lowp=None,
                   on_grads=None):
    """One training step on one row (S + 1,), in place on the dicts
    ``params``, ``mu``, ``nu``: the loss, and each layer's AdamW update as
    soon as its gradients exist (a layer's parameters are not read again
    once it has been differentiated).  ``on_grads(n, grads)`` sees each
    subtree of the mean gradient before it is used.  ``dims`` holds the
    widths (``counts_olmo_hybrid``'s names), eps and ``kinds`` (the kept
    layers' kinds in order)."""
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
