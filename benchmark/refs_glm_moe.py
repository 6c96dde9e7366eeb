"""The plain reference of the GLM-4.7-Flash configuration (the DeepSeek-V3
family's block: latent attention with decoupled rotary keys, sigmoid-routed
experts with a shared expert, a multi-token-prediction module), as one chip
of the stated deployment holds it: the layer equations written
straightforwardly, independent of the program.

float32 ``jax.numpy`` with ``HIGHEST``-precision products, no kernel, no
sort, nothing imported from the program.  Attention is masked dense scores,
every held expert is applied to every token and weighed by a mask, the loss
and the gradients come from ``jax.vjp``.  So that three steps at the cell's
size fit one chip beside the optimizer's moments, the work goes layer by
layer (a layer's input is kept, the layer is differentiated on its own),
attention and the loss in blocks of rows and the experts one at a time,
each differentiated on its own: the same equations, no other arithmetic.
``lowp`` makes the CONTROL: every matmul operand rounded to that type
(``refs.q``).

Every layer: ``h = x + MLA(RMS1(x))``, ``y = h + FFN(RMS2(h))``; ``RMS(x) =
x / sqrt(mean(x^2) + eps) * scale``; final RMSNorm, then the untied head.

- MLA: ``cq = RMS(u Wqa)``; ``q = cq Wqb``, a head ``[q_nope, q_rot]``;
  ``[ckv, kr] = u Wkva``; ``ckv = RMS(ckv)``; a head ``[k_nope, v] = ckv
  Wkvb``; rotary positions on ``q_rot`` and ``kr``: columns ``(2i, 2i+1)``
  of position ``s`` turn by ``s theta^(-2i/R)``; ``k = [k_nope, kr]``, the
  one ``kr`` for all heads; ``o = softmax(causal(q k^T / sqrt(nope +
  rope))) v``; ``out = concat(o) Wo``.
- dense FFN: ``(SiLU(g) * v) W2`` with ``[g, v] = u W1``.
- expert layer: ``s = sigmoid(u Wr)``; the ``top_k`` experts with the
  largest ``s + b`` (no gradient reaches ``b``); ``w_e = scale s_e / (sum
  of the chosen s + 1e-20)``; ``y = sum over the chosen AND HELD e of w_e
  E_e(u) + E_shared(u)``: what the absent experts would add is left out.
- MTP: ``h'_i = [RMS_e(Emb(t_{i+1})), RMS_h(x_i)] Weh`` on the trunk's
  output before the final norm, one expert block, a norm of its own, the
  same embedding and head, cross-entropy against ``t_{i+2}``; ``loss =
  L_main + lambda L_mtp``.

Departures from the published description: the deployment's cut alone; what
the published config does not state is listed under ``assumed`` in the
configuration file.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from refs import HP, q
from refs_sambay import (_blocks, _f32, _mm, _tree_add as _add,
                         _tree_scale as _scale, ref_adamw)

__all__ = ["ref_train_step", "subtree_norms", "leaf_norm_dict"]

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x (S, heads, R): the columns (2i, 2i + 1) of position s turned by
    the angle s theta^(-2i / R)."""
    S, _, R = x.shape
    ang = (jnp.arange(S, dtype=F32)[:, None]
           * F32(theta) ** (-jnp.arange(0, R, 2, dtype=F32) / R))[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


def _dense_rows(qh, kh, vh, lowp):
    """``softmax(causal(q k^T / sqrt(d))) v`` by masked dense scores, a
    block of rows at a time: q, k (S, H, d), v (S, H, dv)."""
    S, H, d = qh.shape
    rb = _blocks(S, 512)
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(args):
        qa, r0 = args
        live = cols <= r0 + jnp.arange(rb)[:, None]
        s = jnp.einsum("qhd,khd->hqk", q(qa, lowp), q(kh, lowp),
                       precision=HP) / np.float32(math.sqrt(d))
        pr = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(pr, lowp), q(vh, lowp),
                          precision=HP)

    o = jax.lax.map(block, (qh.reshape(S // rb, rb, H, d),
                            jnp.arange(0, S, rb, dtype=jnp.int32)))
    return o.reshape(S, H, -1)


def _mla(u, p, dims, lowp):
    S = u.shape[0]
    H, N, R, V = dims["heads"], dims["nope"], dims["rope"], dims["v_dim"]
    cq = _rms(_mm(u, p["wqa"], lowp), p["q_norm"], dims["eps"])
    qh = _mm(cq, p["wqb"], lowp).reshape(S, H, N + R)
    kva = _mm(u, p["wkva"], lowp)
    ckv = _rms(kva[:, :dims["kv_rank"]], p["kv_norm"], dims["eps"])
    kr = _rope(kva[:, dims["kv_rank"]:].reshape(S, 1, R), dims["theta"])
    kv = _mm(ckv, p["wkvb"], lowp).reshape(S, H, N + V)
    qh = jnp.concatenate([qh[..., :N], _rope(qh[..., N:], dims["theta"])], -1)
    kh = jnp.concatenate([kv[..., :N], jnp.broadcast_to(kr, (S, H, R))], -1)
    o = _dense_rows(qh, kh, kv[..., N:], lowp)
    return _mm(o.reshape(S, H * V), p["wo"], lowp)


def _gated(u, w1, w2, lowp):
    g, v = jnp.split(_mm(u, w1, lowp), 2, axis=-1)
    return _mm(jax.nn.silu(g) * v, w2, lowp)


def _experts(u, p, dims, lowp):
    """(the expert layer's result, the chosen experts (S, top_k))."""
    first, count = dims["held"]
    s = jax.nn.sigmoid(_mm(u, p["router"], lowp))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]),
                           dims["top_k"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = dims["scale"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                                  + 1e-20)
    y = _gated(u, p["sw1"], p["sw2"], lowp)
    one = jax.checkpoint(lambda u, a, b: _gated(u, a, b, lowp))
    for j in range(count):
        w_e = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        y = y + w_e[:, None] * one(u, p["ew1"][j], p["ew2"][j])
    return y, idx


def _layer(p, x, *, kind, dims, lowp):
    """(y, chosen experts or None) of one layer on one row (S, D)."""
    p = _f32(p)
    h = x + _mla(_rms(x, p["ln1"], dims["eps"]), p, dims, lowp)
    u = _rms(h, p["ln2"], dims["eps"])
    if kind == "dense":
        return h + _gated(u, p["w1"], p["w2"], lowp), None
    y, idx = _experts(u, p, dims, lowp)
    return h + y, idx


def _mtp_in(m, e, x, *, eps, lowp):
    """``[RMS_e(e), RMS_h(x)] Weh`` from the module's three leaves."""
    m = _f32(m)
    both = jnp.concatenate([_rms(e, m["enorm"], eps),
                            _rms(x, m["hnorm"], eps)], axis=-1)
    return _mm(both, m["eh_proj"], lowp)


def _head_nll(head, scale, x, tgt, *, eps, lowp):
    """Summed cross-entropy of the row: a final RMSNorm, the head (vocab,
    D), a block of rows at a time."""
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


def _key(dims):
    return tuple(sorted(dims.items()))


@functools.lru_cache(maxsize=None)
def _programs(kind, dims_key, lowp):
    """(forward, backward) of one layer, jitted; the backward
    differentiates the layer on its own from its kept input."""
    f = functools.partial(_layer, kind=kind, dims=dict(dims_key), lowp=lowp)
    bwd = lambda p, x, ct: jax.vjp(lambda p, x: f(p, x)[0], _f32(p), x)[1](ct)
    return jax.jit(f), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _mtp_in_programs(eps, lowp):
    f = functools.partial(_mtp_in, eps=eps, lowp=lowp)
    bwd = lambda m, e, x, ct: jax.vjp(f, _f32(m), e, x)[1](ct)
    return jax.jit(f), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _head_program(eps, lowp):
    f = functools.partial(_head_nll, eps=eps, lowp=lowp)
    vg = jax.value_and_grad(f, argnums=(0, 1, 2))
    return jax.jit(lambda h, s, x, tgt: vg(_f32(h), _f32(s), x, tgt))


_embed_rows = jax.jit(lambda embed, tok: embed[tok].astype(F32))
_embed_grad = jax.jit(
    lambda embed, tok, gx, tok2, ge: jnp.zeros(embed.shape, F32)
    .at[tok].add(gx).at[tok2].add(ge))
MTP_IN = ("enorm", "hnorm", "eh_proj")
TOP = ("embed", "head", "norm_f")


def _row_loss_and_grads(params, row, dims, lowp, sink, on_chosen=None):
    """``(L_main, L_mtp)`` of one row of ids (S + 2,) as sums over its S
    positions, every subtree of the gradient of ``sum_main + lambda
    sum_mtp`` handed to ``sink(name, grads)`` as soon as it exists (name:
    a layer's number, "mtp.block", "mtp" for the module's other leaves,
    None for the leaves outside the layers, last), so that the whole
    tree's never live together.  ``on_chosen(name, idx)`` sees each expert
    layer's chosen experts."""
    kinds, eps, lam = dims["kinds"], dims["eps"], np.float32(dims["lam"])
    S = row.shape[0] - 2
    tok, nxt = row[:S], row[1:S + 1]
    layer_dims = _key({k: v for k, v in dims.items()
                       if k not in ("kinds", "lam")})
    x = _embed_rows(params["embed"], tok)
    kept = []
    for n, (kind, p) in enumerate(zip(kinds, params["layers"])):
        kept.append(x)
        x, idx = _programs(kind, layer_dims, lowp)[0](p, x)
        if idx is not None and on_chosen is not None:
            on_chosen(n, idx)
    head = _head_program(eps, lowp)
    main, (g_head, g_nf, gx) = head(params["head"], params["norm_f"], x, nxt)
    # the MTP module, forward and backward
    m = params["mtp"]
    m_in = {k: m[k] for k in MTP_IN}
    e = _embed_rows(params["embed"], nxt)
    h0 = _mtp_in_programs(eps, lowp)[0](m_in, e, x)
    h1, idx = _programs("moe", layer_dims, lowp)[0](m["block"], h0)
    if on_chosen is not None:
        on_chosen("mtp.block", idx)
    mtp, (g_head2, g_mn, gh1) = head(params["head"], m["norm"], h1,
                                     row[2:])
    g_head = _add(g_head, _scale(g_head2, lam))
    g_block, gh0 = _programs("moe", layer_dims, lowp)[1](
        m["block"], h0, _scale(gh1, lam))
    sink("mtp.block", g_block)
    g_in, g_e, gx_mtp = _mtp_in_programs(eps, lowp)[1](m_in, e, x, gh0)
    sink("mtp", dict(g_in, norm=_scale(g_mn, lam)))
    gx = _add(gx, gx_mtp)
    for n in reversed(range(len(kinds))):
        gp, gx = _programs(kinds[n], layer_dims, lowp)[1](
            params["layers"][n], kept.pop(), gx)
        sink(n, gp)
    sink(None, {"embed": _embed_grad(params["embed"], tok, gx, nxt, g_e),
                "head": g_head, "norm_f": g_nf})
    return float(main), float(mtp)


def subtree_name(name):
    """The prefix of a subtree's leaves in the flat dicts of norms."""
    if name is None:
        return ""
    return f"layers.{name}." if isinstance(name, int) else f"{name}."


def _subtree(tree, name):
    """(the dict ``name`` names inside ``tree``, its leaves' keys)."""
    if name is None:
        return tree, TOP
    if name == "mtp":
        return tree["mtp"], MTP_IN + ("norm",)
    part = tree["layers"][name] if isinstance(name, int) else \
        tree["mtp"]["block"]
    return part, tuple(part)


def ref_train_step(params, mu, nu, row, t, hyper, dims, lowp=None,
                   on_grads=None, on_chosen=None):
    """One training step on one row (S + 2,), in place on the dicts
    ``params``, ``mu``, ``nu``: ``(loss, L_main, L_mtp)``, and each
    subtree's AdamW update as soon as its gradients exist (a subtree's
    parameters are not read again once it has been differentiated).
    ``on_grads(name, grads)`` sees each subtree of the mean gradient
    before it is used."""
    n_pos = np.float32(1.0 / (row.shape[0] - 2))

    def sink(name, g):
        g = _scale(g, n_pos)
        if on_grads is not None:
            on_grads(name, g)
        parts = [_subtree(tree, name)[0] for tree in (params, mu, nu)]
        new = ref_adamw(*({k: part[k] for k in g} for part in parts), g, t,
                        hyper)
        for part, fresh in zip(parts, new):
            part.update(fresh)

    main, mtp = _row_loss_and_grads(params, row, dims, lowp, sink, on_chosen)
    main, mtp = main * float(n_pos), mtp * float(n_pos)
    return main + dims["lam"] * mtp, main, mtp


@jax.jit
def _norm(x, y=None):
    d = x.astype(F32) if y is None else x.astype(F32) - y.astype(F32)
    return jnp.sqrt(jnp.sum(jnp.square(d)))


def subtree_norms(name, a, b=None):
    """{leaf name: norm of ``a - b`` (of ``a``)} of one subtree as ``sink``
    names them."""
    prefix = subtree_name(name)
    return {prefix + k: float(_norm(v) if b is None else _norm(v, b[k]))
            for k, v in a.items()}


def leaf_norm_dict(a, b=None):
    """{leaf name: Euclidean norm of ``a - b`` (of ``a``)} in float32, a
    leaf at a time, over the program's tree."""
    out = {}
    for name in [None, "mtp", "mtp.block", *range(len(a["layers"]))]:
        part, keys = _subtree(a, name)
        other = None if b is None else _subtree(b, name)[0]
        out.update(subtree_norms(name, {k: part[k] for k in keys}, other))
    return out
