"""Expert parallelism: top-k mixture-of-experts with all_to_all dispatch.

Completes the parallelism inventory (SURVEY.md §2: "EP absent in
reference — all-to-all covers the communication substrate it needs").  The
substrate is exactly the reference's sample-sort scatter (sort.jl:24-55):
bucketize locally, exchange buckets all-to-all, process, exchange back.
Here the buckets are tokens routed to experts, the exchange is
``lax.all_to_all`` over the ``ep`` mesh axis, and the whole
route→dispatch→FFN→return→combine path is ONE compiled shard_map program.

Routing (GShard/Switch-style):

- top-``k`` experts per token, gates renormalized over the selected k;
- per-(rank, expert) ``capacity`` slots, default ``ceil(capacity_factor *
  k * n_local / E)`` — slot-major assignment so a token's primary expert
  wins capacity before anyone's secondary;
- tokens whose every slot overflowed pass through on the residual path;
- the auxiliary load-balance loss ``E * Σ_e f_e · P_e`` (Switch eq. 4:
  f_e = fraction of tokens whose top-1 is e, P_e = mean router prob),
  psum-averaged over the expert axis, returned for the trainer to scale.

Beside it, the expert layer of a model whose experts are spread over chips
(``held_experts_ffn``, the DeepSeek-V3 family's layer as
``models/mla_moe.py`` uses it): the layer is told which experts it holds,
routes every token over ALL the published experts (sigmoid scores, a
selection bias, the chosen ``k`` normalised and scaled), and computes the
held experts' part of each token's weighted sum.  No capacity and no
dropped token: the token-slots of held experts are placed, sorted by
expert, in a buffer whose static row bound (``tokens x k``) holds them at
any imbalance; how many rows each expert has stays on the device, and the
three products are grouped matrix products over that buffer
(``lax.ragged_dot``: on the chip the stock operation read faster than a
hand-written kernel at the benchmark's shapes, PERF.md, PR 35).  On one
chip there is no exchange: what the absent experts would add is left out.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry as _tm
from ..parallel.collectives import run_spmd, spmd_mesh

__all__ = ["moe_forward", "init_moe_params", "make_ep_mesh",
           "reference_moe", "route_sigmoid_topk", "held_layout",
           "held_experts_apply", "held_experts_ffn"]


def make_ep_mesh(n_experts: int, axis: str = "ep") -> Mesh:
    return spmd_mesh(n_experts, axis)


def init_moe_params(key, n_experts: int, hidden: int, ffn: int,
                    dtype=jnp.float32):
    """Router + per-expert FFN weights, experts stacked on a leading axis
    (shards P('ep', ...))."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = jnp.asarray(np.sqrt(2.0 / hidden), dtype)
    s2 = jnp.asarray(np.sqrt(2.0 / ffn), dtype)
    return {
        "Wg": jax.random.normal(k1, (hidden, n_experts), dtype) * s1,
        "W1": jax.random.normal(k2, (n_experts, hidden, ffn), dtype) * s1,
        "W2": jax.random.normal(k3, (n_experts, ffn, hidden), dtype) * s2,
    }


def _expert_ffn(x, W1, W2):
    return jax.nn.gelu(x @ W1) @ W2


def _route_topk(x, Wg, n_experts, k, capacity):
    """Top-k routing with per-(rank, expert) capacity.

    Returns per-token/slot expert ids (n, k), renormalized gates (n, k),
    capacity positions (n, k), keep masks (n, k), and the Switch aux-loss
    ingredients (f_e, P_e) over the local tokens.  Slot-major position
    assignment: ALL slot-0 (primary) picks claim capacity before any
    slot-1 pick, mirroring GShard's priority."""
    n = x.shape[0]
    logits = x @ Wg                                     # (n, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = lax.top_k(probs, k)                    # (n, k)
    if k > 1:
        # GShard: renormalize over the selected k.  Top-1 (Switch) keeps
        # the RAW router prob — it is the router's gradient path.
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    flat_e = eidx.T.reshape(-1)                         # (k*n,) slot-major
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)[
        jnp.arange(k * n), flat_e].reshape(k, n).T      # (n, k)
    keep = pos < capacity
    # Switch aux ingredients over the local shard: f_e from the top-1
    # assignment, P_e the mean router prob
    f_e = jnp.mean(jax.nn.one_hot(eidx[:, 0], n_experts,
                                  dtype=probs.dtype), axis=0)
    P_e = jnp.mean(probs, axis=0)
    return eidx, gate, pos, keep, f_e, P_e


@functools.lru_cache(maxsize=32)
def _moe_jit(mesh, capacity: int, k: int):
    axis = mesh.axis_names[0]
    E = mesh.shape[axis]

    def kernel(x, Wg, W1, W2):
        # x: (n, H) local tokens; W1/W2: (1, H, F)/(1, F, H) local expert
        n, H = x.shape
        eidx, gate, pos, keep, f_e, P_e = _route_topk(x, Wg, E, k, capacity)
        posc = jnp.clip(pos, 0, capacity - 1)
        # dispatch buffer: (E, C, H); dropped slots contribute zeros
        buf = jnp.zeros((E, capacity, H), x.dtype)
        for j in range(k):                               # k is small/static
            buf = buf.at[eidx[:, j], posc[:, j]].add(
                x * keep[:, j, None].astype(x.dtype))
        recv = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                              tiled=True)               # (E, C, H)
        y = _expert_ffn(recv.reshape(E * capacity, H), W1[0], W2[0])
        back = lax.all_to_all(y.reshape(E, capacity, H), axis,
                              split_axis=0, concat_axis=0, tiled=True)
        # combine: gated sum over kept slots; residual passthrough only
        # when EVERY slot of a token overflowed
        out = jnp.zeros_like(x)
        for j in range(k):
            yi = back[eidx[:, j], posc[:, j]]           # (n, H)
            out = out + jnp.where(keep[:, j, None],
                                  gate[:, j, None] * yi, 0.0)
        any_kept = jnp.any(keep, axis=-1)
        out = jnp.where(any_kept[:, None], out, x)
        # Switch aux loss, averaged over the expert-parallel ranks
        aux = E * jnp.sum(f_e * P_e)
        aux = lax.psum(aux, axis) / E
        return out, aux

    return run_spmd(
        kernel, mesh,
        in_specs=(P(axis, None), P(), P(axis, None, None),
                  P(axis, None, None)),
        out_specs=(P(axis, None), P()))


def moe_forward(params, x, mesh: Mesh, capacity: int | None = None,
                k: int = 1, capacity_factor: float = 2.0,
                return_aux: bool = False):
    """Route the (N, H) token-sharded batch through the expert-parallel
    layer; returns (N, H) with the same sharding (and the scalar
    load-balance aux loss when ``return_aux``).

    ``capacity`` (per rank per expert) defaults to
    ``ceil(capacity_factor * k * n_local / E)``."""
    x = jnp.asarray(x)
    E = mesh.shape[mesh.axis_names[0]]
    if params["W1"].shape[0] != E:
        raise ValueError(
            f"params have {params['W1'].shape[0]} experts, mesh has {E}")
    if x.shape[0] % E:
        raise ValueError(f"token count {x.shape[0]} must be divisible by "
                         f"the {E} expert ranks")
    if not 1 <= k <= E:
        raise ValueError(f"k must be in [1, {E}], got {k}")
    n_local = x.shape[0] // E
    if capacity is None:
        capacity = max(1, int(np.ceil(capacity_factor * k * n_local / E)))
    if capacity <= 0:
        raise ValueError(f"capacity must be positive, got {capacity}")
    out, aux = _moe_jit(mesh, int(capacity), int(k))(
        x, params["Wg"], params["W1"], params["W2"])
    return (out, aux) if return_aux else out


def reference_moe(params, x, capacity_per_rank_expert: int, n_ranks: int,
                  k: int = 1):
    """Dense oracle replicating the top-k routing + slot-major capacity
    semantics."""
    x = np.asarray(x, np.float32)
    E = params["Wg"].shape[1]
    out = np.zeros_like(x)
    n_local = x.shape[0] // n_ranks
    for r in range(n_ranks):
        xs = x[r * n_local:(r + 1) * n_local]
        logits = xs @ np.asarray(params["Wg"])
        pz = np.exp(logits - logits.max(-1, keepdims=True))
        pz = pz / pz.sum(-1, keepdims=True)
        top = np.argsort(-pz, axis=-1, kind="stable")[:, :k]   # (n, k)
        gates = np.take_along_axis(pz, top, axis=-1)
        if k > 1:
            gates = gates / gates.sum(-1, keepdims=True)
        counts = {e: 0 for e in range(E)}
        kept = np.zeros((n_local, k), bool)
        for j in range(k):                       # slot-major priority
            for i in range(n_local):
                ei = int(top[i, j])
                if counts[ei] < capacity_per_rank_expert:
                    counts[ei] += 1
                    kept[i, j] = True
        for i in range(n_local):
            if not kept[i].any():
                out[r * n_local + i] = xs[i]
                continue
            acc = np.zeros(x.shape[1], np.float32)
            for j in range(k):
                if kept[i, j]:
                    ei = int(top[i, j])
                    h = np.asarray(_expert_ffn(
                        jnp.asarray(xs[i:i + 1]),
                        jnp.asarray(params["W1"][ei]),
                        jnp.asarray(params["W2"][ei])))[0]
                    acc += gates[i, j] * h
            out[r * n_local + i] = acc
    return out


# ---------------------------------------------------------------------------
# the held experts of a layer whose experts are spread over chips
# ---------------------------------------------------------------------------


def route_sigmoid_topk(u, router, bias, k: int, scale: float):
    """``(idx, w)``, both (T, k): for every row of ``u`` (T, D) the ``k``
    experts with the largest ``sigmoid(u router) + bias`` and their weights
    ``scale * s / (sum of the chosen s + 1e-20)``, all in float32 (the
    scores' product at full precision: a near-tie is turned by less).  The
    bias only selects; no gradient reaches it."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)), k)
    # the chosen scores by a one-hot select, not a gather: its cotangent is
    # then dense too, where take_along_axis' is a scatter of T x k scalars
    hot = idx[..., None] == jnp.arange(s.shape[1], dtype=idx.dtype)
    chosen = jnp.sum(jnp.where(hot, s[:, None, :], 0.0), axis=-1)
    w = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w


def held_layout(idx, held, n_experts: int | None = None):
    """Where the token-slots ``idx`` (T, k) of the ``held`` experts (first,
    count) lie in the buffer of ``T * k`` rows: sorted by expert, a token's
    slots of one expert in token order, the rows after them empty.  A dict:
    ``slot_row`` (T, k) the row of each slot and ``slot_ok`` which slots
    are held; ``row_slot`` (T * k,) the flat slot of each row and
    ``row_ok`` which rows hold one; ``sizes`` (count,) the rows of each
    expert.  Integer work on the device: no host read.  The row bound, the
    groups and, where ``n_experts`` says of how many the held are, the rows
    at the expected load are published as the gauge
    ``moe.held_experts.plan`` (trace time, never a step)."""
    first, count = held
    T, k = idx.shape
    plan = dict(rows=T * k, groups=count)
    if n_experts:
        plan["rows_expected"] = T * k * count // n_experts
    for what, n in plan.items():
        _tm.set_gauge("moe.held_experts.plan", n, rows=T * k, groups=count,
                      what=what)
    local = idx.reshape(-1) - first
    ok = (local >= 0) & (local < count)
    key = jnp.where(ok, local, count)                 # not held: last
    onehot = key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :]
    upto = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    sizes = upto[-1]
    start = jnp.cumsum(sizes) - sizes
    rank = jnp.sum(jnp.where(onehot, upto, 0), axis=1) - 1
    slot_row = jnp.where(ok, start[jnp.minimum(key, count - 1)] + rank, 0)
    row_slot = jnp.argsort(key, stable=True).astype(jnp.int32)
    row_ok = jnp.arange(T * k, dtype=jnp.int32) < jnp.sum(sizes)
    return dict(slot_row=slot_row.reshape(T, k), slot_ok=ok.reshape(T, k),
                row_slot=row_slot, row_ok=row_ok, sizes=sizes)


@jax.custom_vjp
def _take_rows(src, idx, ok, back_idx, back_ok):
    """``where(ok, src[idx], 0)`` for a partial permutation of rows whose
    inverse is known: every row of ``src`` is taken by the rows
    ``back_idx[j]`` (n_src, m) of the result where ``back_ok``, so the
    cotangent is a gather too and never a scatter."""
    return jnp.where(ok[:, None], src[idx], 0).astype(src.dtype)


def _take_rows_fwd(src, idx, ok, back_idx, back_ok):
    return _take_rows(src, idx, ok, back_idx, back_ok), (back_idx, back_ok)


def _take_rows_bwd(res, d):
    back_idx, back_ok = res
    got = jnp.where(back_ok[..., None], d[back_idx], 0)
    return (jnp.sum(got.astype(jnp.float32), axis=1).astype(d.dtype),
            None, None, None, None)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def held_experts_apply(u, idx, w, w1, w2, *, held, n_experts=None):
    """``sum over a token's chosen AND HELD e of w_e E_e(u)`` for ``u`` (T,
    D), the chosen experts ``idx`` and their weights ``w`` (T, k): ``w1``
    (count, D, 2F) and ``w2`` (count, F, D) are the gated FFNs ``(SiLU(u
    Wg) * (u Wu)) Wd`` of experts ``held[0] .. held[0] + held[1] - 1``
    (``[Wg, Wu]`` side by side).  The held slots' rows, sorted by expert,
    go through the three products as grouped products over a buffer of
    ``T * k`` rows (``lax.ragged_dot``: a group's rows times its expert's
    matrix, the empty rows after them skipped), so no token is dropped at
    any imbalance; a token none of whose chosen experts is held gets
    zeros."""
    T, D = u.shape
    k = idx.shape[1]
    lay = held_layout(idx, held, n_experts)
    rows, sizes = lay["row_ok"][:, None], lay["sizes"]
    xb = _take_rows(u, lay["row_slot"] // k, lay["row_ok"], lay["slot_row"],
                    lay["slot_ok"])
    # what the rows after the last group hold is the product's affair:
    # select by row, never multiply
    # the two products' results carry names, so that a caller that computes
    # the layer again in its backward (``jax.checkpoint``) can keep them: a
    # product's time follows the held rows, and so would that of computing
    # it again
    gu = checkpoint_name(
        jnp.where(rows, lax.ragged_dot(xb, w1.astype(u.dtype), sizes), 0),
        "experts_up")
    g, v = jnp.split(gu.astype(jnp.float32), 2, axis=-1)
    act = (jax.nn.silu(g) * v).astype(u.dtype)
    out = checkpoint_name(lax.ragged_dot(act, w2.astype(u.dtype), sizes),
                          "experts_down")
    picked = _take_rows(out, lay["slot_row"].reshape(-1),
                        lay["slot_ok"].reshape(-1), lay["row_slot"][:, None],
                        rows)
    y = jnp.sum(picked.reshape(T, k, D).astype(jnp.float32) * w[..., None],
                axis=1)
    return y.astype(u.dtype)


def held_experts_ffn(u, router, bias, w1, w2, *, held, k: int, scale: float):
    """The held experts' part of ``sum over the chosen e of w_e E_e(u)``
    for ``u`` (T, D): ``router`` (D, E) and ``bias`` (E,) over all E
    published experts (``route_sigmoid_topk``: the weights are normalised
    over all ``k`` chosen, held or not), ``w1``, ``w2`` and ``held`` as
    ``held_experts_apply`` takes them."""
    with jax.named_scope("route"):
        idx, w = route_sigmoid_topk(u, router, bias, k, scale)
        # a caller that keeps the products' results must keep the choice
        # they were computed under as well: chosen again in a recomputed
        # forward, a near-tie can turn, and the kept rows would then lie
        # under another layout than the one they were written by
        idx = checkpoint_name(idx, "route_idx")
    with jax.named_scope("experts"):
        return held_experts_apply(u, idx, w, w1, w2, held=held,
                                  n_experts=router.shape[1])
