"""Operations and bytes of the Olmo-Hybrid-7B training step, from shapes
alone: what the ALGORITHM needs (``counts.py``'s rule: no recomputation, no
padding, no re-read counts), so a share worked out from these can only fall
short of 100%.

The gated delta rule of a linear-attention layer is counted in its chunked
form at the kernels' chunk ``C`` (``CHUNK``, 64), a chunk's products at two
operations a multiply-add, once a head: ``K K^T`` over the strictly lower
half, the inverse of the unit lower-triangular ``I + A`` (``C^3 / 3``), its
lower-triangular product with ``[K | V]``, the three products with the
state (``W S^T``, ``Q S^T`` and the state's update ``V'^T K``), ``Q K^T``
and its product with ``V'`` over the causal half; the backward at twice
the forward.  Its bytes: q, k, v (the activations' type), beta and the
running sums (float32) read, o (float32) written in the forward; the same
and dO (float32) read, dq, dk, dv, dbeta and the sums' cotangent written in
the backward, each once (the states the kernels carry between chunks are
theirs).  Attention's operations: causal counted as half
(``counts.flash_attention_flops``).
"""

from __future__ import annotations

from counts import Cost, flash_attention_flops

__all__ = ["layer_params", "olmo_hybrid_params", "gdn_cost",
           "attention_flops", "olmo_hybrid_flops_per_token"]

# positions a chunk of the delta rule (``ops/pallas_gated_delta.py``)
CHUNK = 64


def _mats(kind: str, m: dict) -> int:
    """The layer's matrix parameters (what a token's products read)."""
    D, F = m["dim"], m["ffn"]
    n = D * 2 * F + F * D
    if kind == "linear_attention":
        H = m["lin_heads"]
        qk, vw = H * m["key_dim"], H * m["value_dim"]
        return n + D * (2 * qk + 2 * vw) + D * 2 * H + vw * D
    w = m["heads"] * m["head_dim"]
    return n + D * 3 * w + w * D


def layer_params(kind: str, m: dict) -> int:
    """Parameters of one layer: its matrices, two norm scales, and for a
    linear-attention layer the convolution, A_log, dt_bias and the head
    norm's scale, for the full layer the two QK-norm scales."""
    n = _mats(kind, m) + 2 * m["dim"]
    if kind == "linear_attention":
        H = m["lin_heads"]
        n += m["d_conv"] * H * (2 * m["key_dim"] + m["value_dim"])
        n += 2 * H + m["value_dim"]
    else:
        n += 2 * m["heads"] * m["head_dim"]
    return n


def olmo_hybrid_params(m: dict, kinds, vocab: int) -> int:
    """Parameters with the head untied from the embedding."""
    return 2 * vocab * m["dim"] + m["dim"] + sum(layer_params(k, m)
                                                 for k in kinds)


def gdn_cost(batch: int, seq: int, m: dict, itemsize: int = 2) -> Cost:
    """One linear-attention layer's delta-rule work, forward and
    backward."""
    C, H = CHUNK, m["lin_heads"]
    dk, dv = m["key_dim"], m["value_dim"]
    chunks = batch * seq / C
    tri, strict = C * (C + 1) / 2.0, C * (C - 1) / 2.0
    one = (2.0 * strict * dk + C ** 3 / 3.0 + 2.0 * tri * (dk + dv)
           + 3 * 2.0 * C * dk * dv + 2.0 * tri * (dk + dv))
    fwd = chunks * H * one
    rows = batch * seq * H
    fwd_bytes = rows * ((2 * dk + dv) * itemsize + 2 * 4 + dv * 4)
    bwd_bytes = rows * (2 * (2 * dk + dv) * itemsize + 4 * 4 + dv * 4)
    return Cost(flops=3.0 * fwd, hbm_bytes=float(fwd_bytes + bwd_bytes))


def attention_flops(batch: int, seq: int, m: dict, backward: bool) -> float:
    """Required operations of the full-attention layer's flash kernels."""
    return flash_attention_flops(batch, m["heads"], seq, m["head_dim"],
                                 causal=True, backward=backward)


def olmo_hybrid_flops_per_token(m: dict, kinds, vocab: int,
                                seq: int) -> float:
    """Required forward + backward operations a token (the backward at
    twice the forward's; no recomputation): every matmul at two operations
    a multiply-add, attention and the delta rule as above, and the untied
    head over the vocabulary held here."""
    total = 0.0
    for kind in kinds:
        total += 3.0 * 2.0 * _mats(kind, m)
        if kind == "linear_attention":
            total += gdn_cost(1, seq, m).flops / seq
        else:
            total += sum(attention_flops(1, seq, m, b)
                         for b in (False, True)) / seq
    return total + 3.0 * 2.0 * m["dim"] * vocab
