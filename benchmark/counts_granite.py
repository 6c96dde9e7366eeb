"""Operations and bytes of the granite-4.0-h-micro training step, from shapes
alone: what the ALGORITHM needs (``counts.py``'s rule: no recomputation, no
padding, no re-read counts), so a share worked out from these can only fall
short of 100%.

The chunked state-space-duality (SSD) work of a Mamba-2 layer is counted
at the configuration's published chunk ``Q`` (``mamba_chunk_size``), a
chunk's products at two operations a multiply-add: ``C B^T`` over the
causal half of a chunk once a group, the masked product with ``dt x`` over
the causal half once a head, the chunk's state ``(decay dt x)^T B`` and the
state's part of the output ``C S^T`` once a head; the backward at twice
the forward.  Its bytes: x, dt, B, C read and y written in the forward;
x, dt, B, C, dy read and dx, ddt, dB, dC written in the backward, each
once, in the type the activations are stored in (the states the kernels
carry between chunks are theirs).  Attention's operations: causal counted
as half (``counts.flash_attention_flops``).
"""

from __future__ import annotations

from counts import Cost, flash_attention_flops

__all__ = ["layer_params", "granite_params", "ssd_cost", "attention_flops",
           "granite_flops_per_token"]


def _mats(kind: str, m: dict) -> int:
    """The layer's matrix parameters (what a token's products read)."""
    D, F = m["dim"], m["ffn"]
    n = D * 2 * F + F * D
    if kind == "mamba":
        E, conv = m["d_inner"], m["d_inner"] + 2 * m["n_groups"] * m["d_state"]
        return n + D * (E + conv + m["ssm_heads"]) + E * D
    qw, kvw = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return n + D * (qw + 2 * kvw) + qw * D


def layer_params(kind: str, m: dict) -> int:
    """Parameters of one layer: its matrices, two norm scales, and for a
    Mamba-2 layer the convolution (weights and bias), dt_bias, A_log, D and
    the gated norm's scale."""
    n = _mats(kind, m) + 2 * m["dim"]
    if kind == "mamba":
        conv = m["d_inner"] + 2 * m["n_groups"] * m["d_state"]
        n += m["d_conv"] * conv + conv + 3 * m["ssm_heads"] + m["d_inner"]
    return n


def granite_params(m: dict, kinds, vocab: int) -> int:
    """Parameters with the head tied to the embedding."""
    return vocab * m["dim"] + m["dim"] + sum(layer_params(k, m) for k in kinds)


def ssd_cost(batch: int, seq: int, m: dict, itemsize: int = 2) -> Cost:
    """One Mamba-2 layer's SSD work, forward and backward."""
    Q, H, P = m["chunk"], m["ssm_heads"], m["ssm_head_dim"]
    G, N = m["n_groups"], m["d_state"]
    chunks = batch * seq / Q
    tri = Q * (Q + 1) / 2.0
    fwd = chunks * (2.0 * tri * N * G + H * (2.0 * tri * P
                                             + 2 * 2.0 * Q * P * N))
    rows = batch * seq
    xs, bc = rows * H * P, rows * G * N
    fwd_bytes = (2 * xs + rows * H + 2 * bc) * itemsize + H * itemsize
    bwd_bytes = (3 * xs + 2 * rows * H + 4 * bc) * itemsize + 2 * H * itemsize
    return Cost(flops=3.0 * fwd, hbm_bytes=float(fwd_bytes + bwd_bytes))


def attention_flops(batch: int, seq: int, m: dict, backward: bool) -> float:
    """Required operations of one NoPE attention layer's flash kernels."""
    return flash_attention_flops(batch, m["heads"], seq, m["head_dim"],
                                 causal=True, backward=backward)


def granite_flops_per_token(m: dict, kinds, vocab: int, seq: int) -> float:
    """Required forward + backward operations a token (the backward at
    twice the forward's; no recomputation): every matmul at two operations
    a multiply-add, attention and the SSD products as above, and the tied
    head over the vocabulary held here."""
    total = 0.0
    for kind in kinds:
        total += 3.0 * 2.0 * _mats(kind, m)
        if kind == "mamba":
            total += ssd_cost(1, seq, m).flops / seq
        else:
            total += sum(attention_flops(1, seq, m, b)
                         for b in (False, True)) / seq
    return total + 3.0 * 2.0 * m["dim"] * vocab
