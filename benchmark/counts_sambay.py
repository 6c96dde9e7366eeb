"""Operations and bytes of the SambaY training step, from shapes alone:
what the ALGORITHM needs (``counts.py``'s rule: no recomputation, no
padding, no re-read counts), so a share worked out from these can only
fall short of 100%.

Attention's required operations: causal counted as half, a window layer
its band only, each of a head pair's two score maps once, and ``P V`` at
the value head's width (twice the query head's).  The scan's: the
elementwise recurrence a state element a position, and the HBM bytes of
its operands and results in the type the activations are stored in.
"""

from __future__ import annotations

from counts import Cost

__all__ = ["layer_params", "sambay_params", "attention_pairs",
           "attention_flops", "scan_cost", "sambay_flops_per_token"]

# elementwise operations of the recurrence a state element (E x N) a
# position: forward dt*A, exp, a*h, (dt x)*B, +, h*C, + (7); backward the
# cotangent of each, with exp recounted once (da, G = C dy + g, dC, dB,
# du, G*h_prev, *a, *A, sum, *dt, sum, a*G: 14)
SCAN_FWD_OPS, SCAN_BWD_OPS = 7, 14


def layer_params(kind: str, m: dict) -> int:
    D, F, E, N, R, K = (m["dim"], m["ffn"], m["d_inner"], m["d_state"],
                        m["dt_rank"], m["d_conv"])
    hd = m["head_dim"]
    qw, kvw = m["heads"] * hd, m["kv_heads"] * hd
    n = 4 * D + D * 2 * F + F * D
    if kind == "mamba":
        return n + (D * 2 * E + K * E + E + E * (R + 2 * N) + R * E + E
                    + E * N + E + E * D)
    if kind == "gmu":
        return n + 2 * D * E
    attn = 4 * hd + 2 * hd + qw * D + D
    if kind == "cross":
        return n + attn + D * qw + qw
    return n + attn + D * (qw + 2 * kvw) + qw + 2 * kvw


def sambay_params(m: dict, layers, vocab: int) -> int:
    """Parameters with the head tied to the embedding."""
    return vocab * m["dim"] + 2 * m["dim"] + sum(
        layer_params(kind, m) for _, kind in layers)


def attention_pairs(seq: int, window) -> float:
    """Live (query, key) pairs of a causal sequence, of its window's band
    where it has one."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def attention_flops(batch: int, seq: int, m: dict, window,
                    backward: bool) -> float:
    """Required operations of one differential-attention layer's score
    maps: ``heads`` maps (two a pair), each q k^T at the head's width and
    P V at twice it; the backward's dV and dP at the value width, dQ and
    dK at the head's (the recomputed q k^T does not count)."""
    hd = m["head_dim"]
    per_pair = 2.0 * hd + 2.0 * 2 * hd
    if backward:
        per_pair *= 2.0
    return batch * m["heads"] * attention_pairs(seq, window) * per_pair


def scan_cost(batch: int, seq: int, m: dict, itemsize: int = 2) -> Cost:
    """One Mamba layer's selective scan, forward and backward: the
    recurrence's elementwise operations, and x, Delta, B, C read and y
    written (forward), those and dy read and dx, dDelta, dB, dC written
    (backward), A and dA once."""
    E, N = m["d_inner"], m["d_state"]
    rows = batch * seq
    fwd = (3 * rows * E + 2 * rows * N) * itemsize + E * N * itemsize
    bwd = (5 * rows * E + 4 * rows * N) * itemsize + 2 * E * N * itemsize
    return Cost(flops=float(SCAN_FWD_OPS + SCAN_BWD_OPS) * rows * E * N,
                hbm_bytes=float(fwd + bwd))


def sambay_flops_per_token(m: dict, layers, vocab: int, seq: int) -> float:
    """Required forward + backward operations a token (the backward at
    twice the forward's matmuls; no recomputation): every matmul at two
    operations a multiply-add, attention as ``attention_flops`` counts it,
    the scans' elementwise work, and the tied head over the vocabulary
    held here."""
    D, F, E, N, R = (m["dim"], m["ffn"], m["d_inner"], m["d_state"],
                     m["dt_rank"])
    hd = m["head_dim"]
    qw, kvw = m["heads"] * hd, m["kv_heads"] * hd
    total = 0.0
    for _, kind in layers:
        mats = D * 2 * F + F * D
        if kind == "mamba":
            mats += D * 2 * E + E * (R + 2 * N) + R * E + E * D
            total += scan_cost(1, seq, m).flops / seq
        elif kind == "gmu":
            mats += 2 * D * E
        else:
            mats += qw * D + (D * qw if kind == "cross"
                              else D * (qw + 2 * kvw))
            w = m["window"] if kind == "window" else None
            total += (attention_flops(1, seq, m, w, False)
                      + attention_flops(1, seq, m, w, True)) / seq
        total += 3.0 * 2.0 * mats
    return total + 3.0 * 2.0 * D * vocab
