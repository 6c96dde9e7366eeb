"""Operations and bytes of the GLM-4.7-Flash training step, from shapes
alone: what the ALGORITHM needs (``counts.py``'s rule: no recomputation,
no static row bound), so a share worked out from these falls short of 100%
wherever the step does the work they count.

The routed experts are counted at the EXPECTED load: every token-slot goes
to one of the published experts alike, so a chip that holds ``held`` of
``n_experts`` sees ``top_k * held / n_experts`` expert applications a token
a layer, 0.5 in the cell (4% of the step's operations).  A step whose
routing sends the held experts fewer rows than that does less work than is
counted (the driver prints the rows one step held); the grouped products
have no share of a roofline of their own for that reason: with a collapsed
routing their load is none to four times the expected by the seed, and a
share at the expected load read 38 to 95% and would pass 100 (PERF.md,
PR 35).
Attention is causal, counted as half, ``q k^T`` at the key head's width
(nope + rope) and ``P V`` at the value head's.
"""

from __future__ import annotations

__all__ = ["mla_params", "layer_params", "glm_params", "attention_flops",
           "expert_rows", "layer_matmul_params",
           "glm_flops_per_token"]


def mla_params(m: dict) -> int:
    D, H = m["dim"], m["heads"]
    return (D * m["q_rank"] + m["q_rank"]
            + m["q_rank"] * H * (m["nope"] + m["rope"])
            + D * (m["kv_rank"] + m["rope"]) + m["kv_rank"]
            + m["kv_rank"] * H * (m["nope"] + m["v_dim"])
            + H * m["v_dim"] * D)


def layer_params(kind: str, m: dict) -> int:
    D, Fe = m["dim"], m["moe_ffn"]
    n = 2 * D + mla_params(m)
    if kind == "dense":
        return n + 3 * D * m["ffn"]
    return (n + D * m["n_experts"] + m["n_experts"]
            + (m["held"] + 1) * 3 * D * Fe)


def glm_params(m: dict, kinds, vocab: int, mtp: bool) -> int:
    """Parameters as held: untied embedding and head over the slice, the
    layers kept, and the MTP module (two norms, the 2D x D projection, one
    expert block, a norm)."""
    D = m["dim"]
    n = 2 * vocab * D + D + sum(layer_params(k, m) for k in kinds)
    if mtp:
        n += 3 * D + 2 * D * D + layer_params("moe", m)
    return n


def attention_flops(batch: int, seq: int, m: dict, backward: bool) -> float:
    """Required operations of one layer's attention: ``heads`` causal
    score maps, forward ``q k^T`` at nope + rope and ``P V`` at v_dim; the
    backward's dQ and dK at the key width, dP and dV at the value width
    (the recomputed ``q k^T`` does not count)."""
    pairs = seq * (seq + 1) / 2.0
    per_pair = 2.0 * (m["nope"] + m["rope"]) + 2.0 * m["v_dim"]
    if backward:
        per_pair *= 2.0
    return batch * m["heads"] * pairs * per_pair


def expert_rows(tokens: int, m: dict) -> float:
    """Token-slots the held experts of one layer see at the expected
    load."""
    return tokens * m["top_k"] * m["held"] / float(m["n_experts"])


def layer_matmul_params(kind: str, m: dict) -> float:
    """Weights a token passes in one layer's matmuls (each counted once;
    the routed experts at the expected load)."""
    D, Fe = m["dim"], m["moe_ffn"]
    mla = mla_params(m) - m["q_rank"] - m["kv_rank"]
    if kind == "dense":
        return mla + 3.0 * D * m["ffn"]
    return (mla + D * m["n_experts"] + 3.0 * D * Fe
            + expert_rows(1, m) * 3.0 * D * Fe)


def glm_flops_per_token(m: dict, kinds, vocab: int, seq: int,
                        mtp: bool) -> float:
    """Required forward + backward operations a token (the backward at
    twice the forward; no recomputation): every matmul at two operations a
    multiply-add, attention as ``attention_flops`` counts it, and the head
    over the vocabulary held here, once for the trunk and once for the MTP
    module (whose 2D x D projection and expert block count too)."""
    D = m["dim"]
    blocks = list(kinds) + (["moe"] if mtp else [])
    mats = sum(layer_matmul_params(k, m) for k in blocks)
    mats += (2 if mtp else 1) * D * vocab + (2 * D * D if mtp else 0)
    attn = len(blocks) * (attention_flops(1, seq, m, False)
                          + attention_flops(1, seq, m, True)) / seq
    return 3.0 * 2.0 * mats + attn
