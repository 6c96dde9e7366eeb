"""Operations and bytes of each step, from shapes alone.

The benchmark's own arithmetic: nothing here is imported from the program
(``telemetry/perf.py`` and ``train/tasks.py`` carry their own, which the
benchmark does not read).  Every function returns a ``Cost``: the work the
ALGORITHM needs, not what an implementation happens to do.  How often an
implementation re-reads an array, recomputes an activation or pads a
sequence is its own affair and is not counted, so a share of the roofline
worked out from these counts can only fall short of 100%.

``least_seconds`` turns a cost into the least time one chip (or the fullest
chip of several) could take: the largest of operations over peak FLOP/s, HBM
bytes over peak HBM bandwidth, and bytes leaving the fullest chip over the
chip's interconnect bandwidth.  It also says which of the three bounds.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

__all__ = ["Cost", "load_peaks", "least_seconds", "chain_cost",
           "reduce_cost", "gemm_cost", "reshard_leg_cost", "block_owner_bytes",
           "transformer_flops_per_token", "transformer_params",
           "flash_attention_flops", "adamw_state_bytes"]


@dataclass(frozen=True)
class Cost:
    """Work of one call, per chip where several chips share it.

    flops: floating-point operations on the fullest chip.
    hbm_bytes: bytes that have to cross the fullest chip's HBM interface.
    ici_bytes: bytes that have to leave (or enter, if more) the fullest chip.
    """
    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.flops + other.flops,
                    self.hbm_bytes + other.hbm_bytes,
                    self.ici_bytes + other.ici_bytes)


def load_peaks(device_kind: str, path: Path | None = None) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown kind is
    an error, not a default."""
    path = path or Path(__file__).resolve().parent / "peaks.json"
    table = json.loads(Path(path).read_text())
    row = table.get(device_kind)
    if not isinstance(row, dict):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"device kind {device_kind!r} is not in {path.name} "
                       f"(known: {known}); add it with its source")
    return row


def least_seconds(cost: Cost, peaks: dict) -> tuple[float, str]:
    """(least seconds, which bound) for ``cost`` on a chip with ``peaks``."""
    bounds = {
        "flops": cost.flops / peaks["flops_bf16_per_s"],
        "hbm": cost.hbm_bytes / peaks["hbm_bytes_per_s"],
        "ici": cost.ici_bytes / peaks["ici_bytes_per_s"],
    }
    which = max(bounds, key=bounds.get)
    return bounds[which], which


# ---------------------------------------------------------------------------
# array operations
# ---------------------------------------------------------------------------


def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def chain_cost(shape, itemsize: int, n_inputs: int, flops_per_element: int,
               chips: int = 1) -> Cost:
    """An elementwise chain over ``n_inputs`` arrays of ``shape`` whose
    result is kept: each input read once, the result written once."""
    n = _numel(shape) / chips
    return Cost(flops=flops_per_element * n,
                hbm_bytes=(n_inputs + 1) * n * itemsize)


def reduce_cost(shape, itemsize: int, chips: int = 1) -> Cost:
    """A whole-array reduction to a scalar: the array read once.  A step
    that reduces an array it has just produced could fold the reduction
    into the producer; the step's least traffic then leaves this read
    out (the cell says which)."""
    n = _numel(shape) / chips
    return Cost(flops=2 * n, hbm_bytes=n * itemsize)


def gemm_cost(m: int, n: int, k: int, itemsize: int, chips: int = 1) -> Cost:
    """C = A @ B: 2mnk operations; A, B read and C written once."""
    return Cost(flops=2.0 * m * n * k / chips,
                hbm_bytes=(m * k + k * n + m * n) * itemsize / chips)


def _cuts(n: int, parts: int) -> list[int]:
    """Even block cuts of a length ``n`` axis into ``parts`` (n divisible)."""
    if n % parts:
        raise ValueError(f"axis of {n} does not divide into {parts} blocks")
    step = n // parts
    return [i * step for i in range(parts + 1)]


def block_owner_bytes(shape, itemsize: int, src_grid, dst_grid) -> dict:
    """Bytes that change owner when a 2-D array goes from the block layout
    ``src_grid`` to ``dst_grid`` over the same ranks, rank ``r`` owning the
    ``r``-th block of either grid in row-major order.  Returns, per rank,
    the bytes it has to send and to receive: its old block less what of it
    stays, its new block less what it already held."""
    rows, cols = int(shape[0]), int(shape[1])

    def blocks(grid):
        rc, cc = _cuts(rows, grid[0]), _cuts(cols, grid[1])
        return [(rc[i], rc[i + 1], cc[j], cc[j + 1])
                for i in range(grid[0]) for j in range(grid[1])]

    src, dst = blocks(src_grid), blocks(dst_grid)
    if len(src) != len(dst):
        raise ValueError("both layouts must use the same number of ranks")

    def overlap(a, b):
        r = max(0, min(a[1], b[1]) - max(a[0], b[0]))
        c = max(0, min(a[3], b[3]) - max(a[2], b[2]))
        return r * c

    area = lambda b: (b[1] - b[0]) * (b[3] - b[2])
    send = [(area(s) - overlap(s, d)) * itemsize for s, d in zip(src, dst)]
    recv = [(area(d) - overlap(s, d)) * itemsize for s, d in zip(src, dst)]
    return {"send": send, "recv": recv, "moved": sum(send)}


def reshard_leg_cost(shape, itemsize: int, src_grid, dst_grid) -> Cost:
    """One redistribution: what leaves (or enters) the fullest chip goes
    over its interconnect once; every byte that moves is read from HBM on
    one chip and written on another, so the fullest chip's HBM sees its
    sends plus its receives."""
    own = block_owner_bytes(shape, itemsize, src_grid, dst_grid)
    ici = max(max(own["send"]), max(own["recv"]))
    hbm = max(s + r for s, r in zip(own["send"], own["recv"]))
    return Cost(hbm_bytes=float(hbm), ici_bytes=float(ici))


# ---------------------------------------------------------------------------
# the transformer training step
# ---------------------------------------------------------------------------


def transformer_params(vocab: int, dim: int, layers: int, ffn: int,
                       positions: int, tied_head: bool = False) -> int:
    """Parameters of the decoder as ``models/transformer.py`` lays it out:
    embedding, learned positions, per block two norm scales, qkv, proj and
    the two FFN matrices (no biases), a final norm scale and the head."""
    block = 2 * dim + dim * 3 * dim + dim * dim + 2 * dim * ffn
    head = 0 if tied_head else dim * vocab
    return vocab * dim + positions * dim + layers * block + dim + head


def transformer_flops_per_token(vocab: int, dim: int, layers: int, ffn: int,
                                seq: int, training: bool = True) -> float:
    """Required operations per token of a causal decoder at sequence length
    ``seq``: the matmuls (qkv, proj, FFN up and down, head) at two
    operations a multiply-add, and the two attention products with the
    causal half counted (a position attends to (seq+1)/2 keys on average).
    Training is forward plus backward, the backward at twice the forward;
    recomputation does not count."""
    per_layer = 2.0 * (dim * 3 * dim + dim * dim + 2 * dim * ffn)
    attn = 2.0 * 2.0 * dim * (seq + 1) / 2.0
    fwd = layers * (per_layer + attn) + 2.0 * dim * vocab
    return 3.0 * fwd if training else fwd


def flash_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                          causal: bool = True, backward: bool = False) -> float:
    """Required operations of one attention call over (batch, heads, seq,
    head_dim): forward two products (QK^T, PV), backward four (dV, dP, dQ,
    dK; the recomputed QK^T does not count); causal counted as half."""
    products = 4 if backward else 2
    full = products * 2.0 * batch * heads * seq * seq * head_dim
    return full * (seq + 1) / (2.0 * seq) if causal else full


def adamw_state_bytes(n_params: int, param_itemsize: int = 2) -> float:
    """HBM traffic of one AdamW update with float32 moments: parameters and
    gradients read, two moments read and written, parameters written."""
    return n_params * (2 * param_itemsize + param_itemsize + 4 * 4)
