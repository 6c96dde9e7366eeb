"""Training tasks: the model-side contract the trainer drives.

A :class:`TrainTask` bundles what the :class:`~.trainer.Trainer` needs
and nothing else:

- ``init_params(key) -> pytree`` of f32 leaves,
- ``loss_sum(params, batch, w) -> scalar`` — the **weighted sum** of
  per-example losses over one *local* batch shard (``w`` is the
  per-example weight vector: 1.0 for real examples, 0.0 for the padding
  rows the trainer appends to make the global batch divisible by the
  rank count).  Summing locally and ``psum``-ing globally keeps the
  global loss/gradient exactly independent of how the batch is split,
  which is what the chaos test's bit-identical-resume acceptance rides
  on.
- ``batch(step) -> tuple of host arrays`` — the deterministic data
  pipeline: the same step index must yield the same batch on every
  (re-)run, or a recovery retry could never reproduce the trajectory.

The two constructors reuse the existing model layer rather than define
new networks: :func:`mlp_task` trains :mod:`..models.mlp`'s network on a
fixed random teacher, :func:`transformer_task` trains
:mod:`..models.transformer`'s decoder on next-token prediction.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["TrainTask", "mlp_task", "transformer_task"]


@dataclasses.dataclass(frozen=True)
class TrainTask:
    """The trainer's model-side contract (see module docstring)."""

    name: str
    batch_size: int
    init_params: Callable
    loss_sum: Callable            # (params, batch_tuple, w) -> scalar sum
    batch: Callable               # (step) -> tuple of host np arrays


def _mix_rng(seed: int, step: int) -> np.random.Generator:
    """Per-(task-seed, step) host RNG: plain integer mixing (hash() is
    process-salted, which would break cross-process replay)."""
    return np.random.default_rng((seed * 1_000_003 + step * 8_191)
                                 & 0x7FFFFFFF)


def mlp_task(sizes: Sequence[int] = (16, 32, 32, 4),
             batch_size: int = 56, seed: int = 0) -> TrainTask:
    """Regression on a fixed random teacher with the mesh-sharded MLP
    (:mod:`..models.mlp` — its ``forward`` is reused verbatim; only the
    per-example weighting is new).  ``batch_size=56`` divides both 8 and
    7 ranks, so a shrink from the default CPU mesh needs no re-padding.
    """
    from ..models import mlp
    sizes = tuple(int(s) for s in sizes)
    teacher = np.random.default_rng(seed + 7).standard_normal(
        (sizes[0], sizes[-1])).astype(np.float32) / np.sqrt(sizes[0])

    def init_params(key):
        return mlp.init_params(key, sizes, dtype=jnp.float32)

    def loss_sum(params, batch, w):
        x, y = batch
        pred = mlp.forward(params, x)
        per_ex = jnp.mean(jnp.square(pred - y), axis=-1)   # (B_local,)
        return jnp.sum(per_ex * w)

    def batch(step):
        rng = _mix_rng(seed, step)
        x = rng.standard_normal((batch_size, sizes[0])).astype(np.float32)
        y = np.tanh(x @ teacher).astype(np.float32)
        return x, y

    return TrainTask(name=f"mlp{ 'x'.join(map(str, sizes)) }",
                     batch_size=batch_size, init_params=init_params,
                     loss_sum=loss_sum, batch=batch)


def transformer_task(vocab: int = 64, dim: int = 32, heads: int = 2,
                     layers: int = 1, seq: int = 16,
                     batch_size: int = 56, seed: int = 0) -> TrainTask:
    """Next-token prediction with the decoder from
    :mod:`..models.transformer` (its ``Config``/``init_params``/
    ``forward`` are reused; the per-example token-mean cross-entropy here
    replaces its batch-mean ``loss_fn`` so padding rows can carry zero
    weight)."""
    from ..models import transformer as tr
    cfg = tr.Config(vocab=vocab, dim=dim, heads=heads, layers=layers,
                    max_seq=seq, dtype=jnp.float32)

    def init_params(key):
        # f32 master weights: the trainer's flat vector (and the
        # bit-identical-resume acceptance) is f32 end to end
        return tr.init_params(key, cfg)

    def loss_sum(params, batch, w):
        (tokens,) = batch
        logits = tr.forward(params, tokens[:, :-1], cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = tokens[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        per_ex = jnp.mean(nll, axis=-1)                    # (B_local,)
        return jnp.sum(per_ex * w)

    def batch(step):
        # learnable data: each example is a modular counting sequence
        # from a random offset — next-token prediction has an exact
        # answer, so the loss trajectory visibly descends in a few steps
        rng = _mix_rng(seed, step)
        offs = rng.integers(0, vocab, size=(batch_size, 1), dtype=np.int64)
        toks = (offs + np.arange(seq + 1)) % vocab
        return (toks.astype(np.int32),)

    return TrainTask(name=f"transformer_d{dim}", batch_size=batch_size,
                     init_params=init_params, loss_sum=loss_sum,
                     batch=batch)
