"""`correct` has to come out false when the timed path is broken underneath,
and when the control stands in the program's place; true when neither.

Each test drives a whole tiny run on the CPU (set-up, window, freeing the
state, the reference, the comparison) and plants one fault where the
answer is produced: an answer altered, the exchange between chips left out,
a step that returns its state unchanged, half of the batch left out."""

from types import SimpleNamespace

import numpy as np
import pytest

import harness
from helpers import BENCH, rehearse

CELLS = ["array_stream", "array_gemm", "array_reshard_2x2", "gpt2m_train"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    rc, line = rehearse(capsys, cell)
    assert rc == harness.EXIT_REHEARSAL
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert all(lim is not None for _, lim in line["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The reference in the nearest precision below the configuration's,
    put in the program's place, fails at least one of the cell's numbers."""
    import importlib
    import jax
    import jax.numpy as jnp
    c, _, config, traffic, limits, _ = harness.load_cell(BENCH, cell)
    ctx = SimpleNamespace(cell=c, config=config, traffic=traffic, seed=5,
                          devices=jax.devices()[:c["chips"]], on_tpu=False,
                          tiny=True, root=BENCH, mark=lambda what: None)
    drv = importlib.import_module(f"drivers.{traffic['driver']}").Driver(ctx)
    lowp = jnp.dtype(drv.control_lowp).type
    numbers = drv.compare(drv.control_outputs(lowp), drv.reference())
    lim = limits["tiny_limits"]
    assert any(not v <= lim[k] for k, v in numbers.items()), numbers


def _alter_one(garray):
    return garray.at[3, 5].add(1.0)


def test_altered_gemm_answer(capsys, monkeypatch):
    import distributedarrays_tpu as dat
    real = dat.matmul

    def bad(a, b):
        c = real(a, b)
        out = dat.distribute(_alter_one(c.garray), procs=[0], dist=(1, 1))
        c.close()
        return out

    monkeypatch.setattr(dat, "matmul", bad)
    _, line = rehearse(capsys, "array_gemm")
    assert line["correct"] is False
    v, lim = line["compared"]["C_max_rel"]
    assert v > lim


def test_altered_chain_answer(capsys, monkeypatch):
    import distributedarrays_tpu as dat
    real = dat.djit

    def bad_djit(f):
        g = real(f)

        def call(*xs):
            d = g(*xs)
            out = dat.distribute(_alter_one(d.garray), procs=[0],
                                 dist=(1, 1))
            d.close()
            return out
        return call

    monkeypatch.setattr(dat, "djit", bad_djit)
    _, line = rehearse(capsys, "array_stream")
    assert line["correct"] is False
    assert line["compared"]["D_max_rel"][0] > line["compared"]["D_max_rel"][1]


def test_altered_scalar_answer(capsys, monkeypatch):
    import distributedarrays_tpu as dat
    real = dat.dmean
    monkeypatch.setattr(dat, "dmean", lambda x: real(x) * 1.001)
    _, line = rehearse(capsys, "array_stream")
    assert line["correct"] is False
    assert line["compared"]["mean_rel"][0] > line["compared"]["mean_rel"][1]


def test_exchange_between_chips_left_out(capsys, monkeypatch):
    """A redistribution that relabels each chip's bytes as its new block
    without moving anything."""
    import distributedarrays_tpu as dat
    real = dat.distribute

    def lazy(x, procs=None, dist=None, like=None):
        if not isinstance(x, dat.DArray):
            return real(x, procs=procs, dist=dist, like=like)
        src = np.asarray(x.garray)
        rows, cols = src.shape
        (sr, sc), (dr, dc) = x.pids.shape, dist
        out = np.empty_like(src)
        for r in range(len(procs)):
            i, j = divmod(r, sc)
            blk = src[i * rows // sr:(i + 1) * rows // sr,
                      j * cols // sc:(j + 1) * cols // sc]
            i, j = divmod(r, dc)
            out[i * rows // dr:(i + 1) * rows // dr,
                j * cols // dc:(j + 1) * cols // dc] = blk.reshape(
                    rows // dr, cols // dc)
        return real(out, procs=procs, dist=dist)

    monkeypatch.setattr(dat, "distribute", lazy)
    _, line = rehearse(capsys, "array_reshard_2x2")
    assert line["correct"] is False
    # every chip kept its bytes, so after the whole cycle X2 is X again:
    # only the audit of the intermediates Y and Z can see this fault
    assert line["compared"]["X2_max_rel"][0] == 0
    assert line["compared"]["Y_max_rel"][0] > 0
    assert line["compared"]["Z_max_rel"][0] > 0


def test_shard_on_the_wrong_device(capsys, monkeypatch):
    import distributedarrays_tpu as dat
    real = dat.distribute

    def swapped(x, procs=None, dist=None, like=None):
        if isinstance(x, dat.DArray):
            procs = [procs[1], procs[0]] + list(procs[2:])
        return real(x, procs=procs, dist=dist, like=like)

    monkeypatch.setattr(dat, "distribute", swapped)
    _, line = rehearse(capsys, "array_reshard_2x2")
    assert line["correct"] is False
    assert line["compared"]["misplaced"][0] > 0


def _plant_train_fault(monkeypatch, wrap):
    from drivers import train_step
    init = train_step.Driver.__init__

    def patched(self, ctx):
        init(self, ctx)
        self.wrap_step = wrap

    monkeypatch.setattr(train_step.Driver, "__init__", patched)


def test_step_returns_its_state_unchanged(capsys, monkeypatch):
    def wrap(step):
        def same(params, opt_state, tokens):
            import jax
            keep = jax.tree_util.tree_map(lambda t: t.copy(),
                                          (params, opt_state))
            _, _, loss = step(params, opt_state, tokens)
            return keep[0], keep[1], loss
        return same

    _plant_train_fault(monkeypatch, wrap)
    _, line = rehearse(capsys, "gpt2m_train")
    assert line["correct"] is False
    v, lim = line["compared"]["dparam_norm_gap"]
    assert v == pytest.approx(1.0, abs=1e-6) and v > lim


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    def wrap(step):
        return lambda p, o, tokens: step(p, o, tokens[:tokens.shape[0] // 2])

    _plant_train_fault(monkeypatch, wrap)
    _, line = rehearse(capsys, "gpt2m_train")
    assert line["correct"] is False
    v, lim = line["compared"]["grad_norm_gap"]
    assert v > 10 * lim / 4          # far above any sound reading
