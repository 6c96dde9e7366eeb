"""The per-layer readers on a made-up run: each gives its number from the
peaks table and ``counts``, or nothing where there is nothing to read."""

import importlib
from types import SimpleNamespace

import pytest

import counts

PEAKS = counts.load_peaks("TPU v5 lite")


def _run(**kw):
    reduced = {"window_s": 2.0, "steps": 100, "busy_s_fullest": 1.9,
               "exposed_collective_s_fullest": 0.0, "ops_fullest": {}}
    base = dict(trace={"reduced": reduced}, peaks=PEAKS, chips=1,
                cost=counts.chain_cost((20480, 20480), 4, 3, 3), steps=1000,
                window_s=20.0, dispatch_s=[0.001, 0.003], values={},
                memory_peak_bytes=8_000_000_000, fallback_hits=0,
                reshard_bytes_per_step=0, notes=[], driver=object())
    base.update(kw)
    return SimpleNamespace(**base)


def read(name, run):
    return importlib.import_module(f"layer_metrics.{name}").read(run)


def test_rooflines_from_counts_and_peaks():
    run = _run()
    least = 4 * 20480 ** 2 * 4 / 819e9
    assert read("step_roofline", run) == pytest.approx(100 * least / 0.020)
    assert read("kernel_roofline", run) == pytest.approx(100 * least / 0.019)
    assert read("device_idle_pct", run) == pytest.approx(5.0)
    assert any("bound by hbm" in n for n in run.notes)
    # kernel share x (1 - idle) = step share
    assert read("kernel_roofline", run) * 0.95 == pytest.approx(
        read("step_roofline", run))


def test_counts_and_spans():
    run = _run()
    assert read("dispatch_ms", run) == pytest.approx(2.0)
    assert read("peak_hbm_gb", run) == pytest.approx(8.0)
    assert read("fallback_hits", run) == 0.0
    assert read("reshard_moved_gb", run) is None
    assert read("reshard_moved_gb",
                _run(reshard_bytes_per_step=27.9e9)) == pytest.approx(27.9)


def test_nothing_to_read_gives_nothing():
    run = _run(trace=None)
    for name in ("step_roofline", "kernel_roofline", "device_idle_pct",
                 "collective_exposed_pct", "flash_attn_roofline"):
        assert read(name, run) is None
    assert read("step_mfu", _run()) is None           # no tokens in this cell
    assert read("collective_exposed_pct", _run()) is None
    assert read("flash_attn_roofline", _run()) is None   # no such kernel


def test_mfu_and_flash_share():
    flops = counts.transformer_flops_per_token(50257, 1024, 24, 4096, 1024)
    step = flops * 8192
    drv = SimpleNamespace(attention_flops=lambda: 1.97e12)   # 10 ms at peak
    run = _run(cost=counts.Cost(flops=step), steps=100, window_s=20.0,
               values={"tokens_per_s": 8192 * 5.0}, driver=drv)
    assert read("step_mfu", run) == pytest.approx(
        100 * step * 5.0 / 197e12)
    run.trace["reduced"]["ops_fullest"] = {
        "%jvp_jit_wrapped__.3 = bf16[128,1024,64] custom-call(...)": 2.0,
        "%transpose_jvp_jit_wrapped___.9 = (bf16[1]) custom-call(...)": 3.0,
        "%fusion.1 = fusion(...)": 9.0}
    # 10 ms least over 50 ms of flash kernels a step
    assert read("flash_attn_roofline", run) == pytest.approx(20.0)
