"""The benchmark cell ``granite4h_train_s8k`` rehearsed on the CPU at its tiny
sizes: the run reaches its rehearsal line with the new readers found, the
counts are what the shapes say, the configuration keeps the catalog row's
numbers, and faults planted under the timed path (in the manner of
``benchmark/tests/test_faults.py``) come out as not correct by the tiny
limits."""

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

import jax
import jax.numpy as jnp

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
for _p in (str(BENCH),):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402
from _trace_lock import traced_rehearsal  # noqa: E402

CELL = "granite4h_train_s8k"


def _driver(seed=11):
    import importlib
    c, _, config, traffic, limits, _ = harness.load_cell(BENCH, CELL)
    ctx = SimpleNamespace(cell=c, config=config, traffic=traffic, seed=seed,
                          devices=jax.devices()[:1], on_tpu=False, tiny=True,
                          root=BENCH, mark=lambda what: None)
    mod = importlib.import_module(f"drivers.{traffic['driver']}")
    return mod.Driver(ctx), limits["tiny_limits"]


@pytest.fixture(scope="module")
def reference():
    drv, limits = _driver()
    return drv.reference(), limits


def _numbers(reference, wrap_step=None):
    """The compared numbers of a tiny run whose step is wrapped."""
    drv, _ = _driver()
    drv.wrap_step = wrap_step
    drv.setup()
    harness.run_window(drv, 0.0)
    return drv.compare(drv.finish(), reference[0])


def _fails(numbers, limits):
    return [k for k, v in numbers.items() if not v <= limits[k]]


def test_rehearsal_reaches_its_line_with_the_new_readers(capsys):
    capsys.readouterr()
    with traced_rehearsal():
        rc = harness.main(["--workload", CELL, "--seed", "2147483999",
                           "--seconds", "0.2", "--trace", "1", "--platform",
                           "cpu", "--size", "tiny"], t0=time.perf_counter(),
                          root=BENCH)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == harness.EXIT_REHEARSAL
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    found = line["rehearsal"]["readers_found"]
    assert {"ssd_scan_ms", "ssd_scan_roofline", "flash_attn_roofline",
            "flash_fwd_ms", "flash_bwd_ms", "step_mfu", "phase_ssm_ms",
            "program_hbm_gb"} <= set(found)
    assert "selective_scan_ms" not in found
    assert all(lim is not None for _, lim in line["compared"].values())
    assert line["compiles_in_window"] == 0


def test_control_is_not_correct(reference):
    # (the sound run is the rehearsal above: correct by the same limits)
    ref, limits = reference
    drv, _ = _driver()
    lowp = jnp.dtype(drv.control_lowp).type
    control = drv.compare(drv.control_outputs(lowp), ref)
    assert _fails(control, limits), control


def test_state_not_carried_between_chunks(reference, monkeypatch):
    # each chunk of 32 starts from a zero state: the SSD's one sequential
    # part left out
    from distributedarrays_tpu.models import mamba2_hybrid as M
    real = M.ssd

    def per_chunk(x, dt, A, B, C, chunk):
        parts = [real(x[s:s + chunk], dt[s:s + chunk], A, B[s:s + chunk],
                      C[s:s + chunk], chunk=chunk)
                 for s in range(0, x.shape[0], chunk)]
        return jnp.concatenate(parts, axis=0)

    monkeypatch.setattr(M, "ssd", per_chunk)
    assert _fails(_numbers(reference), reference[1])


def test_c_read_one_position_late(reference, monkeypatch):
    from distributedarrays_tpu.models import mamba2_hybrid as M
    real = M.ssd

    def late(x, dt, A, B, C, chunk):
        shifted = jnp.concatenate([jnp.zeros_like(C[:1]), C[:-1]], axis=0)
        return real(x, dt, A, B, shifted, chunk=chunk)

    monkeypatch.setattr(M, "ssd", late)
    assert _fails(_numbers(reference), reference[1])


def test_half_of_the_tokens_left_out(reference):
    def wrap(step):
        return lambda p, o, tokens: step(
            p, o, tokens[:, :(tokens.shape[1] - 1) // 2 + 1])

    assert _fails(_numbers(reference, wrap), reference[1])


def test_counts_are_what_the_shapes_say():
    import counts_granite as C
    drv, _ = _driver()
    m = dict(drv.m, dim=2048, ffn=8192, heads=32, kv_heads=8, head_dim=64,
             ssm_heads=64, ssm_head_dim=64, d_inner=4096, d_state=128,
             n_groups=1, chunk=256)
    kinds = drv.kinds
    assert kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    # the reckoning of PERF.md section 4: 4.82 GFLOP a token, 39.5 TFLOP
    # a step
    per_token = C.granite_flops_per_token(m, kinds, 12544, 8192)
    assert 4.81e9 < per_token < 4.83e9
    # one layer's SSD products forward: C B^T 0.27 G, the masked product
    # 8.6 G, the chunk states 8.6 G and the states' output 8.6 G
    ssd = C.ssd_cost(1, 8192, m)
    fwd = 32 * (256 * 257 / 2 * 2 * 128 + 64 * (256 * 257 / 2 * 2 * 64
                                                 + 2 * 2 * 256 * 64 * 128))
    assert ssd.flops == 3 * fwd and 26.0e9 < fwd < 26.2e9
    # bound by HBM: 3.15 GB over nine layers, some 3.9 ms at 819 GB/s
    assert 3.1e9 < 9 * ssd.hbm_bytes < 3.2e9
    one = C.attention_flops(1, 8192, m, False)
    assert one == 2 * 2.0 * 32 * 8192 * 8192 * 64 * 8193 / (2 * 8192)
    assert C.attention_flops(1, 8192, m, True) == 2 * one


def test_configuration_file_against_the_catalog_row():
    config = json.loads((BENCH / "configs" / "granite4_h_micro.json")
                        .read_text())
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    differs = sorted(k for k, v in catalog.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == ["num_hidden_layers",
                                                    "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert config["vocab_size"] * 8 == 100352
    types = config["layer_types"]
    assert len(types) == 40 and [i for i, t in enumerate(types)
                                 if t == "attention"] == [5, 15, 25, 35]
    assert config["kept_layers"] == list(range(10))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}["granite4_h_micro"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
