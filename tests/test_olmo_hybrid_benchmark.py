"""The benchmark cell ``olmohyb_train_s8k`` rehearsed on the CPU at its tiny
sizes: the run reaches its rehearsal line, the cell lists the new readers,
the counts are what the shapes say, the configuration keeps the catalog row's
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

CELL = "olmohyb_train_s8k"


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


def test_rehearsal_reaches_its_line(capsys):
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
    assert {"gdn_scan_ms", "gdn_scan_roofline", "phase_linear_ms",
            "flash_attn_roofline", "step_mfu", "program_hbm_gb"} <= set(found)
    assert not {"ssd_scan_ms", "phase_unscoped_ms"} & set(found)
    assert all(lim is not None for _, lim in line["compared"].values())
    assert line["compiles_in_window"] == 0


def test_the_cells_readers_are_the_new_ones_and_the_shared_ones():
    import importlib
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    found = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    assert {"gdn_scan_ms", "gdn_scan_roofline", "phase_linear_ms",
            "flash_attn_roofline", "flash_fwd_ms", "flash_bwd_ms",
            "step_mfu", "phase_attn_ms", "program_hbm_gb"} <= found
    assert not {"ssd_scan_ms", "phase_ssm_ms", "phase_unscoped_ms"} & found
    for name in found:
        assert callable(importlib.import_module(f"layer_metrics.{name}").read)


def test_control_is_not_correct(reference):
    # (the sound run is the rehearsal above: correct by the same limits)
    ref, limits = reference
    drv, _ = _driver()
    lowp = jnp.dtype(drv.control_lowp).type
    control = drv.compare(drv.control_outputs(lowp), ref)
    assert _fails(control, limits), control


def test_state_not_carried_between_chunks(reference, monkeypatch):
    # each chunk of 64 starts from a zero state: the delta rule's one
    # sequential part left out (the tiny row of 96 positions spans two)
    from distributedarrays_tpu.models import olmo_hybrid as M
    real = M.gated_delta

    def per_chunk(q, k, v, beta, g, chunk=64):
        parts = [real(q[s:s + chunk], k[s:s + chunk], v[s:s + chunk],
                      beta[s:s + chunk], g[s:s + chunk], chunk=chunk)
                 for s in range(0, q.shape[0], chunk)]
        return jnp.concatenate(parts, axis=0)

    monkeypatch.setattr(M, "gated_delta", per_chunk)
    assert _fails(_numbers(reference), reference[1])


def test_beta_held_under_one(reference, monkeypatch):
    # the transition's negative eigenvalues taken away
    from distributedarrays_tpu.models import olmo_hybrid as M
    real = M.gated_delta

    def clamped(q, k, v, beta, g):
        return real(q, k, v, jnp.minimum(beta, 1.0), g)

    monkeypatch.setattr(M, "gated_delta", clamped)
    assert _fails(_numbers(reference), reference[1])


def test_half_of_the_tokens_left_out(reference):
    def wrap(step):
        return lambda p, o, tokens: step(
            p, o, tokens[:, :(tokens.shape[1] - 1) // 2 + 1])

    assert _fails(_numbers(reference, wrap), reference[1])


def test_counts_are_what_the_shapes_say():
    import counts_olmo_hybrid as C
    drv, _ = _driver()
    m = dict(drv.m, dim=3840, ffn=11008, heads=30, head_dim=128,
             lin_heads=30, key_dim=96, value_dim=192)
    kinds = drv.kinds
    assert kinds == ("linear_attention",) * 3 + ("full_attention",)
    # the reckoning of PERF.md section 4: 5.514 GFLOP a token, 45.17 TFLOP
    # a step
    per_token = C.olmo_hybrid_flops_per_token(m, kinds, 12544, 8192)
    assert 5.51e9 < per_token < 5.52e9
    # one layer's delta-rule products forward, a chunk a head: K K^T 0.39 M,
    # the inverse 0.09 M, T [K | V] 1.20 M, the three state products
    # 7.08 M, Q K^T and P V' 1.20 M
    gdn = C.gdn_cost(1, 8192, m)
    one = (2 * 2016 * 96 + 64 ** 3 / 3 + 2 * 2080 * 288
           + 3 * 2 * 64 * 96 * 192 + 2 * 2080 * 288)
    assert gdn.flops == 3 * 128 * 30 * one and 38.1e9 < 128 * 30 * one < 38.3e9
    # bound by HBM: 0.95 GB a layer, some 1.16 ms at 819 GB/s
    assert 0.94e9 < gdn.hbm_bytes < 0.96e9
    one = C.attention_flops(1, 8192, m, False)
    assert one == 2 * 2.0 * 30 * 8192 * 8192 * 128 * 8193 / (2 * 8192)
    assert C.attention_flops(1, 8192, m, True) == 2 * one


def test_configuration_file_against_the_catalog_row():
    config = json.loads((BENCH / "configs" / "olmo_hybrid_7b.json")
                        .read_text())
    catalog = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    differs = sorted(k for k, v in catalog.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == ["num_hidden_layers",
                                                    "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 100352}
    assert config["vocab_size"] * 8 == 100352
    types = config["layer_types"]
    assert len(types) == 32 and [i for i, t in enumerate(types)
                                 if t == "full_attention"] == list(
        range(3, 32, 4))
    assert config["kept_layers"] == [0, 1, 2, 3]
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}["olmo_hybrid_7b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
