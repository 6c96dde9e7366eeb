"""The benchmark cell ``phi4mf_train_s8k`` rehearsed on the CPU at its tiny
sizes: the run reaches its rehearsal line with both new readers found, the
counts are what the shapes say, and faults planted under the timed path
(in the manner of ``benchmark/tests/test_faults.py``) come out as not
correct by the tiny limits."""

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

CELL = "phi4mf_train_s8k"


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


def test_rehearsal_reaches_its_line_with_both_new_readers(capsys):
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
    assert {"selective_scan_ms", "selective_scan_roofline",
            "flash_attn_roofline", "flash_fwd_ms", "flash_bwd_ms",
            "step_mfu"} <= set(found)
    assert all(lim is not None for _, lim in line["compared"].values())
    assert line["compiles_in_window"] == 0


def test_control_is_not_correct(reference):
    # (the sound run is the rehearsal above: correct by the same limits)
    ref, limits = reference
    drv, _ = _driver()
    lowp = jnp.dtype(drv.control_lowp).type
    control = drv.compare(drv.control_outputs(lowp), ref)
    assert _fails(control, limits), control


def test_window_edge_left_out(reference, monkeypatch):
    from distributedarrays_tpu.models import sambay as S
    real = S.flash_attention
    monkeypatch.setattr(S, "flash_attention", lambda q, k, v, causal,
                        window: real(q, k, v, causal=causal, window=None))
    assert _fails(_numbers(reference), reference[1])


def test_lambda_left_at_lambda_init(reference, monkeypatch):
    from distributedarrays_tpu.models import sambay as S
    real = S._diff_attention

    def frozen(q, k, v, p, index, cfg, window):
        still = {n: jnp.zeros_like(p[n]) for n in ("lq1", "lq2")}
        return real(q, k, v, {**p, **still}, index, cfg, window)

    monkeypatch.setattr(S, "_diff_attention", frozen)
    assert _fails(_numbers(reference), reference[1])


def test_m_star_taken_after_the_gate(reference, monkeypatch):
    from distributedarrays_tpu.models import sambay as S
    real = S._mamba

    def gated(u, p, cfg):
        mix, m = real(u, p, cfg)
        z = jnp.split(u @ p["in_proj"], 2, axis=-1)[1]
        return mix, (m.astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(m.dtype)

    monkeypatch.setattr(S, "_mamba", gated)
    assert _fails(_numbers(reference), reference[1])


def test_half_of_the_tokens_left_out(reference):
    def wrap(step):
        return lambda p, o, tokens: step(
            p, o, tokens[:, :(tokens.shape[1] - 1) // 2 + 1])

    assert _fails(_numbers(reference, wrap), reference[1])


def test_counts_are_what_the_shapes_say():
    import counts_sambay as C
    config = json.loads((BENCH / "configs" / "phi4_mini_flash.json")
                        .read_text())
    drv, _ = _driver()
    m = dict(drv.m, dim=2560, ffn=10240, heads=40, kv_heads=20, head_dim=64,
             window=512, d_inner=5120, d_state=16, d_conv=4, dt_rank=160)
    layers = drv.layers
    # the issue's arithmetic: 0.70 B parameters, some 38 TFLOP a step
    n = C.sambay_params(m, layers, config["vocab_size"])
    assert 0.69e9 < n < 0.70e9
    from distributedarrays_tpu.models import sambay as S
    shapes = jax.eval_shape(lambda: S.init_params(jax.random.key(0), S.Config(
        vocab=25008, dim=2560, ffn=10240, heads=40, kv_heads=20, head_dim=64,
        window=512, layers=layers)))
    assert n == sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    step = C.sambay_flops_per_token(m, layers, 25008, 8192) * 8192
    assert 36e12 < step < 40e12
    # a window's band against the causal half
    assert C.attention_pairs(8192, None) == 8192 * 8193 / 2
    assert C.attention_pairs(8192, 512) == 512 * 513 / 2 + 7680 * 512
    assert C.attention_pairs(64, 512) == C.attention_pairs(64, None)
    full = C.attention_flops(1, 8192, m, None, False)
    assert full == 40 * (8192 * 8193 / 2) * (2 * 64 + 2 * 128)
    assert C.attention_flops(1, 8192, m, None, True) == 2 * full
    scan = C.scan_cost(1, 8192, m)
    assert scan.flops == 21 * 8192 * 5120 * 16
    assert scan.hbm_bytes > 8 * 8192 * 5120 * 2


def test_configuration_file_against_the_catalog_row():
    config = json.loads((BENCH / "configs" / "phi4_mini_flash.json")
                        .read_text())
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    differs = sorted(k for k, v in catalog.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == ["num_hidden_layers",
                                                    "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    assert config["vocab_size"] * 8 == 200064
    assert config["kept_layers"] == [14, 15, 16, 17, 18, 19]
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}["phi4_mini_flash"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
