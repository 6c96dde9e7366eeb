"""The benchmark cell ``glm47f_train_s8k`` rehearsed on the CPU at its tiny
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

CELL = "glm47f_train_s8k"
CONFIG = BENCH / "configs" / "glm47_flash.json"


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
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == harness.EXIT_REHEARSAL
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    found = line["rehearsal"]["readers_found"]
    assert {"moe_experts_ms", "flash_attn_roofline", "flash_fwd_ms",
            "flash_bwd_ms", "step_mfu"} <= set(found)
    assert "route_flip_share" in line["compared"]
    assert all(lim is not None for _, lim in line["compared"].values())
    assert line["compiles_in_window"] == 0
    # what the held experts saw and each loss's two parts are printed
    assert "held rows a step" in out.err and "L_mtp" in out.err


def test_control_is_not_correct(reference):
    # (the sound run is the rehearsal above: correct by the same limits)
    ref, limits = reference
    drv, _ = _driver()
    lowp = jnp.dtype(drv.control_lowp).type
    control = drv.compare(drv.control_outputs(lowp), ref)
    assert _fails(control, limits), control


def test_rotary_turn_left_off_the_keys(reference, monkeypatch):
    from distributedarrays_tpu.models import mla_moe as M
    real = M.rope
    monkeypatch.setattr(M, "rope", lambda x, theta: x if x.shape[-2] == 1
                        else real(x, theta))
    assert _fails(_numbers(reference), reference[1])


def test_routed_scale_left_out(reference, monkeypatch):
    from distributedarrays_tpu.models import mla_moe as M
    real = M.held_experts_ffn
    monkeypatch.setattr(M, "held_experts_ffn", lambda *a, scale, **kw: real(
        *a, scale=1.0, **kw))
    assert _fails(_numbers(reference), reference[1])


def test_weights_normalised_over_the_held_experts_only(reference,
                                                       monkeypatch):
    # the weights are normalised over all four chosen, held or not
    from distributedarrays_tpu.models import moe as E
    real = E.route_sigmoid_topk

    def over_held(u, router, bias, k, scale):
        idx, w = real(u, router, bias, k, scale)
        held = (idx >= 4) & (idx < 8)           # the tiny preset's share
        kept = jnp.where(held, w, 0.0)
        return idx, scale * kept / (kept.sum(-1, keepdims=True) + 1e-20)

    monkeypatch.setattr(E, "route_sigmoid_topk", over_held)
    assert _fails(_numbers(reference), reference[1])


def test_shared_expert_left_out(reference, monkeypatch):
    from distributedarrays_tpu.models import mla_moe as M
    real = M._gated

    def no_shared(u, w1, w2):
        out = real(u, w1, w2)
        return jnp.zeros_like(out) if w1.shape[1] == 64 else out

    monkeypatch.setattr(M, "_gated", no_shared)   # 2 x 32: the tiny expert
    assert _fails(_numbers(reference), reference[1])


def test_mtp_embeds_the_position_before(reference, monkeypatch):
    # h'_i takes Emb(t_{i+1}); one position early is t_i, the trunk's own
    from distributedarrays_tpu.models import mla_moe as M
    real = M._mtp_trunk
    monkeypatch.setattr(M, "_mtp_trunk", lambda params, x, tok, *rest:
                        real(params, x, jnp.roll(tok, 1, axis=1), *rest))
    assert _fails(_numbers(reference), reference[1])


def test_mtp_loss_left_out(reference, monkeypatch):
    from distributedarrays_tpu.models import mla_moe as M
    real = M.loss_parts

    def main_only(params, tokens, cfg):
        main, mtp = real(params, tokens, cfg)
        return main, 0.0 * mtp

    monkeypatch.setattr(M, "loss_parts", main_only)
    assert _fails(_numbers(reference), reference[1])


def test_half_of_the_tokens_left_out(reference):
    def wrap(step):
        return lambda p, o, tokens: step(
            p, o, tokens[:, :(tokens.shape[1] - 2) // 2 + 2])

    assert _fails(_numbers(reference, wrap), reference[1])


def test_counts_are_what_the_shapes_say():
    import counts_glm_moe as C
    config = json.loads(CONFIG.read_text())
    m = dict(dim=2048, heads=20, q_rank=768, kv_rank=512, nope=192, rope=64,
             v_dim=256, ffn=10240, moe_ffn=1536, n_experts=64, held=8,
             top_k=4)
    kinds = ("dense", "moe", "moe", "moe", "moe")
    # the issue's arithmetic: 706.5 M parameters, 29.7 TFLOP a step
    assert C.mla_params(m) == 21_757_952 + 768 + 512
    n = C.glm_params(m, kinds, config["vocab_size"], True)
    assert 706.4e6 < n < 706.6e6
    from distributedarrays_tpu.models import mla_moe as M
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.key(0), M.Config(
        vocab=19360, dim=2048, heads=20, q_rank=768, kv_rank=512, nope=192,
        rope=64, v_dim=256, ffn=10240, moe_ffn=1536, n_experts=64,
        held=(0, 8), layers=tuple(enumerate(kinds)), mtp=47)))
    assert n == sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    per_token = C.glm_flops_per_token(m, kinds, 19360, 8192, True)
    assert 3.60e9 < per_token < 3.66e9
    assert 29.5e12 < per_token * 8192 < 29.9e12
    # 0.5 expert applications a token a layer at the expected load
    assert C.expert_rows(8192, m) == 4096
    fwd = C.attention_flops(1, 8192, m, False)
    assert fwd == 20 * (8192 * 8193 / 2) * (2 * 256 + 2 * 256)
    assert C.attention_flops(1, 8192, m, True) == 2 * fwd
    share = 6 * 3 * fwd / (per_token * 8192)
    assert 0.41 < share < 0.43                 # attention's kernels: 42%
    # the routed experts, forward and backward: 4% of the step
    routed = 5 * 3 * 2 * 4096 * 3 * 2048 * 1536
    assert 0.035 < routed / (per_token * 8192) < 0.045


def test_configuration_file_against_the_catalog_row():
    config = json.loads(CONFIG.read_text())
    catalog = {"attention_bias": False, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 10240,
               "max_position_embeddings": 202752,
               "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
               "topk_method": "noaux_tc", "norm_topk_prob": True,
               "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
               "n_routed_experts": 64, "n_shared_experts": 1,
               "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
               "first_k_dense_replace": 1, "num_hidden_layers": 47,
               "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
               "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 1000000,
               "tie_word_embeddings": False, "q_lora_rank": 768,
               "kv_lora_rank": 512, "qk_nope_head_dim": 192,
               "qk_rope_head_dim": 64, "v_head_dim": 256,
               "vocab_size": 154880}
    assert all(k in config for k in catalog)
    differs = sorted(k for k, v in catalog.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 47,
                                   "n_routed_experts": 64,
                                   "vocab_size": 154880}
    assert config["vocab_size"] * 8 == 154880
    assert config["n_routed_experts"] * 8 == 64
    assert config["held_experts"] == [0, 8]
    assert config["kept_layers"] == [0, 1, 2, 3, 4]
    for key in ("rope", "latent_norms", "selection_bias", "router",
                "mtp_order", "mtp_lambda"):
        assert key in config["assumed"], key
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}["glm47_flash"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm47_flash", "train_glm_b1_s8192", 1)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert per_layer["moe_experts_ms"]["workloads"] == [CELL]
    # no share of a roofline for the grouped products: their load is the
    # seed's draw under this traffic, and a share at the expected load
    # passes 100 where the held experts win no token
    assert "moe_experts_roofline" not in per_layer
