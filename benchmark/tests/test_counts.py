"""``counts.py`` against values worked by hand for the four steps."""

import pytest

import counts

PEAKS = counts.load_peaks("TPU v5 lite")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.load_peaks("cpu")
    with pytest.raises(KeyError):
        counts.load_peaks("TPU v4")


def test_peaks_are_the_published_ones():
    assert PEAKS["flops_bf16_per_s"] == 197e12
    assert PEAKS["ops_int8_per_s"] == 393e12
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    assert PEAKS["hbm_bytes"] == 16e9
    assert PEAKS["ici_bytes_per_s"] == 1600e9 / 8


def test_stream_step():
    # 20480^2 f32: three arrays read, one written = 4 * 1.6777 GB
    c = counts.chain_cost((20480, 20480), 4, 3, 3)
    assert c.hbm_bytes == 4 * 20480 * 20480 * 4 == 6710886400
    secs, which = counts.least_seconds(c, PEAKS)
    assert which == "hbm"
    assert secs == pytest.approx(6710886400 / 819e9)      # 8.194 ms
    assert secs * 1e3 == pytest.approx(8.194, abs=1e-3)
    r = counts.reduce_cost((20480, 20480), 4)
    assert r.hbm_bytes == 20480 * 20480 * 4


def test_gemm_step():
    c = counts.gemm_cost(16384, 16384, 16384, 4)
    assert c.flops == 2 * 16384 ** 3 == 8796093022208
    assert c.hbm_bytes == 3 * 16384 * 16384 * 4
    secs, which = counts.least_seconds(c, PEAKS)
    assert which == "flops"
    assert secs * 1e3 == pytest.approx(44.650, abs=1e-3)


def test_reshard_legs():
    shape, gib = (32768, 65536), 2 ** 30
    # (4,1) -> (1,4): every chip keeps the quarter of its block that lies in
    # its own column block: 2 GiB - 0.5 GiB leave each chip
    a = counts.block_owner_bytes(shape, 4, (4, 1), (1, 4))
    assert a["send"] == [1.5 * gib] * 4 and a["recv"] == [1.5 * gib] * 4
    # (1,4) -> (2,2): chips 0 and 3 keep half of their block, 1 and 2 none
    b = counts.block_owner_bytes(shape, 4, (1, 4), (2, 2))
    assert b["send"] == [1 * gib, 2 * gib, 2 * gib, 1 * gib]
    # (2,2) -> (4,1): every chip keeps the half of its block that lies in
    # its own row block
    c = counts.block_owner_bytes(shape, 4, (2, 2), (4, 1))
    assert c["send"] == [1 * gib] * 4
    assert a["moved"] + b["moved"] + c["moved"] == 16 * gib
    legs = (counts.reshard_leg_cost(shape, 4, (4, 1), (1, 4))
            + counts.reshard_leg_cost(shape, 4, (1, 4), (2, 2))
            + counts.reshard_leg_cost(shape, 4, (2, 2), (4, 1)))
    assert legs.ici_bytes == 4.5 * gib          # 1.5 + 2 + 1 out of a chip
    secs, which = counts.least_seconds(legs, PEAKS)
    assert which == "ici"
    assert secs * 1e3 == pytest.approx(24.159, abs=1e-3)
    # the cell's own 32768 x 49152: three quarters of every count above
    cell = (32768, 49152)
    legs = (counts.reshard_leg_cost(cell, 4, (4, 1), (1, 4))
            + counts.reshard_leg_cost(cell, 4, (1, 4), (2, 2))
            + counts.reshard_leg_cost(cell, 4, (2, 2), (4, 1)))
    assert legs.ici_bytes == 3.375 * gib
    assert counts.least_seconds(legs, PEAKS)[0] * 1e3 == pytest.approx(
        18.119, abs=1e-3)


def test_train_step():
    v, e, layers, f, s = 50257, 1024, 24, 4096, 1024
    n = counts.transformer_params(v, e, layers, f, 1024)
    # 51.46 M embedding, 1.05 M positions, 24 * 12.585 M, 1024, 51.46 M head
    assert n == 50257 * 1024 * 2 + 1024 * 1024 + 24 * (
        12 * 1024 * 1024 + 2048) + 1024 == 406_014_976
    per_tok = counts.transformer_flops_per_token(v, e, layers, f, s)
    fwd = 24 * (2 * 12 * 1024 ** 2 + 2 * 2 * 1024 * 1025 / 2) \
        + 2 * 1024 * 50257
    assert per_tok == pytest.approx(3 * fwd)
    assert per_tok / 1e9 == pytest.approx(2.271, abs=2e-3)   # GFLOP a token
    step = per_tok * 8 * 1024
    assert step / 1e12 == pytest.approx(18.60, abs=0.02)     # TFLOP a step


def test_flash_attention_flops():
    # one head, S=1024, D=64, full: 2 products * 2 * 1024^2 * 64
    full = counts.flash_attention_flops(1, 1, 1024, 64, causal=False)
    assert full == 2 * 2 * 1024 * 1024 * 64
    half = counts.flash_attention_flops(1, 1, 1024, 64, causal=True)
    assert half == pytest.approx(full * 1025 / 2048)
    bwd = counts.flash_attention_flops(1, 1, 1024, 64, causal=False,
                                       backward=True)
    assert bwd == 2 * full
