"""The model-FLOP counts of both configurations at their benchmarked depths,
pinned, and the pieces they are made of checked by hand."""
from __future__ import annotations

import json

import pytest

import flops
import peaks
from conftest import HERE


def _conf(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_mamba2_per_token_at_8_layers():
    conf = _conf("mamba2-2.7b")
    c = conf["config"]
    assert c["n_layer"] == 8
    D, Di, H, N, P, Q = 2560, 5120, 80, 128, 64, 256
    per_layer = D * (2 * Di + 2 * N + H) + Di * D + 4 * (Di + 2 * N)
    assert per_layer == 40_203_264
    head = D * 50288                       # 50277 padded to a multiple of 16
    matmul = 6 * (8 * per_layer + head)
    ssd = 3 * 8 * 2 * H * (Q / 2 * N + Q / 2 * P + 2 * N * P)
    assert flops.per_token(conf, 8192) == pytest.approx(matmul + ssd, rel=1e-12)
    assert flops.per_token(conf, 8192) == pytest.approx(2.859466752e9, rel=1e-9)
    # the SSD count does not depend on the sequence, only on the block
    assert flops.per_token(conf, 2048) == flops.per_token(conf, 8192)


def test_qwen3_per_token_at_2_layers():
    conf = _conf("qwen3-14b")
    assert conf["config"]["num_hidden_layers"] == 2
    D, H, KV, hd, F, V, S = 5120, 40, 8, 128, 17408, 151936, 2048
    per_layer = 2 * D * H * hd + 2 * D * KV * hd + 3 * D * F
    assert per_layer == 330_301_440
    attention = 2 * 3 * (2 * 2 * H * hd * S / 2)
    expect = 6 * (2 * per_layer + D * V) + attention
    assert flops.per_token(conf, S) == pytest.approx(expect, rel=1e-12)
    assert flops.per_token(conf, S) == pytest.approx(8.75692032e9, rel=1e-9)
    # attention grows with the context, the rest does not
    assert flops.per_token(conf, 2 * S) - flops.per_token(conf, S) == \
        pytest.approx(attention, rel=1e-12)


def test_embedding_gather_is_not_counted():
    conf = _conf("qwen3-14b")
    untied = flops.per_token(conf, 2048)
    conf["config"]["tie_word_embeddings"] = True     # head still multiplies
    assert flops.per_token(conf, 2048) == untied


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
