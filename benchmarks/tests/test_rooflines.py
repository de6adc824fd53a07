"""The operation and byte counts against numbers written out by hand, and the
table of peaks."""

import json
import os

import pytest

from conftest import BENCH

import harness
from rooflines import attention, decode_tick, decoder


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_mistral_counts():
    cfg = config("mistral-7b-v0.3-d12")
    # wq, wo: 4096*4096 each; wk, wv: 4096*1024 each; three MLP matrices 4096*14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808                      # 218.1M a layer
    assert decoder.layer_matmul_params(cfg) == layer
    head = 4096 * 32768                              # 134.2M; the embedding as much again
    assert decoder.matmul_params(cfg) == 12 * layer + head == 2_751_463_424
    assert decoder.total_params(cfg) == 12 * layer + 2 * head + 25 * 4096
    assert round(decoder.total_params(cfg) / 1e9, 3) == 2.886
    # rank 16 on q, k, v, o: 16 * (8192 + 5120 + 5120 + 8192) a layer
    assert decoder.lora_params(cfg) == 12 * 16 * 26624 == 5_111_808


def test_internlm2_counts():
    cfg = config("internlm2-1.8b")
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560
    head = 2048 * 92544
    assert decoder.matmul_params(cfg) == 24 * layer + head == 1_699_479_552
    assert round(decoder.total_params(cfg) / 1e9, 2) == 1.89
    # keys and values of one token: 2 * 8 heads * 128 * 2 bytes * 24 layers
    assert decode_tick.kv_bytes_per_token(cfg) == 98304 == 96 * 1024
    assert decode_tick.weight_bytes(cfg) == 2 * 1_699_479_552
    assert decode_tick.tick_bytes(cfg, 1000) == 2 * 1_699_479_552 + 98_304_000


def test_train_flops_per_token():
    cfg = config("mistral-7b-v0.3-d12")
    cell = {"traffic": {"seq_len": 1024}}
    # causal scores and values forward: 2 * 32 heads * 128 * 1024^2 a layer and
    # sequence; three times that with the backward pass; twelve layers
    att = 12 * 3 * 2 * 32 * 128 * 1024 * 1024
    assert attention.causal_flops(cfg, 1024, backward=True) == att
    want = 4 * 2_751_463_424 + 6 * 5_111_808 + att / 1024
    assert decoder.train_flops_per_token(cfg, cell) == want
    assert 1.1e10 < want < 1.2e10


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.find_devices(1, harness.load_json("peaks.json"))


def test_cpu_is_refused():
    with pytest.raises(harness.BenchError, match="needs a TPU"):
        harness.find_devices(1, harness.load_json("peaks.json"))
