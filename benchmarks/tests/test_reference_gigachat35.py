"""At a tiny size on the CPU the plain reference of the ``gigachat3_5`` decoder
(the linear layers step by step) and ``LlamaLM`` (the chunked form) agree on
logits in float32; a bfloat16 run of the program, the int8 control and the
bfloat16 witness fall outside the tolerance that holds them; the margins are
over the biased scores; the rooflines count the real configuration's
parameters and bytes as its file states them.  (The layer-by-layer cases and the engine's are tier-1:
``tests/test_gigachat35.py``.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH

import weights_gigachat35 as weights
from drivers import serve_gigachat35 as drv
from reference import gigachat35_decoder as ref

TOL = 2e-5
with open(os.path.join(BENCH, "tests", "tiny_gigachat35.json")) as f:
    TINY = json.load(f)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def setting():
    lcfg = drv.program_config(TINY, 128, attn_impl="blockwise", remat="none")
    base, lora = weights.make_base(TINY, 5), weights.make_lora(TINY, 5)
    x = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(2, 100)), jnp.int32)
    return lcfg, base, lora, x


def test_float32_program_agrees_and_bfloat16_falls_outside(setting):
    from fedml_tpu.llm.model import LlamaLM
    lcfg, base, lora, x = setting
    want, _ = ref.logits(base, lora, x, TINY)
    assert rel(LlamaLM(lcfg).apply({"params": base, "lora": lora}, x), want) < TOL
    low = dataclasses.replace(lcfg, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    assert rel(LlamaLM(low).apply({"params": base, "lora": lora}, x), want) > 10 * TOL


def test_lower_precisions_fall_outside_and_the_tail_is_the_whole(setting):
    _, base, lora, x = setting
    want, margin = ref.logits(base, lora, x, TINY)
    own, _ = ref.logits(base, lora, x, TINY, quant="bfloat16")
    low, _ = ref.logits(base, lora, x, TINY, quant="int8")
    assert 10 * TOL < rel(own, want) < rel(low, want)
    out = ref.forced_gaps(base, lora, x[:1], 40, 24, TINY, quant=("int8", "bfloat16"))
    rows = np.asarray(want[0, 40:64])
    best = rows.max(-1)
    nxt = rows[np.arange(24), np.asarray(x[0, 41:65])]
    assert rel(out["gap"], best - nxt) < TOL and rel(out["spread"], best - np.median(rows, -1)) < TOL
    assert np.allclose(out["margin"], margin[0, 40:64], atol=1e-5)      # one row, not two: rounding
    assert set(out) == {"gap", "spread", "margin", "control_gap", "witness_gap"}
    with pytest.raises(ValueError, match="unknown quant"):
        ref.logits(base, lora, x, TINY, quant="fp8")


def test_the_margin_is_over_the_biased_scores_of_held_experts(setting):
    _, base, lora, x = setting
    flat = jax.tree_util.tree_map_with_path(
        lambda path, p: jnp.zeros_like(p) if path[-1].key == "select_bias" else p, base)
    _, with_bias = ref.logits(base, lora, x, TINY)
    _, without = ref.logits(flat, lora, x, TINY)
    assert not np.allclose(np.asarray(with_bias), np.asarray(without))
    # a share that holds no expert of the two at the edge has no margin there
    _, share = ref.logits(base, lora, x, TINY, held=(0, 1))
    assert np.isinf(np.asarray(share)).mean() > np.isinf(np.asarray(with_bias)).mean()


def test_the_real_configuration_counts_as_its_file_states():
    from rooflines import gigachat35 as rl
    with open(os.path.join(BENCH, "configs", "gigachat3.5-ep16-d5.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads", "serve-reason-4k.gigachat3.5-ep16-d5.json")) as f:
        cell = json.load(f)
    assert rl.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    assert round(rl.mla_params(cfg) / 1e6, 1) == 159.8 and round(rl.linear_params(cfg) / 1e6, 1) == 235.8
    dense = rl.linear_params(cfg) + 3 * 7168 * 18432
    sparse = 16 * 44_040_192 + 44_040_192 + 7168 * 256
    assert [round(n / 1e6, 1) for n in (dense, rl.mla_params(cfg) + sparse, rl.linear_params(cfg) + sparse)] == [
        632.2, 910.4, 986.3]
    assert round(rl.total_params(cfg) / 1e9, 2) == 4.73
    assert rl.state_bytes_per_row_and_layer(cfg) == 64 * 128 * 128 * 4 + 3 * 16384 * 2 == 4_292_608
    lcfg = drv.program_config(cfg, 5648)
    assert (lcfg.dim, lcfg.n_layers, lcfg.vocab_size, lcfg.n_experts, lcfg.experts_held, lcfg.moe_top_k,
            lcfg.first_dense_layers, lcfg.moe_ffn_dim, lcfg.ffn_dim, lcfg.kv_lora_rank, lcfg.v_head_dim) == (
        7168, 5, 16032, 256, (0, 16), 8, 1, 2048, 18432, 512, 128)
    assert lcfg.layer_types == ("linear_attention", "full_attention") + ("linear_attention",) * 3
    assert (lcfg.linear_key_heads, lcfg.linear_value_heads, lcfg.linear_key_head_dim,
            lcfg.linear_value_head_dim, lcfg.linear_conv_kernel) == (32, 64, 128, 128, 4)
    assert lcfg.moe_select_bias and lcfg.moe_scoring == "sigmoid" and lcfg.moe_routed_scale == 2.5
    assert lcfg.swiglu_limit == 10.0 and lcfg.gated_attention and lcfg.sandwich_norm and lcfg.norm_kind == "gated"
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"live_kv_tokens_mean": 96 * 3000.0, "experts_hit_mean": 15.2, "live_lanes_mean": 96.0}
    tick = rl.tick_least_seconds(cfg, cell, counters, peak)
    assert 0.0150 < tick < 0.0160                  # bytes bind: matrices, experts, state
    state = rl.linear_state_kernel(cfg, cell, counters, peak)
    assert state == pytest.approx(96 * 4 * 2 * 4_292_608 / 819e9) and 0.25 < state / tick < 0.27
    assert rl.tick_least_seconds(cfg, cell, {}, peak) == 0.0 and rl.linear_state_kernel(cfg, cell, {}, peak) == 0.0
    assert rl.expert_gmm_kernel(cfg, cell, counters, peak) == pytest.approx(2 * 4 * 15.2 * 44_040_192 / 819e9)
    assert 0.010 < rl.chunk_least_seconds(cfg, cell, counters, peak) < 0.012
    # the latent kernel reads one latent layer's rows, 576 bf16 numbers a token
    assert rl.attn_read_kernel(cfg, cell, counters, peak) == pytest.approx(96 * 3000 * 576 * 2 / 819e9)
    assert rl.attn_read_kernel(cfg, cell, {}, peak) == 0.0
