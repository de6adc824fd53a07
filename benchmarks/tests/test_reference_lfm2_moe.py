"""At a tiny size on the CPU the plain reference of the ``lfm2_moe`` decoder
and ``LlamaLM`` agree on logits in float32; a bfloat16 run of the program, and
the int8 control, fall outside the tolerance that holds them; the selection
bias changes the experts chosen on a share of the tokens; the rooflines count
what the issue reckoned for the real configuration.  (The layer-by-layer cases
and the engine's are tier-1: ``tests/test_lfm2_moe.py``.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH

import weights_lfm2_moe as weights
from drivers import serve_lfm2_moe as drv
from reference import lfm2_moe_decoder as ref

TOL = 2e-5
with open(os.path.join(BENCH, "tests", "tiny_lfm2_moe.json")) as f:
    TINY = json.load(f)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def setting():
    lcfg = drv.program_config(TINY, 96, attn_impl="blockwise", remat="none")
    base, lora = weights.make_base(TINY, 5), weights.make_lora(TINY, 5)
    x = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(2, 72)), jnp.int32)
    return lcfg, base, lora, x


def test_float32_program_agrees_and_bfloat16_falls_outside(setting):
    from fedml_tpu.llm.model import LlamaLM
    lcfg, base, lora, x = setting
    theirs = jax.eval_shape(LlamaLM(lcfg).init, jax.random.PRNGKey(0), x)
    assert weights.same_layout(base, theirs["params"]) == "" and weights.same_layout(lora, theirs["lora"]) == ""
    want, _ = ref.logits(base, lora, x, TINY)
    assert rel(LlamaLM(lcfg).apply({"params": base, "lora": lora}, x), want) < TOL
    low = dataclasses.replace(lcfg, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    assert rel(LlamaLM(low).apply({"params": base, "lora": lora}, x), want) > 10 * TOL


def test_int8_control_falls_outside_and_the_tail_is_the_whole(setting):
    _, base, lora, x = setting
    want, margin = ref.logits(base, lora, x, TINY)
    low, _ = ref.logits(base, lora, x, TINY, quant="int8")
    assert rel(low, want) > 100 * TOL
    out = ref.forced_gaps(base, lora, x[:1], 40, 24, TINY, quant="int8")
    rows = np.asarray(want[0, 40:64])
    best = rows.max(-1)
    nxt = rows[np.arange(24), np.asarray(x[0, 41:65])]
    assert rel(out["gap"], best - nxt) < TOL and rel(out["spread"], best - np.median(rows, -1)) < TOL
    assert np.array_equal(np.asarray(out["margin"]), np.asarray(margin[0, 40:64]))
    assert float(jnp.max(out["control_gap"])) > 0


def test_bfloat16_witness_lies_between_the_reference_and_the_control(setting):
    _, base, lora, x = setting
    want, _ = ref.logits(base, lora, x, TINY)
    own, _ = ref.logits(base, lora, x, TINY, quant="bfloat16")
    low, _ = ref.logits(base, lora, x, TINY, quant="int8")
    assert 10 * TOL < rel(own, want) < rel(low, want)
    out = ref.forced_gaps(base, lora, x[:1], 40, 24, TINY, quant=("int8", "bfloat16"))
    first = np.asarray(own[0, 40:64]).argmax(-1)
    rows = np.asarray(want[0, 40:64])
    assert np.allclose(out["witness_gap"], rows.max(-1) - rows[np.arange(24), first], atol=1e-5)
    assert set(out) == {"gap", "spread", "margin", "control_gap", "witness_gap"}
    with pytest.raises(ValueError, match="unknown quant"):
        ref.logits(base, lora, x, TINY, quant="fp8")


def test_the_bias_changes_the_selection_and_the_margin_is_over_the_biased_scores(setting):
    _, base, lora, x = setting
    moved = np.asarray(ref.bias_changed(base, lora, x, TINY))
    assert moved.shape == (4, 2, 72) and 0.02 < moved.mean() < 0.5
    # without the bias: nothing changes, and the margins are other margins
    flat = jax.tree_util.tree_map_with_path(
        lambda path, p: jnp.zeros_like(p) if path[-1].key == "select_bias" else p, base)
    assert not np.asarray(ref.bias_changed(flat, lora, x, TINY)).any()
    _, with_bias = ref.logits(base, lora, x, TINY)
    _, without = ref.logits(flat, lora, x, TINY)
    assert not np.allclose(np.asarray(with_bias), np.asarray(without))


def test_the_real_configuration_is_what_the_issue_reckoned():
    from rooflines import lfm2_moe as rl
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b-d13.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads", "serve-chat-512.lfm2-8b-a1b-d13.json")) as f:
        cell = json.load(f)
    assert rl.expert_params(cfg) == 3 * 2048 * 1792 == 11_010_048
    assert rl.conv_params(cfg) == 2048 * 6144 + 2048 * 2048 == 16_777_216
    assert rl.attention_params(cfg) == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    sparse_conv = 16_777_216 + 2048 * 3 + 32 * 11_010_048 + 2048 * 32 + 32 + 2 * 2048
    sparse_attn = 10_485_760 + 2 * 64 + 32 * 11_010_048 + 2048 * 32 + 32 + 2 * 2048
    dense_conv = 16_777_216 + 2048 * 3 + 3 * 2048 * 7168 + 2 * 2048
    assert [round(n / 1e6, 1) for n in (sparse_conv, sparse_attn, dense_conv)] == [369.2, 362.9, 60.8]
    assert rl.total_params(cfg) == dense_conv + 9 * sparse_conv + 3 * sparse_attn + 2048 * 65536 + 2048
    assert round(rl.total_params(cfg) / 1e9, 2) == 4.61
    assert rl.kv_bytes_per_token_and_layer(cfg) == 2048 and rl.state_bytes_per_row_and_layer(cfg) == 8192
    assert rl.lora_params(cfg) == 3 * 16 * (2 * (2048 + 2048) + 2 * (2048 + 512)) \
        + 10 * 16 * ((2048 + 6144) + (2048 + 2048))
    lcfg = drv.program_config(cfg, 736)
    assert (lcfg.dim, lcfg.n_layers, lcfg.vocab_size, lcfg.n_heads, lcfg.n_kv_heads, lcfg.ffn_dim,
            lcfg.moe_ffn_dim, lcfg.n_experts, lcfg.moe_top_k, lcfg.first_dense_layers, lcfg.conv_kernel,
            lcfg.rope_theta, lcfg.norm_eps, lcfg.moe_scoring, lcfg.experts_held) == (
        2048, 13, 65536, 32, 8, 7168, 1792, 32, 4, 1, 3, 1e6, 1e-5, "sigmoid", None)
    assert lcfg.layer_types == ("conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
                                "conv", "conv", "conv", "full_attention", "conv", "conv")
    assert lcfg.qk_norm and lcfg.moe_select_bias and lcfg.tie_embeddings and lcfg.moe_norm_topk
    assert lcfg.conv_layers == 10 and not lcfg.windowed and lcfg.n_shared_experts == 0
    # every published width is the catalog's
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["conv_L_cache"], cfg["vocab_size"], len(cfg["layer_types"])) == (
        2048, 32, 8, 1792, 7168, 32, 4, 3, 65536, 24)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"live_kv_tokens_mean": 35e3, "experts_hit_mean": 32.0}
    tick = rl.tick_least_seconds(cfg, cell, counters, peak)
    assert 0.0110 < tick < 0.0118                  # bytes bind: the 9.2 GB of matrices
    fewer = rl.tick_least_seconds(cfg, cell, dict(counters, experts_hit_mean=31.0), peak)
    assert tick - fewer == pytest.approx(2 * 12 * 11_010_048 / 819e9)
    assert rl.tick_least_seconds(cfg, cell, {}, peak) == 0.0
    assert 0.0110 < rl.chunk_least_seconds(cfg, cell, counters, peak) < 0.0120      # bytes bind here too
    assert rl.forward_flops_per_token(cfg, cell) == 2.0 * (
        rl.fixed_matmul_params(cfg) + 12 * 4 * 11_010_048 + rl.lora_params(cfg))
