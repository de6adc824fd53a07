"""At a tiny size on the CPU the plain reference of the latent-attention,
sparse-expert decoder and ``LlamaLM`` agree on logits in float32; a bfloat16
run of the program, and the int8 control, fall outside the tolerance that holds
them.  (The layer-by-layer cases (a)-(h) are tier-1: ``tests/test_mla_moe.py``.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH

import weights_mla_moe as weights
from drivers import serve_mla_moe as drv
from reference import mla_moe_decoder as ref

TOL = 2e-5
with open(os.path.join(BENCH, "tests", "tiny_mla_moe.json")) as f:
    TINY = json.load(f)
HELD = (TINY["experts_held"]["first"], TINY["experts_held"]["count"])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def setting():
    lcfg = drv.program_config(TINY, 64, attn_impl="blockwise", remat="none")
    base, lora = weights.make_base(TINY, 5), weights.make_lora(TINY, 5)
    x = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(2, 48)), jnp.int32)
    return lcfg, base, lora, x


def test_layout_is_the_programs(setting):
    from fedml_tpu.llm.model import LlamaLM
    lcfg, base, lora, x = setting
    theirs = jax.eval_shape(LlamaLM(lcfg).init, jax.random.PRNGKey(0), x)
    assert weights.same_layout(base, theirs["params"]) == ""
    assert weights.same_layout(lora, theirs["lora"]) == ""


def test_float32_program_agrees_and_bfloat16_falls_outside(setting):
    from fedml_tpu.llm.model import LlamaLM
    lcfg, base, lora, x = setting
    want, _ = ref.logits(base, lora, x, TINY, HELD)
    assert rel(LlamaLM(lcfg).apply({"params": base, "lora": lora}, x), want) < TOL
    low = dataclasses.replace(lcfg, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    assert rel(LlamaLM(low).apply({"params": base, "lora": lora}, x), want) > 10 * TOL


def test_int8_control_falls_outside(setting):
    _, base, lora, x = setting
    want, _ = ref.logits(base, lora, x, TINY, HELD)
    low, _ = ref.logits(base, lora, x, TINY, HELD, quant="int8")
    assert rel(low, want) > 100 * TOL


def test_the_real_configuration_is_what_the_issue_reckoned():
    from rooflines import mla_moe
    with open(os.path.join(BENCH, "configs", "a.x-k1-ep16-d7.json")) as f:
        cfg = json.load(f)
    # q_a 7168x1536, q_b 1536x12288, kv_a 7168x576, kv_b 512x16384, o 8192x7168
    assert mla_moe.mla_params(cfg) == 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    assert mla_moe.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    sparse = mla_moe.mla_params(cfg) + 13 * 44_040_192 + 7168 * 192
    dense = mla_moe.mla_params(cfg) + 3 * 7168 * 18432
    matrices = dense + 6 * sparse + 2 * 7168 * 20480
    assert mla_moe.fixed_matmul_params(cfg) + mla_moe.held_expert_params(cfg) + 7168 * 20480 == matrices
    assert round(mla_moe.total_params(cfg) / 1e9, 2) == 4.84
    assert mla_moe.lora_params(cfg) == 7 * 16 * (7168 + 1536 + 1536 + 12288 + 7168 + 576 + 8192 + 7168)
    assert mla_moe.latent_bytes_per_token(cfg) == 7 * 576 * 2 == 8064
    lcfg = drv.program_config(cfg, 2896)
    assert (lcfg.dim, lcfg.n_layers, lcfg.vocab_size, lcfg.n_experts, lcfg.experts_held,
            lcfg.moe_top_k, lcfg.kv_lora_rank, lcfg.q_lora_rank, lcfg.ffn_dim, lcfg.moe_ffn_dim,
            lcfg.norm_eps, lcfg.rope_theta, lcfg.lora_rank) == (
                7168, 7, 20480, 192, (0, 12), 8, 512, 1536, 18432, 2048, 1e-6, 10000.0, 16)
    cell = {"engine": {"slots": 64, "prefill_chunk_tokens": 512},
            "traffic": {"adapters": {"count": 16}, "prompt": {"lo": 1536, "hi": 2560}}}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = mla_moe.tick_least_seconds(cfg, cell, {"live_kv_tokens_mean": 139000, "experts_hit_mean": 11.2}, peak)
    assert 0.012 < least < 0.0135                       # the issue's 12.8 ms
    assert mla_moe.tick_least_seconds(cfg, cell, {}, peak) == 0.0
