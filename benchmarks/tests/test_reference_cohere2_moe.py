"""At a tiny size on the CPU the plain reference of the ``cohere2_moe`` decoder
and ``LlamaLM`` agree on logits in float32; a bfloat16 run of the program, and
the int8 control, fall outside the tolerance that holds them; the rooflines
count what the issue reckoned for the real configuration.  (The layer-by-layer
cases are tier-1: ``tests/test_cohere2_moe.py``.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH

import weights_cohere2_moe as weights
from drivers import serve_cohere2_moe as drv
from reference import cohere2_moe_decoder as ref

TOL = 2e-5
with open(os.path.join(BENCH, "tests", "tiny_cohere2_moe.json")) as f:
    TINY = json.load(f)
HELD = (TINY["experts_held"]["first"], TINY["experts_held"]["count"])


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
    want, _ = ref.logits(base, lora, x, TINY, HELD)
    assert rel(LlamaLM(lcfg).apply({"params": base, "lora": lora}, x), want) < TOL
    low = dataclasses.replace(lcfg, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    assert rel(LlamaLM(low).apply({"params": base, "lora": lora}, x), want) > 10 * TOL


def test_int8_control_falls_outside_and_the_tail_is_the_whole(setting):
    _, base, lora, x = setting
    want, margin = ref.logits(base, lora, x, TINY, HELD)
    low, _ = ref.logits(base, lora, x, TINY, HELD, quant="int8")
    assert rel(low, want) > 100 * TOL
    # the tail the comparison reads is a slice of the whole forward
    out = ref.forced_gaps(base, lora, x[:1], 40, 24, TINY, HELD, quant="int8")
    rows = np.asarray(want[0, 40:64])
    best = rows.max(-1)
    nxt = rows[np.arange(24), np.asarray(x[0, 41:65])]
    assert rel(out["gap"], best - nxt) < TOL and rel(out["spread"], best - np.median(rows, -1)) < TOL
    assert np.array_equal(np.asarray(out["margin"]), np.asarray(margin[0, 40:64]))
    assert float(jnp.max(out["control_gap"])) > 0


def test_row_blocks_of_the_feed_forward_change_nothing(setting, monkeypatch):
    """144 rows in blocks of 32 (the last one padded) against all at once."""
    _, base, lora, x = setting
    want, margin = ref.logits(base, lora, x, TINY, HELD)
    monkeypatch.setattr(ref, "ROW_BLOCK", 32)
    ref._logits_jit.cache_clear()
    try:
        got, again = ref.logits(base, lora, x, TINY, HELD)
    finally:
        ref._logits_jit.cache_clear()
    assert rel(got, want) < 2e-6 and np.allclose(np.asarray(margin), np.asarray(again), rtol=1e-4, atol=1e-6)


def test_two_class_requests_hold_every_block_alike():
    import traffic_two_class
    mix = {"requests": 256, "block": 16,
           "classes": [{"name": "short", "per_block": 12, "prompt": {"lo": 896, "hi": 1152}},
                       {"name": "long", "per_block": 4, "prompt": {"lo": 10752, "hi": 13824}}],
           "answer": {"lo": 224, "hi": 288}, "adapters": {"count": 16, "power_a": 1.0}, "stagger_first": 54}
    sets, orders = [], set()
    for seed in (3, 5, 7, 2 ** 31 + 11):
        r = traffic_two_class.Requests(mix, 32768, seed)
        # the seed draws a block's order, and every block has it: any 16 in a row hold 4 long
        assert (r.kinds.reshape(16, 16) == r.kinds[:16]).all() and r.kinds[:16].sum() == 4
        assert (np.convolve(np.tile(r.kinds, 2), np.ones(16, int), "valid") == 4).all()
        orders.add(tuple(r.kinds[:16]))
        long_, short = r.prompts[r.kinds == 1], r.prompts[r.kinds == 0]
        assert (long_.min(), long_.max(), long_.mean()) == (10776, 13800, 12288.0)
        assert short.min() >= 896 and short.max() <= 1152 and short.mean() == 1024.0
        # every block holds one long prompt from each quarter of the range
        quarters = np.sort((long_.reshape(16, 4) - 10752) // 769, axis=1)
        assert (quarters == np.arange(4)).all()
        sets.append((np.sort(r.prompts), np.sort(r.answers)))
        q = r[300]
        assert q["class"] in ("short", "long") and len(q["prompt_ids"]) == r.prompts[300 % 256]
        assert r[3]["max_tokens"] <= r.answers[3]          # the first callers ask for a part
    assert all((a == sets[0][0]).all() and (b == sets[0][1]).all() for a, b in sets)   # one set for every seed
    assert len(orders) == 4                                 # and an order of its own


def test_the_real_configuration_is_what_the_issue_reckoned():
    from rooflines import cohere2_moe as rl
    with open(os.path.join(BENCH, "configs", "command-a-plus-ep8-d4.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads", "serve-mixed-12k.command-a-plus-ep8-d4.json")) as f:
        cell = json.load(f)
    # q 4096x16384, k and v 4096x1024, o 16384x4096
    assert rl.attention_params(cfg) == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert rl.expert_params(cfg) == 3 * 4096 * 4096 == 50_331_648
    layer = 142_606_336 + 4 * 50_331_648 + 4096 * 128 + 16 * 50_331_648
    assert round(layer / 1e6, 1) == 1149.8
    assert rl.fixed_matmul_params(cfg) + rl.held_expert_params(cfg) == 4 * layer + 4096 * 32768
    assert round(rl.total_params(cfg) / 1e9, 2) == 4.73
    assert rl.lora_params(cfg) == 4 * 16 * (2 * (4096 + 16384) + 2 * (4096 + 1024))
    assert rl.kv_bytes_per_token_and_layer(cfg) == 4096
    lcfg = drv.program_config(cfg, 14112)
    assert (lcfg.dim, lcfg.n_layers, lcfg.vocab_size, lcfg.n_heads, lcfg.n_kv_heads, lcfg.head_dim,
            lcfg.ffn_dim, lcfg.n_experts, lcfg.experts_held, lcfg.moe_top_k, lcfg.n_shared_experts,
            lcfg.shared_expert_scale, lcfg.sliding_window, lcfg.layer_types) == (
        4096, 4, 32768, 128, 8, 128, 4096, 128, (0, 16), 8, 4, 0.25, 4096,
        ("sliding_attention",) * 3 + ("full_attention",))
    assert lcfg.parallel_block and lcfg.tie_embeddings and not lcfg.rope_full_layers and lcfg.norm_kind == "layer"
    # every published width is the catalog's
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["num_shared_experts"]) == (4096, 4096, 128, 128, 8, 8, 4096, 4)
    # the rooflines: a window layer's context is capped at the window
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    chunks = rl.chunk_contexts(cfg, cell)
    assert len(chunks) == 1 + 12 and max(c[3] for c in chunks) == 4096 + 1023
    assert max(c[4] for c in chunks) == 1024 * 4096 and chunks[-1][2] == 1024 * 11264 + 1024 * 1025 / 2
    counters = {"live_kv_tokens_mean": 120e3, "live_window_tokens_mean": 80e3, "experts_hit_mean": 15.0}
    tick = rl.tick_least_seconds(cfg, cell, counters, peak)
    assert 0.012 < tick < 0.016                    # bytes bind: 9.2 GB of matrices, 1.5 GB of live K/V
    more = rl.tick_least_seconds(cfg, cell, dict(counters, live_window_tokens_mean=120e3), peak)
    assert more - tick == pytest.approx(3 * 40e3 * 4096 / 819e9)
    assert rl.tick_least_seconds(cfg, cell, {}, peak) == 0.0
    assert 0.02 < rl.chunk_least_seconds(cfg, cell, counters, peak) < 0.03      # operations bind
    assert rl.forward_flops_per_token(cfg, cell) == 2.0 * (
        rl.fixed_matmul_params(cfg) + 4 * 50_331_648 + rl.lora_params(cfg))
