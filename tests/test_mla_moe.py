"""Latent attention and the sparse-expert layer against the plain reference
(``benchmarks/reference/mla_moe_decoder.py``, which imports nothing of the
program) at a small size on the CPU, float32, seeded weights: (a) the full
sequence, (b) chunked prefill and paged ticks, by logits and through the
engine, (c) absorbed against expanded, (d) YaRN, (e) the router, (f) the
shares of a layer add up to the uncut layer, (g) no drop under skew, (h) the
experts' counters."""

import copy
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import weights_mla_moe as weights  # noqa: E402
from drivers.serve_mla_moe import program_config  # noqa: E402
from reference import mla_moe_decoder as ref  # noqa: E402

from fedml_tpu.llm import mla, moe  # noqa: E402
from fedml_tpu.llm.model import (MLP, LlamaConfig, LlamaLM, YarnScaling,  # noqa: E402
                                 config_from_args, yarn_inv_freq)

TOL = 2e-5          # float32 against float32, relative to the tensor's scale
with open(os.path.join(BENCH, "tests", "tiny_mla_moe.json")) as f:
    TINY = json.load(f)
HELD = (TINY["experts_held"]["first"], TINY["experts_held"]["count"])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def uncut(cfg):
    cfg = copy.deepcopy(cfg)
    cfg["experts_held"] = None
    cfg["n_routed_experts"] = TINY["experts_held"]["of"]
    return cfg


@pytest.fixture(scope="module")
def setting():
    lcfg = program_config(TINY, 96, attn_impl="blockwise", remat="none")
    base, lora = weights.make_base(TINY, 5), weights.make_lora(TINY, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(2, 40)), jnp.int32)
    return lcfg, base, lora, tokens


# -- (a) the full sequence ---------------------------------------------------------

def test_layout_is_the_programs(setting):
    lcfg, base, lora, tokens = setting
    theirs = jax.eval_shape(LlamaLM(lcfg).init, jax.random.PRNGKey(0), tokens)
    assert weights.same_layout(base, theirs["params"]) == ""
    assert weights.same_layout(lora, theirs["lora"]) == ""
    assert lcfg.experts_held == HELD and lcfg.n_experts == 32 and lcfg.latent_attention
    assert [lcfg.sparse_layer(i) for i in range(3)] == [False, True, True]


def test_mla_module_full_sequence_agrees(setting):
    lcfg, base, lora, tokens = setting
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64))
    got = mla.MLA(lcfg).apply({"params": base["layer_1"]["attention"],
                               "lora": lora["layer_1"]["attention"]}, x, jnp.arange(40))
    with jax.default_matmul_precision("highest"):
        want = ref.mla(x, base["layer_1"]["attention"], lora["layer_1"]["attention"], TINY, None)
    assert rel(got, want) < TOL


@pytest.mark.parametrize("with_lora", [True, False], ids=["adapter", "zero-adapter"])
def test_whole_model_agrees(setting, with_lora):
    lcfg, base, lora, tokens = setting
    lo = lora if with_lora else jax.tree_util.tree_map(jnp.zeros_like, lora)
    got = LlamaLM(lcfg).apply({"params": base, "lora": lo}, tokens)
    want, margin = ref.logits(base, lo if with_lora else None, tokens, TINY, HELD)
    assert rel(got, want) < TOL
    assert np.isfinite(np.asarray(margin)).any() and float(jnp.min(margin)) >= 0


def test_uncut_model_agrees():
    """All 32 experts held: the same code, ``held=None``."""
    cfg = uncut(TINY)
    lcfg = program_config(cfg, 64, attn_impl="blockwise", remat="none")
    assert lcfg.experts_held is None
    base, lora = weights.make_base(cfg, 6), weights.make_lora(cfg, 6)
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 256, size=(1, 24)), jnp.int32)
    got = LlamaLM(lcfg).apply({"params": base, "lora": lora}, tokens)
    assert rel(got, ref.logits(base, lora, tokens, cfg)[0]) < TOL


def test_published_config_arrives_through_the_arguments(tmp_path):
    """A published config.json as a path: rope_theta, rms_norm_eps,
    rope_scaling and the new keys reach the configuration."""
    import types
    published = {k: v for k, v in TINY.items() if k not in (
        "name", "source", "architecture", "experts_held", "published", "reduced", "assumed",
        "compute_dtype", "weight_dtype", "lora")}
    published["n_routed_experts"] = 32
    path = tmp_path / "config.json"
    path.write_text(json.dumps(published))
    cfg = config_from_args(types.SimpleNamespace(
        model="llama", llm_config_json=str(path), llm_experts_held="8,8", llm_norm_eps=1e-5))
    assert (cfg.rope_theta, cfg.norm_eps, cfg.dim, cfg.n_layers) == (10000.0, 1e-5, 64, 3)
    assert cfg.rope_scaling == YarnScaling(factor=32, original_max_position_embeddings=16,
                                           beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (24, 16, 8, 8, 8)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.moe_ffn_dim, cfg.first_dense_layers,
            cfg.n_shared_experts, cfg.moe_scoring, cfg.moe_n_group, cfg.moe_topk_group,
            cfg.moe_norm_topk, cfg.moe_routed_scale, cfg.experts_held) == (
                32, 4, 16, 1, 1, "sigmoid", 4, 2, True, 2.5, (8, 8))
    # selection under a bias that is not in the gates is route's own rule
    assert config_from_args(types.SimpleNamespace(
        llm_config_json={**published, "topk_method": "noaux_tc"})).moe_select_bias
    assert not cfg.moe_select_bias
    with pytest.raises(ValueError, match="some_other_rule"):
        config_from_args(types.SimpleNamespace(llm_config_json={**published, "topk_method": "some_other_rule"}))


def test_int8_cache_with_latent_attention_raises_at_construction(setting):
    import dataclasses
    with pytest.raises(ValueError, match="not defined for latent attention"):
        dataclasses.replace(setting[0], kv_cache_dtype="int8")


# -- (b) chunked prefill, then paged ticks -------------------------------------------

def _kernel_in_interpret_mode(monkeypatch):
    """What ``MLA._paged_attend`` reads through where the program is lowered
    for a TPU, on the CPU: the kernel of ``ops/latent_attention.py`` in
    interpret mode, whatever the dtype."""
    monkeypatch.setattr(mla, "_read_pool", lambda cfg, *operands: mla.attend_pool(
        *operands, split=(cfg.kv_lora_rank, cfg.qk_nope_head_dim), interpret=True))


@pytest.mark.parametrize("chunk,kernel", [(8, False), (32, False), (32, True)],
                         ids=["absorbed-chunks", "expanded-chunks", "kernel-chunks-and-ticks"])
def test_paged_prefill_and_ticks_agree_by_logits(setting, chunk, kernel, monkeypatch):
    """Two requests on two adapters: each prompt goes into the latent pool
    chunk by chunk, then both decode in ONE batch, each slot on its own
    adapter; every position's logits against the reference's full forward.
    The third case reads the pool through the kernel (the absorbed form over
    each lane's own pages) in both programs: the wiring of ``_paged_attend``."""
    import dataclasses
    lcfg, base, _, _ = setting
    assert mla.absorbed_is_cheaper(lcfg, 8) and not mla.absorbed_is_cheaper(lcfg, 32)
    if kernel:
        _kernel_in_interpret_mode(monkeypatch)
    ptok, pages = 4, 40
    pm = LlamaLM(dataclasses.replace(lcfg, kv_page_tokens=ptok, kv_pool_pages=pages))
    loras = [weights.make_lora(TINY, 5, index=i + 1) for i in range(2)]
    rng = np.random.default_rng(7)
    seqs = [rng.integers(1, 256, size=n) for n in (45, 29)]
    prompts = (37, 21)
    max_blocks = 20
    btabs = np.zeros((2, max_blocks), np.int32)
    btabs[0, :12] = 1 + np.arange(12)
    btabs[1, :8] = 20 + np.arange(8)
    pool = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: pm.apply(
            {"params": base, "lora": loras[0]}, jnp.zeros((1, chunk), jnp.int32), decode=True,
            start_pos=jnp.zeros((1,), jnp.int32), block_tables=jnp.asarray(btabs[:1]),
            mutable=["cache"]))[1]["cache"])
    assert [p.shape for p in jax.tree_util.tree_leaves(pool)] == [(pages, ptok, 128)] * 3
    got = [np.zeros((len(s), 256)) for s in seqs]
    for r in range(2):
        for cs in range(0, prompts[r], chunk):
            seg = np.zeros((1, chunk), np.int32)
            real = seqs[r][cs:min(cs + chunk, prompts[r])]
            seg[0, :len(real)] = real
            logits, mut = pm.apply(
                {"params": base, "lora": loras[r], "cache": pool}, jnp.asarray(seg), decode=True,
                start_pos=jnp.asarray([cs], jnp.int32), block_tables=jnp.asarray(btabs[r:r + 1]),
                mutable=["cache"])
            pool = mut["cache"]
            got[r][cs:cs + len(real)] = np.asarray(logits[0, :len(real)])
    stacked = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), *loras)
    for t in range(8):
        poss = np.array([prompts[0] + t, prompts[1] + t], np.int32)
        toks = np.array([seqs[0][poss[0]], seqs[1][poss[1]]], np.int32)
        logits, mut = pm.apply(
            {"params": base, "lora": stacked, "cache": pool}, jnp.asarray(toks)[:, None],
            decode=True, start_pos=jnp.asarray(poss), block_tables=jnp.asarray(btabs),
            mutable=["cache"])
        pool = mut["cache"]
        for r in range(2):
            got[r][poss[r]] = np.asarray(logits[r, 0])
    for r in range(2):
        want, _ = ref.logits(base, loras[r], jnp.asarray(seqs[r])[None], TINY, HELD)
        assert rel(got[r], want[0]) < 2 * TOL


def test_engine_serves_two_adapters_and_counts(setting):
    """Through ``ContinuousBatchingEngine``: chunked prefill, ticks and the
    adapter bank; each served token is the reference's best (float32), and
    the counters the programs bring back are the reference's own count."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    model = LlamaLM(lcfg)
    eng = ContinuousBatchingEngine(model, base, slots=2, buf_len=96, adapter_slots=4,
                                   kv_page_tokens=4, prefill_chunk_tokens=32)
    try:
        loras = {f"a{i}": weights.make_lora(TINY, 5, index=i + 1) for i in range(2)}
        for name, tree in loras.items():
            eng.registry.register(name, tree)
        rng = np.random.default_rng(11)
        prompts = {n: [int(t) for t in rng.integers(1, 256, size=k)] for n, k in (("a0", 41), ("a1", 19))}
        queues = {n: eng.submit(p, max_new_tokens=9, adapter=n) for n, p in prompts.items()}
        outs = {}
        for n, q in queues.items():
            outs[n] = []
            while (t := q.get(timeout=300)) is not None:
                outs[n].append(t)
        stats = eng.kv_stats()
    finally:
        eng.stop()
    assert stats["kv_bytes_per_token"] == 3 * 128 * 4         # three layers, a row of 24 float32 in one lane tile
    assert stats["moe_layers_ticked"] in (2 * stats["ticks"], 2 * stats["ticks"] + 2)
    pairs = 0
    for n in prompts:
        assert len(outs[n]) == 9
        ids = jnp.asarray(prompts[n] + outs[n])[None]
        out = ref.forced_gaps(base, loras[n], ids, TINY, HELD)
        span = slice(len(prompts[n]) - 1, ids.shape[1] - 1)
        assert float(jnp.max((out["gap"] / out["spread"])[span])) < 1e-4
        pairs += _pairs_by_hand(base, loras[n], np.asarray(ids), len(prompts[n]), chunk=32)
    # every prompt position once in its chunks, the chunks' padding rows too
    # (the program computes them), and every decoded token but the last once
    # in a tick; a tick's idle lane repeats its last token and is counted
    assert stats["expert_pairs"] >= pairs > 0
    assert 0 < stats["experts_hit"] <= HELD[1] * stats["moe_layers_ticked"]


def test_attn_pages_counts_what_the_latent_kernel_visits(setting, monkeypatch):
    """The engine's two programs with the read forced through the kernel in
    interpret mode: every served token is still the reference's best, and
    ``attn_pages`` on the spans and in ``kv_stats()`` is what
    ``visited_pages`` counts, over the model's three layers, for the
    positions each chunk and tick of each request stood at.  Without the
    kernel (this process lowers for the CPU) the same spans read 0."""
    from fedml_tpu import obs
    from fedml_tpu.ops import latent_attention as la
    from fedml_tpu.ops import paged_attention as pa
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    lora = weights.make_lora(TINY, 5, index=1)
    rng = np.random.default_rng(3)
    requests = [([int(t) for t in rng.integers(1, 256, size=n)], m) for n, m in ((41, 9), (19, 6), (33, 7))]

    def serve():
        obs.configure(enabled=True, reset=True, jax_hooks=False)
        eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=2, buf_len=96, adapter_slots=2,
                                       kv_page_tokens=4, prefill_chunk_tokens=16)
        try:
            eng.registry.register("a0", lora)
            queues = [eng.submit(ids, max_new_tokens=m, adapter="a0") for ids, m in requests]
            outs = []
            for q in queues:
                outs.append([])
                while (t := q.get(timeout=300)) is not None:
                    outs[-1].append(t)
            spans = [e for e in obs.get_tracer().events()
                     if e["name"] in ("serve.tick", "serve.chunk") and e["ph"] == "E"]
            return outs, eng.kv_stats(), spans, eng.max_blocks
        finally:
            eng.stop()
            obs.configure(enabled=False)

    _, stats, spans, _ = serve()
    assert stats["attn_pages"] == 0 and spans
    assert all(e["args"]["attn_pages"] == 0 for e in spans)

    _kernel_in_interpret_mode(monkeypatch)
    monkeypatch.setattr(la, "engages", lambda *operands: True)
    outs, stats, spans, blocks = serve()
    for (ids, m), out in zip(requests, outs):
        assert len(out) == m
        got = ref.forced_gaps(base, lora, jnp.asarray(ids + out)[None], TINY, HELD)
        assert float(jnp.max((got["gap"] / got["spread"])[len(ids) - 1:len(ids) + m - 1])) < 1e-4

    def pages(pos):
        return lcfg.n_layers * pa.visited_pages(np.asarray(pos), np.ones(1, np.int64), window=0,
                                                ring=False, entries=blocks, ptok=4)

    # a prompt's chunks stand at 0, 16, ...; its ticks write n .. n + m - 2
    want = sum(sum(pages(cs + np.arange(16)[None]) for cs in range(0, len(ids), 16))
               + sum(pages([[p]]) for p in range(len(ids), len(ids) + m - 1))
               for ids, m in requests)
    assert stats["attn_pages"] == want > 0
    assert sum(e["args"]["attn_pages"] for e in spans) == want
    assert all(e["args"]["attn_pages"] > 0 for e in spans)


def _pairs_by_hand(base, lora, ids, n_prompt, chunk):
    """Pairs the held experts compute for the real positions of one request:
    the reference's routing, counted in numpy."""
    total = 0
    x = base["tok_embed"]["embedding"][ids].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(TINY["num_hidden_layers"]):
            layer = base[f"layer_{i}"]
            if "moe_mlp" in layer:
                h = x + ref.mla(ref.rms_norm(x, layer["attn_norm"]["scale"], 1e-6), layer["attention"],
                                lora[f"layer_{i}"]["attention"], TINY, None)
                hn = ref.rms_norm(h, layer["mlp_norm"]["scale"], 1e-6)[0]
                _, idx, _ = ref.route(hn, layer["moe_mlp"]["router"]["kernel"], TINY, HELD, None)
                idx = np.asarray(idx)[: ids.shape[1] - 1]
                total += int(((idx >= HELD[0]) & (idx < HELD[0] + HELD[1])).sum())
            x, _ = ref.block(x, layer, lora[f"layer_{i}"]["attention"], TINY, HELD, None)
    return total


# -- (c) absorbed against expanded ---------------------------------------------------

@pytest.mark.parametrize("rows", [1, 5])
def test_absorbed_form_equals_expanded_form(rows):
    h, rank, nope, rp, dv, b, w = 4, 16, 8, 8, 6, 3, 24
    ks = jax.random.split(jax.random.PRNGKey(rows), 5)
    q_nope = jax.random.normal(ks[0], (b, h, rows, nope))
    q_rope = jax.random.normal(ks[1], (b, h, rows, rp))
    window = jnp.pad(jax.random.normal(ks[2], (b, w, rank + rp)), ((0, 0), (0, 0), (0, 8)))
    w_kvb = jax.random.normal(ks[3], (rank, h, nope + dv)) * rank ** -0.5
    pos = jax.random.randint(ks[4], (b, rows), 2, w)
    args = (q_nope, q_rope, window, w_kvb, pos, 0.3, (rank, nope))
    a, e = mla.attend_absorbed(*args), mla.attend_expanded(*args)
    assert a.shape == (b, h, rows, dv)
    assert rel(a, e) < TOL


# -- (d) YaRN ---------------------------------------------------------------------------

def test_yarn_frequencies_and_scale_by_hand():
    """The published A.X-K1 numbers: rotary width 64, theta 1e4, factor 32
    over 4096 original positions, beta 32 and 1.  Pair i turns
    4096 / (2 pi 1e4^(2i/64)) times in the original context: more than 32
    up to pair 10 (floor of 10.47), fewer than 1 from pair 23 (ceil of 22.5)."""
    y = YarnScaling(factor=32, original_max_position_embeddings=4096, beta_fast=32, beta_slow=1,
                    mscale=1, mscale_all_dim=1)
    f = np.asarray(yarn_inv_freq(64, 10000.0, y), np.float64)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4)) == pytest.approx(10.47, abs=0.01)
    assert 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(1e4)) == pytest.approx(22.52, abs=0.01)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)           # kept
    np.testing.assert_allclose(f[23:], plain[23:] / 32, rtol=1e-6)      # slowed 32 times
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(f[16], plain[16] * (1 - ramp) + plain[16] / 32 * ramp, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.inv_freq(64, 10000.0, TINY["rope_scaling"] | {
        "original_max_position_embeddings": 4096})), f, rtol=1e-6)
    cfg = LlamaConfig(q_lora_rank=1, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, rope_scaling=y)
    m = 0.1 * math.log(32) + 1
    assert m == pytest.approx(1.34657, abs=1e-5)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert mla.softmax_scale(cfg) == pytest.approx(0.130861, abs=1e-6)
    assert mla.latent_width(cfg) == 576 and mla.pool_row_width(cfg) == 640 and mla.absorbed_is_cheaper(cfg, 1) \
        and mla.absorbed_is_cheaper(cfg, 170) and not mla.absorbed_is_cheaper(cfg, 171)


# -- (e) the router ----------------------------------------------------------------------

def test_router_groups_topk_normalisation_and_scale_by_hand():
    """Eight experts in four groups of two, the two best groups stay, three
    experts a token.  Group scores (sums of the two best, here of both):
    0.9+0.1, 0.5+0.45, 0.6+0.3, 0.2+0.7: groups 0 and 1 stay although expert
    7 (0.7) and 4 (0.6) score above most of theirs."""
    s = jnp.asarray([[0.9, 0.1, 0.5, 0.45, 0.6, 0.3, 0.2, 0.7]])
    gates, idx = moe.route(s, top_k=3, n_group=4, topk_group=2, norm_topk=True, scale=2.5)
    assert idx.tolist() == [[0, 2, 3]]
    np.testing.assert_allclose(gates[0], 2.5 * np.array([0.9, 0.5, 0.45]) / 1.85, rtol=1e-6)
    plain, pidx = moe.route(s, top_k=3, n_group=4, topk_group=4, norm_topk=False, scale=1.0)
    assert pidx.tolist() == [[0, 7, 4]]                      # every group stays: plain top-k
    np.testing.assert_allclose(plain[0], [0.9, 0.7, 0.6], rtol=1e-6)
    assert moe.route(s, 3)[1].tolist() == [[0, 7, 4]]        # one group: the same


def test_router_agrees_with_the_reference(setting):
    _, base, _, _ = setting
    w = base["layer_1"]["moe_mlp"]["router"]["kernel"]
    hn = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    with jax.default_matmul_precision("highest"):
        want_g, want_i, margin = ref.route(hn, w, TINY, HELD, None)
        got_g, got_i = moe.route(jax.nn.sigmoid(hn @ w), 4, 4, 2, True, 2.5)
    assert (np.asarray(got_i) == np.asarray(want_i)).all()
    assert rel(got_g, want_g) < TOL
    np.testing.assert_allclose(np.asarray(got_g).sum(-1), 2.5, rtol=1e-5)
    # two of four groups of eight: every token's experts lie in two groups
    assert all(len(set(row // 8)) <= 2 for row in np.asarray(got_i))
    assert (np.asarray(margin) > 0).all()


# -- (f) the shares of a layer add up to the uncut layer ---------------------------------

def test_shares_add_up_to_the_uncut_layer():
    """E = 32 over 4 shares: the routed parts that the four shares compute,
    with the shared expert counted once, are the uncut reference layer."""
    cfg = uncut(TINY)
    whole = weights.make_base(cfg, 9)["layer_1"]
    hn = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 64))
    with jax.default_matmul_precision("highest"):
        routed, _ = ref.experts(hn[0], whole, cfg, None, None)
        shared = ref.swiglu(hn[0], {n: whole["shared_expert"][n]["kernel"]
                                    for n in ("w_gate", "w_up", "w_down")}, None)
    lcfg = program_config(cfg, 64)
    total = MLP(lcfg, width=16).apply({"params": whole["shared_expert"]}, hn)[0]
    for share in range(4):
        part_cfg = dict(cfg, experts_held={"first": 8 * share, "count": 8, "of": 32})
        part = weights.make_base(part_cfg, 9)["layer_1"]["moe_mlp"]
        for name in ("w_gate", "w_up", "w_down"):      # a share's weights are a slice of the whole
            assert (part[name] == whole["moe_mlp"][name][8 * share:8 * share + 8]).all()
        layer = moe.MoEMLP(dim=64, ffn_dim=16, n_experts=32, top_k=4, scoring="sigmoid", n_group=4,
                           topk_group=2, routed_scale=2.5, held=(8 * share, 8))
        total = total + layer.apply({"params": part}, hn)[0]
        # and each share is the reference's share
        with jax.default_matmul_precision("highest"):
            want, _ = ref.experts(hn[0], dict(whole, moe_mlp=part), part_cfg, (8 * share, 8), None)
        assert rel(layer.apply({"params": part}, hn)[0], want) < TOL
    assert rel(total, routed + shared) < TOL


# -- (g) no drop under skew; (h) the counters ------------------------------------------

def test_every_token_to_one_expert_still_equals_the_reference():
    cfg = uncut(TINY)
    layer_w = weights.make_base(cfg, 3)["layer_1"]
    router = jnp.zeros((64, 32)).at[0].set(-8.0).at[0, jnp.asarray([3, 9, 17, 28])].set(8.0)
    layer_w = dict(layer_w, moe_mlp=dict(layer_w["moe_mlp"], router={"kernel": router}))
    hn = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 64)).at[..., 0].set(4.0)
    layer = moe.MoEMLP(dim=64, ffn_dim=16, n_experts=32, top_k=4, scoring="sigmoid", n_group=4,
                       topk_group=4, routed_scale=2.5)
    got, state = layer.apply({"params": layer_w["moe_mlp"]}, hn, mutable=[moe.COUNTERS])
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(hn[0], layer_w, dict(cfg, topk_group=4), None, None)
    assert rel(got[0], want) < TOL
    # (h) by hand: 40 tokens, 4 experts each, all on the same four
    assert np.asarray(state[moe.COUNTERS]["layer"][0]).tolist() == [160, 4, 40, 0]


def test_counters_of_a_share_by_hand():
    """Six tokens, two experts each of eight, experts 2..4 held."""
    experts = jnp.asarray([[0, 2], [2, 3], [3, 7], [2, 4], [5, 6], [2, 1]])
    gates = jnp.ones((6, 2)) * 0.5
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 8))
    w = [jax.random.normal(jax.random.PRNGKey(i), s) for i, s in enumerate(((3, 8, 4), (3, 8, 4), (3, 4, 8)))]
    out, sizes, tiles = moe.expert_ffn(x, gates, experts, *w, 2)
    assert sizes.tolist() == [4, 2, 1] and int(tiles) == 0   # ragged_dot: no kernel's tiles                       # expert 2: tokens 0, 1, 3, 5
    want = np.zeros((6, 8))
    for n in range(6):
        for e in np.asarray(experts[n]):
            if 2 <= e <= 4:
                g, u = x[n] @ w[0][e - 2], x[n] @ w[1][e - 2]
                want[n] += 0.5 * np.asarray((jax.nn.silu(g) * u) @ w[2][e - 2])
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
    assert (np.asarray(out)[4] == 0).all()                   # token 4 chose no held expert
