"""Window and full attention layers in one stack, a parallel block, averaged
shared experts and a tied head against the plain reference
(``benchmarks/reference/cohere2_moe_decoder.py``, which imports nothing of the
program) at a small size on the CPU, float32, seeded weights: (a) the full
sequence and what ``config_from_published`` reads and refuses, (b) the window
in every form of the attention, (c) chunked prefill and ticks through the two
pools, by logits and through the engine, (d) pages taken back behind the
window, (e) the shares of a layer add up to the uncut layer."""

import copy
import dataclasses
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import weights_cohere2_moe as weights  # noqa: E402
from drivers.serve_cohere2_moe import PUBLISHED, program_config  # noqa: E402
from reference import cohere2_moe_decoder as ref  # noqa: E402

from fedml_tpu.llm import model as M  # noqa: E402
from fedml_tpu.llm import moe  # noqa: E402
from fedml_tpu.llm.model import (MLP, LayerNorm, LlamaConfig, LlamaLM,  # noqa: E402
                                 config_from_args, config_from_published)
from fedml_tpu.ops.attention import (blockwise_attention,  # noqa: E402
                                     flash_attention_bwd_pallas,
                                     flash_attention_fwd_pallas)

TOL = 2e-5          # float32 against float32, relative to the tensor's scale
with open(os.path.join(BENCH, "tests", "tiny_cohere2_moe.json")) as f:
    TINY = json.load(f)
HELD = (TINY["experts_held"]["first"], TINY["experts_held"]["count"])
W = TINY["sliding_window"]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def uncut(cfg):
    cfg = copy.deepcopy(cfg)
    cfg["experts_held"] = None
    cfg["num_experts"] = TINY["experts_held"]["of"]
    return cfg


def published(cfg=TINY, **changed):
    out = {k: cfg[k] for k in PUBLISHED if k in cfg}
    out["num_experts"] = TINY["experts_held"]["of"]
    return {**out, **changed}


@pytest.fixture(scope="module")
def setting():
    lcfg = program_config(TINY, 160, attn_impl="blockwise", remat="none")
    base, lora = weights.make_base(TINY, 5), weights.make_lora(TINY, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(2, 80)), jnp.int32)
    return lcfg, base, lora, tokens


# -- (a) the full sequence, and the configuration ------------------------------------

def test_layout_and_configuration_are_the_programs(setting):
    lcfg, base, lora, tokens = setting
    theirs = jax.eval_shape(LlamaLM(lcfg).init, jax.random.PRNGKey(0), tokens)
    assert weights.same_layout(base, theirs["params"]) == ""
    assert weights.same_layout(lora, theirs["lora"]) == ""
    assert "lm_head" not in theirs["params"] and "mlp_norm" not in theirs["params"]["layer_0"]
    assert lcfg.mixed_attention and lcfg.parallel_block and lcfg.tie_embeddings
    assert (lcfg.norm_kind, lcfg.head_dim, lcfg.sliding_window, lcfg.shared_expert_scale) == (
        "layer", 16, W, 0.5)
    assert [lcfg.layer_window(i) for i in range(4)] == [W, W, W, 0]
    assert [lcfg.layer_rope(i) for i in range(4)] == [True, True, True, False]
    assert (lcfg.n_experts, lcfg.moe_top_k, lcfg.moe_scoring, lcfg.experts_held,
            lcfg.n_shared_experts, lcfg.moe_ffn_dim or lcfg.ffn_dim) == (16, 3, "sigmoid", HELD, 2, 32)


@pytest.mark.parametrize("with_lora", [True, False], ids=["adapter", "zero-adapter"])
def test_whole_model_agrees(setting, with_lora):
    """80 positions against a window of 24: most queries see a window only."""
    lcfg, base, lora, tokens = setting
    lo = lora if with_lora else jax.tree_util.tree_map(jnp.zeros_like, lora)
    got = LlamaLM(lcfg).apply({"params": base, "lora": lo}, tokens)
    want, margin = ref.logits(base, lo if with_lora else None, tokens, TINY, HELD)
    assert rel(got, want) < TOL
    assert np.isfinite(np.asarray(margin)).any() and float(jnp.min(margin)) >= 0


def test_uncut_model_agrees_under_remat_and_flash_falls_back():
    """All 16 experts held: the same code, ``held=None``; the trainer's
    forward (remat, ``attn_impl`` flash, which off the TPU is the scan)."""
    cfg = uncut(TINY)
    lcfg = program_config(cfg, 64, attn_impl="flash", remat="full")
    assert lcfg.experts_held is None
    base, lora = weights.make_base(cfg, 6), weights.make_lora(cfg, 6)
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 256, size=(1, 40)), jnp.int32)
    got = LlamaLM(lcfg).apply({"params": base, "lora": lora}, tokens)
    assert rel(got, ref.logits(base, lora, tokens, cfg)[0]) < TOL


def test_window_mask_dropped_in_one_layer_is_seen(setting):
    """The comparison is tight enough for the mask: layer 1 as a full layer
    falls outside the tolerance."""
    lcfg, base, lora, tokens = setting
    kinds = list(lcfg.layer_types)
    kinds[1] = "full_attention"
    wrong = dataclasses.replace(lcfg, layer_types=tuple(kinds), rope_full_layers=True)
    want, _ = ref.logits(base, lora, tokens, TINY, HELD)
    assert rel(LlamaLM(wrong).apply({"params": base, "lora": lora}, tokens), want) > 100 * TOL


def test_layer_norm_tied_head_and_logit_scale_by_hand(setting):
    lcfg, base, lora, tokens = setting
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 64)) * 3 + 1
    scale = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (64,))
    got = LayerNorm(1e-5).apply({"params": {"scale": scale}}, x)
    xc = np.asarray(x, np.float64) - np.asarray(x, np.float64).mean(-1, keepdims=True)
    want = xc / np.sqrt((xc ** 2).mean(-1, keepdims=True) + 1e-5) * np.asarray(scale)
    assert rel(got, want) < TOL
    plain = LlamaLM(lcfg).apply({"params": base, "lora": lora}, tokens[:, :16])
    scaled = LlamaLM(dataclasses.replace(lcfg, logit_scale=0.25)).apply(
        {"params": base, "lora": lora}, tokens[:, :16])
    assert rel(scaled, 0.25 * plain) < 1e-6
    hidden = LlamaLM(lcfg).apply({"params": base, "lora": lora}, tokens[:, :16], return_hidden=True)
    assert rel(plain, hidden @ base["tok_embed"]["embedding"].T) < TOL


def test_published_config_arrives_through_the_arguments(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(published()))
    cfg = config_from_args(types.SimpleNamespace(
        model="llama", llm_config_json=str(path), llm_experts_held="4,4", llm_max_seq_len=96))
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim) == (
        64, 4, 8, 2, 16, 32)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.norm_kind, cfg.max_seq_len) == (50000.0, 1e-5, "layer", 96)
    assert cfg.layer_types == tuple(TINY["layer_types"]) and cfg.sliding_window == W
    assert (cfg.parallel_block, cfg.tie_embeddings, cfg.logit_scale, cfg.rope_full_layers,
            cfg.shared_expert_scale, cfg.experts_held) == (True, True, 1.0, False, 0.5, (4, 4))
    # a window that no layer is said to have is no window
    assert config_from_published({"sliding_window": 4096, "hidden_size": 64}).get("sliding_window") is None


def test_the_keys_and_no_familys_name_decide_the_fields():
    """Norm kind, NoPE in the full layers and the shared experts' scale come
    from keys the configuration carries, whatever ``model_type`` says."""
    want = config_from_published(published())
    assert (want["norm_kind"], want["rope_full_layers"], want["shared_expert_scale"]) == ("layer", False, 0.5)
    assert config_from_published(published(model_type="some_other_family")) == want
    bare = published()
    del bare["model_type"]
    assert config_from_published(bare) == want
    # without the keys: the root-mean-square norm, rotary in every layer, the shared sum whole
    plain = published(rms_norm_eps=1e-6)
    del plain["rope_full_layers"], plain["shared_expert_combination_strategy"]
    got = config_from_published(plain)
    assert not {"norm_kind", "rope_full_layers", "shared_expert_scale"} & set(got)


@pytest.mark.parametrize("key, value, named", [
    ("conv_bias", True, "conv_bias"),
    ("rotary_pct", 0.5, "rotary_pct"),
    ("use_gated_activation", False, "use_gated_activation"),
    ("shared_expert_combination_strategy", "concat", "shared_expert_combination_strategy"),
    ("first_k_dense_replace", 2, "prefix_dense"),
    ("position_embedding_type", "rope_neox", "position_embedding_type"),
])
def test_keys_that_are_not_computed_raise_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_from_published(published(**{key: value}))


def test_configurations_that_are_not_computed_raise(setting):
    lcfg = setting[0]
    with pytest.raises(ValueError, match="int8 cache"):
        dataclasses.replace(lcfg, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="sliding_window > 0"):
        dataclasses.replace(lcfg, sliding_window=0)
    with pytest.raises(ValueError, match="for each of 4 layers"):
        dataclasses.replace(lcfg, layer_types=("full_attention",))
    with pytest.raises(ValueError, match="tie_embeddings"):
        dataclasses.replace(lcfg, streaming_xent_chunk=64)
    with pytest.raises(ValueError, match="norm_kind"):
        dataclasses.replace(lcfg, norm_kind="batch")


# -- (b) the window in every form of the attention -------------------------------------

def _naive(q, k, v, window):
    s = q.shape[-2]
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (ahead >= 0) & (ahead < window)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(ks[0], (2, 4, 96, 16)), jax.random.normal(ks[1], (2, 2, 96, 16)),
            jax.random.normal(ks[2], (2, 2, 96, 16)))


@pytest.mark.parametrize("window", [1, 7, 40, 96, 200])
def test_blockwise_window_agrees_with_the_mask_by_hand(qkv, window):
    q, k, v = qkv
    with jax.default_matmul_precision("highest"):
        got = blockwise_attention(q, k, v, causal=True, block_k=16, window=window)
        assert rel(got, _naive(q, k, v, window)) < TOL
    with pytest.raises(ValueError, match="causal"):
        blockwise_attention(q, k, v, causal=False, window=window)


@pytest.mark.parametrize("window, blocks", [(7, (32, 16)), (40, (16, 32)), (64, (32, 32))])
def test_flash_kernels_window_in_interpret_mode(qkv, window, blocks):
    """Forward and backward kernels against the scan and its VJP: blocks
    wholly outside every window of a query block are skipped, the rest masked."""
    q, k, v = qkv
    bq, bk = blocks
    with jax.default_matmul_precision("highest"):
        out, lse = flash_attention_fwd_pallas(q, k, v, True, None, bq, bk, return_lse=True,
                                              interpret=True, window=window)
        want, vjp = jax.vjp(lambda q, k, v: blockwise_attention(q, k, v, True, window=window), q, k, v)
        assert rel(out, want) < TOL
        g = jax.random.normal(jax.random.PRNGKey(9), out.shape)
        got = flash_attention_bwd_pallas(q, k, v, out, lse, g, True, None, bq, bk,
                                         interpret=True, window=window)
        for a, b in zip(got, vjp(g)):
            assert rel(a, b) < 5 * TOL


def test_dense_cache_decode_keeps_the_window(setting):
    """The single-request path (``generate``'s cache): prefill 30, then one
    token at a time, against the full forward."""
    lcfg, base, lora, tokens = setting
    model = LlamaLM(lcfg)
    want = model.apply({"params": base, "lora": lora}, tokens[:1, :44])
    logits, mut = model.apply({"params": base, "lora": lora}, tokens[:1, :30], decode=True,
                              start_pos=jnp.asarray(0), mutable=["cache"])
    got = [logits[0]]
    for t in range(30, 44):
        logits, mut = model.apply({"params": base, "lora": lora, "cache": mut["cache"]},
                                  tokens[:1, t:t + 1], decode=True, start_pos=jnp.asarray(t),
                                  mutable=["cache"])
        got.append(logits[0])
    assert rel(jnp.concatenate(got), want[0]) < TOL


# -- (c) chunked prefill, then paged ticks, through the two pools --------------------------

class _Ring:
    """A slot's window table as the engine keeps it, by hand: block j at entry
    ``j % entries``, pages handed out in order and never twice, the entries
    of blocks wholly behind ``lo - window + 1`` back at the trash page."""

    def __init__(self, entries, ptok, first_page):
        self.tab = np.zeros(entries, np.int32)
        self.entries, self.ptok, self.next_page = entries, ptok, first_page
        self.first = self.upto = 0

    def slide(self, lo, hi):
        first = max((lo - W + 1) // self.ptok, 0)
        for j in range(self.first, first):
            self.tab[j % self.entries] = 0
        for j in range(self.upto, hi // self.ptok + 1):
            assert self.tab[j % self.entries] == 0, "an entry still held"
            self.tab[j % self.entries] = self.next_page
            self.next_page += 1
        self.first, self.upto = first, max(self.upto, hi // self.ptok + 1)
        return self.tab[None].copy()


@pytest.mark.parametrize("walk_all", [False, True], ids=["gathered-full-layer", "walked-full-layer"])
def test_paged_prefill_and_ticks_agree_by_logits(setting, walk_all, monkeypatch):
    """Two requests on two adapters: each prompt goes into the two pools chunk
    by chunk, then both decode in ONE batch; every position's logits against
    the reference's full forward.  The window is 24 tokens, a page 4, a chunk
    16: the ring has 11 entries and turns three times over the longer request.
    With ``WALK_MIN_BYTES`` 0 the full layer's read walks its table in slabs
    too (at the cell's size it has to)."""
    lcfg, base, _, _ = setting
    if walk_all:
        monkeypatch.setattr(M, "WALK_MIN_BYTES", 0)
    ptok, chunk, entries = 4, 16, (W + 16) // 4 + 1
    pm = LlamaLM(dataclasses.replace(lcfg, kv_page_tokens=ptok, kv_pool_pages=64,
                                     kv_window_pool_pages=80))
    loras = [weights.make_lora(TINY, 5, index=i + 1) for i in range(2)]
    rng = np.random.default_rng(7)
    seqs = [rng.integers(1, 256, size=n) for n in (141, 60)]
    prompts = (131, 50)
    full = np.zeros((2, 40), np.int32)
    full[0, :36] = 1 + np.arange(36)
    full[1, :16] = 40 + np.arange(16)
    rings = [_Ring(entries, ptok, 1), _Ring(entries, ptok, 41)]
    tabs0 = {"full": jnp.asarray(full[:1]), "window": jnp.zeros((1, entries), jnp.int32)}
    pool = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: pm.apply(
            {"params": base, "lora": loras[0]}, jnp.zeros((1, chunk), jnp.int32), decode=True,
            start_pos=jnp.zeros((1,), jnp.int32), block_tables=tabs0, mutable=["cache"]))[1]["cache"])
    shapes = {name: pool[name]["attention"]["k"].shape for name in pool}
    assert shapes == {"layer_0": (80, 4, 2, 16), "layer_1": (80, 4, 2, 16),
                      "layer_2": (80, 4, 2, 16), "layer_3": (64, 4, 2, 16)}
    got = [np.zeros((len(s), 256)) for s in seqs]
    for r in range(2):
        for cs in range(0, prompts[r], chunk):
            seg = np.zeros((1, chunk), np.int32)
            real = seqs[r][cs:min(cs + chunk, prompts[r])]
            seg[0, :len(real)] = real
            tabs = {"full": jnp.asarray(full[r:r + 1]),
                    "window": jnp.asarray(rings[r].slide(cs, cs + len(real) - 1))}
            logits, mut = pm.apply(
                {"params": base, "lora": loras[r], "cache": pool}, jnp.asarray(seg), decode=True,
                start_pos=jnp.asarray([cs], jnp.int32), block_tables=tabs, mutable=["cache"])
            pool = mut["cache"]
            got[r][cs:cs + len(real)] = np.asarray(logits[0, :len(real)])
    assert rings[0].next_page - 1 > entries          # the ring turned: entries were used again
    stacked = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), *loras)
    for t in range(10):
        poss = np.array([prompts[0] + t, prompts[1] + t], np.int32)
        toks = np.array([seqs[0][poss[0]], seqs[1][poss[1]]], np.int32)
        tabs = {"full": jnp.asarray(full), "window": jnp.asarray(np.concatenate(
            [rings[r].slide(int(poss[r]), int(poss[r])) for r in range(2)]))}
        logits, mut = pm.apply(
            {"params": base, "lora": stacked, "cache": pool}, jnp.asarray(toks)[:, None],
            decode=True, start_pos=jnp.asarray(poss), block_tables=tabs, mutable=["cache"])
        pool = mut["cache"]
        for r in range(2):
            got[r][poss[r]] = np.asarray(logits[r, 0])
    for r in range(2):
        want, _ = ref.logits(base, loras[r], jnp.asarray(seqs[r])[None], TINY, HELD)
        assert rel(got[r], want[0]) < 2 * TOL


def _serve(eng, requests):
    queues = [eng.submit(ids, max_new_tokens=m, adapter=name) for ids, m, name in requests]
    outs = []
    for q in queues:
        outs.append([])
        while (t := q.get(timeout=300)) is not None:
            outs[-1].append(t)
    return outs


def _gaps(base, loras, requests, outs):
    """The widest gap of a served token below the reference's best, in units
    of the position's spread, over all requests."""
    worst = 0.0
    for (ids, m, name), out in zip(requests, outs):
        assert len(out) == m
        seq = np.zeros((1, 192), np.int32)
        seq[0, :len(ids) + m] = ids + out
        got = ref.forced_gaps(base, loras[name], jnp.asarray(seq), len(ids) - 1, 48, TINY, HELD)
        worst = max(worst, float(jnp.max((got["gap"] / got["spread"])[:m])))
    return worst


@pytest.fixture(scope="module")
def served(setting):
    """Six requests on two adapters through ``ContinuousBatchingEngine`` with
    three slots and a window pool of 28 pages, where three whole rings would
    be 33: what one slot gives back behind its window another takes."""
    from fedml_tpu import obs
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=3, buf_len=160, adapter_slots=3,
                                   kv_page_tokens=4, prefill_chunk_tokens=16,
                                   kv_pool_pages=121, kv_window_pool_pages=29)
    try:
        loras = {f"a{i}": weights.make_lora(TINY, 5, index=i + 1) for i in range(2)}
        for name, tree in loras.items():
            eng.registry.register(name, tree)
        rng = np.random.default_rng(11)
        requests = [([int(t) for t in rng.integers(1, 256, size=n)], m, f"a{i % 2}")
                    for i, (n, m) in enumerate(((100, 30), (7, 12), (53, 40), (120, 20), (30, 30), (90, 10)))]
        outs = _serve(eng, requests)
        stats = eng.kv_stats()
        events = obs.get_tracer().events()
        state = {"wtabs": eng._wtabs.copy(), "free": eng.window_pool.pages_free,
                 "dev": sorted(eng._dev), "entries": eng.window_blocks}
    finally:
        eng.stop()
        obs.configure(enabled=False)
    return base, loras, requests, outs, stats, events, state


def test_engine_serves_through_two_pools(served):
    base, loras, requests, outs, stats, _, state = served
    assert _gaps(base, loras, requests, outs) < 1e-4
    assert state["entries"] == (W + 16) // 4 + 1 == 11 and "wtabs" in state["dev"]
    # four layers of 2 x 2 x 16 float32 numbers a token, whichever pool holds them
    assert stats["kv_bytes_per_token"] == 4 * 2 * 2 * 16 * 4
    assert stats["window_pool_pages"] == 29 and stats["pool_pages"] == 121
    # everything is given back at the end: nothing leaks from either pool
    assert stats["window_pages_free"] == state["free"] == 28 and stats["pages_free"] == 120
    assert not state["wtabs"].any()


def test_pages_taken_back_behind_the_window_are_reused(served):
    """The window pool handed out more pages than it has, several times over,
    and refused some admissions meanwhile (they waited); no served token moved
    (the gaps above).  A window layer's reservation is ``min(prompt + answer,
    window + chunk + page)`` tokens."""
    _, _, requests, _, stats, _, _ = served
    wp = stats["window_pool"]
    assert wp["reserved_pages"] == wp["released_pages"] > 3 * 28
    assert stats["window_pages_freed"] > 28
    # each pool gave every block of every request a page once: the full
    # layers' at admission, the window layers' 11 at admission at most and
    # the rest as the window slid
    whole = sum(-(-(len(ids) + m) // 4) for ids, m, _ in requests)
    assert stats["pool"]["reserved_pages"] == wp["reserved_pages"] == whole
    # early went the blocks wholly behind the last tick's window: that tick
    # writes position n + m - 2
    assert stats["window_pages_freed"] == sum(
        max((len(ids) + m - 2 - W + 1) // 4, 0) for ids, m, _ in requests)


def test_spans_and_gauges_of_the_two_pools(served):
    events = served[5]
    ticks = [e for e in events if e["name"] == "serve.tick" and e["ph"] == "E"]
    chunks = [e for e in events if e["name"] == "serve.chunk" and e["ph"] == "E"]
    assert ticks and all({"live_full_tokens", "live_window_tokens", "window_pages_freed"}
                         <= set(e["args"]) for e in ticks)
    assert all("window_pages_freed" in e["args"] for e in chunks)
    assert sum(e["args"]["window_pages_freed"] for e in ticks) > 0
    assert sum(e["args"]["window_pages_freed"] for e in chunks) > 0
    for e in ticks:
        full, held = e["args"]["live_full_tokens"], e["args"]["live_window_tokens"]
        assert 0 < held <= full
    assert any(e["args"]["live_window_tokens"] < e["args"]["live_full_tokens"] for e in ticks)
    gauges = {e["name"] for e in events if e["ph"] == "C"}
    assert {"serve.kv_pages_free", "serve.kv_pages_free.full", "serve.kv_pages_free.window"} <= gauges


def test_attn_pages_is_zero_where_the_jnp_walk_ran(served):
    """On the CPU every walk is the ``jnp`` loop: the counter is on every
    tick and chunk and in ``kv_stats()``, and it is 0, as ``expert_tiles``
    is where ``ragged_dot`` ran."""
    _, _, _, _, stats, events, _ = served
    spans = [e for e in events if e["name"] in ("serve.tick", "serve.chunk") and e["ph"] == "E"]
    assert {e["name"] for e in spans} == {"serve.tick", "serve.chunk"}
    assert all(e["args"]["attn_pages"] == 0 for e in spans)
    assert stats["attn_pages"] == 0


def test_attn_pages_counts_what_the_kernel_visits(setting, monkeypatch):
    """The kernel form forced on the CPU in interpret mode, for the window
    layers' rings and (``WALK_MIN_BYTES`` 0) the full layer's table: every
    served token is still the reference's best, and ``attn_pages`` on the
    spans and in ``kv_stats()`` is what ``visited_pages`` counts for the
    positions each chunk and tick of each request stood at."""
    from fedml_tpu import obs
    from fedml_tpu.ops import paged_attention as pa
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    monkeypatch.setattr(M, "WALK_MIN_BYTES", 0)
    monkeypatch.setattr(pa, "engages", lambda *operands: True)
    monkeypatch.setattr(M, "_walk_pages", lambda q, k, v, tables, pos, window, ring, scale, dtype:
                        pa.paged_attention(q, k, v, tables, pos, window=window, ring=ring,
                                           sm_scale=scale, interpret=True))
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=2, buf_len=160, adapter_slots=2,
                                   kv_page_tokens=4, prefill_chunk_tokens=16)
    try:
        loras = {"a0": weights.make_lora(TINY, 5, index=1)}
        eng.registry.register("a0", loras["a0"])
        rng = np.random.default_rng(7)
        requests = [([int(t) for t in rng.integers(1, 256, size=n)], m, "a0")
                    for n, m in ((100, 12), (30, 8), (53, 10))]
        outs = _serve(eng, requests)
        stats, events = eng.kv_stats(), obs.get_tracer().events()
        blocks, ring = eng.max_blocks, eng.window_blocks
    finally:
        eng.stop()
        obs.configure(enabled=False)
    assert _gaps(base, loras, requests, outs) < 1e-4

    kinds = [(lcfg.layer_types.count("sliding_attention"), dict(window=W, ring=True, entries=ring)),
             (lcfg.layer_types.count("full_attention"), dict(window=0, ring=False, entries=blocks))]

    def pages(pos):
        return sum(n * pa.visited_pages(np.asarray(pos), np.ones(1, np.int64), ptok=4, **kind)
                   for n, kind in kinds)

    # a prompt's chunks stand at 0, 16, ...; its ticks write n .. n + m - 2
    want = sum(sum(pages(cs + np.arange(16)[None]) for cs in range(0, len(ids), 16))
               + sum(pages([[p]]) for p in range(len(ids), len(ids) + m - 1))
               for ids, m, _ in requests)
    assert stats["attn_pages"] == want > 0
    spans = [e for e in events if e["name"] in ("serve.tick", "serve.chunk") and e["ph"] == "E"]
    assert sum(e["args"]["attn_pages"] for e in spans) == want
    assert all(e["args"]["attn_pages"] > 0 for e in spans)


def test_a_page_freed_too_early_is_seen(setting, monkeypatch):
    """The planted fault of the rehearsal at tier-1 size: the window believed
    a page shorter than it is, so a page still inside it goes to another
    holder; a served token then lies far from the reference's best."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    real = ContinuousBatchingEngine._slide_window
    monkeypatch.setattr(ContinuousBatchingEngine, "_slide_window",
                        lambda self, i, s, lo: real(self, i, s, lo + 8))
    eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=2, buf_len=160, adapter_slots=2,
                                   kv_page_tokens=4, prefill_chunk_tokens=16)
    try:
        loras = {"a0": weights.make_lora(TINY, 5, index=1)}
        eng.registry.register("a0", loras["a0"])
        rng = np.random.default_rng(3)
        requests = [([int(t) for t in rng.integers(1, 256, size=n)], 24, "a0") for n in (110, 95)]
        outs = _serve(eng, requests)
    finally:
        eng.stop()
    assert _gaps(base, loras, requests, outs) > 1e-2


def test_one_kind_of_layer_builds_one_pool():
    """A model of full layers only, and one of window layers only: one pool,
    today's reservations, a carried state without a window table."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine, PagedKVUnsupportedError
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
                      max_seq_len=64, dtype=jnp.float32)
    windowed = dataclasses.replace(cfg, layer_types=("sliding_attention",) * 2, sliding_window=12)
    for c in (cfg, windowed):
        model = LlamaLM(c)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        eng = ContinuousBatchingEngine(model, params, slots=2, buf_len=64, kv_page_tokens=4,
                                       prefill_chunk_tokens=8)
        try:
            assert eng.window_pool is None and "wtabs" not in eng._dev
            assert "window_pool" not in eng.kv_stats()
            assert len({p.shape for p in jax.tree_util.tree_leaves(eng._pool)}) == 1
            ids = [int(t) for t in np.random.default_rng(5).integers(1, 97, size=29)]
            out = eng.generate(ids, max_new_tokens=12)
        finally:
            eng.stop()
        # one table addresses every layer; the window, where there is one, is the mask
        want = np.asarray(model.apply({"params": params}, jnp.asarray([ids + out])))[0]
        assert out == [int(t) for t in want[len(ids) - 1:-1].argmax(-1)]
    mixed = dataclasses.replace(cfg, layer_types=("sliding_attention", "full_attention"),
                                sliding_window=12)
    model = LlamaLM(mixed)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(PagedKVUnsupportedError, match="window pool"):
        ContinuousBatchingEngine(model, params, slots=2, buf_len=64, kv_page_tokens=4,
                                 prefix_cache_slots=2)


# -- (e) the shares of a layer add up to the uncut layer ---------------------------------

def test_shares_add_up_to_the_uncut_layer():
    """E = 16 over 4 shares: the routed parts that the four shares compute,
    with the shared experts (one SwiGLU of twice the width, times a half) and
    the attention counted once, are the uncut reference layer."""
    cfg = uncut(TINY)
    whole = weights.make_base(cfg, 9)["layer_1"]
    lora = weights.make_lora(cfg, 9)["layer_1"]["attention"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.block(x, whole, lora, cfg, W, None, None)
        n = ref.layer_norm(x, whole["attn_norm"]["scale"], 1e-5)
    lcfg = program_config(cfg, 64, attn_impl="blockwise", remat="none")
    attn = M.Attention(lcfg, window=W).apply(
        {"params": whole["attention"], "lora": lora}, n, jnp.arange(48))
    shared = MLP(lcfg, width=2 * 32).apply({"params": whole["shared_expert"]}, n) * 0.5
    total = x + attn + shared
    for share in range(4):
        part_cfg = dict(cfg, experts_held={"first": 4 * share, "count": 4, "of": 16})
        part = weights.make_base(part_cfg, 9)["layer_1"]["moe_mlp"]
        for name in ("w_gate", "w_up", "w_down"):      # a share's weights are a slice of the whole
            assert (part[name] == whole["moe_mlp"][name][4 * share:4 * share + 4]).all()
        layer = moe.MoEMLP(dim=64, ffn_dim=32, n_experts=16, top_k=3, scoring="sigmoid",
                           held=(4 * share, 4))
        got = layer.apply({"params": part}, n)
        with jax.default_matmul_precision("highest"):
            mine, _ = ref.experts(n[0], dict(whole, moe_mlp=part), part_cfg, (4 * share, 4), None)
        assert rel(got[0], mine) < TOL
        total = total + got
    assert rel(total, want) < TOL
