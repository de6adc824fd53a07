"""Gated delta-rule linear-attention layers beside gated latent attention,
sandwich zero-centred gated norms and clamped SwiGLU experts chosen under a
selection bias, against the plain reference
(``benchmarks/reference/gigachat35_decoder.py``, which imports nothing of the
program and runs the linear layers step by step) at a small size on the CPU,
float32, seeded weights, logits at every position: (a) the chunked form
against the recurrence, the full sequence, and what ``config_from_published``
reads and refuses, (b) the expert shares and the clamp, (c) the state by rows
through the paged programs: chunks of several blocks, ticks, the reset, the
hold and the trash row, (d) through the engine: the single-request path, a
slot used again, spans, gauges and ``kv_stats()``, and the planted faults."""

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

import weights_gigachat35 as weights  # noqa: E402
from drivers.serve_gigachat35 import PUBLISHED, program_config  # noqa: E402
from reference import gigachat35_decoder as ref  # noqa: E402

from fedml_tpu.llm import linear_attention as la  # noqa: E402
from fedml_tpu.llm import moe  # noqa: E402
from fedml_tpu.llm.model import (LlamaConfig, LlamaLM, MLP,  # noqa: E402
                                 config_from_args, config_from_published)
from fedml_tpu.ops import grouped_matmul as gm  # noqa: E402

TOL = 2e-5          # float32 against float32, relative to the tensor's scale
with open(os.path.join(BENCH, "tests", "tiny_gigachat35.json")) as f:
    TINY = json.load(f)
with open(os.path.join(BENCH, "configs", "gigachat3.5-ep16-d5.json")) as f:
    CELL_CONFIG = json.load(f)
#: what the tests multiply the drawn selection bias by, so that it changes the
#: experts chosen (2 of 8 here) on a good share of a layer's tokens
BIAS_TIMES = 8.0


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def published(**changed):
    return {**{k: TINY[k] for k in PUBLISHED if k in TINY}, "n_routed_experts": 8, **changed}


def _is(path, name):
    return getattr(path[-1], "key", None) == name


def _base(cfg=TINY, seed=5):
    return jax.tree_util.tree_map_with_path(
        lambda path, p: p * BIAS_TIMES if _is(path, "select_bias") else p, weights.make_base(cfg, seed))


@pytest.fixture(scope="module")
def setting():
    lcfg = program_config(TINY, 160, attn_impl="blockwise", remat="none")
    base = _base()
    lora = weights.make_lora(TINY, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(2, 150)), jnp.int32)
    return lcfg, base, lora, tokens


# -- (a) the chunked form, the full sequence, the configuration ----------------------

def _rule_inputs(rng, b, h, s, dk, dv):
    q = la.l2norm(jnp.asarray(rng.normal(size=(b, h, s, dk)), jnp.float32)) * dk ** -0.5
    k = la.l2norm(jnp.asarray(rng.normal(size=(b, h, s, dk)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(b, h, s, dv)), jnp.float32)
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(b, h, s)), jnp.float32))
    g = -jnp.asarray(rng.uniform(0.0, 0.3, size=(b, h, s)), jnp.float32)
    return q, k, v, beta, g


@pytest.mark.parametrize("s, block", [(64, 64), (200, 64), (37, 16), (5, 8)],
                         ids=["one-block", "blocks-and-padding", "small-blocks", "short"])
def test_chunked_form_is_the_recurrence(s, block):
    """The chunked form (blocks, the UT transform, the state carried from
    block to block) against the step at a time, from a state that is not zero;
    the state at the end too."""
    rng = np.random.default_rng(s)
    q, k, v, beta, g = _rule_inputs(rng, 2, 3, s, 8, 6)
    s0 = jnp.asarray(rng.normal(size=(2, 3, 8, 6)), jnp.float32)
    o, s1 = la.chunked(q, k, v, beta, g, s0, block)
    state, want = s0, []
    for t in range(s):
        state, ot = la.step(state, q[:, :, t], k[:, :, t], v[:, :, t], beta[:, :, t], g[:, :, t])
        want.append(ot)
    assert rel(o, jnp.stack(want, axis=2)) < 1e-5
    assert rel(s1, state) < 1e-5


def test_padding_past_a_lane_leaves_its_state_alone():
    """beta = g = 0 past a lane's real positions (a final chunk's padding):
    the state is the one after its last real position."""
    rng = np.random.default_rng(3)
    q, k, v, beta, g = _rule_inputs(rng, 1, 2, 40, 8, 8)
    s0 = jnp.zeros((1, 2, 8, 8), jnp.float32)
    _, want = la.chunked(q[:, :, :23], k[:, :, :23], v[:, :, :23], beta[:, :, :23], g[:, :, :23], s0, 16)
    real = jnp.arange(40) < 23
    _, got = la.chunked(q, k, v, jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0), s0, 16)
    assert rel(got, want) < 1e-6


def test_layout_and_configuration_are_the_programs(setting):
    lcfg, base, lora, tokens = setting
    theirs = jax.eval_shape(LlamaLM(lcfg).init, jax.random.PRNGKey(0), tokens)
    assert weights.same_layout(base, theirs["params"]) == ""
    assert weights.same_layout(lora, theirs["lora"]) == ""
    assert set(theirs["lora"]["layer_0"]) == {"linear_attn"} and set(theirs["lora"]["layer_1"]) == {"attention"}
    assert set(theirs["lora"]["layer_0"]["linear_attn"]) == {"q", "k", "v", "o"}
    p = theirs["params"]
    assert set(p["layer_2"]) >= {"attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm", "shared_expert"}
    assert p["layer_0"]["linear_attn"]["conv_weight"].shape == (2 * 2 * 8 + 4 * 8, 4)
    assert p["layer_1"]["attention"]["gate"]["kernel"].shape == (64, 4 * 8)
    assert p["final_norm"]["gate"].shape == (64,) and "mlp" in p["layer_0"]
    assert (lcfg.layer_types, lcfg.linear_layers, lcfg.state_layers, lcfg.first_dense_layers) == (
        ("linear_attention", "full_attention") + ("linear_attention",) * 3, 4, 4, 1)
    assert lcfg.norm_kind == "gated" and lcfg.sandwich_norm and lcfg.gated_attention
    assert lcfg.moe_select_bias and lcfg.moe_scoring == "sigmoid" and lcfg.swiglu_limit == 2.0
    assert (lcfg.linear_key_heads, lcfg.linear_value_heads, lcfg.linear_conv_kernel) == (2, 4, 4)


@pytest.mark.parametrize("with_lora", [True, False], ids=["adapters", "base"])
def test_whole_model_agrees_at_every_position(setting, with_lora):
    """150 positions: the linear layers' chunked form over three blocks, against the
    reference's step at a time."""
    lcfg, base, lora, tokens = setting
    if not with_lora:
        lora = jax.tree_util.tree_map(jnp.zeros_like, lora)
    want, _ = ref.logits(base, lora, tokens, TINY)
    got = LlamaLM(lcfg).apply({"params": base, "lora": lora}, tokens)
    assert rel(got, want) < TOL


@pytest.mark.parametrize("leaf", ["gate", "select_bias", "o_norm", "dt_bias", "conv_weight"])
def test_gates_norms_bias_and_taps_are_each_seen(setting, leaf):
    """Flatten one kind of leaf in the program's weights and the logits move."""
    lcfg, base, lora, tokens = setting
    want, _ = ref.logits(base, lora, tokens[:, :40], TINY)
    broken = jax.tree_util.tree_map_with_path(lambda path, p: p * 0 if _is(path, leaf) else p, base)
    got = LlamaLM(lcfg).apply({"params": broken, "lora": lora}, tokens[:, :40])
    assert rel(got, want) > 100 * TOL


def test_the_gradient_of_the_adapters_agrees(setting):
    from fedml_tpu.llm.model import causal_nll
    lcfg, base, lora, tokens = setting
    model = LlamaLM(dataclasses.replace(lcfg, remat="full"))
    toks = tokens[:, :48]

    def loss(adapters):
        return causal_nll(model.apply({"params": base, "lora": adapters}, toks[:, :-1]), toks[:, 1:])

    value, got = jax.value_and_grad(loss)(lora)
    want_value, want = jax.value_and_grad(lambda a: ref.nll(base, a, toks, TINY))(lora)
    assert abs(float(value) - float(want_value)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0 and rel(g, w) < 5e-4, jax.tree_util.keystr(path)


def test_published_keys_arrive():
    want = config_from_published(published())
    assert want["layer_types"] == ("linear_attention", "full_attention") + ("linear_attention",) * 3
    assert (want["linear_key_heads"], want["linear_value_heads"], want["linear_key_head_dim"],
            want["linear_value_head_dim"], want["linear_conv_kernel"], want["linear_gate_scale"],
            want["linear_norm_eps"]) == (2, 4, 8, 8, 4, 2.0, 1e-6)
    assert want["sandwich_norm"] and want["norm_kind"] == "gated" and want["norm_gate_scale"] == 2.0
    assert want["gated_attention"] and want["swiglu_limit"] == 2.0 and want["moe_select_bias"]
    cfg = config_from_args(types.SimpleNamespace(model="llama", llm_config_json=published()))
    assert cfg.layer_linear(0) and not cfg.layer_linear(1) and cfg.sparse_layer(1)


@pytest.mark.parametrize("changed, named", [
    ({"linear_attention_type": "Mamba2"}, "linear_attention_type 'Mamba2'"),
    ({"linear_gating_type": "swish"}, "linear_gating_type 'swish'"),
    ({"layernorm_type": "pre"}, "layernorm_type 'pre'"),
    ({"norm_type": "LayerNorm"}, "norm_type 'LayerNorm'"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers 1"),
    ({"use_shared_expert_sigmoid": True}, "use_shared_expert_sigmoid"),
    ({"rope_interleave": False}, "rope_interleave false"),
    ({"qk_head_dim": 24}, "qk_head_dim 24"),
    ({"use_mla_scaling_factor": False}, "use_mla_scaling_factor false"),
    ({"full_attention_layers": [5]}, "full_attention_layers"),
    ({"linear_attention_type": None}, "full_attention_layers without linear_attention_type"),
])
def test_keys_that_are_not_computed_raise_by_name(changed, named):
    with pytest.raises(ValueError, match=named):
        config_from_published(published(**changed))


def test_the_published_row_uncut_no_longer_builds_silently():
    """The configuration's file with its published values put back is the
    catalog's row: fed to ``config_from_published`` it raises by name (the
    multi-token prediction modules are not built); without them it is 30
    linear-attention layers and 10 latent ones, not 40 latent layers."""
    row = {k: v for k, v in CELL_CONFIG.items()
           if k not in ("published", "reduced", "experts_held", "assumed", "departures", "deployment",
                        "lora", "precision", "name", "source", "architecture")}
    row.update(CELL_CONFIG["published"])
    with pytest.raises(ValueError, match="num_nextn_predict_layers 2"):
        config_from_published(row)
    got = config_from_published({**row, "num_nextn_predict_layers": 0})
    kinds = got["layer_types"]
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == list(range(3, 40, 4))
    assert got["sandwich_norm"] and got["gated_attention"] and got["swiglu_limit"] == 10.0
    assert got["n_experts"] == 256 and got["first_dense_layers"] == 3
    LlamaConfig(**{k: v for k, v in got.items() if k != "rope_scaling"})


def test_configurations_that_are_not_computed_raise(setting):
    lcfg = setting[0]
    with pytest.raises(ValueError, match="linear_conv_kernel >= 2"):
        dataclasses.replace(lcfg, linear_conv_kernel=1)
    with pytest.raises(ValueError, match="a multiple of the key heads"):
        dataclasses.replace(lcfg, linear_value_heads=3)
    with pytest.raises(ValueError, match="sandwich_norm with parallel_block"):
        dataclasses.replace(lcfg, parallel_block=True)
    with pytest.raises(ValueError, match="gated_attention is computed for latent attention only"):
        LlamaConfig(gated_attention=True)


# -- (b) the expert shares and the clamp ---------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer(setting):
    """Two shares of 4 of the 8 experts: each holds the uncut layer's experts
    as slices, and the program's parts of a sparse layer, with the shared
    expert counted once, add up to the reference's whole layer."""
    lcfg, base, _, _ = setting
    hn = jax.random.normal(jax.random.PRNGKey(2), (1, 30, 64), jnp.float32)
    layer = base["layer_2"]
    whole, _ = ref.experts(hn[0], layer, TINY, None, None)
    assert rel(_apply_feed_forward(lcfg, layer, hn), whole[None]) < TOL
    parts = []
    for first in (0, 4):
        held = _base(dict(TINY, experts_held={"first": first, "count": 4, "of": 8}))["layer_2"]
        for name in ("w_gate", "w_up", "w_down"):
            assert np.array_equal(held["moe_mlp"][name], layer["moe_mlp"][name][first:first + 4])
        parts.append(_apply_feed_forward(dataclasses.replace(lcfg, experts_held=(first, 4)), held, hn))
        share, _ = ref.experts(hn[0], held, TINY, (first, 4), None)
        assert rel(parts[-1], share[None]) < TOL
    shared = _apply_feed_forward(lcfg, layer, hn, shared_only=True)
    assert rel(parts[0] + parts[1] - shared, whole[None]) < TOL


def _apply_feed_forward(cfg, layer, hn, shared_only=False):
    """A sparse layer's feed-forward (routed part and shared expert) of the
    program, over ``hn``; ``shared_only``: the shared expert alone."""
    import flax.linen as nn
    from fedml_tpu.llm.model import _feed_forward

    class FF(nn.Module):
        @nn.compact
        def __call__(self, x):
            if shared_only:
                return MLP(cfg, width=cfg.n_shared_experts * cfg.moe_ffn_dim, name="shared_expert")(x)
            return _feed_forward(cfg, True, x)

    params = {k: layer[k] for k in ("moe_mlp", "shared_expert") if k in layer}
    return FF().apply({"params": params}, hn)


def test_the_clamp_engages_in_the_kernel_and_in_mlp():
    """Weights scaled so that gate and up pass the limit on most rows: the
    gate-and-up kernel (interpreted), ``ragged_dot``'s form and ``MLP`` compute
    ``silu(min(g, L)) * clip(u, -L, L)``, and differ from the unclamped."""
    rng = np.random.default_rng(0)
    limit = 10.0
    rows = jnp.asarray(rng.normal(size=(24, 128)), jnp.float32)
    w_gate = jnp.asarray(rng.normal(size=(2, 128, 128)), jnp.float32)     # products of about 11
    w_up = jnp.asarray(rng.normal(size=(2, 128, 128)), jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(2, 128, 128)) * 0.05, jnp.float32)
    sizes = jnp.asarray([10, 14], jnp.int32)
    group = np.repeat([0, 1], [10, 14])
    g = jnp.einsum("rk,rkn->rn", rows, w_gate[group])
    u = jnp.einsum("rk,rkn->rn", rows, w_up[group])
    assert float(jnp.mean(g > limit)) > 0.1 and float(jnp.mean(jnp.abs(u) > limit)) > 0.2
    want = jax.nn.silu(jnp.minimum(g, limit)) * jnp.clip(u, -limit, limit)
    got = gm.gated_matmul(rows, w_gate, w_up, sizes, limit=limit, interpret=True)
    assert rel(got, want) < 1e-5 and rel(got, jax.nn.silu(g) * u) > 0.1
    down = jnp.einsum("rk,rkn->rn", want, w_down[group])
    assert rel(gm.swiglu_ragged(rows, w_gate, w_up, w_down, sizes, limit), down) < 1e-5
    assert rel(gm.swiglu_pallas(rows, w_gate, w_up, w_down, sizes, True, limit), down) < 1e-5
    cfg = LlamaConfig(vocab_size=8, dim=128, n_layers=1, n_heads=1, n_kv_heads=1, ffn_dim=128,
                      dtype=jnp.float32, swiglu_limit=limit)
    params = {n: {"kernel": w[0]} for n, w in (("w_gate", w_gate), ("w_up", w_up), ("w_down", w_down))}
    got = MLP(cfg).apply({"params": params}, rows[None])[0]
    assert rel(got, jnp.einsum("rk,kn->rn", jax.nn.silu(jnp.minimum(rows @ w_gate[0], limit))
                               * jnp.clip(rows @ w_up[0], -limit, limit), w_down[0])) < 1e-5


def test_experts_are_clamped_through_the_layer():
    """``moe.expert_ffn`` passes the limit to the grouped matmuls."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(12, 128)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((4, 128, 64), (4, 128, 64), (4, 64, 128))]
    gates = jnp.full((12, 2), 0.5, jnp.float32)
    experts = jnp.asarray(rng.integers(0, 4, size=(12, 2)), jnp.int32)
    clamped = moe.expert_ffn(x, gates, experts, *w, 0, 10.0)[0]
    plain = moe.expert_ffn(x, gates, experts, *w, 0)[0]
    want = sum(0.5 * (jax.nn.silu(jnp.minimum(x @ w[0][e], 10.0)) * jnp.clip(x @ w[1][e], -10, 10)) @ w[2][e]
               * (experts == e).sum(1, keepdims=True) for e in range(4))
    assert rel(clamped, want) < 1e-5 and rel(plain, want) > 0.1


# -- (c) the state by rows, through the paged programs -----------------------------------

SLOTS, PTOK, CHUNK = 3, 4, 32


def _paged(lcfg):
    return LlamaLM(dataclasses.replace(lcfg, kv_page_tokens=PTOK, kv_pool_pages=96, state_slots=SLOTS))


def _empty_pool(pm, base, lora):
    tab = jnp.zeros((1, 40), jnp.int32)
    shapes = jax.eval_shape(lambda: pm.apply(
        {"params": base, "lora": lora}, jnp.zeros((1, CHUNK), jnp.int32), decode=True,
        start_pos=jnp.zeros((1,), jnp.int32), block_tables=tab, mutable=["cache"]))[1]["cache"]
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _chunks(pm, base, lora, pool, seq, n, slot, table, got, upto=None, start=0):
    """The prompt's positions ``start .. upto - 1`` chunk by chunk through
    slot ``slot``'s row, as the engine's chunk program calls the model."""
    for cs in range(start, n if upto is None else upto, CHUNK):
        seg = np.zeros((1, CHUNK), np.int32)
        real = seq[cs:min(cs + CHUNK, n)]
        seg[0, :len(real)] = real
        logits, mut = pm.apply(
            {"params": base, "lora": lora, "cache": pool}, jnp.asarray(seg), decode=True,
            start_pos=jnp.asarray([cs], jnp.int32), block_tables=jnp.asarray(table[None]),
            state_rows=jnp.asarray([slot], jnp.int32), seq_lens=jnp.asarray([len(real)], jnp.int32),
            mutable=["cache"])
        pool = mut["cache"]
        got[cs:cs + len(real)] = np.asarray(logits[0, :len(real)])
    return pool


def _tables():
    tabs = np.zeros((SLOTS, 40), np.int32)
    for r in range(SLOTS):
        tabs[r, :30] = 1 + 30 * r + np.arange(30)
    return tabs


def _tick(pm, base, loras, pool, toks, poss, live, tabs):
    """One tick of all lanes as the engine's tick program calls the model: a
    lane that is not live gets the all-trash table and the trash row."""
    stacked = jax.tree_util.tree_map(lambda *rows: jnp.stack(rows), *loras)
    live = np.asarray(live)
    logits, mut = pm.apply(
        {"params": base, "lora": stacked, "cache": pool}, jnp.asarray(toks, jnp.int32)[:, None],
        decode=True, start_pos=jnp.asarray(poss, jnp.int32),
        block_tables=jnp.asarray(np.where(live[:, None], tabs, 0)),
        state_rows=jnp.asarray(np.where(live, np.arange(SLOTS), SLOTS), jnp.int32), mutable=["cache"])
    return np.asarray(logits[:, 0]), mut["cache"]


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 8 positions: a chunk of 32 holds four of them."""
    monkeypatch.setattr(la, "BLOCK", 8)


def test_chunks_of_several_blocks_then_ticks_agree_by_logits(setting, small_blocks):
    """Two requests on two adapters in slots 0 and 2: each prompt goes in
    over chunks of four blocks (the last one padded), the state carried from
    chunk to chunk and handed to the ticks; then both decode in ONE batch
    whose middle lane is not live and writes the trash row.  Every position's
    logits against the reference's full forward (the rule step by step)."""
    lcfg, base, _, _ = setting
    pm = _paged(lcfg)
    loras = [weights.make_lora(TINY, 5, index=i + 1) for i in range(3)]
    pool = _empty_pool(pm, base, loras[0])
    assert pool["layer_0"]["linear_attn"]["linear_state"].shape == (SLOTS + 1, 4, 8, 8)
    assert pool["layer_0"]["linear_attn"]["linear_state"].dtype == jnp.float32
    assert pool["layer_0"]["linear_attn"]["linear_conv"].shape == (SLOTS + 1, 3, 64)
    assert set(pool["layer_1"]["attention"]) == {"latent"}
    rng = np.random.default_rng(7)
    seqs = {0: rng.integers(1, 256, size=86), 2: rng.integers(1, 256, size=80)}
    prompts = {0: 74, 2: 68}
    tabs = _tables()
    got = {r: np.zeros((len(s), 256)) for r, s in seqs.items()}
    for r in (0, 2):
        pool = _chunks(pm, base, loras[r], pool, seqs[r], prompts[r], r, tabs[r], got[r])
    for t in range(12):
        poss = [prompts[0] + t, 0, prompts[2] + t]
        toks = [seqs[0][poss[0]], 0, seqs[2][poss[2]]]
        logits, pool = _tick(pm, base, loras, pool, toks, poss, [True, False, True], tabs)
        for r in (0, 2):
            got[r][poss[r]] = logits[r]
    for r in (0, 2):
        want, _ = ref.logits(base, loras[r], jnp.asarray(seqs[r])[None], TINY)
        assert rel(got[r], want[0]) < 2 * TOL
    # the trash row took the idle lane's writes; slot 1's rows were never touched
    state = np.asarray(pool["layer_0"]["linear_attn"]["linear_state"])
    assert not state[1].any() and state[SLOTS].any()


def test_a_first_chunk_starts_from_zeros_whatever_its_row_holds(setting):
    """The reset: a request's first chunk (its first position is 0) into a row
    that holds garbage gives the logits a clean row gives; a later chunk reads
    what is there."""
    lcfg, base, lora, _ = setting
    pm = _paged(lcfg)
    seq = np.random.default_rng(9).integers(1, 256, size=70)
    tabs = _tables()
    clean, dirty = np.zeros((70, 256)), np.zeros((70, 256))
    _chunks(pm, base, lora, _empty_pool(pm, base, lora), seq, 70, 1, tabs[1], clean)
    soil = lambda pool: jax.tree_util.tree_map_with_path(  # noqa: E731
        lambda path, p: p + 3.0 if _is(path, "linear_state") or _is(path, "linear_conv") else p, pool)
    _chunks(pm, base, lora, soil(_empty_pool(pm, base, lora)), seq, 70, 1, tabs[1], dirty)
    assert np.array_equal(clean, dirty)
    want, _ = ref.logits(base, lora, jnp.asarray(seq)[None], TINY)
    assert rel(dirty, want[0]) < 2 * TOL
    again = np.zeros((70, 256))
    half = _chunks(pm, base, lora, _empty_pool(pm, base, lora), seq, 70, 1, tabs[1], again, upto=32)
    _chunks(pm, base, lora, soil(half), seq, 70, 1, tabs[1], again, start=32)
    assert rel(again[32:], want[0][32:]) > 100 * TOL


@pytest.mark.parametrize("address", ["the trash row", "its own row"])
def test_a_slot_between_two_chunks_rides_ticks_and_keeps_its_state(setting, address):
    """The hold: slot 1 has had its first chunk and waits for its second while
    two ticks run for slot 0; its lane is not live and addresses the trash
    row, so its second chunk reads what the first left.  Were the lane to
    address its own row (the fault), the ticks' writes would be what the
    second chunk reads."""
    lcfg, base, _, _ = setting
    pm = _paged(lcfg)
    loras = [weights.make_lora(TINY, 5, index=i + 1) for i in range(3)]
    rng = np.random.default_rng(13)
    waiting, running = rng.integers(1, 256, size=60), rng.integers(1, 256, size=44)
    tabs = _tables()
    pool = _empty_pool(pm, base, loras[0])
    pool = _chunks(pm, base, loras[0], pool, running, 40, 0, tabs[0], np.zeros((44, 256)))
    got = np.zeros((60, 256))
    pool = _chunks(pm, base, loras[1], pool, waiting, 60, 1, tabs[1], got, upto=32)
    for t in range(2):
        live = [True, address == "its own row", False]
        _, pool = _tick(pm, base, loras, pool, [running[40 + t], 0, 0], [40 + t, 0, 0], live,
                        tabs if address == "the trash row" else np.where([[1], [0], [1]], tabs, 0))
    _chunks(pm, base, loras[1], pool, waiting, 60, 1, tabs[1], got, start=32)
    want, _ = ref.logits(base, loras[1], jnp.asarray(waiting)[None], TINY)
    if address == "the trash row":
        assert rel(got, want[0]) < 2 * TOL
    else:
        assert rel(got[32:], want[0][32:]) > 100 * TOL


# -- (d) through the engine ---------------------------------------------------------------

def _serve(eng, requests):
    queues = [eng.submit(ids, max_new_tokens=m, adapter=name) for ids, m, name in requests]
    outs = []
    for q in queues:
        outs.append([])
        while (t := q.get(timeout=300)) is not None:
            outs[-1].append(t)
    return outs


def _gaps(base, loras, requests, outs, buf=160):
    """The widest gap of a served token below the reference's best, in units
    of the position's spread, over all requests."""
    worst = 0.0
    for (ids, m, name), out in zip(requests, outs):
        assert len(out) == m
        seq = np.zeros((1, buf), np.int32)
        seq[0, :len(ids) + m] = ids + out
        got = ref.forced_gaps(base, loras[name], jnp.asarray(seq), len(ids) - 1, 32, TINY)
        worst = max(worst, float(jnp.max((got["gap"] / got["spread"])[:m])))
    return worst


def _requests(seed, sizes):
    rng = np.random.default_rng(seed)
    return [([int(t) for t in rng.integers(1, 256, size=n)], m, f"a{i % 2}")
            for i, (n, m) in enumerate(sizes)]


def _engine(lcfg, base, slots, loras):
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=slots, buf_len=160, adapter_slots=3,
                                   kv_page_tokens=4, prefill_chunk_tokens=CHUNK)
    for name, tree in loras.items():
        eng.registry.register(name, tree)
    return eng


@pytest.fixture(scope="module")
def served(setting):
    """Six requests on two adapters through ``ContinuousBatchingEngine`` with
    two slots: prompts of one to three chunks, every slot used again, slots
    that wait between their chunks while the other ticks."""
    from fedml_tpu import obs
    lcfg, base, _, _ = setting
    loras = {f"a{i}": weights.make_lora(TINY, 5, index=i + 1) for i in range(2)}
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    eng = _engine(lcfg, base, 2, loras)
    try:
        requests = _requests(11, ((90, 24), (7, 12), (40, 20), (70, 16), (33, 10), (12, 9)))
        outs = _serve(eng, requests)
        stats = eng.kv_stats()
        events = obs.get_tracer().events()
        leaves = {jax.tree_util.keystr(p): (l.shape, l.dtype)
                  for p, l in jax.tree_util.tree_leaves_with_path(eng._pool)}
    finally:
        eng.stop()
        obs.configure(enabled=False)
    return base, loras, requests, outs, stats, events, leaves


def test_engine_serves_with_matrix_state_beside_the_latent_pool(served):
    base, loras, requests, outs, stats, _, leaves = served
    assert _gaps(base, loras, requests, outs) < 1e-4
    shapes = sorted(set(v for v in leaves.values()), key=str)
    assert ((3, 4, 8, 8), jnp.float32) in shapes and ((3, 3, 64), jnp.float32) in shapes
    assert len(leaves) == 4 * 2 + 1
    row = 4 * 8 * 8 * 4 + 3 * 64 * 4          # a slot's matrix state and conv rows, a layer
    assert stats["state_rows"] == 3 and stats["state_bytes"] == 3 * 4 * row
    assert stats["kv_bytes_per_token"] == 128 * 4     # one latent row, padded to whole lanes


def test_the_single_request_cached_path_is_refused_by_name(served):
    """The latent layer keeps its cache in the page pool only: a model with
    one decodes through the engine."""
    from fedml_tpu.serving.templates.openai_compat import generate
    base, loras, requests, _, _, _, _ = served
    model = LlamaLM(program_config(TINY, 160, attn_impl="blockwise", remat="none"))
    ids, m, name = requests[0]
    with pytest.raises(NotImplementedError, match="paged pool only"):
        generate(None, base, ids, max_new_tokens=m, buf_len=160, model=model, lora=loras[name])


def test_spans_and_gauges_of_the_linear_state(served):
    from readers import spans as reader
    _, _, requests, _, _, events, _ = served
    row = 4 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    spans = reader.paired(events, 0.0)
    ticks = [e for e in spans if e["name"] == "serve.tick"]
    chunks = [e for e in spans if e["name"] == "serve.chunk"]
    assert ticks and all(e["args"]["linear_state_bytes"] == e["args"]["live"] * row for e in ticks)
    assert any(e["args"]["state_held"] > 0 for e in ticks)
    assert chunks and all(e["args"]["linear_state_bytes"] == row for e in chunks)
    assert sum(e["args"]["state_carried"] for e in chunks) == sum(
        -(-len(ids) // CHUNK) - 1 for ids, _, _ in requests)
    gauges = {e["name"]: e["args"] for e in events if e["ph"] == "C"}
    assert "serve.state_bytes" in gauges


def test_a_slot_used_again_gives_what_a_fresh_engine_gives(setting):
    lcfg, base, _, _ = setting
    loras = {f"a{i}": weights.make_lora(TINY, 5, index=i + 1) for i in range(2)}
    requests = _requests(17, ((70, 10), (21, 10)))
    eng = _engine(lcfg, base, 1, loras)
    try:
        used = [_serve(eng, [r])[0] for r in requests]
    finally:
        eng.stop()
    eng = _engine(lcfg, base, 1, loras)
    try:
        assert _serve(eng, [requests[1]])[0] == used[1]
    finally:
        eng.stop()


def test_a_model_with_linear_layers_shares_no_prefix(setting):
    from fedml_tpu.serving.batching import ContinuousBatchingEngine, PagedKVUnsupportedError
    lcfg, base, _, _ = setting
    with pytest.raises(PagedKVUnsupportedError, match="linear-attention layers"):
        ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=2, buf_len=96, kv_page_tokens=4,
                                 prefix_cache_slots=2)


@pytest.mark.parametrize("fault", ["the matrix state kept in bfloat16 between steps",
                                   "the last chunk's state lost on its way to the first tick"])
def test_the_planted_faults_are_seen(setting, fault):
    """The calibration's two planted faults at tier-1 size: the engine's
    logits, taken through the paged programs as the engine calls them, move
    from the reference's by far more than float32 rounding; the lost
    hand-over also moves the served tokens."""
    import calibrate_gigachat35 as cal
    lcfg, base, _, _ = setting
    loras = {"a0": weights.make_lora(TINY, 5, index=1)}
    requests = [(ids, m, "a0") for ids, m, _ in _requests(3, ((70, 24), (45, 24)))]
    with cal.FAULTS[fault]():
        eng = _engine(lcfg, base, 2, loras)
        try:
            outs = _serve(eng, requests)
        finally:
            eng.stop()
        pm = _paged(lcfg)
        seq = np.asarray(requests[0][0] + outs[0])
        pool = _empty_pool(pm, base, loras["a0"])
        got = np.zeros((len(seq), 256))
        pool = _chunks(pm, base, loras["a0"], pool, seq, 70, 0, _tables()[0], got)
        if fault.startswith("the last chunk"):
            pool = cal._state_leaves(pool, lambda p: p.at[0].set(0))
        for t in range(23):
            logits, pool = _tick(pm, base, [loras["a0"]] * 3, pool, [seq[70 + t], 0, 0],
                                 [70 + t, 0, 0], [True, False, False], _tables())
            got[70 + t] = logits[0]
    want, _ = ref.logits(base, loras["a0"], jnp.asarray(seq)[None], TINY)
    assert rel(got[:93], want[0][:93]) > 100 * TOL
    if fault.startswith("the last chunk"):
        assert _gaps(base, loras, requests, outs) > 1e-2


def test_the_core_has_a_module_of_its_own_in_the_program_maps(setting):
    """Everything between the linear layer's projections runs under the scope
    ``core``: the tick's and the chunk's maps (``obs/programs.py``) have rows
    under ``layer_N/linear_attn/core`` apart from the projections' own."""
    lcfg, base, _, _ = setting
    loras = {"a0": weights.make_lora(TINY, 5, index=1)}
    eng = _engine(lcfg, base, 2, loras)
    try:
        _serve(eng, [(list(range(1, 40)), 3, "a0")])
        maps = eng.program_ops()
    finally:
        eng.stop()
    for name in ("paged_step_mt", "paged_chunk"):
        paths = {row["path"] for row in maps[name].values()}
        core = {p for p in paths if p.startswith("layer_0/linear_attn/core")}
        assert core, sorted(paths)[:20]
        projections = {p for p in paths if p.startswith("layer_0/linear_attn/") and p not in core}
        assert any(p.startswith("layer_0/linear_attn/q") for p in projections), sorted(projections)


# -- (e) the check's reading of the matrix state ------------------------------------------

def _state_reading(lcfg, base, fault=None):
    """Three requests through the engine on two slots under the driver's
    ``SlotLog``, then the driver's ``state_gaps`` over both slots."""
    import contextlib
    import calibrate_gigachat35 as cal
    from drivers.serve_gigachat35 import SlotLog, state_gaps
    loras = {f"a{i}": weights.make_lora(TINY, 5, index=i + 1) for i in range(2)}
    requests = _requests(21, ((90, 20), (40, 12), (70, 16)))
    with cal.FAULTS[fault]() if fault else contextlib.nullcontext():
        eng = _engine(lcfg, base, 2, loras)
        log = SlotLog(eng)
        try:
            outs = _serve(eng, requests)
        finally:
            eng.stop()
        return state_gaps(eng, log.slots, TINY, 5, 2, 160), log.slots, requests, outs


def test_the_state_check_reads_each_slot_where_its_request_left_it(setting):
    """Each slot's row holds the state after its last request's prompt and
    every token but the last: the log has that request's prompt and tokens,
    the slot's position on the device says how many it absorbed, and the rule
    fed the program's own projections of them lands on the row (float32)."""
    lcfg, base, _, _ = setting
    got, slots, requests, outs = _state_reading(lcfg, base)
    served = {tuple(ids): (name, out) for (ids, _, name), out in zip(requests, outs)}
    for prompt, adapter, toks in slots.values():
        assert served[tuple(prompt)] == (adapter, toks)
    assert got["state_slots_read"] == 2 and got["state_gap"] < 1e-5
    assert set(got["state_positions"]) <= {len(ids) + m - 1 for ids, m, _ in requests}


def test_the_state_check_sees_a_state_kept_in_bfloat16(setting):
    lcfg, base, _, _ = setting
    got, _, _, _ = _state_reading(lcfg, base, "the matrix state kept in bfloat16 between steps")
    assert got["state_gap"] > 1e-3


def test_the_reference_state_stops_at_n(setting):
    """``ref.state_after`` over a buffer longer than the positions it is
    given: the positions from ``n`` on leave the state as position ``n - 1``
    left it, and the state is the program's chunked form's on the same
    inputs."""
    lcfg, base, _, _ = setting
    layer = base["layer_0"]["linear_attn"]
    rng = np.random.default_rng(4)
    conv = 2 * TINY["linear_num_key_heads"] * TINY["linear_key_head_dim"] \
        + TINY["linear_num_value_heads"] * TINY["linear_value_head_dim"]
    mixed = jnp.asarray(rng.normal(size=(1, 48, conv)), jnp.float32)
    b_in, a_in = (jnp.asarray(rng.normal(size=(1, 48, TINY["linear_num_value_heads"])), jnp.float32)
                  for _ in range(2))
    whole = ref.state_after(layer, mixed[:, :29], b_in[:, :29], a_in[:, :29], 29, TINY)
    assert rel(ref.state_after(layer, mixed, b_in, a_in, 29, TINY), whole) < 1e-6
    q, k, v, beta, g = ref.rule_inputs(mixed[:, :29], b_in[:, :29], a_in[:, :29], layer, TINY)
    heads = lambda t: jnp.moveaxis(t, 1, 2)          # noqa: E731
    _, chunked = la.chunked(heads(q), heads(k), heads(v), heads(beta), heads(g),
                            jnp.zeros_like(whole), 8)
    assert rel(chunked, whole) < 1e-5
