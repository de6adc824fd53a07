"""Gated short-convolution layers beside attention layers with norms on q and
k, and experts chosen under a selection bias, against the plain reference
(``benchmarks/reference/lfm2_moe_decoder.py``, which imports nothing of the
program) at a small size on the CPU, float32, seeded weights, logits at every
position: (a) the full sequence, its gradients, and what
``config_from_published`` reads and refuses, (b) the selection under a bias,
(c) the state by rows through the paged programs: three chunks and ticks, the
reset, the hold, (d) through the engine: the single-request path, a slot used
again, spans, gauges and ``kv_stats()``, what is refused."""

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

import weights_lfm2_moe as weights  # noqa: E402
from drivers.serve_lfm2_moe import PUBLISHED, program_config  # noqa: E402
from reference import lfm2_moe_decoder as ref  # noqa: E402

from fedml_tpu.llm import moe  # noqa: E402
from fedml_tpu.llm.model import (LlamaConfig, LlamaLM, causal_nll,  # noqa: E402
                                 config_from_args, config_from_published)

TOL = 2e-5          # float32 against float32, relative to the tensor's scale
with open(os.path.join(BENCH, "tests", "tiny_lfm2_moe.json")) as f:
    TINY = json.load(f)
#: what the tests multiply the drawn selection bias by, so that it changes the
#: experts chosen (2 of 8 here) on a fifth to a half of a layer's tokens
BIAS_TIMES = 4.0


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def published(**changed):
    return {**{k: TINY[k] for k in PUBLISHED if k in TINY}, "layer_types": weights.kinds(TINY), **changed}


def _is(path, name):
    return getattr(path[-1], "key", None) == name


@pytest.fixture(scope="module")
def setting():
    lcfg = program_config(TINY, 96, attn_impl="blockwise", remat="none")
    base = jax.tree_util.tree_map_with_path(
        lambda path, p: p * BIAS_TIMES if _is(path, "select_bias") else p, weights.make_base(TINY, 5))
    lora = weights.make_lora(TINY, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(2, 80)), jnp.int32)
    return lcfg, base, lora, tokens


# -- (a) the full sequence, and the configuration ------------------------------------

def test_layout_and_configuration_are_the_programs(setting):
    lcfg, base, lora, tokens = setting
    theirs = jax.eval_shape(LlamaLM(lcfg).init, jax.random.PRNGKey(0), tokens)
    assert weights.same_layout(base, theirs["params"]) == ""
    assert weights.same_layout(lora, theirs["lora"]) == ""
    # adapters of two shapes in one tree: the mixer's projections, by the layer's kind
    assert set(theirs["lora"]["layer_0"]) == {"conv"} and set(theirs["lora"]["layer_2"]) == {"attention"}
    assert set(theirs["lora"]["layer_0"]["conv"]) == {"in_proj", "out_proj"}
    assert theirs["params"]["layer_2"]["attention"]["q_norm"]["scale"].shape == (16,)
    assert theirs["params"]["layer_1"]["moe_mlp"]["select_bias"].dtype == jnp.float32
    assert "mlp" in theirs["params"]["layer_0"] and "moe_mlp" not in theirs["params"]["layer_0"]
    assert "lm_head" not in theirs["params"]
    assert (lcfg.layer_types, lcfg.conv_kernel, lcfg.conv_layers, lcfg.first_dense_layers) == (
        ("conv", "conv", "full_attention", "conv", "conv"), 3, 4, 1)
    assert lcfg.qk_norm and lcfg.moe_select_bias and lcfg.tie_embeddings and lcfg.moe_scoring == "sigmoid"


@pytest.mark.parametrize("with_lora", [True, False], ids=["adapters", "base"])
def test_whole_model_agrees_at_every_position(setting, with_lora):
    lcfg, base, lora, tokens = setting
    if not with_lora:
        lora = jax.tree_util.tree_map(jnp.zeros_like, lora)
    want, _ = ref.logits(base, lora, tokens, TINY)
    got = LlamaLM(lcfg).apply({"params": base, "lora": lora}, tokens)
    assert rel(got, want) < TOL
    # the bias decides which experts run on a stated share of a layer's tokens
    moved = np.asarray(ref.bias_changed(base, lora, tokens, TINY)).mean(axis=(1, 2))
    assert moved.shape == (4,) and 0.15 < moved.min() and moved.max() < 0.6, moved


def test_the_bias_the_norms_and_the_taps_are_each_seen(setting):
    """Leave one of them out of the program's weights and the logits move."""
    lcfg, base, lora, tokens = setting
    want, _ = ref.logits(base, lora, tokens, TINY)
    for name, flat in (("select_bias", 0.0), ("q_norm", None), ("conv_weight", None)):
        broken = jax.tree_util.tree_map_with_path(
            lambda path, p: (p * 0 + (flat if flat is not None else 1.0))
            if any(getattr(k, "key", None) == name for k in path) else p, base)
        got = LlamaLM(lcfg).apply({"params": broken, "lora": lora}, tokens)
        assert rel(got, want) > 100 * TOL, name


def test_gradients_of_the_adapters_agree(setting):
    """``jax.grad`` of the reference's loss over the LoRA leaves (rows of two
    shapes) against the program's, under remat as the trainer runs it."""
    lcfg, base, lora, tokens = setting
    model = LlamaLM(dataclasses.replace(lcfg, remat="full"))

    def loss(adapters):
        logits = model.apply({"params": base, "lora": adapters}, tokens[:, :-1])
        return causal_nll(logits, tokens[:, 1:])

    value, got = jax.value_and_grad(loss)(lora)
    want_value, want = jax.value_and_grad(lambda a: ref.nll(base, a, tokens, TINY))(lora)
    assert abs(float(value) - float(want_value)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0 and rel(g, w) < 5e-4, jax.tree_util.keystr(path)
    # no gradient reaches the selection bias
    g = jax.grad(lambda p: causal_nll(model.apply({"params": p, "lora": lora}, tokens[:, :-1]),
                                      tokens[:, 1:]))(base)
    assert not np.asarray(g["layer_1"]["moe_mlp"]["select_bias"]).any()
    assert np.asarray(g["layer_1"]["moe_mlp"]["router"]["kernel"]).any()


def test_published_keys_arrive_whatever_the_family_is_called():
    want = config_from_published(published())
    assert (want["conv_kernel"], want["first_dense_layers"], want["moe_select_bias"], want["qk_norm"],
            want["norm_eps"], want["moe_ffn_dim"], want["n_experts"], want["moe_top_k"],
            want["moe_norm_topk"], want["moe_routed_scale"], want["tie_embeddings"]) == (
        3, 1, True, True, 1e-5, 32, 8, 2, True, 1.0, True)
    assert config_from_published(published(model_type="some_other_family")) == want
    # taps that no layer has are passed over, as a window is
    plain = published(layer_types=["full_attention"] * 5)
    assert "conv_kernel" not in config_from_published(plain)
    cfg = config_from_args(types.SimpleNamespace(model="llama", llm_config_json=published()))
    assert cfg.layer_conv(0) and not cfg.layer_conv(2) and cfg.sparse_layer(1) and not cfg.sparse_layer(0)


@pytest.mark.parametrize("changed, named", [
    ({"conv_bias": True}, "conv_bias"),
    ({"norm_topk_prob": False}, "use_expert_bias with norm_topk_prob false"),
    ({"layer_types": ["conv", "linear_attention", "full_attention", "conv", "conv"]}, "linear_attention"),
])
def test_keys_that_are_not_computed_raise_by_name(changed, named):
    with pytest.raises(ValueError, match=named):
        config_from_published(published(**changed))


def test_configurations_that_are_not_computed_raise(setting):
    lcfg = setting[0]
    with pytest.raises(ValueError, match="conv_kernel >= 2"):
        dataclasses.replace(lcfg, conv_kernel=0)
    with pytest.raises(ValueError, match="for each of 5 layers"):
        dataclasses.replace(lcfg, layer_types=("conv",))
    with pytest.raises(ValueError, match="not for latent attention"):
        dataclasses.replace(lcfg, q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
                            v_head_dim=8, layer_types=None)


# -- (b) the selection under a bias ------------------------------------------------------

@pytest.mark.parametrize("groups", [(1, 1), (4, 2)], ids=["plain", "group-limited"])
def test_selection_under_a_bias_against_brute_force(groups):
    """The k best of ``s + b`` (of the best groups by ``s + b``) are chosen;
    the gates are the chosen experts' ``s`` over their sum: the bias is not in
    them.  A zero bias, and none, are today's rule."""
    n_group, topk_group = groups
    rng = np.random.default_rng(3)
    s = 1.0 / (1.0 + np.exp(-rng.normal(size=(200, 16)))).astype(np.float32)
    b = (0.2 * rng.normal(size=16)).astype(np.float32)
    gates, experts = moe.route(jnp.asarray(s), 3, n_group, topk_group, True, 1.0, jnp.asarray(b))
    gates, experts = np.asarray(gates), np.asarray(experts)
    by = s + b
    moved = 0
    for t in range(200):
        allowed = np.arange(16)
        if n_group > 1:
            per = 16 // n_group
            group_score = np.sort(by[t].reshape(n_group, per), axis=1)[:, -2:].sum(1)
            keep = np.argsort(-group_score)[:topk_group]
            allowed = np.concatenate([np.arange(g * per, (g + 1) * per) for g in keep])
        want = allowed[np.argsort(-by[t][allowed])[:3]]
        assert list(experts[t]) == list(want)
        assert np.allclose(gates[t], s[t][want] / s[t][want].sum(), rtol=1e-6)
        moved += set(want) != set(np.argsort(-s[t])[:3])
    assert moved > 20
    none = moe.route(jnp.asarray(s), 3, n_group, topk_group)
    zero = moe.route(jnp.asarray(s), 3, n_group, topk_group, bias=jnp.zeros(16))
    assert all(np.array_equal(np.asarray(a), np.asarray(z)) for a, z in zip(none, zero))


# -- (c) the state by rows, through the paged programs -----------------------------------

SLOTS, PTOK, CHUNK = 3, 4, 16


def _paged(lcfg):
    return LlamaLM(dataclasses.replace(lcfg, kv_page_tokens=PTOK, kv_pool_pages=64, state_slots=SLOTS))


def _empty_pool(pm, base, lora):
    tab = jnp.zeros((1, 24), jnp.int32)
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
    tabs = np.zeros((SLOTS, 24), np.int32)
    for r in range(SLOTS):
        tabs[r, :20] = 1 + 20 * r + np.arange(20)
    return tabs


def _tick(pm, base, loras, pool, toks, poss, live, tabs, gather=False):
    """One tick of all lanes as the engine's tick program calls the model: lane
    i is slot i's row, read and written where it lies, and a lane that is not
    live gets the all-trash table and ``seq_lens`` 0 (it holds its row).
    ``gather``: the lanes' rows addressed by index instead, a lane that is not
    live sent to the trash row."""
    stacked = jax.tree_util.tree_map(lambda *rows: jnp.stack(rows), *loras)
    live = np.asarray(live)
    rows = {"state_rows": jnp.asarray(np.where(live, np.arange(SLOTS), SLOTS), jnp.int32)} \
        if gather else {"seq_lens": jnp.asarray(live, jnp.int32)}
    logits, mut = pm.apply(
        {"params": base, "lora": stacked, "cache": pool}, jnp.asarray(toks, jnp.int32)[:, None],
        decode=True, start_pos=jnp.asarray(poss, jnp.int32),
        block_tables=jnp.asarray(np.where(live[:, None], tabs, 0)), mutable=["cache"], **rows)
    return np.asarray(logits[:, 0]), mut["cache"]


def test_three_chunks_then_ticks_agree_by_logits(setting):
    """Two requests on two adapters in slots 0 and 2: each prompt goes in
    over three chunks (the last one padded), the state carried from chunk to
    chunk and handed to the ticks; then both decode in ONE batch whose third
    lane is not live.  Every position's logits against the reference's full
    forward."""
    lcfg, base, _, _ = setting
    pm = _paged(lcfg)
    loras = [weights.make_lora(TINY, 5, index=i + 1) for i in range(3)]
    pool = _empty_pool(pm, base, loras[0])
    assert {name: pool[name]["conv"]["conv_state"].shape for name in pool if "conv" in pool[name]} == {
        f"layer_{i}": (SLOTS + 1, 2, 64) for i in (0, 1, 3, 4)}
    assert pool["layer_2"]["attention"]["k"].shape == (64, PTOK, 2, 16)
    rng = np.random.default_rng(7)
    seqs = {0: rng.integers(1, 256, size=56), 2: rng.integers(1, 256, size=50)}
    prompts = {0: 44, 2: 38}
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


def test_a_first_chunk_starts_from_zeros_whatever_its_row_holds(setting):
    """The reset: a request's first chunk (its first position is 0) into a row
    that holds another request's state, and garbage, gives the logits a clean
    row gives; a later chunk reads what is there."""
    lcfg, base, lora, _ = setting
    pm = _paged(lcfg)
    seq = np.random.default_rng(9).integers(1, 256, size=40)
    tabs = _tables()
    clean, dirty = np.zeros((40, 256)), np.zeros((40, 256))
    _chunks(pm, base, lora, _empty_pool(pm, base, lora), seq, 40, 1, tabs[1], clean)
    soiled = jax.tree_util.tree_map_with_path(
        lambda path, p: p + 3.0 if _is(path, "conv_state") else p, _empty_pool(pm, base, lora))
    pool = _chunks(pm, base, lora, soiled, seq, 40, 1, tabs[1], dirty)
    assert np.array_equal(clean, dirty)
    want, _ = ref.logits(base, lora, jnp.asarray(seq)[None], TINY)
    assert rel(dirty, want[0]) < 2 * TOL
    # the other rows were left alone, and row 1 holds the prompt's last two rows of u
    state = np.asarray(pool["layer_0"]["conv"]["conv_state"])
    assert (state[[0, 2, 3]] == 3.0).all() and not (state[1] == 3.0).any()
    # a chunk that is not a request's first reads its row: soil it and the logits move
    again = np.zeros((40, 256))
    half = _chunks(pm, base, lora, _empty_pool(pm, base, lora), seq, 40, 1, tabs[1], again, upto=16)
    half = jax.tree_util.tree_map_with_path(
        lambda path, p: p + 3.0 if _is(path, "conv_state") else p, half)
    _chunks(pm, base, lora, half, seq, 40, 1, tabs[1], again, start=16)
    assert rel(again[16:], want[0][16:]) > 100 * TOL


@pytest.mark.parametrize("address", ["the trash row", "its own row", "held in place"])
def test_a_slot_between_two_chunks_rides_ticks_and_keeps_its_state(setting, address):
    """The hold: slot 1 has had its first chunk and waits for its second while
    two ticks run for slot 0; its lane is not live and either holds its row in
    place (the engine's tick) or addresses the trash row (the gather form), so
    its second chunk reads what the first left and its logits are those of a
    slot that rode no tick.  Were the lane live on its own row (the fault),
    the ticks' garbage would be what the second chunk reads."""
    lcfg, base, _, _ = setting
    pm = _paged(lcfg)
    loras = [weights.make_lora(TINY, 5, index=i + 1) for i in range(3)]
    rng = np.random.default_rng(13)
    waiting, running = rng.integers(1, 256, size=30), rng.integers(1, 256, size=24)
    tabs = _tables()
    pool = _empty_pool(pm, base, loras[0])
    pool = _chunks(pm, base, loras[0], pool, running, 20, 0, tabs[0], np.zeros((24, 256)))
    got = np.zeros((30, 256))
    pool = _chunks(pm, base, loras[1], pool, waiting, 30, 1, tabs[1], got, upto=16)
    for t in range(2):
        live = [True, address == "its own row", False]
        _, pool = _tick(pm, base, loras, pool, [running[20 + t], 0, 0], [20 + t, 0, 0], live,
                        tabs if address != "its own row" else np.where([[1], [0], [1]], tabs, 0),
                        gather=address == "the trash row")
    _chunks(pm, base, loras[1], pool, waiting, 30, 1, tabs[1], got, start=16)
    want, _ = ref.logits(base, loras[1], jnp.asarray(waiting)[None], TINY)
    if address != "its own row":
        assert rel(got, want[0]) < 2 * TOL
    else:
        assert rel(got[16:], want[0][16:]) > 100 * TOL


@pytest.mark.parametrize("slot", [1, 2], ids=["held between two chunks", "free at position 0"])
def test_a_lane_that_is_not_live_leaves_its_rows_bit_for_bit(setting, slot):
    """``seq_lens`` 0: every convolution layer's row of a lane that is not live
    is what it was, bit for bit, over two ticks, whatever its position (a free
    lane at position 0 does not reset its row); the trash row is not
    touched."""
    lcfg, base, _, _ = setting
    pm = _paged(lcfg)
    loras = [weights.make_lora(TINY, 5, index=i + 1) for i in range(3)]
    rng = np.random.default_rng(19)
    tabs = _tables()
    pool = _empty_pool(pm, base, loras[0])
    for r, n, upto in ((0, 20, None), (1, 30, 16), (2, 18, None)):
        pool = _chunks(pm, base, loras[r], pool, rng.integers(1, 256, size=n), n, r, tabs[r],
                       np.zeros((n, 256)), upto=upto)
    pool = jax.tree_util.tree_map_with_path(
        lambda path, p: p.at[SLOTS].set(3.0) if _is(path, "conv_state") else p, pool)
    after = pool
    for t in range(2):
        _, after = _tick(pm, base, loras, after, [9, 0, 0], [20 + t, 16, 0], [True, False, False], tabs)
    for name in ("layer_0", "layer_1", "layer_3", "layer_4"):
        was, now = (np.asarray(p[name]["conv"]["conv_state"]) for p in (pool, after))
        assert was[slot].any() and np.array_equal(now[slot], was[slot]), name
        assert np.array_equal(now[SLOTS], was[SLOTS]) and not np.array_equal(now[0], was[0]), name


# -- (d) through the engine ---------------------------------------------------------------

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
        seq = np.zeros((1, 96), np.int32)
        seq[0, :len(ids) + m] = ids + out
        got = ref.forced_gaps(base, loras[name], jnp.asarray(seq), len(ids) - 1, 24, TINY)
        worst = max(worst, float(jnp.max((got["gap"] / got["spread"])[:m])))
    return worst


def _requests(seed, sizes):
    rng = np.random.default_rng(seed)
    return [([int(t) for t in rng.integers(1, 256, size=n)], m, f"a{i % 2}")
            for i, (n, m) in enumerate(sizes)]


@pytest.fixture(scope="module")
def served(setting):
    """Seven requests on two adapters through ``ContinuousBatchingEngine`` with
    three slots: prompts of one to four chunks of 16, every slot used again,
    slots that wait between their chunks while the others tick."""
    from fedml_tpu import obs
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=3, buf_len=96, adapter_slots=3,
                                   kv_page_tokens=4, prefill_chunk_tokens=16)
    try:
        loras = {f"a{i}": weights.make_lora(TINY, 5, index=i + 1) for i in range(2)}
        for name, tree in loras.items():
            eng.registry.register(name, tree)
        requests = _requests(11, ((60, 20), (7, 12), (33, 24), (50, 20), (16, 16), (45, 10), (17, 9)))
        outs = _serve(eng, requests)
        stats = eng.kv_stats()
        events = obs.get_tracer().events()
        state = {"dev": sorted(eng._dev), "leaves": {
            jax.tree_util.keystr(p): l.shape for p, l in jax.tree_util.tree_leaves_with_path(eng._pool)}}
    finally:
        eng.stop()
        obs.configure(enabled=False)
    return base, loras, requests, outs, stats, events, state


def test_engine_serves_with_state_beside_the_pool(served):
    base, loras, requests, outs, stats, _, state = served
    assert _gaps(base, loras, requests, outs) < 1e-4
    # one attention layer's K and V in pages; four convolution layers' rows, a row a slot and the trash row
    shapes = sorted(set(state["leaves"].values()))
    assert shapes == [(4, 2, 64), (73, 4, 2, 16)] and len(state["leaves"]) == 4 + 2
    # the carried slot state is the one-pool engine's: the rows are the slots' own numbers
    assert state["dev"] == ["aids", "btabs", "keys", "left", "poss", "temps", "toks"]
    # a token's bytes count pages only; the state is counted apart, whole
    assert stats["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert stats["state_rows"] == 4 and stats["state_bytes"] == 4 * 4 * 2 * 64 * 4
    assert stats["pool"]["exhausted"] == 0 and stats["pages_free"] == 72


def test_single_request_cached_path_is_the_engine(served):
    from fedml_tpu.serving.templates.openai_compat import generate
    base, loras, requests, outs, _, _, _ = served
    lcfg = program_config(TINY, 96, attn_impl="blockwise", remat="none")
    model = LlamaLM(lcfg)
    for (ids, m, name), out in list(zip(requests, outs))[:3]:
        want = generate(None, base, ids, max_new_tokens=m, buf_len=96, model=model, lora=loras[name])
        assert out == want


def test_a_slot_used_again_gives_what_a_fresh_engine_gives(setting):
    """One slot, three requests one after the other, on two adapters: the
    second and third find the slot's rows as the one before left them, and
    get the tokens an engine that has served nothing gives."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    loras = {f"a{i}": weights.make_lora(TINY, 5, index=i + 1) for i in range(2)}
    requests = _requests(17, ((40, 12), (21, 12), (35, 12)))

    def engine():
        eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=1, buf_len=96, adapter_slots=3,
                                       kv_page_tokens=4, prefill_chunk_tokens=16)
        for name, tree in loras.items():
            eng.registry.register(name, tree)
        return eng

    eng = engine()
    try:
        used = [_serve(eng, [r])[0] for r in requests]
    finally:
        eng.stop()
    for r, out in zip(requests[1:], used[1:]):
        eng = engine()
        try:
            assert _serve(eng, [r])[0] == out
        finally:
            eng.stop()
    assert _gaps(base, loras, requests, used) < 1e-4


def test_spans_and_gauges_of_the_state(served):
    from readers import spans as reader
    _, _, requests, _, _, events, _ = served
    spans = reader.paired(events, 0.0)          # the arguments of a span's two events, merged
    ticks = [e for e in spans if e["name"] == "serve.tick"]
    chunks = [e for e in spans if e["name"] == "serve.chunk"]
    assert ticks and all({"state_rows", "state_held"} <= set(e["args"]) for e in ticks)
    assert all(e["args"]["state_rows"] == e["args"]["live"] for e in ticks)
    # a slot sat between two chunks of its prompt while a tick ran
    assert any(e["args"]["state_held"] > 0 for e in ticks)
    assert all(e["args"]["state_rows"] == 1 for e in chunks)
    # every chunk but a request's first carried the state
    firsts = sum(1 for e in chunks if e["args"]["state_carried"] == 0)
    assert firsts == len(requests)
    assert sum(e["args"]["state_carried"] for e in chunks) == sum(
        -(-len(ids) // 16) - 1 for ids, _, _ in requests)
    assert all(e["args"]["state_carried"] == int(e["args"]["start"] > 0) for e in chunks)
    gauges = {e["name"]: e["args"] for e in events if e["ph"] == "C"}
    assert "serve.state_bytes" in gauges


def test_a_tick_that_does_not_write_back_is_seen(setting):
    """One of the rehearsal's planted faults at tier-1 size: after every tick
    the rows of state are what they were before it."""
    import calibrate_lfm2_moe as cal
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    lcfg, base, _, _ = setting
    loras = {"a0": weights.make_lora(TINY, 5, index=1)}
    requests = [(ids, m, "a0") for ids, m, _ in _requests(3, ((40, 20), (35, 20)))]
    with cal.FAULTS["a tick that does not write its lanes' rows back"]():
        eng = ContinuousBatchingEngine(LlamaLM(lcfg), base, slots=2, buf_len=96, adapter_slots=2,
                                       kv_page_tokens=4, prefill_chunk_tokens=16)
        try:
            eng.registry.register("a0", loras["a0"])
            outs = _serve(eng, requests)
        finally:
            eng.stop()
    assert _gaps(base, loras, requests, outs) > 1e-2


def test_a_model_without_conv_layers_builds_no_state():
    from fedml_tpu import obs
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
                      max_seq_len=64, dtype=jnp.float32)
    model = LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    eng = ContinuousBatchingEngine(model, params, slots=2, buf_len=64, kv_page_tokens=4,
                                   prefill_chunk_tokens=8)
    try:
        assert eng.paged_model.cfg.state_slots == 0 and not eng._stateful
        assert all(p.ndim == 4 for p in jax.tree_util.tree_leaves(eng._pool))
        ids = [int(t) for t in np.random.default_rng(5).integers(1, 97, size=29)]
        eng.generate(ids, max_new_tokens=6)
        stats, events = eng.kv_stats(), obs.get_tracer().events()
    finally:
        eng.stop()
        obs.configure(enabled=False)
    assert "state_bytes" not in stats and "state_rows" not in stats
    from readers import spans as reader
    spans = [e for e in reader.paired(events, 0.0) if e["name"] in ("serve.tick", "serve.chunk")]
    assert {e["name"] for e in spans} == {"serve.tick", "serve.chunk"}
    assert not any({"state_rows", "state_held", "state_carried"} & set(e["args"]) for e in spans)
    assert "serve.state_bytes" not in {e["name"] for e in events if e["ph"] == "C"}


def test_heads_narrower_than_a_lane_tile_keep_a_pages_row_flat():
    """8 kv heads of 64, the cell's own, and 2 of 64: a page's row is the heads
    side by side, whole lane tiles, and the pool has no head axis (with a
    trailing axis of half a tile the chip's compiler pads every page to twice
    its size and copies the pool around every write and read:
    tests/test_chip_compile.py).  Rows that are no whole tiles keep the head
    axis.  The engine's greedy tokens are the full forward's."""
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    for kv, hd, leaf in ((2, 64, (29, 4, 128)), (2, 16, (29, 4, 2, 16))):
        cfg = LlamaConfig(vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=kv, head_dim=hd,
                          ffn_dim=64, max_seq_len=64, dtype=jnp.float32, qk_norm=True,
                          layer_types=("conv", "full_attention"), conv_kernel=3)
        model = LlamaLM(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        eng = ContinuousBatchingEngine(model, params, slots=2, buf_len=56, kv_page_tokens=4,
                                       prefill_chunk_tokens=8, kv_pool_pages=29)
        try:
            shapes = {k: v.shape for k, v in eng._pool["layer_1"]["attention"].items()}
            assert shapes == {"k": leaf, "v": leaf}
            assert eng.kv_stats()["kv_bytes_per_token"] == 2 * kv * hd * 4
            ids = [int(t) for t in np.random.default_rng(5).integers(1, 97, size=29)]
            out = eng.generate(ids, max_new_tokens=12)
        finally:
            eng.stop()
        want = np.asarray(model.apply({"params": params}, jnp.asarray([ids + out])))[0]
        assert out == [int(t) for t in want[len(ids) - 1:-1].argmax(-1)]


def test_what_needs_a_snapshot_of_the_state_is_refused_by_name(setting):
    from fedml_tpu.serving.batching import ContinuousBatchingEngine, PagedKVUnsupportedError
    from fedml_tpu.serving.templates.openai_compat import OpenAICompatServer, PrefixCache, generate
    lcfg, base, lora, _ = setting
    model = LlamaLM(lcfg)
    with pytest.raises(PagedKVUnsupportedError, match="convolution layers"):
        ContinuousBatchingEngine(model, base, slots=2, buf_len=96, kv_page_tokens=4, prefix_cache_slots=2)
    apply_fn = lambda params, tokens: model.apply({"params": params, "lora": lora}, tokens)  # noqa: E731
    with pytest.raises(ValueError, match="prefix_cache_slots with a model that has convolution"):
        OpenAICompatServer(apply_fn, base, model=model, buf_len=96, prefix_cache_slots=2)
    with pytest.raises(ValueError, match="draft_model with a model that has convolution"):
        OpenAICompatServer(apply_fn, base, model=model, buf_len=96, draft_model=model, draft_params=base)
    with pytest.raises(ValueError, match="prefix_cache with a model that has convolution"):
        generate(apply_fn, base, [1, 2, 3], max_new_tokens=2, buf_len=96, model=model, lora=lora,
                 prefix_cache=PrefixCache(2))


def test_the_plain_path_keeps_its_compiled_step(setting):
    """``generate`` without ``model`` runs the whole buffer a token, through one jitted step a
    ``(apply_fn, top_k, top_p)``: a second request finds the first's step, and with it its trace."""
    from fedml_tpu.serving.templates import openai_compat as oc
    lcfg, base, lora, tokens = setting
    model = LlamaLM(lcfg)
    traces = []

    def apply_fn(params, toks):
        traces.append(toks.shape)
        return model.apply({"params": params, "lora": lora}, toks)

    assert oc._build_plain_step(apply_fn, 0, 1.0) is oc._build_plain_step(apply_fn, 0, 1.0)
    assert oc._build_plain_step(apply_fn, 4, 1.0) is not oc._build_plain_step(apply_fn, 0, 1.0)
    ids = [int(t) for t in tokens[0, :20]]
    outs = [oc.generate(apply_fn, base, ids, max_new_tokens=3, buf_len=32) for _ in range(2)]
    assert outs[0] == outs[1] and len(traces) == 1, traces
    want = np.asarray(model.apply({"params": base, "lora": lora}, jnp.asarray([ids + outs[0]])))[0]
    assert outs[0] == [int(t) for t in want[len(ids) - 1:-1].argmax(-1)]
