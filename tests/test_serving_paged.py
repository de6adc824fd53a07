"""fedkv (ISSUE 20): the serving memory plane — per-layer KV page
pools + block tables, chunked prefill, copy-on-write prefix page
sharing, and the adapter bank demoted to an N-row cache over the
fedstore tier.  The page pool is the engine's one KV cache (ISSUE 33).

The engine contracts pinned here:

- the engine's output is BIT-IDENTICAL to the single-request cached
  decode, ``generate(model=...)`` over the model's contiguous cache
  (greedy AND sampled, single-stream AND concurrent, incl. multi-token
  horizons and prompts long enough to exercise chunked prefill);
- prefix reuse shares PAGES (refcounts), never copies KV, and every
  page returns to the free list once its sharers drain;
- page exhaustion parks requests (no deadlock, no corruption) and an
  unservable request fails open instead of wedging the pool;
- an in-flight pinned adapter row streams bit-identically while the
  cache evicts and re-pages-in everything around it;
- page churn + adapter miss -> evict -> page-in adds ZERO steady-state
  recompiles (block tables are traced data, free-list bookkeeping is
  host-side);
- a page size of 0, a model without a ``LlamaConfig`` and a speculative
  draft beside ``batch_slots`` are refused by name.
"""

import dataclasses
import os
import queue
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm.model import LlamaConfig, LlamaLM
from fedml_tpu.serving.adapters import AdapterMissError, AdapterRegistry
from fedml_tpu.serving.adapter_store import AdapterStore
from fedml_tpu.serving.batching import (ContinuousBatchingEngine,
                                        PagedKVUnsupportedError)
from fedml_tpu.serving.paged_kv import (PagedBlockPool, PagedPrefixCache,
                                        PageExhaustedError)
from fedml_tpu.serving.templates.openai_compat import generate
from fedml_tpu.store.pager import AsyncRowFetcher

BUF = 48
PTOK = 8


def rand_lora(seed, lora_zeros, scale=0.5):
    """Saturated adapters (A and B nonzero) — identity-init B would make
    every adapter ≡ base and let a wrong-row page-in pass silently."""
    flat, treedef = jax.tree_util.tree_flatten(lora_zeros)
    leaves = [scale * jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), l.shape, l.dtype)
        for i, l in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@pytest.fixture(scope="module")
def paged_setup():
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=BUF,
                      dtype=jnp.float32, attn_impl="blockwise")
    model = LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


@pytest.fixture(scope="module")
def mt_setup():
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=BUF,
                      dtype=jnp.float32, attn_impl="blockwise", lora_rank=4)
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    loras = {f"a{i}": rand_lora(10 + i, variables["lora"])
             for i in range(6)}
    return model, variables["params"], loras


def _drain(q):
    return [t for t in iter(q.get, None)]


def _paged(model, params, slots=4, **kw):
    kw.setdefault("kv_page_tokens", PTOK)
    kw.setdefault("prefill_chunk_tokens", 16)
    return ContinuousBatchingEngine(model, params, slots=slots,
                                    buf_len=BUF, **kw)


def _ref(model, params, prompt, n, temp=0.0, seed=0, eos=None, lora=None,
         buf_len=BUF):
    """The reference of every "bit for bit" test here: the single-request
    cached decode over the model's contiguous ``decode=True`` cache."""
    return generate(None, params, prompt, max_new_tokens=n,
                    temperature=temp, seed=seed, buf_len=buf_len, eos_id=eos,
                    model=model, lora=lora)


# ---------------------------------------------------------------- parity

def test_engine_matches_generate_single_stream(paged_setup):
    """Greedy + sampled single-stream parity, including a prompt long
    enough (40 tokens, chunk 16) that prefill takes three chunks."""
    _, model, params = paged_setup
    paged = _paged(model, params, slots=2)
    prompts = [[5, 17, 42], [7], list(range(1, 41)), [60, 2, 9, 9]]
    try:
        for p in prompts:
            for temp, seed in ((0.0, 0), (0.9, 3)):
                out = paged.generate(p, max_new_tokens=8,
                                     temperature=temp, seed=seed)
                assert out == _ref(model, params, p, 8, temp, seed), (p, temp)
    finally:
        paged.stop()


def test_engine_matches_generate_concurrent_sampled(paged_setup):
    """4 concurrent sampled streams (distinct seeds/temps) through the
    engine equal each request's own ``generate()`` — admission-time key
    splits and per-slot block tables keep streams independent."""
    _, model, params = paged_setup
    paged = _paged(model, params, slots=4)
    reqs = [([5, 17, 42], 0.8, 1), ([7, 7], 0.0, 0),
            (list(range(2, 30)), 0.9, 5), ([60], 0.7, 9)]
    try:
        qs = [paged.submit(p, max_new_tokens=10, temperature=t, seed=s)
              for p, t, s in reqs]
        assert [_drain(q) for q in qs] == \
            [_ref(model, params, p, 10, t, s) for p, t, s in reqs]
    finally:
        paged.stop()


def test_engine_matches_generate_multi_token_horizon(paged_setup):
    _, model, params = paged_setup
    paged = _paged(model, params, slots=2, horizon=4)
    try:
        for p in ([5, 17, 42], list(range(1, 20))):
            assert paged.generate(p, max_new_tokens=9) == \
                _ref(model, params, p, 9)
    finally:
        paged.stop()


# -------------------------------------------- the engine as it is built
#
# A default-constructed engine (slots and buf_len only) keeps its KV in the
# page pool: 16-token pages, 64-token chunks, a page for every position of
# every slot.  Prompt lengths sit on either side of a page's end and of a
# chunk's end.

DEFAULT_BUF = 128


@pytest.fixture(scope="module")
def default_engine():
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=DEFAULT_BUF,
                      dtype=jnp.float32, attn_impl="blockwise")
    model = LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(model, params, slots=3,
                                   buf_len=DEFAULT_BUF)
    yield model, params, eng
    eng.stop()


def test_default_engine_is_paged(default_engine):
    _, _, eng = default_engine
    assert [name for name, *_ in eng.step_programs()] == \
        ["decode_step", "prefill_chunk"]
    assert (eng.kv_page_tokens, eng.prefill_chunk) == (16, 64)
    # the capacity of a cache of buf_len a slot: nothing admitted parks
    assert eng.kv_pool_pages == 1 + 3 * (DEFAULT_BUF // 16)
    pool = jax.tree_util.tree_leaves(eng._pool)
    assert {p.shape[:2] for p in pool} == {(eng.kv_pool_pages, 16)}


@pytest.mark.parametrize("n_prompt", [5, 15, 16, 17, 63, 64, 65, 100])
def test_default_engine_matches_generate(default_engine, n_prompt):
    """Shorter than a page, across a page's end (16), across a chunk's end
    (64) and two chunks deep: greedy and sampled streams equal the
    single-request cached decode bit for bit."""
    model, params, eng = default_engine
    ids = [int(t) for t in
           np.random.default_rng(n_prompt).integers(1, 97, n_prompt)]
    for temp, seed in ((0.0, 0), (0.9, 3)):
        assert eng.generate(ids, max_new_tokens=20, temperature=temp,
                            seed=seed) == \
            _ref(model, params, ids, 20, temp, seed, buf_len=DEFAULT_BUF)
    kv = eng.kv_stats()
    assert kv["pool"]["exhausted"] == 0
    assert kv["pages_free"] == kv["pool_pages"] - 1


@pytest.mark.parametrize("page", [0, -16])
def test_page_size_must_be_positive(paged_setup, page):
    _, model, params = paged_setup
    with pytest.raises(ValueError, match="kv_page_tokens"):
        ContinuousBatchingEngine(model, params, slots=2, buf_len=BUF,
                                 kv_page_tokens=page)


def test_model_without_llama_config_is_refused():
    """The engine rebuilds the model with the pool's geometry: a module
    that carries no ``LlamaConfig`` is refused at construction, before a
    thread or a server is started."""
    import flax.linen as nn

    class Bare(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            return nn.Embed(97, 8)(tokens)

    before = threading.active_count()
    with pytest.raises(PagedKVUnsupportedError, match="LlamaConfig"):
        ContinuousBatchingEngine(Bare(), {}, slots=2, buf_len=BUF,
                                 metrics_port=0)
    assert threading.active_count() == before


# ------------------------------------------------ a finish learned late
#
# The engine launches tick k+1 before it reads tick k (ISSUE 32).  Budget and
# buffer end are counts the host has; only eos is learned from the tokens:
# the slot's lane runs once more for nothing, and its pages go back to the
# pool with that lane's write queued before whatever uses them next.

#: a slot for each of four requests and pages for three, or three slots
#: and the default pool: a page for every position
EOS_CASES = {"paged": dict(slots=4, kv_page_tokens=PTOK, kv_pool_pages=13,
                           prefill_chunk_tokens=16),
             "paged_horizon3": dict(slots=4, kv_page_tokens=PTOK, horizon=3,
                                    kv_pool_pages=13, prefill_chunk_tokens=16),
             "three_slots": dict(slots=3)}


@pytest.mark.parametrize("case", sorted(EOS_CASES))
def test_eos_mid_stream_matches_generate_and_frees_its_pages(paged_setup, case):
    """One slot ends by eos mid-stream while its neighbours go on: every
    request's tokens equal ``generate()``'s, the lane that ran on is
    counted, all pages are free after the drain, and the request that was
    admitted into the freed pages (the pool holds no others for it) reads
    bit-equal."""
    _, model, params = paged_setup

    def ref(prompt, n, temp=0.0, seed=0, eos=None):
        return _ref(model, params, prompt, n, temp, seed, eos)

    first = [5, 17, 42, 8, 3]
    eos = ref(first, 12)[5]
    # 3 + 5 + 3 of the 12 usable pages: the fourth request's 2 pages are
    # there only once the first has ended, by eos, and given its 3 back
    reqs = [(first, 12, 0.0, 0, eos), (list(range(1, 21)), 14, 0.8, 3, None),
            ([7, 9, 2], 14, 0.0, 0, None), ([60, 2, 9, 9, 31, 4], 10, 0.7, 5, None)]
    want = [ref(*r) for r in reqs]
    assert 0 < len(want[0]) <= 5 and [len(w) for w in want[1:]] == [14, 14, 10]
    eng = ContinuousBatchingEngine(model, params, buf_len=BUF,
                                   **EOS_CASES[case])
    try:
        with eng._cond:         # all four before the loop's next pass
            qs = [eng.submit(p, max_new_tokens=n, temperature=t, seed=s, eos_id=e)
                  for p, n, t, s, e in reqs]
        assert [_drain(q) for q in qs] == want
        kv = eng.kv_stats()
        assert kv["lanes_burned"] >= 1 and kv["ticks_ahead"] > 0
        # the fourth had to wait: for pages, or for one of three slots
        assert (kv["pool"]["exhausted"] >= 1) == ("kv_pool_pages"
                                                  in EOS_CASES[case])
        assert kv["pages_free"] == kv["pool_pages"] - 1
    finally:
        eng.stop()


# ------------------------------------------------ where a write lands
#
# The pool is (pool_pages, page_tokens, h_kv, d): ``pool[page, offset]`` is
# one token's row for every kv head.  Each case drives the paged model the
# way a tick (s=1) or a chunk (b=1) does, holds its logits to the dense
# cache's bit for bit, and then reads the pool itself: the row is where the
# block table says, and no other page was touched.

def _paged_model(cfg, pages, **kw):
    return LlamaLM(dataclasses.replace(cfg, kv_page_tokens=PTOK,
                                       kv_pool_pages=pages, **kw))


def _apply(m, params, cache, toks, start, btab=None):
    """One decode call: paged with a block table (``start`` per slot), the
    dense cache's without (``start`` a scalar).  ``cache=None`` makes it."""
    variables = {"params": params}
    if cache is not None:
        variables["cache"] = cache
    logits, mut = m.apply(
        variables, jnp.asarray(toks, jnp.int32), decode=True,
        start_pos=jnp.asarray(start, jnp.int32),
        block_tables=None if btab is None else jnp.asarray(btab, jnp.int32),
        mutable=["cache"])
    return np.asarray(logits), mut["cache"]


def _leaf(cache, name, layer=1):
    return np.asarray(cache[f"layer_{layer}"]["attention"][name])


def _touched(pool, name="k"):
    """Pages of the layer-1 pool that hold anything but zeros."""
    a = _leaf(pool, name)
    return sorted(np.flatnonzero(a.reshape(a.shape[0], -1).any(axis=1)))


def _case_page_boundary(cfg, model, params):
    """Ticks across a page's end: position 7 is the last row of page 3,
    position 8 the first of page 5."""
    pm = _paged_model(cfg, 8)
    btab = [[3, 5, 0, 0, 0, 0]]
    pool = cache = None
    for pos, tok in enumerate(range(11, 20)):           # positions 0..8
        lp, pool = _apply(pm, params, pool, [[tok]], [pos], btab)
        ld, cache = _apply(model, params, cache, [[tok]], pos)
        assert np.array_equal(lp, ld), pos
    for name in ("k", "v"):
        got, want = _leaf(pool, name), _leaf(cache, name)
        assert np.array_equal(got[3, 7], want[0, :, 7])
        assert np.array_equal(got[5, 0], want[0, :, 8])
        assert np.array_equal(got[3], want[0, :, :8].transpose(1, 0, 2))
        assert not got[5, 1:].any()
        assert _touched(pool, name) == [3, 5]


def _case_chunk_padding_trash(cfg, model, params):
    """A 16-token chunk over a 5-token prompt that reserved one page: rows
    8..15 are padding, their block-table entry is 0, they land in page 0."""
    pm = _paged_model(cfg, 8)
    chunk = [[21, 22, 23, 24, 25] + [0] * 11]
    lp, pool = _apply(pm, params, None, chunk, [0], [[2, 0, 0, 0, 0, 0]])
    ld, cache = _apply(model, params, None, chunk, 0)
    assert np.array_equal(lp[0, :5], ld[0, :5])
    for name in ("k", "v"):
        got, want = _leaf(pool, name), _leaf(cache, name)
        assert np.array_equal(got[2], want[0, :, :8].transpose(1, 0, 2))
        assert np.array_equal(got[0], want[0, :, 8:16].transpose(1, 0, 2))
        assert _touched(pool, name) == [0, 2]


def _case_idle_slots_trash(cfg, model, params):
    """A tick with two idle slots (zero block tables, position 0) beside a
    live one: both write row 0 of the trash page at once, and the live
    slot's row and logits are what the same tick gives with the idle slots
    on pages of their own."""
    pm = _paged_model(cfg, 8)
    live = [4, 0, 0, 0, 0, 0]
    _, pool = _apply(pm, params, None, [[31, 32, 33]], [0], [live])
    toks, poss = [[0], [34], [0]], [0, 3, 0]
    apart_l, apart = _apply(
        pm, params, pool, toks, poss,
        [[6, 0, 0, 0, 0, 0], live, [7, 0, 0, 0, 0, 0]])
    both_l, both = _apply(pm, params, pool, toks, poss,
                          [[0] * 6, live, [0] * 6])
    assert np.array_equal(both_l, apart_l)
    for name in ("k", "v"):
        got = _leaf(both, name)
        assert np.array_equal(got[4], _leaf(apart, name)[4])
        assert got[4, 3].any()
        assert np.array_equal(got[0, 0], _leaf(apart, name)[6, 0])
        assert _touched(both, name) == [0, 4]
        assert not got[0, 1:].any()


def _case_sharer_keeps_lent_pages(cfg, model, params):
    """Two full pages lent to a second slot: its replay starts past them
    (a chunk from position 16, then a tick beside the owner), reads them,
    and leaves them bit for bit as the owner wrote them."""
    pm = _paged_model(cfg, 10)
    prefix = [list(range(40, 56))]                        # pages 2 and 3
    owner, sharer = [2, 3, 7, 0, 0, 0], [2, 3, 6, 0, 0, 0]
    _, pool = _apply(pm, params, None, prefix, [0], [owner])
    _, cache = _apply(model, params, None, prefix, 0)
    lent = {n: _leaf(pool, n)[[2, 3]].copy() for n in ("k", "v")}
    tail = [[61, 62, 63] + [0] * 13]
    lp, pool = _apply(pm, params, pool, tail, [16], [sharer])
    ld, cache = _apply(model, params, cache, tail, 16)
    assert np.array_equal(lp[0, :3], ld[0, :3])
    # one tick of both: the owner at 16 in its own page 7, the sharer at
    # 19 (two slots against the dense cache's one: close, not bitwise)
    lp, pool = _apply(pm, params, pool, [[70], [64]], [16, 19],
                      [owner, sharer])
    ld, cache = _apply(model, params, cache, [[64]], 19)
    np.testing.assert_allclose(lp[1], ld[0], rtol=1e-5, atol=1e-5)
    for name in ("k", "v"):
        got, want = _leaf(pool, name), _leaf(cache, name)
        assert np.array_equal(got[[2, 3]], lent[name])
        assert np.array_equal(got[6, :3],
                              want[0, :, 16:19].transpose(1, 0, 2))
        np.testing.assert_allclose(got[6, 3], want[0, :, 19], rtol=1e-5,
                                   atol=1e-6)
        assert got[7, 0].any() and not got[7, 1:].any()


def _case_int8_pools_and_scales(cfg, model, params):
    """The int8 twin: four pools a layer; a chunk and a tick across a page's
    end put each row's values and its scale where the dense int8 cache has
    them."""
    dense8 = LlamaLM(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    pm = _paged_model(cfg, 8, kv_cache_dtype="int8")
    btab = [[5, 1, 0, 0, 0, 0]]
    chunk = [list(range(3, 11))]                          # positions 0..7
    lp, pool = _apply(pm, params, None, chunk, [0], btab)
    ld, cache = _apply(dense8, params, None, chunk, 0)
    assert np.array_equal(lp, ld)
    lp, pool = _apply(pm, params, pool, [[12]], [8], btab)
    ld, cache = _apply(dense8, params, cache, [[12]], 8)
    assert np.array_equal(lp, ld)
    for name in ("k", "v"):
        got, want = _leaf(pool, name), _leaf(cache, name)
        assert got.dtype == np.int8 and got.shape == (8, PTOK, 2, 8)
        assert np.array_equal(got[5], want[0, :, :8].transpose(1, 0, 2))
        assert np.array_equal(got[1, 0], want[0, :, 8])
        sc, want_sc = _leaf(pool, name + "_scale"), \
            _leaf(cache, name + "_scale")
        assert sc.shape == (8, PTOK, 2)
        assert np.array_equal(sc[5], want_sc[0, :, :8].T)
        assert np.array_equal(sc[1, 0], want_sc[0, :, 8])
        assert _touched(pool, name) == [1, 5]
        assert _touched(pool, name + "_scale") == [1, 5]


def _case_horizon4(cfg, model, params):
    """horizon=4: the pool is the scan's carry.  Ten tokens burn two lanes
    of the third dispatch past the reservation (into the trash page), the
    answers cross a page's end mid-horizon, and the streams are those of
    the single-request decode one token at a time."""
    paged = _paged(model, params, slots=2, horizon=4)
    reqs = [([5, 17, 42, 8, 9], 0.0, 0), (list(range(1, 15)), 0.8, 3)]
    try:
        qs = [paged.submit(p, max_new_tokens=10, temperature=t, seed=s)
              for p, t, s in reqs]
        assert [_drain(q) for q in qs] == \
            [_ref(model, params, p, 10, t, s) for p, t, s in reqs]
        kv = paged.kv_stats()
        assert kv["pages_free"] == kv["pool_pages"] - 1
    finally:
        paged.stop()


@pytest.mark.parametrize("case", [
    _case_page_boundary, _case_chunk_padding_trash, _case_idle_slots_trash,
    _case_sharer_keeps_lent_pages, _case_int8_pools_and_scales,
    _case_horizon4], ids=lambda f: f.__name__[len("_case_"):])
def test_paged_write_lands_where_the_block_table_says(paged_setup, case):
    case(*paged_setup)


# -------------------------------------------- pages, sharing, parking

def test_prefix_page_sharing_and_release(paged_setup):
    """A repeated prompt shares its full prefix pages (COW refcounts, no
    KV copies): outputs stay identical, kv_stats shows shared pages, and
    after the engine drains every page is back on the free list."""
    _, model, params = paged_setup
    eng = _paged(model, params, slots=2, prefix_cache_slots=4)
    prompt = list(range(3, 27))  # 24 tokens = 3 full pages
    try:
        first = eng.generate(prompt, max_new_tokens=6)
        again = eng.generate(prompt, max_new_tokens=6)
        assert again == first
        kv = eng.kv_stats()
        assert kv["prefix"]["hits"] >= 1
        assert kv["pages_shared"] > 0
    finally:
        eng.stop()


def test_all_pages_free_after_drain(paged_setup):
    _, model, params = paged_setup
    eng = _paged(model, params, slots=3)
    try:
        qs = [eng.submit([i + 1, i + 2, i + 3], max_new_tokens=12)
              for i in range(6)]
        for q in qs:
            assert len(_drain(q)) == 12
        kv = eng.kv_stats()
        assert kv["pages_free"] == kv["pool_pages"] - 1  # page 0 = trash
    finally:
        eng.stop()


def test_page_exhaustion_parks_and_completes(paged_setup):
    """A pool too small for all slots at once: late requests park on
    page exhaustion and complete as earlier slots free pages — every
    stream still matches its own ``generate()``."""
    _, model, params = paged_setup
    # 4 slots want up to ceil((3+12)/8)=2 pages each; 5 usable pages
    # means at most 2 concurrent — the rest must park, not fail
    eng = _paged(model, params, slots=4, kv_pool_pages=6)
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
        qs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        outs = [_drain(q) for q in qs]
        assert outs == [_ref(model, params, p, 12) for p in prompts]
        kv = eng.kv_stats()
        assert kv["pool"]["exhausted"] >= 1
        assert kv["pages_free"] == kv["pool_pages"] - 1
    finally:
        eng.stop()


def test_unservable_request_fails_open(paged_setup):
    """A request whose worst case exceeds the whole pool can never be
    admitted — it must fail open (empty stream) without wedging the
    engine or leaking pages."""
    _, model, params = paged_setup
    eng = _paged(model, params, slots=2, kv_pool_pages=3)
    try:
        # needs ceil(min(40+8, BUF)/8) = 6 pages > 2 usable
        big = eng.submit(list(range(1, 41)), max_new_tokens=8)
        assert _drain(big) == []
        # engine still serves requests that do fit
        small = eng.submit([5, 17, 42], max_new_tokens=4)
        assert len(_drain(small)) == 4
        kv = eng.kv_stats()
        assert kv["pages_free"] == kv["pool_pages"] - 1
    finally:
        eng.stop()


# ------------------------------------------------- adapter cache mode

def test_adapter_cache_mode_matches_bank_engine(mt_setup):
    """6 adapters through a 3-row cache over the store equal the plain
    full-bank engine's outputs and the single-request decode under the
    adapter's own tree, with evictions actually happening."""
    model, params, loras = mt_setup
    bank = ContinuousBatchingEngine(model, params, slots=2, buf_len=BUF,
                                    adapter_slots=8)
    cache = _paged(model, params, slots=2, adapter_cache_slots=3)
    try:
        for n, t in loras.items():
            bank.registry.register(n, t)
            cache.registry.register(n, t)
        names = sorted(loras) + sorted(loras)  # revisit all -> refetches
        for i, n in enumerate(names):
            p = [3 + i, 11, 19]
            want = _ref(model, params, p, 5, lora=loras[n])
            assert cache.generate(p, max_new_tokens=5, adapter=n) == want, n
            assert bank.generate(p, max_new_tokens=5, adapter=n) == want, n
        st = cache.registry.stats
        assert st["cache_evictions"] > 0
        assert st["cache_misses"] >= len(loras)
        assert st["cache_hits"] + st["cache_misses"] > 0
    finally:
        bank.stop()
        cache.stop()


def test_pinned_inflight_row_bit_identical_across_churn(mt_setup):
    """The acceptance pin: a long in-flight stream on adapter a0 stays
    BIT-IDENTICAL while every other cache row is evicted and re-paged-in
    around it (a0's row is pinned; eviction may only zombie it)."""
    model, params, loras = mt_setup
    quiet = _paged(model, params, slots=4, adapter_cache_slots=2)
    churn = _paged(model, params, slots=4, adapter_cache_slots=2)
    try:
        for eng in (quiet, churn):
            for n, t in loras.items():
                eng.registry.register(n, t)
        ref = quiet.generate([5, 17, 42], max_new_tokens=20, adapter="a0")

        out_q = churn.submit([5, 17, 42], max_new_tokens=20, adapter="a0")
        got = [out_q.get(timeout=60)]  # a0 is live and pinned from here
        churners = []
        for i in range(1, 6):  # 5 other adapters through 2 rows
            churners.append(churn.submit([7, i], max_new_tokens=3,
                                         adapter=f"a{i}"))
        got += _drain(out_q)
        for q in churners:
            assert len(_drain(q)) == 3
        assert got == ref
        assert churn.registry.stats["cache_evictions"] > 0
    finally:
        quiet.stop()
        churn.stop()


def test_cache_mode_unknown_adapter_fails_at_submit(mt_setup):
    model, params, loras = mt_setup
    eng = _paged(model, params, slots=2, adapter_cache_slots=2)
    try:
        eng.registry.register("a0", loras["a0"])
        with pytest.raises(KeyError):
            eng.submit([1, 2], max_new_tokens=2, adapter="nope")
    finally:
        eng.stop()


def _bank_bytes(eng):
    return sum(np.asarray(x).nbytes
               for x in jax.tree_util.tree_leaves(eng.registry.bank))


def test_adapter_store_scales_names_flat_bank(mt_setup, tmp_path):
    """Registered names scale far past the bank — 32, then 10,000 names
    through 2 rows with a disk spill tier — while the resident bank bytes
    stay constant, and a name from the far end of the store still serves
    the stream of its own tree."""
    model, params, loras = mt_setup
    eng = _paged(model, params, slots=2, adapter_cache_slots=2,
                 adapter_store_dir=str(tmp_path))
    try:
        seed = jax.tree_util.tree_map(np.asarray, loras["a0"])

        def tree(i):
            return jax.tree_util.tree_map(
                lambda x: x * np.float32(1.0 + (i % 64) / 64.0), seed)

        bank0 = _bank_bytes(eng)
        registered = 0
        for names in (32, 10_000):
            for i in range(registered, names):
                eng.registry.register(f"n{i}", tree(i))
            registered = names
            assert len(eng.registry.store) == names
            for i in (0, 17, names - 1, 5):
                assert eng.generate([2, 3, 5], max_new_tokens=3,
                                    adapter=f"n{i}") == \
                    _ref(model, params, [2, 3, 5], 3, lora=tree(i)), i
            assert _bank_bytes(eng) == bank0  # flat HBM at every scale
        assert eng.registry.stats["cache_evictions"] > 0
    finally:
        eng.stop()


# ------------------------------------------------ recompiles, refusal

def test_zero_steady_state_recompiles_under_churn(mt_setup):
    """Page churn + prefix sharing + adapter miss -> evict -> page-in
    cycles reuse the warmed programs: JaxRuntimeAudit counts ZERO
    backend compiles."""
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    model, params, loras = mt_setup
    eng = _paged(model, params, slots=3, adapter_cache_slots=2,
                 prefix_cache_slots=4, kv_pool_pages=20)
    try:
        for n, t in loras.items():
            eng.registry.register(n, t)
        # warm: base + adapter + chunked-prefill + sampled programs
        eng.generate([5, 17, 42], max_new_tokens=2)
        eng.generate([5, 17, 42], max_new_tokens=2, adapter="a0")
        eng.generate(list(range(1, 40)), max_new_tokens=2)
        eng.generate([5, 17, 42], max_new_tokens=2, temperature=0.8)
        with JaxRuntimeAudit() as audit:
            mix = [None, "a0", "a3", "a5", "a1", "a4", None, "a2"]
            qs = [eng.submit([i + 1, i + 2, i + 3], max_new_tokens=6,
                             temperature=0.5 * (i % 2), seed=i,
                             adapter=mix[i % len(mix)])
                  for i in range(8)]
            for q in qs:
                _drain(q)
        assert audit.compilations == 0
    finally:
        eng.stop()


def test_mixed_run_with_parking_compiles_nothing_and_leaks_no_page(paged_setup):
    """Twelve requests of mixed lengths, greedy and sampled, on four slots
    over pages for about two of them: requests park for pages and for
    slots, prompts take one to three chunks.  After the warm-up nothing
    compiles, and after the drain every page is back on the free list."""
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    _, model, params = paged_setup
    eng = _paged(model, params, slots=4, kv_pool_pages=9)
    rng = np.random.default_rng(0)
    reqs = [([int(t) for t in rng.integers(1, 97, n)], 0.7 * (i % 2), i)
            for i, n in enumerate([3, 20, 7, 33, 1, 16, 17, 9, 30, 2, 12, 24])]
    try:
        eng.generate([5, 17, 42], max_new_tokens=2)
        eng.generate(list(range(1, 40)), max_new_tokens=2, temperature=0.8)
        with JaxRuntimeAudit() as audit:
            qs = [eng.submit(p, max_new_tokens=8, temperature=t, seed=s)
                  for p, t, s in reqs]
            outs = [_drain(q) for q in qs]
        assert audit.compilations == 0
        kv = eng.kv_stats()
        assert kv["pool"]["exhausted"] >= 1
        assert kv["pool_pages"] - 1 - kv["pages_free"] == 0     # leaked
        assert kv["pool"]["reserved_pages"] == kv["pool"]["released_pages"]
    finally:
        eng.stop()
    assert outs == [_ref(model, params, p, 8, t, s) for p, t, s in reqs]


def test_server_refuses_a_draft_beside_batch_slots(paged_setup):
    """Speculation writes multi-token verify blocks into contiguous
    per-request caches: beside the engine's page pool it is refused at
    construction by the named error, which says where speculation lives;
    without ``batch_slots`` the same server is built."""
    from fedml_tpu.serving.templates.openai_compat import OpenAICompatServer
    _, model, params = paged_setup

    def apply_fn(p, t):
        return model.apply({"params": p}, t)

    with pytest.raises(PagedKVUnsupportedError, match="speculative_generate"):
        OpenAICompatServer(apply_fn, params, buf_len=BUF, model=model,
                           batch_slots=2, draft_model=model,
                           draft_params=params)
    srv = OpenAICompatServer(apply_fn, params, buf_len=BUF, model=model,
                             draft_model=model, draft_params=params)
    assert srv._engine is None and srv.draft_model is model


def test_server_knob_validation(paged_setup):
    from fedml_tpu.serving.templates.openai_compat import OpenAICompatServer
    cfg, model, params = paged_setup

    def apply_fn(p, t):
        return model.apply({"params": p}, t)

    with pytest.raises(ValueError, match="batch_slots"):
        OpenAICompatServer(apply_fn, params, buf_len=BUF, model=model,
                           kv_page_tokens=PTOK)
    with pytest.raises(ValueError, match="mutually"):
        OpenAICompatServer(apply_fn, params, buf_len=BUF, model=model,
                           batch_slots=2, adapter_cache_slots=2,
                           adapter_slots=4)
    with pytest.raises(PagedKVUnsupportedError):
        OpenAICompatServer(apply_fn, params, buf_len=BUF, model=model,
                           batch_slots=2, kv_page_tokens=PTOK,
                           draft_model=model, draft_params=params)


# ------------------------------------------------------- unit pieces

def test_paged_block_pool_refcounts():
    pool = PagedBlockPool(6)  # page 0 reserved
    assert pool.pages_free == 5
    a = pool.reserve(3)
    assert 0 not in a and pool.pages_free == 2
    pool.share(a[:2])  # second reference on two pages
    pool.release(a)    # drops the first reference
    assert pool.pages_free == 3  # a[2] free; a[0], a[1] still shared
    with pytest.raises(PageExhaustedError):
        pool.reserve(4)
    pool.release(a[:2])
    assert pool.pages_free == 5


def test_paged_prefix_cache_cow():
    pool = PagedBlockPool(10)
    cache = PagedPrefixCache(capacity=2, page_tokens=4, pool=pool)
    params = object()
    prompt = list(range(12))  # 3 full pages
    pages = pool.reserve(3)
    cache.insert(prompt, pages, params, None)
    pool.release(pages)  # caller done; the cache's reference keeps them
    full, lent = cache.lookup(prompt, params, None)
    # full-page span always leaves the final token to replay
    assert full == 2 and lent == pages[:2]
    miss_full, _ = cache.lookup([99, 98], params, None)
    assert miss_full == 0
    # adapter-token pinning: another version never shares
    assert cache.lookup(prompt, params, object())[0] == 0
    # params swap flushes and releases everything
    cache.lookup(prompt, object(), None)
    assert pool.pages_free == 9


def test_paged_prefix_cache_evict_for_pages():
    pool = PagedBlockPool(8)
    cache = PagedPrefixCache(capacity=4, page_tokens=4, pool=pool)
    params = object()
    p1, p2 = pool.reserve(3), pool.reserve(3)
    cache.insert(list(range(12)), p1, params, None)
    cache.insert(list(range(50, 62)), p2, params, None)
    pool.release(p1)
    pool.release(p2)
    assert pool.pages_free == 1
    dropped = cache.evict_for_pages(4)
    assert dropped >= 1 and pool.pages_free >= 4


def test_adapter_store_roundtrip(mt_setup, tmp_path):
    model, _, loras = mt_setup
    store = AdapterStore(model, registered=128, max_resident_pages=2,
                         spill_dir=str(tmp_path))
    tree = jax.tree_util.tree_map(np.asarray, loras["a1"])
    store.put("x", tree)
    store.put("y", jax.tree_util.tree_map(lambda a: a * 2.0, tree))
    assert "x" in store and "z" not in store
    got = store.get("x")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(KeyError):
        store.get("z")
    store.remove("x")
    assert "x" not in store and len(store) == 1


def test_async_row_fetcher():
    done = threading.Event()
    f = AsyncRowFetcher(on_done=lambda k: done.set())
    try:
        assert f.request("k", lambda: 41 + 1) is True
        assert done.wait(timeout=10)
        ok, val = f.take("k")
        assert ok and val == 42
        assert f.take("k") == (False, None)  # pop-once
        # errors park and re-raise on take, not on the worker thread
        done.clear()
        f.request("bad", lambda: 1 / 0)
        assert done.wait(timeout=10)
        with pytest.raises(ZeroDivisionError):
            f.take("bad")
    finally:
        f.close()


def test_estimate_paged_serving_memory():
    from fedml_tpu.core.memory_estimate import estimate_paged_serving_memory
    est = estimate_paged_serving_memory(
        n_params=1e6, n_slots=8, pool_bytes=64 * 2**20,
        block_table_bytes=8 * 64 * 4, window_bytes=2 * 2**20,
        vocab_size=97, horizon=1, bank_bytes=2**20)
    assert est["kv_pool"] == 64 * 2**20
    assert est["adapter_bank"] == 2**20
    # step work prices the gather window + logits + jit slack, but NO
    # cache copy — the pool is donated into the step
    assert est["step_work"] == pytest.approx(
        2 * 2**20 + 8 * 97 * 4.0 + est["params"] * 0.25)
    assert est["total"] == pytest.approx(1.25 * (
        est["params"] + est["kv_pool"] + est["block_tables"]
        + est["adapter_bank"] + est["step_work"]))
    assert est["total_gib"] == pytest.approx(est["total"] / 2**30)
