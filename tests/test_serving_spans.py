"""The engine thread's live span tree (ISSUE 27): one ``serve.iter`` per loop
pass with ``serve.admit`` / ``serve.chunk`` / ``serve.tick`` under it, on the
tracer's per-thread stack; args are host ints; off means no event and no
transfer.  Since ISSUE 32 a tick is launched before the one before it is
read: its ``readback`` / ``emit`` / ``free`` serve the previous dispatch, a
``serve.flush`` reads the last one of a drain, and a steady tick uploads
nothing."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import obs
from fedml_tpu.llm.model import LlamaConfig, LlamaLM
from fedml_tpu.obs.jaxhooks import count_put
from fedml_tpu.serving.batching import ContinuousBatchingEngine

from tests.span_tree import children, named, spans_of

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import fedtrace  # noqa: E402

BUF, PTOK, CHUNK = 64, 8, 16
#: prompt, answer length, adapter: one prompt of three chunks, one of two
REQUESTS = [(list(range(1, 41)), 6, "a0"), ([5, 17, 42], 9, None),
            (list(range(3, 23)), 4, "a1"), ([7], 5, "a0"),
            (list(range(2, 35)), 7, None)]


@pytest.fixture(scope="module")
def mt_model():
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=BUF,
                      dtype=jnp.float32, attn_impl="blockwise", lora_rank=4)
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten(variables["lora"])
    loras = {f"a{i}": jax.tree_util.tree_unflatten(treedef, [
        0.5 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(10 + i), j),
                                l.shape, l.dtype) for j, l in enumerate(leaves)])
        for i in range(2)}
    return model, variables["params"], loras


def paged_engine(mt_model):
    model, params, loras = mt_model
    eng = ContinuousBatchingEngine(model, params, slots=3, buf_len=BUF,
                                   adapter_slots=4, kv_page_tokens=PTOK,
                                   prefill_chunk_tokens=CHUNK)
    for name, tree in loras.items():
        eng.registry.register(name, tree)
    return eng


def serve_all(eng, requests=REQUESTS, **kw):
    # under the engine's condition (re-entrant): the loop sees all of them
    # at once, so what it does with them does not depend on the threads
    with eng._cond:
        qs = [eng.submit(p, max_new_tokens=n, adapter=a, **kw)
              for p, n, a in requests]
    return [[t for t in iter(q.get, None)] for q in qs]


@pytest.fixture(scope="module")
def traced(mt_model):
    """One traced run of a paged multi-adapter engine: its events, its spans
    and what the callers received."""
    tracer = obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        eng = paged_engine(mt_model)
        try:
            outputs = serve_all(eng)
            stats = eng.kv_stats()
        finally:
            eng.stop()
        events = tracer.export_chrome()["traceEvents"]
    finally:
        obs.configure(enabled=False, reset=True)
    return {"events": events, "outputs": outputs, "stats": stats,
            "spans": spans_of([e for e in events if e["ph"] in "BE"])}


def test_every_iteration_leaves_a_tree_that_nests(traced):
    assert fedtrace.validate_events(traced["events"]) == []
    spans = traced["spans"]
    by_id = {s["id"]: s for s in spans}
    iters = named(spans, "serve.iter")
    assert iters and [s["args"]["iter"] for s in iters] == \
        sorted(s["args"]["iter"] for s in iters)
    assert all({"live", "prefilling", "queued"} <= set(s["args"]) for s in iters)
    engine_tid = iters[0]["tid"]
    # the three phases that serve a dispatch already made also run under
    # the flush that reads the last one of a drain
    served = ("serve.tick", "serve.flush")
    want_parent = {"serve.admit": "serve.iter", "serve.chunk": "serve.iter",
                   "serve.tick": "serve.iter", "serve.flush": "serve.iter",
                   "serve.chunk.gather": "serve.chunk",
                   "serve.chunk.dispatch": "serve.chunk",
                   "serve.tick.stage": "serve.tick", "serve.tick.dispatch": "serve.tick",
                   "serve.tick.readback": served, "serve.tick.emit": served,
                   "serve.tick.free": served}
    seen = set()
    for s in spans:
        if s["name"] in want_parent:
            parent = by_id[s["parent"]]
            assert parent["name"] in want_parent[s["name"]], s
            assert parent["t0"] <= s["t0"] and s["t1"] <= parent["t1"], s
            assert s["tid"] == engine_tid
            seen.add(s["name"])
    assert seen == set(want_parent)
    # a tick's five phases follow one another inside it
    for tick in named(spans, "serve.tick"):
        kids = children(spans, tick)
        assert [k["name"] for k in kids] == [
            "serve.tick.stage", "serve.tick.dispatch", "serve.tick.readback",
            "serve.tick.emit", "serve.tick.free"]
        assert all(a["t1"] <= b["t0"] for a, b in zip(kids, kids[1:]))
    for flush in named(spans, "serve.flush"):
        assert [k["name"] for k in children(spans, flush)] == [
            "serve.tick.readback", "serve.tick.emit", "serve.tick.free"]
    # no chunk waits for its token any more
    assert not named(spans, "serve.chunk.readback")
    # every arg is a host number or a string, never a device value
    for s in spans:
        assert all(isinstance(v, (int, float, str)) for v in s["args"].values()), s


def test_tick_tokens_are_what_the_callers_received(traced):
    received = sum(len(o) for o in traced["outputs"])
    assert [len(o) for o in traced["outputs"]] == [n for _, n, _ in REQUESTS]
    ticks = named(traced["spans"], "serve.tick")
    # a tick delivers what the dispatch before it produced, the flush what
    # the last one did; the first token of a request comes from its final
    # chunk and is counted apart
    served = ticks + named(traced["spans"], "serve.flush")
    assert sum(t["args"]["tokens"] for t in served) == received - len(REQUESTS)
    assert sum(t["args"].get("first_tokens", 0) for t in served) == len(REQUESTS)
    assert all(0 <= t["args"]["tokens"] <= 3 and 1 <= t["args"]["live"] <= 3
               for t in ticks)
    assert all(t["args"]["live_kv_tokens"] > 0 for t in ticks)
    finished = sum(s["args"]["finished"] for s in named(traced["spans"], "serve.tick.emit"))
    assert 0 < finished <= len(REQUESTS)


def test_chunks_of_a_request_carry_its_id_and_cover_its_prompt(traced):
    spans = traced["spans"]
    admits = sorted(named(spans, "serve.admit"), key=lambda s: s["args"]["request"])
    assert [s["args"]["request"] for s in admits] == [1, 2, 3, 4, 5]
    chunks = named(spans, "serve.chunk")
    for rid, (prompt, _, _) in enumerate(REQUESTS, start=1):
        mine = sorted((c for c in chunks if c["args"]["request"] == rid),
                      key=lambda c: c["t0"])
        assert sum(c["args"]["tokens"] for c in mine) == len(prompt)
        assert [c["args"]["start"] for c in mine] == list(range(0, len(prompt), CHUNK))
        assert [c["args"]["final"] for c in mine] == [0] * (len(mine) - 1) + [1]
        assert len({c["args"]["slot"] for c in mine}) == 1
    gathers = named(spans, "serve.chunk.gather")
    assert len(gathers) == len(chunks) and all("adapter_row" in g["args"] for g in gathers)


def test_the_request_tree_shares_the_id_and_stays_on_its_own_lane(traced):
    spans = traced["spans"]
    engine_tid = named(spans, "serve.iter")[0]["tid"]
    for name in ("serve.request", "serve.queue", "serve.decode"):
        rows = named(spans, name)
        assert sorted(s["args"]["request"] for s in rows) == [1, 2, 3, 4, 5]
        assert all(s["tid"] < 0 and s["tid"] != engine_tid for s in rows)
    for req in named(spans, "serve.request"):
        prompt, n, adapter = REQUESTS[req["args"]["request"] - 1]
        assert req["args"]["prompt_tokens"] == len(prompt)
        assert req["args"]["output_tokens"] == n
        assert req["args"]["adapter"] == (adapter or "base")


def test_idle_engine_waits_in_a_span(mt_model):
    tracer = obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        eng = paged_engine(mt_model)
        try:
            time.sleep(0.7)               # nothing to do: the loop's wait times out
            assert eng.generate([5, 17], max_new_tokens=2) != []
        finally:
            eng.stop()
        spans = spans_of([e for e in tracer.events() if e["ph"] in "BE"])
    finally:
        obs.configure(enabled=False, reset=True)
    waits = named(spans, "serve.wait")
    assert waits and all(w["parent"] is None for w in waits)
    assert max(w["t1"] - w["t0"] for w in waits) > 0.3e6


def test_off_means_no_event_and_no_other_transfer(mt_model):
    """The existing pin (equal ``JaxRuntimeAudit`` counts traced and
    untraced), extended to serving."""
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit

    def audited(traced):
        if traced:
            obs.configure(enabled=True, reset=True, jax_hooks=False)
        eng = paged_engine(mt_model)
        try:
            serve_all(eng)                 # warm every program
            with JaxRuntimeAudit() as audit:
                outputs = serve_all(eng)
        finally:
            eng.stop()
        return audit, outputs

    tracer = obs.get_tracer()
    assert not tracer.enabled
    tracer.reset()
    try:
        base, want = audited(traced=False)
        assert tracer.events() == []
        on, got = audited(traced=True)
        assert named(spans_of([e for e in tracer.events() if e["ph"] in "BE"]),
                     "serve.tick")
    finally:
        obs.configure(enabled=False, reset=True)
    assert got == want
    assert base.compilations == on.compilations == 0
    assert (on.device_puts, on.device_gets) == (base.device_puts, base.device_gets)


# -- one tick ahead (ISSUE 32) ------------------------------------------------

#: long enough that nothing finishes while a test looks at the stream
LONG = [([5, 17, 42], 40, "a0"), ([7, 9], 40, None), ([3, 1, 4, 1, 5], 40, "a1")]


def take(q, n, timeout=120.0):
    return [q.get(timeout=timeout) for _ in range(n)]


def test_ahead_is_zero_only_with_nothing_outstanding(mt_model):
    """A tick is launched over an unread one except the first after an idle
    wait or a flush; ``kv_stats()`` counts the same."""
    tracer = obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        eng = paged_engine(mt_model)
        try:
            serve_all(eng)
            assert eng.generate([5, 17], max_new_tokens=4) != []   # after an idle wait
            stats = eng.kv_stats()
        finally:
            eng.stop()
        spans = spans_of([e for e in tracer.events() if e["ph"] in "BE"])
    finally:
        obs.configure(enabled=False, reset=True)
    ticks = named(spans, "serve.tick")
    flushes = named(spans, "serve.flush")
    order = sorted(ticks + flushes, key=lambda s: s["t0"])
    for before, at in zip([None] + order, order):
        if at["name"] == "serve.tick":
            # outstanding: a tick that no flush has read since
            assert at["args"]["ahead"] == int(
                before is not None and before["name"] == "serve.tick"), at
    ahead = sum(t["args"]["ahead"] for t in ticks)
    assert 0 < ahead < len(ticks) and len(flushes) >= 2
    assert stats["ticks"] == len(ticks) and stats["ticks_ahead"] == ahead
    assert stats["flushes"] == len(flushes) and stats["lanes_burned"] == 0
    assert all(t["args"]["burned"] == 0 for t in order)
    # the last dispatch of a drain is read by a flush, never left behind
    assert order[-1]["name"] == "serve.flush"


def test_steady_tick_uploads_nothing_and_reads_back_once(mt_model):
    """With tracing off, a tick in which no slot changed state (no admission,
    no finish) makes no host-to-device transfer and one device-to-host
    transfer: the slot state stays on the device."""
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    assert not obs.get_tracer().enabled
    eng = paged_engine(mt_model)
    try:
        with eng._cond:
            qs = [eng.submit(p, max_new_tokens=n, adapter=a) for p, n, a in LONG]
        for q in qs:                      # all admitted, prefilled and ticking
            take(q, 5)
        # explicit transfers are counted; an implicit upload (a numpy or
        # Python value handed to a program) fails the engine's thread
        jax.config.update("jax_transfer_guard_host_to_device", "disallow")
        try:
            with JaxRuntimeAudit() as audit:
                t0 = eng.kv_stats()["ticks"]
                for q in qs:
                    take(q, 12)
                # the engine may have run ahead of this thread and have had
                # these tokens queued already: ticks inside the audit count
                deadline = time.monotonic() + 60
                while eng.kv_stats()["ticks"] - t0 < 6 \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
                t1 = eng.kv_stats()["ticks"]
        finally:
            jax.config.update("jax_transfer_guard_host_to_device", "allow")
        assert t1 - t0 >= 6
        assert audit.device_puts == 0
        # a tick either side of the two counts may fall inside the audit
        assert 1 <= audit.device_gets <= t1 - t0 + 2
        assert audit.compilations == 0
        for q in qs:
            assert len([t for t in iter(q.get, None)]) == 40 - 17
    finally:
        eng.stop()


def test_swap_and_stop_mid_stream_deliver_what_was_produced(mt_model):
    """A weight swap staged mid-stream lands after every request has all of
    its tokens, with no read-back outstanding; ``stop()`` mid-stream delivers
    every token a launched tick produced before it ends the streams."""
    model, params, _ = mt_model
    eng = paged_engine(mt_model)
    try:
        want = serve_all(eng, LONG)
        with eng._cond:
            qs = [eng.submit(p, max_new_tokens=n, adapter=a) for p, n, a in LONG]
        heads = [take(q, 3) for q in qs]
        eng.update_params(jax.tree_util.tree_map(lambda x: x * 1.01, params))
        assert eng._unread is None and not eng._firsts
        got = [h + [t for t in iter(q.get, None)] for h, q in zip(heads, qs)]
        assert got == want                   # one weight version end to end
        assert serve_all(eng, LONG) != want  # the swap landed
    finally:
        eng.stop()

    tracer = obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        eng = paged_engine(mt_model)
        try:
            with eng._cond:
                qs = [eng.submit(p, max_new_tokens=n, adapter=a) for p, n, a in LONG]
            heads = [take(q, 3) for q in qs]
        finally:
            eng.stop()
        assert eng._unread is None and not eng._firsts
        got = [h + [t for t in iter(q.get, None)] for h, q in zip(heads, qs)]
        spans = spans_of([e for e in tracer.events() if e["ph"] in "BE"])
    finally:
        obs.configure(enabled=False, reset=True)
    assert all(g == w[:len(g)] and len(g) < len(w) for g, w in zip(got, want))
    # no budget ended: every lane of every launched tick made a token, and
    # every one of them reached its caller
    ticks = named(spans, "serve.tick")
    assert sum(map(len, got)) == sum(t["args"]["live"] for t in ticks) + len(LONG)
    assert named(spans, "serve.flush")


# -- a model with sparse layers: what the expert layers did ------------------

@pytest.fixture(scope="module")
def traced_sparse():
    """A traced run of a paged engine over a small latent-attention model
    with one dense and two sparse layers (8 of 32 experts held)."""
    from fedml_tpu.llm.model import YarnScaling
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=3, n_heads=4, n_kv_heads=4,
                      ffn_dim=48, max_seq_len=BUF, dtype=jnp.float32,
                      attn_impl="blockwise", lora_rank=4, q_lora_rank=12,
                      kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
                      v_head_dim=8, rope_scaling=YarnScaling(4, 16, 32, 1, 1, 1),
                      n_experts=32, moe_top_k=4, moe_ffn_dim=16,
                      first_dense_layers=1, n_shared_experts=1,
                      moe_scoring="sigmoid", moe_n_group=4, moe_topk_group=2,
                      moe_routed_scale=2.5, experts_held=(0, 8))
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    tracer = obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        eng = ContinuousBatchingEngine(model, variables["params"], slots=3,
                                       buf_len=BUF, adapter_slots=4,
                                       kv_page_tokens=PTOK,
                                       prefill_chunk_tokens=CHUNK)
        try:
            outputs = serve_all(eng, [(p, n, None) for p, n, _ in REQUESTS])
            stats = eng.kv_stats()
        finally:
            eng.stop()
        events = tracer.export_chrome()["traceEvents"]
    finally:
        obs.configure(enabled=False, reset=True)
    return {"events": events, "outputs": outputs, "stats": stats,
            "spans": spans_of([e for e in events if e["ph"] in "BE"])}


def test_sparse_ticks_say_what_the_experts_did(traced_sparse):
    assert [len(o) for o in traced_sparse["outputs"]] == [n for _, n, _ in REQUESTS]
    ticks = named(traced_sparse["spans"], "serve.tick")
    served = ticks + named(traced_sparse["spans"], "serve.flush")
    # the counters ride the tokens: a dispatch's are on the span that read
    # it back, the tick launched over it or the flush
    read = [t for t in served if "expert_pairs" in t["args"]]
    assert ticks and len(read) == len(ticks)
    assert all(("expert_pairs" in t["args"]) == bool(t["args"]["ahead"]) for t in ticks)
    assert all({"expert_pairs", "experts_hit", "expert_load_max"}
               <= set(t["args"]) for t in read)
    for t in read:
        a = t["args"]
        # three lanes, two sparse layers, four experts a token, eight held
        assert 0 <= a["expert_pairs"] <= 3 * 2 * 4
        assert 0 <= a["experts_hit"] <= min(2 * 8, a["expert_pairs"])
        assert a["expert_load_max"] <= 3 and (a["expert_load_max"] > 0) == (a["expert_pairs"] > 0)
        assert all(isinstance(a[k], int) for k in ("expert_pairs", "experts_hit", "expert_load_max"))
    stats = traced_sparse["stats"]
    assert stats["moe_layers_ticked"] == 2 * len(ticks)
    assert stats["experts_hit"] == sum(t["args"]["experts_hit"] for t in read)
    # what the experts did over a prompt comes behind the request's first
    # token, on the span that read it; no chunk waits for it
    chunks = named(traced_sparse["spans"], "serve.chunk")
    assert sum(c["args"]["final"] for c in chunks) == len(REQUESTS)
    assert not any("expert_pairs" in c["args"] for c in chunks)
    firsts = [t for t in served if "first_tokens" in t["args"]]
    assert sum(t["args"]["first_tokens"] for t in firsts) == len(REQUESTS)
    # a prompt of three chunks computes 48 rows in two sparse layers
    assert all(0 <= t["args"]["prefill_expert_pairs"]
               <= t["args"]["first_tokens"] * 48 * 2 * 4 for t in firsts)
    assert stats["expert_pairs"] == sum(t["args"]["expert_pairs"] for t in read) \
        + sum(t["args"]["prefill_expert_pairs"] for t in firsts) > 0


def test_sparse_gauges_and_bytes_a_token(traced_sparse):
    counters = {}
    for e in traced_sparse["events"]:
        if e["ph"] == "C":
            counters.setdefault(e["name"], []).append(e["args"])
    # three layers, one latent row (8 + 4 float32 in one lane tile) a token and layer
    assert traced_sparse["stats"]["kv_bytes_per_token"] == 3 * 128 * 4
    assert {tuple(a.values()) for a in counters["serve.kv_bytes_per_token"]} == {(1536,)}
    assert "serve.expert_load_max" in counters


def test_latent_ticks_and_chunks_carry_attn_pages(traced_sparse, traced):
    """A model with latent attention has a kernel for its paged read
    (``ops/latent_attention.py``), so its ticks and chunks say how many pages
    it visited: 0 here, where the programs are lowered for the CPU and the
    ``jnp`` forms run (``tests/test_mla_moe.py`` forces the kernel and counts
    by hand).  A dense model's spans have no such arg."""
    served = named(traced_sparse["spans"], "serve.tick") \
        + named(traced_sparse["spans"], "serve.chunk")
    assert served and all(s["args"]["attn_pages"] == 0 for s in served)
    assert traced_sparse["stats"]["attn_pages"] == 0
    dense = named(traced["spans"], "serve.tick") + named(traced["spans"], "serve.chunk")
    assert dense and not any("attn_pages" in s["args"] for s in dense)


def test_dense_engine_has_no_expert_args(traced):
    ticks = named(traced["spans"], "serve.tick")
    assert ticks and not any("expert_pairs" in t["args"] for t in ticks)
    assert not any("expert_pairs" in c["args"] for c in named(traced["spans"], "serve.chunk"))
    assert not any(e["ph"] == "C" and e["name"] == "serve.expert_load_max"
                   for e in traced["events"])


# -- what the tracer gained for this ---------------------------------------

def test_span_end_args_and_public_origin():
    tracer = obs.get_tracer()
    with tracer.span("off") as sp:          # off: the shared no-op swallows it
        sp.set(tokens=3)
    assert tracer.events() == []
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        before = time.perf_counter()
        with tracer.span("tick", cat="engine", live=2) as sp:
            sp.set(tokens=5)
            sp.set(finished=1)
        after = time.perf_counter()
        b, e = [ev for ev in tracer.events() if ev["name"] == "tick"]
        assert b["args"]["live"] == 2 and "tokens" not in b["args"]
        assert e["args"] == {"tokens": 5, "finished": 1}
        # origin_s puts a ts on time.perf_counter
        assert before <= tracer.origin_s + b["ts"] / 1e6 <= \
            tracer.origin_s + e["ts"] / 1e6 <= after
        origin = tracer.origin_s
        tracer.reset()
        assert tracer.origin_s >= origin
        with pytest.raises(AttributeError):
            tracer.origin_s = 0.0
    finally:
        obs.configure(enabled=False, reset=True)


def test_count_put_counts_the_staged_tree_only_when_tracing():
    tracer = obs.get_tracer()
    tree = (np.zeros((4, 8), np.int32), None, {"w": np.zeros(3, np.float32)})
    assert count_put(tracer, tree) == 0 and tracer.events() == []
    obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        assert count_put(tracer, tree) == 4 * 8 * 4 + 3 * 4
        count_put(tracer, tree)
        assert tracer.summary()["counters"]["device_put_bytes"] == 2 * 140
        # nothing wraps jax's own functions any more
        assert jax.device_put.__module__.startswith("jax")
    finally:
        obs.configure(enabled=False, reset=True)
