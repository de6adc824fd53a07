"""The engine thread's live span tree (ISSUE 27): one ``serve.iter`` per loop
pass with ``serve.admit`` / ``serve.chunk`` / ``serve.tick`` under it, on the
tracer's per-thread stack; args are host ints; off means no event and no
transfer."""

import dataclasses
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
from fedml_tpu.serving.batching import (ContinuousBatchingEngine,
                                        SpeculativeBatchingEngine)

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


def serve_all(eng):
    qs = [eng.submit(p, max_new_tokens=n, adapter=a) for p, n, a in REQUESTS]
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
        finally:
            eng.stop()
        events = tracer.export_chrome()["traceEvents"]
    finally:
        obs.configure(enabled=False, reset=True)
    return {"events": events, "outputs": outputs,
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
    want_parent = {"serve.admit": "serve.iter", "serve.chunk": "serve.iter",
                   "serve.tick": "serve.iter", "serve.chunk.gather": "serve.chunk",
                   "serve.chunk.dispatch": "serve.chunk",
                   "serve.chunk.readback": "serve.chunk",
                   "serve.tick.stage": "serve.tick", "serve.tick.dispatch": "serve.tick",
                   "serve.tick.readback": "serve.tick", "serve.tick.emit": "serve.tick",
                   "serve.tick.free": "serve.tick"}
    seen = set()
    for s in spans:
        if s["name"] in want_parent:
            parent = by_id[s["parent"]]
            assert parent["name"] == want_parent[s["name"]], s
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
    # every arg is a host number or a string, never a device value
    for s in spans:
        assert all(isinstance(v, (int, float, str)) for v in s["args"].values()), s


def test_tick_tokens_are_what_the_callers_received(traced):
    received = sum(len(o) for o in traced["outputs"])
    assert [len(o) for o in traced["outputs"]] == [n for _, n, _ in REQUESTS]
    ticks = named(traced["spans"], "serve.tick")
    # the first token of a request comes from its final chunk, the rest from ticks
    assert sum(t["args"]["tokens"] for t in ticks) == received - len(REQUESTS)
    assert all(0 <= t["args"]["tokens"] <= t["args"]["live"] <= 3 for t in ticks)
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
    # a final chunk reads its token back, the others do not
    by_id = {s["id"]: s for s in spans}
    assert all(by_id[s["parent"]]["args"]["final"] == 1
               for s in named(spans, "serve.chunk.readback"))
    assert len(named(spans, "serve.chunk.readback")) == len(REQUESTS)
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


def test_speculative_engine_opens_the_same_tick(mt_model):
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_dim=64, max_seq_len=BUF + 8, dtype=jnp.float32,
                      attn_impl="blockwise")
    model = LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    draft = LlamaLM(dataclasses.replace(cfg, dim=16, n_layers=1, n_heads=2,
                                        n_kv_heads=2, ffn_dim=32))
    dparams = draft.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    tracer = obs.configure(enabled=True, reset=True, jax_hooks=False)
    try:
        eng = SpeculativeBatchingEngine(model, params, draft, dparams, slots=2,
                                        buf_len=BUF, k=3)
        try:
            outs = [eng.generate(p, max_new_tokens=n) for p, n in (([5, 17, 42], 9), ([7, 7], 4))]
        finally:
            eng.stop()
        events = tracer.export_chrome()["traceEvents"]
    finally:
        obs.configure(enabled=False, reset=True)
    assert fedtrace.validate_events(events) == []
    spans = spans_of([e for e in events if e["ph"] in "BE"])
    ticks = named(spans, "serve.tick")
    assert ticks and sum(t["args"]["tokens"] for t in ticks) == sum(map(len, outs)) - 2
    by_id = {s["id"]: s for s in spans}
    for name in ("stage", "dispatch", "readback", "emit", "free"):
        kids = named(spans, f"serve.tick.{name}")
        assert len(kids) == len(ticks)
        assert all(by_id[k["parent"]]["name"] == "serve.tick" for k in kids)
    # the dense path's prefill span still nests under admission
    assert all(by_id[s["parent"]]["name"] == "serve.admit"
               for s in named(spans, "serve.prefill"))


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
            outputs = [[t for t in iter(q.get, None)] for q in [
                eng.submit(p, max_new_tokens=n) for p, n, _ in REQUESTS]]
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
    assert ticks and all({"expert_pairs", "experts_hit", "expert_load_max"}
                         <= set(t["args"]) for t in ticks)
    for t in ticks:
        a = t["args"]
        # three lanes, two sparse layers, four experts a token, eight held
        assert 0 <= a["expert_pairs"] <= 3 * 2 * 4
        assert 0 <= a["experts_hit"] <= min(2 * 8, a["expert_pairs"])
        assert a["expert_load_max"] <= 3 and (a["expert_load_max"] > 0) == (a["expert_pairs"] > 0)
        assert all(isinstance(a[k], int) for k in ("expert_pairs", "experts_hit", "expert_load_max"))
    stats = traced_sparse["stats"]
    assert stats["moe_layers_ticked"] == 2 * len(ticks)
    assert stats["experts_hit"] == sum(t["args"]["experts_hit"] for t in ticks)
    chunks = named(traced_sparse["spans"], "serve.chunk")
    finals = [c for c in chunks if c["args"]["final"]]
    assert len(finals) == len(REQUESTS)
    assert all("expert_pairs" in c["args"] for c in finals)
    assert all("expert_pairs" not in c["args"] for c in chunks if not c["args"]["final"])
    # a prompt of three chunks computes 48 rows in two sparse layers
    assert all(0 <= c["args"]["expert_pairs"] <= 48 * 2 * 4 for c in finals)
    assert stats["expert_pairs"] == sum(t["args"]["expert_pairs"] for t in ticks) \
        + sum(c["args"]["expert_pairs"] for c in finals) > 0


def test_sparse_gauges_and_bytes_a_token(traced_sparse):
    counters = {}
    for e in traced_sparse["events"]:
        if e["ph"] == "C":
            counters.setdefault(e["name"], []).append(e["args"])
    # three layers, one latent row (8 + 4 float32 in one lane tile) a token and layer
    assert traced_sparse["stats"]["kv_bytes_per_token"] == 3 * 128 * 4
    assert {tuple(a.values()) for a in counters["serve.kv_bytes_per_token"]} == {(1536,)}
    assert "serve.expert_load_max" in counters


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
