"""Traffic of the kind "requests to the OpenAI-compatible endpoint": the window
drives ``POST /v1/chat/completions`` with ``stream: true`` over HTTP on an
in-process ``OpenAICompatServer`` with the paged batching engine and a bank of
LoRA adapters (the program streams on the chat route only; ``/v1/completions``
ignores ``stream``).

The loop is closed: ``callers`` callers, each sending its next request when
its last one ends, started ``ramp_seconds`` before the window.  Base,
adapters, prompts and lengths come from ``--seed``.  After the window a sample
of the finished requests is teacher-forced through the plain reference."""

from __future__ import annotations

import gc
import time

import numpy as np

import traffic
import weights
from reference import dense_decoder as ref

#: how long an answer still open at the window's close is waited for, counted
#: from ``finish``'s own start: in a traced run ``stop_trace`` comes between
#: the close and ``finish`` and can take most of a minute
WAIT_S = 60.0


def build_server(cfg: dict, engine: dict, n_adapters: int, seed: int):
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.templates.openai_compat import OpenAICompatServer

    m = weights.dims(cfg)
    lcfg = LlamaConfig(
        vocab_size=m["v"], dim=m["d"], n_layers=m["layers"], n_heads=m["h"],
        n_kv_heads=m["kv"], ffn_dim=m["f"], max_seq_len=int(engine["buf_len"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")).type, lora_rank=int(cfg["lora"]["rank"]),
        lora_alpha=float(cfg["lora"]["alpha"]))
    model = LlamaLM(lcfg)
    theirs = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    ours = jax.eval_shape(lambda: (weights.make_base(cfg, 0), weights.make_lora(cfg, 0)))
    for a, b, what in ((ours[0], theirs["params"], "base"), (ours[1], theirs["lora"], "adapters")):
        diff = weights.same_layout(a, b)
        if diff:
            raise RuntimeError(f"the {what} the benchmark makes do not fit the program: {diff}")
    base = weights.make_base(cfg, seed)
    adapters = {traffic.adapter_name(i): weights.make_lora(cfg, seed, index=i + 1)
                for i in range(n_adapters)}
    zero = jax.tree_util.tree_map(jnp.zeros_like, next(iter(adapters.values())))

    def apply_fn(params, tokens):      # the single-request path; not driven here
        return model.apply({"params": params, "lora": zero}, tokens)

    srv = OpenAICompatServer(
        apply_fn, base, tokenizer=traffic.IdTokenizer(), model=model,
        buf_len=int(engine["buf_len"]), batch_slots=int(engine["slots"]),
        adapters=adapters, adapter_slots=int(engine["adapter_slots"]),
        kv_page_tokens=int(engine["page_tokens"]),
        kv_pool_pages=int(engine.get("pool_pages", 0)),
        prefill_chunk_tokens=int(engine["prefill_chunk_tokens"]))
    del adapters, zero
    return srv


def setup(run) -> dict:
    cfg, t, engine = run.cfg, run.cell["traffic"], run.cell["engine"]
    t0 = time.perf_counter()
    srv = build_server(cfg, engine, int(t["adapters"]["count"]), run.seed)
    port = srv.start()
    built_s = time.perf_counter() - t0
    client = traffic.LoadClient(port)
    # warm-up: the chunk and tick programs, a prompt shorter and one longer
    # than a chunk
    rng = np.random.default_rng([run.seed, 0x3A53])
    chunk = int(engine["prefill_chunk_tokens"])
    warm = [{"idx": -1 - i, "prompt_ids": [int(x) for x in rng.integers(
                1, cfg["vocab_size"], size=n)], "max_tokens": 6, "adapter": traffic.adapter_name(ad)}
            for i, (n, ad) in enumerate(((chunk // 2, 0), (2 * chunk + 5, 0), (chunk + 1, 1), (7, 1)))]
    t1 = time.perf_counter()
    recs = [client._send(w, time.perf_counter()) for w in warm]
    client.drain(time.perf_counter() + 1100.0)
    bad = [r["error"] or f"{len(r['tokens'])} tokens" for r in recs
           if r["error"] or len(r["tokens"]) != r["max_tokens"]]
    if bad:
        srv.stop()
        raise RuntimeError(f"warm-up requests failed: {bad}")
    run.note(server_built_s=built_s, warm_requests_s=time.perf_counter() - t1,
             kv=srv._engine.kv_stats())
    requests = traffic.Requests(t, int(cfg["vocab_size"]), run.seed)
    # the callers start before the window: it opens on an engine that is full
    ramp = client.run_closed(requests, int(t["callers"]), time.perf_counter(),
                             float(t["ramp_seconds"]))
    return {"srv": srv, "client": client, "requests": requests, "ramp": ramp}


def window(state: dict, run, seconds: float) -> None:
    t0 = time.perf_counter()
    state["ticks0"] = state["srv"]._engine.kv_stats()
    records = state["ramp"] + state["client"].run_closed(
        state["requests"], 0, t0, seconds, first=len(state["ramp"]))
    state["ticks1"] = state["srv"]._engine.kv_stats()
    state["records"], state["t0"] = records, t0


def finish(state: dict, run) -> dict:
    client, srv, records = state["client"], state["srv"], state["records"]
    seconds = run.seconds
    t0 = state["t0"]
    t1 = t0 + seconds
    client.drain(time.perf_counter() + WAIT_S)
    drained = time.perf_counter()
    kv = srv._engine.kv_stats()
    # requests of the ramp that had ended before the window opened are no part of it
    records = [r for r in records if not (r["done"] and (r["last"] or t0) < t0)]
    state["records"] = records

    ok = [r for r in records if r["error"] is None and r["status"] == 200 and r["tokens"]]
    failed = len(records) - len(ok)
    stamps = np.array([s for r in ok for s in r["stamps"]])
    in_window = int(np.sum((stamps >= t0) & (stamps <= t1)))

    # counters for the per-layer readers
    prompt_tokens = sum(len(r["prompt_ids"]) for r in ok
                        if r["first"] is not None and t0 <= r["first"] <= t1)
    live = 0.0
    for r in ok:
        s = np.clip(np.array(r["stamps"] + [r["stamps"][-1]]), t0, t1)
        depth = len(r["prompt_ids"]) + np.arange(len(r["stamps"]))
        live += float(np.sum(depth * np.diff(s)))
    live /= seconds
    run.counters.update(prompt_tokens=prompt_tokens, generated_tokens=in_window,
                        live_kv_tokens_mean=live, requests=len(records))
    # how full the engine was: what the window's ticks and chunks say
    k0, k1 = state["ticks0"], state["ticks1"]
    ticks = k1["ticks"] - k0["ticks"]
    pool_tokens = (kv["pool_pages"] - 1) * int(run.cell["engine"]["page_tokens"])
    life = [r["last"] - r["sent"] for r in ok if r["last"] is not None]
    # the engine feeds every live slot at every tick: a stretch of the window in
    # which no caller hears anything is a stall of the whole engine
    heard = np.unique(np.concatenate([[t0], stamps[(stamps >= t0) & (stamps <= t1)], [t1]]))
    silences = np.diff(heard)
    return {"window": (t0, t1), "attempted": len(records), "failed": failed,
            "metrics": {"serve_tokens_per_s": (in_window / seconds, "tokens/s")},
            "notes": {"requests": len(records), "tokens_in_window": in_window,
                      "drain_s": drained - t1, "kv": kv, "ticks_in_window": ticks,
                      "chunks_in_window": k1["prefill_chunks"] - k0["prefill_chunks"],
                      "tokens_per_tick": in_window / max(ticks, 1),
                      "pages_free_at_open_and_close": [k0["pages_free"], k1["pages_free"]],
                      "live_kv_tokens_mean": live, "pool_tokens": pool_tokens,
                      "live_kv_share_of_pool": live / pool_tokens,
                      "longest_request_s": max(life) if life else None,
                      "longest_silence_s": float(silences.max()),
                      "silent_over_half_a_second_s": float(silences[silences > 0.5].sum())}}


# -- the comparison ----------------------------------------------------------------

def sample(records, n: int, seed: int):
    """``n`` of the finished requests, drawn from the seed, the longest among
    them."""
    done = [r for r in records if r["error"] is None and r["status"] == 200 and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt_ids"]) + len(r["tokens"]))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [r for r in done if r is not longest]
    picks = [rest[i] for i in rng.permutation(len(rest))[: max(n - 1, 0)]]
    return [longest] + picks


def forced(cfg: dict, base, adapters: dict, rec: dict, length: int, quant=None) -> dict:
    """One request through the reference: the widest gap, in units of the
    position's logit spread, by which a served token's logit lies below the
    reference's best."""
    import jax.numpy as jnp
    ids = rec["prompt_ids"] + rec["tokens"]
    seq = np.zeros((1, length), np.int32)
    seq[0, :len(ids)] = ids
    out = ref.forced_gaps(base, adapters.get(rec["adapter"]), jnp.asarray(seq), cfg, quant)
    span = slice(len(rec["prompt_ids"]) - 1, len(ids) - 1)
    spread = np.asarray(out["spread"])[span]
    res = {"served_gap": float(np.max(np.asarray(out["gap"])[span] / spread))}
    if quant is not None:
        res["control_gap"] = float(np.max(np.asarray(out["control_gap"])[span] / spread))
    return res


def reference_weights(cfg: dict, seed: int, names) -> tuple:
    base = weights.make_base(cfg, seed)
    adapters = {name: weights.make_lora(cfg, seed, index=traffic.adapter_index(name) + 1)
                for name in sorted(set(n for n in names if n))}
    return base, adapters


def check(state: dict, run, result: dict) -> dict:
    import jax
    records = state.pop("records")
    state.pop("client").close()
    state.pop("srv").stop()
    state.clear()
    gc.collect()
    jax.clear_caches()
    spec = run.cell["check"]
    picks = sample(records, int(spec["sample"]), run.seed)
    base, adapters = reference_weights(run.cfg, run.seed, [r["adapter"] for r in picks])
    length = int(run.cell["engine"]["buf_len"])
    gaps = [forced(run.cfg, base, adapters, r, length)["served_gap"] for r in picks]
    unanswered = sum(1 for r in records if r["error"] is not None or r["status"] != 200)
    # an answer is as long as asked for, or ends at the engine's buffer
    short = sum(1 for r in records if r["error"] is None and r["status"] == 200
                and not min(r["max_tokens"], length - len(r["prompt_ids"]) - 1)
                <= len(r["tokens"]) <= r["max_tokens"])
    numbers = {"served_gap": max(gaps) if gaps else 1e30,
               "unanswered": unanswered, "short_answers": short}
    run.note(checked_requests=len(picks),
             checked_tokens=sum(len(r["tokens"]) for r in picks))
    return {k: {"value": v, "limit": spec["limits"][k]} for k, v in numbers.items()
            if k in spec["limits"]}
