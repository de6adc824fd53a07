"""Traffic of the kind "requests to the OpenAI-compatible endpoint", for a
``cohere2_moe`` configuration (``architecture: cohere2_moe_decoder``: window
and full attention layers in one stack, a parallel block, sigmoid-routed
experts beside averaged shared experts) under requests of two classes of
length in one queue: ``drivers/serve.py``'s closed loop and window, with a
server, weights, generator, reference and comparison of this architecture's
own.

What ``benchmarks/README.md`` would say of it.  A configuration of this kind
brings ``weights_cohere2_moe.py`` and ``reference/cohere2_moe_decoder.py``;
its file holds every published key with the depth, the vocabulary and the
experts held here cut as ``reduced`` lists, and ``experts_held`` (``first``,
``count``, ``of``: the router's published width).  The program is built the way
a user builds it: ``config_from_args`` reads the published keys
(``llm_config_json``) and is told which experts live here
(``llm_experts_held``).  A cell of this driver is data only: ``serve``'s keys
with ``traffic.classes`` in place of ``traffic.prompt``
(``traffic_two_class.py``), ``engine.window_pool_pages`` beside
``engine.pool_pages`` (the engine keeps a pool per kind of layer), and under
``check`` also ``sample_long``, ``near_tie_margin`` and ``answer_tail``.

``finish`` also counts what the expert layers did (``expert_pairs``,
``experts_hit_mean``) and what a layer of each kind held
(``live_kv_tokens_mean``: a full layer; ``live_window_tokens_mean``: a window
layer, a request's depth capped at the window) for the rooflines.

**How ``correct`` is decided.**  As in ``serve_mla_moe``: a sample drawn from
the seed of the requests the window finished, ``check.sample`` of them of which
``check.sample_long`` are of the last class (the long one; the longest is
always among them), each teacher-forced once through the reference with its
adapter at its own length (a short one in a buffer of its class's, a long one
in the engine's).  Positions whose routing margin is under
``check.near_tie_margin`` are set apart (``near_tie_share``);
``served_gap`` (the widest gap, in units of the position's logit spread, by
which a served token's logit lies below the reference's best) and
``served_gap_q99`` are taken over the rest.  ``PERF.md`` §2 gives the readings
the margin and the limits were set from."""

from __future__ import annotations

import dataclasses
import gc
import time
import types

import numpy as np

import traffic
import traffic_two_class
import weights_cohere2_moe as weights
from drivers import serve
from drivers.serve import window  # noqa: F401  (the harness calls it here)
from drivers.serve_mla_moe import numbers, readings  # noqa: F401
from reference import cohere2_moe_decoder as ref

#: the keys of a published ``config.json`` that the program reads, and the one
#: the configuration's file adds as assumed (``rope_full_layers``)
PUBLISHED = ("model_type", "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "intermediate_size", "rope_theta", "layer_norm_eps",
             "rms_norm_eps", "layer_types", "sliding_window", "rope_full_layers",
             "num_experts_per_tok", "num_shared_experts", "first_k_dense_replace",
             "expert_selection_fn", "norm_topk_prob", "attention_bias", "hidden_act", "logit_scale",
             "tie_word_embeddings", "use_parallel_block", "use_qk_norm", "use_gated_activation",
             "rotary_pct", "position_embedding_type", "shared_expert_combination_strategy",
             "prefix_dense_intermediate_size", "prefix_dense_sliding_window_pattern")


def program_config(cfg: dict, max_seq_len: int, **overrides):
    """The program's configuration, from the published keys of ``cfg`` (depth
    and vocabulary as cut, the router at its published width) and the experts
    held here, through the program's own ``config_from_args``."""
    from fedml_tpu.llm.model import config_from_args
    first, count, of = weights.held(cfg)
    published = {k: cfg[k] for k in PUBLISHED if k in cfg}
    published["layer_types"] = list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]
    published["num_experts"] = of
    args = types.SimpleNamespace(
        model="llama", llm_config_json=published, llm_max_seq_len=int(max_seq_len),
        llm_experts_held=(first, count) if count < of else None,
        model_dtype=cfg.get("compute_dtype", "bfloat16"))
    lcfg = config_from_args(args)
    if not getattr(lcfg, "windowed", False) or not getattr(lcfg, "parallel_block", False):
        import harness
        raise harness.BenchError(
            "the program in this checkout computes no sliding-window layers or no parallel "
            "block (cohere2_moe): it cannot run this configuration")
    return dataclasses.replace(lcfg, lora_rank=int(cfg["lora"]["rank"]),
                               lora_alpha=float(cfg["lora"]["alpha"]), **overrides)


def build_server(cfg: dict, engine: dict, n_adapters: int, seed: int):
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.model import LlamaLM
    from fedml_tpu.serving.templates.openai_compat import OpenAICompatServer

    model = LlamaLM(program_config(cfg, int(engine["buf_len"])))
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
        kv_window_pool_pages=int(engine.get("window_pool_pages", 0)),
        prefill_chunk_tokens=int(engine["prefill_chunk_tokens"]))
    del adapters, zero
    return srv


def setup(run) -> dict:
    """Build, warm the chunk and the tick program (a prompt shorter than a
    chunk, one of several chunks that slides the window pool's tables, one
    as long as the long class's), start the callers ``ramp_seconds`` before
    the window."""
    cfg, t, engine = run.cfg, run.cell["traffic"], run.cell["engine"]
    t0 = time.perf_counter()
    srv = build_server(cfg, engine, int(t["adapters"]["count"]), run.seed)
    port = srv.start()
    built_s = time.perf_counter() - t0
    client = traffic.LoadClient(port)
    rng = np.random.default_rng([run.seed, 0x3A53])
    chunk = int(engine["prefill_chunk_tokens"])
    longest = max(int(c["prompt"]["hi"]) for c in t["classes"])
    warm = [{"idx": -1 - i, "prompt_ids": [int(x) for x in rng.integers(
                1, cfg["vocab_size"], size=n)], "max_tokens": 6, "adapter": traffic.adapter_name(ad)}
            for i, (n, ad) in enumerate(((chunk // 2, 0), (longest, 0), (chunk + 1, 1), (7, 1)))]
    t1 = time.perf_counter()
    recs = [client._send(w, time.perf_counter()) for w in warm]
    client.drain(time.perf_counter() + 2400.0)
    bad = [r["error"] or f"{len(r['tokens'])} tokens" for r in recs
           if r["error"] or len(r["tokens"]) != r["max_tokens"]]
    if bad:
        srv.stop()
        raise RuntimeError(f"warm-up requests failed: {bad}")
    run.note(server_built_s=built_s, warm_requests_s=time.perf_counter() - t1,
             kv=srv._engine.kv_stats())
    requests = traffic_two_class.Requests(t, int(cfg["vocab_size"]), run.seed)
    ramp = client.run_closed(requests, int(t["callers"]), time.perf_counter(),
                             float(t["ramp_seconds"]))
    return {"srv": srv, "client": client, "requests": requests, "ramp": ramp}


def finish(state: dict, run) -> dict:
    result = serve.finish(state, run)
    k0, k1 = state["ticks0"], state["ticks1"]
    t0, t1 = result["window"]
    layers = k1.get("moe_layers_ticked", 0) - k0.get("moe_layers_ticked", 0)
    notes = result["notes"]
    if layers:
        hit = (k1["experts_hit"] - k0["experts_hit"]) / layers
        run.counters.update(expert_pairs=k1["expert_pairs"] - k0["expert_pairs"],
                            experts_hit_mean=hit)
        notes.update(expert_pairs=run.counters["expert_pairs"], experts_hit_mean=hit,
                     kv_bytes_per_token=k1.get("kv_bytes_per_token"))
    # what a window layer holds of the decoding requests, as serve.finish
    # counts what a full layer holds: a request's depth, capped at the window
    w = int(run.cfg["sliding_window"])
    held = 0.0
    for r in state["records"]:
        if r["error"] is None and r["status"] == 200 and r["tokens"]:
            s = np.clip(np.array(r["stamps"] + [r["stamps"][-1]]), t0, t1)
            depth = np.minimum(len(r["prompt_ids"]) + np.arange(len(r["stamps"])), w)
            held += float(np.sum(depth * np.diff(s)))
    held /= run.seconds
    run.counters["live_window_tokens_mean"] = held
    notes["live_window_tokens_mean"] = held
    if "window_pool_pages" in k1:
        pool_tokens = (k1["window_pool_pages"] - 1) * int(run.cell["engine"]["page_tokens"])
        ticks = max(k1["ticks"] - k0["ticks"], 1)
        notes.update(window_pool_tokens=pool_tokens,
                     live_window_share_of_pool=held / pool_tokens,
                     window_pages_free_at_open_and_close=[k0["window_pages_free"],
                                                          k1["window_pages_free"]],
                     window_pages_freed_per_tick=(k1["window_pages_freed"]
                                                  - k0["window_pages_freed"]) / ticks,
                     reservations_refused_in_window={
                         "full": k1["pool"]["exhausted"] - k0["pool"]["exhausted"],
                         "window": k1["window_pool"]["exhausted"] - k0["window_pool"]["exhausted"]})
    by_class = {}
    for r in state["records"]:
        by_class[r.get("class", "?")] = by_class.get(r.get("class", "?"), 0) + 1
    notes["requests_by_class"] = by_class
    return result


# -- the comparison ----------------------------------------------------------------

def sample(records, n: int, n_long: int, long_class: str, seed: int):
    """``n`` of the finished requests drawn from the seed, ``n_long`` of them
    of ``long_class`` (the longest of all among them), as far as there are."""
    done = [r for r in records if r["error"] is None and r["status"] == 200 and r["tokens"]]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    long_ = [r for r in done if r.get("class") == long_class]
    rest = [r for r in done if r.get("class") != long_class]
    picks = []
    if long_ and n_long:
        longest = max(long_, key=lambda r: len(r["prompt_ids"]) + len(r["tokens"]))
        others = [r for r in long_ if r is not longest]
        picks = [longest] + [others[i] for i in rng.permutation(len(others))[: n_long - 1]]
    return picks + [rest[i] for i in rng.permutation(len(rest))[: max(n - len(picks), 0)]]


def forced(cfg: dict, base, adapters: dict, rec: dict, length: int, tail: int, quant=None) -> dict:
    """One request through the reference in a buffer of ``length``: for each
    served token its gap in units of the position's logit spread, and the
    position's routing margin (``tail`` positions from the prompt's last are
    computed, the request's own are kept)."""
    import jax.numpy as jnp
    ids = rec["prompt_ids"] + rec["tokens"]
    seq = np.zeros((1, length), np.int32)
    seq[0, :len(ids)] = ids
    first, count, of = weights.held(cfg)
    out = ref.forced_gaps(base, adapters.get(rec["adapter"]), jnp.asarray(seq),
                          len(rec["prompt_ids"]) - 1, tail, cfg,
                          (first, count) if count < of else None, quant)
    span = slice(0, len(rec["tokens"]))
    spread = np.asarray(out["spread"])[span]
    res = {"gaps": np.asarray(out["gap"])[span] / spread,
           "margins": np.asarray(out["margin"])[span]}
    if quant is not None:
        res["control_gaps"] = np.asarray(out["control_gap"])[span] / spread
    return res


def reference_weights(cfg: dict, seed: int, names) -> tuple:
    base = weights.make_base(cfg, seed)
    adapters = {name: weights.make_lora(cfg, seed, index=traffic.adapter_index(name) + 1)
                for name in sorted(set(n for n in names if n))}
    return base, adapters


def buffers(cell: dict) -> dict:
    """The reference's buffer for each class of length: the class's longest
    prompt and answer in whole 128s, at most the engine's buffer."""
    t, tail = cell["traffic"], int(cell["check"]["answer_tail"])
    cap = int(cell["engine"]["buf_len"])
    return {c["name"]: min(-(-(int(c["prompt"]["hi"]) + tail + 1) // 128) * 128, cap)
            for c in t["classes"]}


def forced_sample(state: dict, run, quant=None) -> tuple:
    """Free the program's state, then the sampled requests through the
    reference: ``(records, picks, rows, seconds)``."""
    import jax
    records = state.pop("records")
    state.pop("client").close()
    state.pop("srv").stop()
    state.clear()
    gc.collect()
    jax.clear_caches()
    spec, classes = run.cell["check"], run.cell["traffic"]["classes"]
    picks = sample(records, int(spec["sample"]), int(spec["sample_long"]),
                   classes[-1]["name"], run.seed)
    base, adapters = reference_weights(run.cfg, run.seed, [r["adapter"] for r in picks])
    sizes, tail = buffers(run.cell), int(spec["answer_tail"])
    t0 = time.perf_counter()
    rows = [forced(run.cfg, base, adapters, r, sizes[r["class"]], tail, quant) for r in picks]
    return records, picks, rows, time.perf_counter() - t0


def answered(records, length: int) -> dict:
    """The exact counts: requests that got no answer, and answers that are
    neither as long as asked for nor ended by the engine's buffer."""
    return {"unanswered": sum(1 for r in records if r["error"] is not None or r["status"] != 200),
            "short_answers": sum(
                1 for r in records if r["error"] is None and r["status"] == 200
                and not min(r["max_tokens"], length - len(r["prompt_ids"]) - 1)
                <= len(r["tokens"]) <= r["max_tokens"])}


def held_to(out: dict, limits: dict) -> dict:
    """``out``'s numbers, each beside the cell's limit for it: what ``check``
    hands the harness, and what the calibration judges its control by."""
    return {k: {"value": v, "limit": limits[k]} for k, v in out.items() if k in limits}


def passes(checks: dict) -> bool:
    """``harness.run_cell``'s own comparison of what ``check`` returns."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def check(state: dict, run, result: dict) -> dict:
    spec = run.cell["check"]
    records, picks, rows, reference_s = forced_sample(state, run)
    out = {**numbers(rows, float(spec["near_tie_margin"])),
           **answered(records, int(run.cell["engine"]["buf_len"]))}
    run.note(checked_requests=len(picks), checked_tokens=sum(len(r["tokens"]) for r in picks),
             checked_by_class={c: sum(1 for r in picks if r["class"] == c)
                               for c in sorted(set(r["class"] for r in picks))},
             reference_s=reference_s, **readings(rows))
    return held_to(out, spec["limits"])
