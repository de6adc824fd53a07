"""Traffic of the kind "requests to the OpenAI-compatible endpoint", for a
latent-attention, sparse-expert configuration (``architecture:
mla_moe_decoder``): ``drivers/serve.py``'s closed loop, window and sample,
with a server, weights, reference and comparison of this architecture's own.

What ``benchmarks/README.md`` would say of it.  A configuration of this kind
brings ``weights_mla_moe.py`` and ``reference/mla_moe_decoder.py``; its file
holds every published key with the depth, the vocabulary and the experts held
here cut as ``reduced`` lists, ``experts_held`` (``first``, ``count``, ``of``:
the router's published width) and the deployment the share stands for.  The
program is built the way a user builds it: ``config_from_args`` reads the
published keys (``llm_config_json``) and is told which experts live here
(``llm_experts_held``); nothing is lent to a preset.  A cell of this driver is
data only: ``serve``'s keys, and under ``check`` also ``near_tie_margin``.

``finish`` also counts what the expert layers did (``expert_pairs``,
``experts_hit_mean``: held experts that got a token, a sparse layer and tick)
for the tick's roofline.

**How ``correct`` is decided.**  As in ``serve``: a sample of the requests the
window finished, the longest among them, each teacher-forced once through the
reference with its adapter; ``served_gap`` is the widest gap, in units of the
position's logit spread, by which a served token's logit lies below the
reference's best.  New here: where two routing scores nearly tie, a bfloat16
hidden state and the float32 one choose different experts, and the layer's
output then differs by a whole expert's contribution, not by rounding.  The
reference reports each position's smallest routing margin over the sparse
layers (``reference/mla_moe_decoder.py::route``: only choices that change what
the held experts add count).  Positions whose margin is under
``check.near_tie_margin`` are set apart: their share of the checked positions
is ``near_tie_share``, a checked number with a limit of its own, and
``served_gap`` (the widest gap) and ``served_gap_q99`` (the gap one position in
a hundred exceeds) are taken over the rest.  ``PERF.md`` §2 gives the readings
the margin and the limits were set from."""

from __future__ import annotations

import dataclasses
import gc
import time
import types

import numpy as np

import traffic
import weights_mla_moe as weights
from drivers import serve
from drivers.serve import sample, window  # noqa: F401  (the harness calls them here)
from reference import mla_moe_decoder as ref

#: the keys of a published ``config.json`` that the program reads
PUBLISHED = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "intermediate_size", "rope_theta", "rope_scaling",
             "rms_norm_eps", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "num_experts_per_tok", "moe_intermediate_size", "first_k_dense_replace",
             "n_shared_experts", "scoring_func", "n_group", "topk_group", "norm_topk_prob",
             "routed_scaling_factor", "topk_method", "attention_bias", "hidden_act")


def program_config(cfg: dict, max_seq_len: int, **overrides):
    """The program's configuration, from the published keys of ``cfg`` (depth
    and vocabulary as cut, the router at its published width) and the experts
    held here, through the program's own ``config_from_args``."""
    from fedml_tpu.llm.model import config_from_args
    first, count, of = weights.held(cfg)
    published = {k: cfg[k] for k in PUBLISHED if k in cfg}
    published["n_routed_experts"] = of
    args = types.SimpleNamespace(
        model="llama", llm_config_json=published, llm_max_seq_len=int(max_seq_len),
        llm_experts_held=(first, count) if count < of else None,
        model_dtype=cfg.get("compute_dtype", "bfloat16"))
    lcfg = config_from_args(args)
    if not getattr(lcfg, "latent_attention", False):
        import harness
        raise harness.BenchError(
            "the program in this checkout has no latent attention or does not read a "
            "published config.json (llm_config_json): it cannot run this configuration")
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
        prefill_chunk_tokens=int(engine["prefill_chunk_tokens"]))
    del adapters, zero
    return srv


def setup(run) -> dict:
    """``serve.setup`` with this architecture's server: build, warm the chunk
    and the tick program (a prompt shorter and one longer than a chunk),
    start the callers ``ramp_seconds`` before the window."""
    cfg, t, engine = run.cfg, run.cell["traffic"], run.cell["engine"]
    t0 = time.perf_counter()
    srv = build_server(cfg, engine, int(t["adapters"]["count"]), run.seed)
    port = srv.start()
    built_s = time.perf_counter() - t0
    client = traffic.LoadClient(port)
    rng = np.random.default_rng([run.seed, 0x3A53])
    chunk = int(engine["prefill_chunk_tokens"])
    warm = [{"idx": -1 - i, "prompt_ids": [int(x) for x in rng.integers(
                1, cfg["vocab_size"], size=n)], "max_tokens": 6, "adapter": traffic.adapter_name(ad)}
            for i, (n, ad) in enumerate(((chunk // 2, 0), (2 * chunk + 5, 0), (chunk + 1, 1), (7, 1)))]
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
    requests = traffic.Requests(t, int(cfg["vocab_size"]), run.seed)
    ramp = client.run_closed(requests, int(t["callers"]), time.perf_counter(),
                             float(t["ramp_seconds"]))
    return {"srv": srv, "client": client, "requests": requests, "ramp": ramp}


def finish(state: dict, run) -> dict:
    result = serve.finish(state, run)
    k0, k1 = state["ticks0"], state["ticks1"]
    layers = k1.get("moe_layers_ticked", 0) - k0.get("moe_layers_ticked", 0)
    if layers:
        hit = (k1["experts_hit"] - k0["experts_hit"]) / layers
        run.counters.update(expert_pairs=k1["expert_pairs"] - k0["expert_pairs"],
                            experts_hit_mean=hit)
        result["notes"].update(expert_pairs=run.counters["expert_pairs"], experts_hit_mean=hit,
                               kv_bytes_per_token=k1.get("kv_bytes_per_token"))
    return result


# -- the comparison ----------------------------------------------------------------

def forced(cfg: dict, base, adapters: dict, rec: dict, length: int, quant=None) -> dict:
    """One request through the reference: for each served token its gap in
    units of the position's logit spread, and the position's routing margin."""
    import jax.numpy as jnp
    ids = rec["prompt_ids"] + rec["tokens"]
    seq = np.zeros((1, length), np.int32)
    seq[0, :len(ids)] = ids
    first, count, of = weights.held(cfg)
    out = ref.forced_gaps(base, adapters.get(rec["adapter"]), jnp.asarray(seq), cfg,
                          (first, count) if count < of else None, quant)
    span = slice(len(rec["prompt_ids"]) - 1, len(ids) - 1)
    spread = np.asarray(out["spread"])[span]
    res = {"gaps": np.asarray(out["gap"])[span] / spread,
           "margins": np.asarray(out["margin"])[span]}
    if quant is not None:
        res["control_gaps"] = np.asarray(out["control_gap"])[span] / spread
    return res


def numbers(rows, margin: float, key: str = "gaps") -> dict:
    """Over the positions whose routing margin is at least ``margin``:
    ``served_gap``, the widest gap, and ``served_gap_q99``, the gap that one
    position in a hundred exceeds (a flip at an earlier position reaches a
    later one through the attention, so single positions read far above the
    rest; the quantile is what the lower precision moves most); and
    ``near_tie_share``, the share of the positions set apart."""
    gaps = np.concatenate([r[key] for r in rows]) if rows else np.zeros(0)
    near = np.concatenate([r["margins"] for r in rows]) < margin if rows else np.zeros(0, bool)
    clear = gaps[~near]
    return {"served_gap": float(clear.max()) if clear.size else 1e30,
            "served_gap_q99": float(np.quantile(clear, 0.99)) if clear.size else 1e30,
            "near_tie_share": float(near.mean()) if near.size else 1.0}


def readings(rows, top: int = 24) -> dict:
    """What a limit is set from: the widest gaps of the checked positions,
    each beside its routing margin, and how the margins are spread."""
    if not rows:
        return {}
    gaps = np.concatenate([r["gaps"] for r in rows])
    margins = np.concatenate([r["margins"] for r in rows])
    order = np.argsort(-gaps)[:top]
    return {"widest_gaps_with_margins": [[float(gaps[i]), float(margins[i])] for i in order],
            "margin_quantiles": {str(q): float(np.quantile(margins, q))
                                 for q in (0.01, 0.05, 0.1, 0.25, 0.5)},
            "margins_infinite_share": float(np.mean(np.isinf(margins)))}


def reference_weights(cfg: dict, seed: int, names) -> tuple:
    base = weights.make_base(cfg, seed)
    adapters = {name: weights.make_lora(cfg, seed, index=traffic.adapter_index(name) + 1)
                for name in sorted(set(n for n in names if n))}
    return base, adapters


def forced_sample(state: dict, run, quant=None) -> tuple:
    """Free the program's state, then the sampled requests through the
    reference: ``(records, picks, rows, seconds)``, a row of :func:`forced` a
    pick and the seconds the reference took over them."""
    import jax
    records = state.pop("records")
    state.pop("client").close()
    state.pop("srv").stop()
    state.clear()
    gc.collect()
    jax.clear_caches()
    picks = sample(records, int(run.cell["check"]["sample"]), run.seed)
    base, adapters = reference_weights(run.cfg, run.seed, [r["adapter"] for r in picks])
    length = int(run.cell["engine"]["buf_len"])
    t0 = time.perf_counter()
    rows = [forced(run.cfg, base, adapters, r, length, quant) for r in picks]
    return records, picks, rows, time.perf_counter() - t0


def check(state: dict, run, result: dict) -> dict:
    spec = run.cell["check"]
    length = int(run.cell["engine"]["buf_len"])
    records, picks, rows, reference_s = forced_sample(state, run)
    out = numbers(rows, float(spec["near_tie_margin"]))
    out["unanswered"] = sum(1 for r in records if r["error"] is not None or r["status"] != 200)
    # an answer is as long as asked for, or ends at the engine's buffer
    out["short_answers"] = sum(
        1 for r in records if r["error"] is None and r["status"] == 200
        and not min(r["max_tokens"], length - len(r["prompt_ids"]) - 1)
        <= len(r["tokens"]) <= r["max_tokens"])
    run.note(checked_requests=len(picks), checked_tokens=sum(len(r["tokens"]) for r in picks),
             reference_s=reference_s, **readings(rows))
    return {k: {"value": v, "limit": spec["limits"][k]} for k, v in out.items()
            if k in spec["limits"]}
