"""Traffic of the kind "requests to the OpenAI-compatible endpoint", for an
``lfm2_moe`` configuration (``architecture: lfm2_moe_decoder``: gated
short-convolution layers with one attention layer in four, norms on q and k,
sigmoid-routed experts chosen under a selection bias): ``drivers/serve.py``'s
closed loop, window and one-class generator (``traffic.py``), with a server,
weights, reference and comparison of this architecture's own.

What ``benchmarks/README.md`` would say of it.  A configuration of this kind
brings ``weights_lfm2_moe.py`` and ``reference/lfm2_moe_decoder.py``; its file
holds every published key, the depth and the leading dense layers cut as
``reduced`` lists, ``layer_types`` whole (the layers run are its first
``num_hidden_layers``), and three keys of its own that the published config
has no name for and the file lists under ``assumed``: ``scoring_func``,
``use_qk_norm``, ``tie_word_embeddings``.  The program is built the way a user
builds it: ``config_from_args`` reads the published keys (``llm_config_json``).
A program that computes no such layers says so at once and the run prints no
result.  A cell of this driver is data only: ``serve``'s keys, and under
``check`` also ``near_tie_margin`` and ``answer_tail``.

``finish`` also counts what the expert layers did (``expert_pairs``,
``experts_hit_mean``: experts that got a token, a sparse layer and tick) and
what the engine holds that is not pages (``state_bytes``, ``state_rows``) for
the rooflines.

**How ``correct`` is decided.**  As in ``serve_mla_moe``: a sample of the
requests the window finished, drawn from the seed, the longest among them,
each teacher-forced once through the reference with its adapter.  Positions
whose routing margin (over ``s + b``, the selection's own scores) is under
``check.near_tie_margin`` are set apart (``near_tie_share``); the gap, in
units of the position's logit spread, by which a served token's logit lies
below the reference's best is taken over the rest: ``served_gap_q90`` and
``served_gap_q99``, the gaps one position in ten and in a hundred exceed, are
held to limits; ``served_gap``, the widest, is a reading in the run's notes
and no limit (the int8 control reads inside the program's range).  New here,
``served_gap_q90``, the deciding number: this chip holds **every** expert of
twelve sparse layers, so a choice that bfloat16 flips anywhere before a
position reaches it through the attention layers and a position's gap does not
depend on its own margin (``PERF.md`` section 2); a third of the served tokens
are not the reference's best, and neither is a third of the tokens that the
reference itself puts first when it computes in bfloat16 (``quant="bfloat16"``,
the witness, read by the calibration and by no run).  At the
worst of the runs read the control's least ``served_gap_q90`` is 2.55 times
the program's largest, its least ``served_gap_q99`` 1.65 times.  ``PERF.md``
section 2 gives the readings the margin and the limits were set from, and
which of the three planted faults the chip's ``correct`` sees.  It guards the
precision and the selection; the state's reset, carry and hold (two positions
of a prompt, which reach an answer through the attention layers) rest on
``tests/test_lfm2_moe.py``: float32, logits at every position."""

from __future__ import annotations

import dataclasses
import gc
import time
import types

import numpy as np

import traffic
import weights_lfm2_moe as weights
from drivers import serve
from drivers.serve import sample, window  # noqa: F401  (the harness calls them here)
from drivers.serve_cohere2_moe import answered, held_to, passes  # noqa: F401
from drivers.serve_mla_moe import readings  # noqa: F401
from reference import lfm2_moe_decoder as ref

#: the keys of a published ``config.json`` that the program reads, and the
#: three the configuration's file adds as assumed
PUBLISHED = ("model_type", "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "intermediate_size", "moe_intermediate_size", "rope_theta",
             "norm_eps", "layer_types", "conv_L_cache", "conv_bias", "num_dense_layers",
             "num_experts", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
             "use_expert_bias", "scoring_func", "use_qk_norm", "tie_word_embeddings")


def program_config(cfg: dict, max_seq_len: int, **overrides):
    """The program's configuration, from the published keys of ``cfg`` (depth
    and leading dense layers as cut), through the program's own
    ``config_from_args``."""
    import harness
    from fedml_tpu.llm.model import config_from_args
    published = {k: cfg[k] for k in PUBLISHED if k in cfg}
    published["layer_types"] = weights.kinds(cfg)
    args = types.SimpleNamespace(
        model="llama", llm_config_json=published, llm_max_seq_len=int(max_seq_len),
        model_dtype=cfg.get("compute_dtype", "bfloat16"))
    try:
        lcfg = config_from_args(args)
    except ValueError as e:
        raise harness.BenchError(
            f"the program in this checkout cannot run this configuration (lfm2_moe): {e}")
    if not (getattr(lcfg, "conv_layers", 0) and getattr(lcfg, "qk_norm", False)
            and getattr(lcfg, "moe_select_bias", False)):
        raise harness.BenchError(
            "the program in this checkout computes no short-convolution layers, no norms on "
            "q and k or no selection bias (lfm2_moe): it cannot run this configuration")
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
    and the tick program (a prompt shorter than a chunk, and prompts of two and
    of three chunks, whose later chunks carry the state), start the callers
    ``ramp_seconds`` before the window."""
    cfg, t, engine = run.cfg, run.cell["traffic"], run.cell["engine"]
    t0 = time.perf_counter()
    srv = build_server(cfg, engine, int(t["adapters"]["count"]), run.seed)
    port = srv.start()
    built_s = time.perf_counter() - t0
    client = traffic.LoadClient(port)
    rng = np.random.default_rng([run.seed, 0x3A53])
    chunk = int(engine["prefill_chunk_tokens"])
    longest = min(2 * chunk + 5, int(engine["buf_len"]) - 8)
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
    requests = traffic.Requests(t, int(cfg["vocab_size"]), run.seed)
    ramp = client.run_closed(requests, int(t["callers"]), time.perf_counter(),
                             float(t["ramp_seconds"]))
    return {"srv": srv, "client": client, "requests": requests, "ramp": ramp}


def finish(state: dict, run) -> dict:
    result = serve.finish(state, run)
    k0, k1 = state["ticks0"], state["ticks1"]
    notes = result["notes"]
    layers = k1.get("moe_layers_ticked", 0) - k0.get("moe_layers_ticked", 0)
    if layers:
        hit = (k1["experts_hit"] - k0["experts_hit"]) / layers
        run.counters.update(expert_pairs=k1["expert_pairs"] - k0["expert_pairs"],
                            experts_hit_mean=hit)
        notes.update(expert_pairs=run.counters["expert_pairs"], experts_hit_mean=hit,
                     kv_bytes_per_token=k1.get("kv_bytes_per_token"))
    if "state_bytes" in k1:
        run.counters.update(state_bytes=k1["state_bytes"], state_rows=k1["state_rows"])
        notes.update(state_bytes=k1["state_bytes"], state_rows=k1["state_rows"])
    notes["reservations_refused_in_window"] = k1["pool"]["exhausted"] - k0["pool"]["exhausted"]
    return result


# -- the comparison ----------------------------------------------------------------

def numbers(rows, margin: float, key: str = "gaps") -> dict:
    """``serve_mla_moe.numbers`` and, over the same clear positions,
    ``served_gap_q90``: the gap that one position in ten exceeds."""
    from drivers import serve_mla_moe
    out = serve_mla_moe.numbers(rows, margin, key)
    gaps = np.concatenate([r[key] for r in rows]) if rows else np.zeros(0)
    clear = gaps[np.concatenate([r["margins"] for r in rows]) >= margin] if rows else gaps
    out["served_gap_q90"] = float(np.quantile(clear, 0.9)) if clear.size else 1e30
    return out


def forced(cfg: dict, base, adapters: dict, rec: dict, length: int, tail: int, quant=None) -> dict:
    """One request through the reference in a buffer of ``length``: for each
    served token its gap in units of the position's logit spread, and the
    position's routing margin (``tail`` positions from the prompt's last are
    computed, the request's own are kept); for each lower precision in
    ``quant`` the gap of the token it puts first (``ref.forced_gaps``)."""
    import jax.numpy as jnp
    ids = rec["prompt_ids"] + rec["tokens"]
    seq = np.zeros((1, length), np.int32)
    seq[0, :len(ids)] = ids
    out = ref.forced_gaps(base, adapters.get(rec["adapter"]), jnp.asarray(seq),
                          len(rec["prompt_ids"]) - 1, tail, cfg, quant)
    span = slice(0, len(rec["tokens"]))
    spread = np.asarray(out["spread"])[span]
    res = {"gaps": np.asarray(out["gap"])[span] / spread,
           "margins": np.asarray(out["margin"])[span]}
    for name in ref.LOWER.values():         # control_gap -> control_gaps, witness_gap -> witness_gaps
        if name in out:
            res[name + "s"] = np.asarray(out[name])[span] / spread
    return res


def reference_weights(cfg: dict, seed: int, names) -> tuple:
    base = weights.make_base(cfg, seed)
    adapters = {name: weights.make_lora(cfg, seed, index=traffic.adapter_index(name) + 1)
                for name in sorted(set(n for n in names if n))}
    return base, adapters


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
    spec = run.cell["check"]
    picks = sample(records, int(spec["sample"]), run.seed)
    base, adapters = reference_weights(run.cfg, run.seed, [r["adapter"] for r in picks])
    length, tail = int(run.cell["engine"]["buf_len"]), int(spec["answer_tail"])
    t0 = time.perf_counter()
    rows = [forced(run.cfg, base, adapters, r, length, tail, quant) for r in picks]
    return records, picks, rows, time.perf_counter() - t0


def check(state: dict, run, result: dict) -> dict:
    spec = run.cell["check"]
    records, picks, rows, reference_s = forced_sample(state, run)
    out = {**numbers(rows, float(spec["near_tie_margin"])),
           **answered(records, int(run.cell["engine"]["buf_len"]))}
    run.note(checked_requests=len(picks), checked_tokens=sum(len(r["tokens"]) for r in picks),
             reference_s=reference_s, **readings(rows),
             **{k: v for k, v in out.items() if k not in spec["limits"]})      # read, and held to nothing
    return held_to(out, spec["limits"])
