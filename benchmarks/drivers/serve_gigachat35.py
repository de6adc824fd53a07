"""Traffic of the kind "requests to the OpenAI-compatible endpoint", for a
``gigachat3_5`` configuration (``architecture: gigachat35_decoder``: gated
delta-rule linear-attention layers beside latent attention, sandwich gated
norms, clamped SwiGLU experts chosen under a selection bias):
``drivers/serve.py``'s closed loop, window and one-class generator
(``traffic.py``), with a server, weights, reference and comparison of this
architecture's own.

What ``benchmarks/README.md`` would say of it.  A configuration of this kind
brings ``weights_gigachat35.py`` and ``reference/gigachat35_decoder.py``; its
file holds every published key with the depth, the leading dense layers, the
latent layers' places, the experts held here, the vocabulary and the
multi-token prediction modules cut as ``reduced`` lists, ``experts_held``
(``first``, ``count``, ``of``: the router's published width), and two keys of
its own that the published config has no name for and the file lists under
``assumed``: ``scoring_func`` and ``topk_method``.  The program is built the way
a user builds it: ``config_from_args`` reads the published keys
(``llm_config_json``) and is told which experts live here
(``llm_experts_held``).  A program that computes no linear-attention layers
says so at once and the run prints no result.  A cell of this driver is data
only: ``serve``'s keys, and under ``check`` also ``near_tie_margin`` and
``answer_tail``.

**The window's close.**  An answer here is some four thousand tokens, a minute
and more of ticks, so at the close most callers are in the middle of one.
Those requests are cut there: their connections are closed, the tokens they
got inside the window count as every other's, and they are checked by the
reference as far as they got; they are counted neither as unanswered nor as
short (``cut_at_close`` in the notes), and a request that had no token yet is
dropped (it was never served in the window).  The engine is stopped before the
reference runs.

``finish`` also counts what the expert layers did (``expert_pairs``,
``experts_hit_mean``), what the engine holds that is not pages
(``state_bytes``, ``state_rows``) and the live lanes of a tick
(``live_lanes_mean``: the window's tokens less the first tokens of requests,
which come from chunks, over its ticks) for the rooflines.

**How ``correct`` is decided.**  As in ``serve_lfm2_moe``: a sample of the
window's requests, drawn from the seed, the longest among them, each
teacher-forced once through the reference with its adapter, and the gaps of
the served tokens' logits below the reference's best in units of the
position's spread, over positions whose routing margin (over ``s + b``, where
a held expert is one of the two) is at least ``check.near_tie_margin``:
``served_gap_q90`` and ``served_gap_q99`` are held to limits, ``served_gap``
is a reading of the notes.  The logits cannot tell a matrix state kept in
bfloat16 from the bfloat16 of the activations around it, so the state itself
is held too: ``state_gap`` (``state_gaps``), sampled slots' rows of the first
linear layer against the reference's recurrence fed the program's own
projections.  ``PERF.md`` section 2 gives the readings the limits were set
from: the program on several seeds, the int8 control and two planted faults
(``calibrate_gigachat35.py::FAULTS``)."""

from __future__ import annotations

import dataclasses
import gc
import logging
import time
import types

import numpy as np

import traffic
import weights_gigachat35 as weights
from drivers import serve_lfm2_moe
from drivers.serve import sample, window  # noqa: F401  (the harness calls them here)
from drivers.serve_cohere2_moe import held_to, passes  # noqa: F401
from drivers.serve_mla_moe import readings  # noqa: F401
from reference import gigachat35_decoder as ref

#: the keys of a published ``config.json`` that the program reads, and the
#: two the configuration's file adds as assumed
PUBLISHED = ("model_type", "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "intermediate_size", "max_position_embeddings", "rope_theta",
             "rope_scaling", "rope_interleave", "rms_norm_eps", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim", "v_head_dim",
             "num_experts_per_tok", "moe_intermediate_size", "first_k_dense_replace",
             "n_shared_experts", "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
             "attention_bias", "hidden_act", "norm_type", "layernorm_type", "layernorm_gating_weight",
             "gated_attention", "use_shared_expert_sigmoid", "use_mla_scaling_factor",
             "linear_attention_type", "full_attention_layers", "linear_key_head_dim",
             "linear_value_head_dim", "linear_conv_kernel_dim", "linear_num_key_heads",
             "linear_num_value_heads", "linear_gating_type", "linear_sigmoid_gate_scale",
             "linear_attn_o_norm_eps", "swiglu_limit", "tie_word_embeddings",
             "num_nextn_predict_layers", "scoring_func", "topk_method")
#: the server's logger: a cut connection's next write fails there, by design
SERVER_LOG = "fedml_tpu.serving.templates.openai_compat"
#: how long the engine's thread is given to end after the server is stopped
STOP_WAIT_S = 60.0


def program_config(cfg: dict, max_seq_len: int, **overrides):
    """The program's configuration, from the published keys of ``cfg`` (as
    cut, the router at its published width) and the experts held here, through
    the program's own ``config_from_args``."""
    import harness
    from fedml_tpu.llm.model import config_from_args
    first, count, of = weights.held(cfg)
    published = {k: cfg[k] for k in PUBLISHED if k in cfg}
    published["n_routed_experts"] = of
    args = types.SimpleNamespace(
        model="llama", llm_config_json=published, llm_max_seq_len=int(max_seq_len),
        llm_experts_held=(first, count) if count < of else None,
        model_dtype=cfg.get("compute_dtype", "bfloat16"))
    try:
        lcfg = config_from_args(args)
    except ValueError as e:
        raise harness.BenchError(
            f"the program in this checkout cannot run this configuration (gigachat3_5): {e}")
    if not (getattr(lcfg, "linear_layers", 0) and getattr(lcfg, "gated_attention", False)
            and getattr(lcfg, "sandwich_norm", False) and getattr(lcfg, "swiglu_limit", 0)):
        raise harness.BenchError(
            "the program in this checkout computes no linear-attention layers, no gated latent "
            "attention, no sandwich norms or no clamped SwiGLU (gigachat3_5): it cannot run "
            "this configuration")
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
    """``serve_lfm2_moe.setup`` with this architecture's server: build, warm the
    chunk and the tick program (a prompt shorter than a chunk, and prompts of
    two and of three chunks, whose later chunks carry the state), start the
    callers ``ramp_seconds`` before the window."""
    cfg, t, engine = run.cfg, run.cell["traffic"], run.cell["engine"]
    t0 = time.perf_counter()
    srv = build_server(cfg, engine, int(t["adapters"]["count"]), run.seed)
    log = SlotLog(srv._engine)
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
    return {"srv": srv, "client": client, "requests": requests, "ramp": ramp, "slot_log": log}


class SlotLog:
    """What each of the engine's slots was last given and has delivered: the
    prompt, the adapter and the tokens of the request it holds or held last,
    recorded on the engine's own thread as it admits a request and delivers a
    token.  ``read_state`` reads from it what a slot's row of state has
    absorbed."""

    def __init__(self, engine):
        self.slots = {}
        admit, emit = engine._admit, engine._emit

        def admitted(req, slot):
            self.slots[slot] = (list(req["prompt_ids"]), req["adapter"], [])
            return admit(req, slot)

        def emitted(i, tok):
            s = engine._slots[i]
            before = s.out_tokens
            more = emit(i, tok)
            if s.out_tokens > before and i in self.slots:
                self.slots[i][2].append(int(tok))
            return more

        engine._admit, engine._emit = admitted, emitted


def cut_at_close(state: dict) -> int:
    """Close the connections still open: what they got in the window stays
    theirs; a request that got nothing is dropped.  Returns how many were
    cut."""
    client = state["client"]
    logging.getLogger(SERVER_LOG).setLevel(logging.CRITICAL)
    # the handler of a cut request fails at its next write: nothing to print
    state["srv"]._server.handle_error = lambda request, address: None
    cut = 0
    for conn in list(client.open.values()):
        conn.rec["cut"] = True
        client._close(conn)
        cut += 1
    state["records"] = [r for r in state["records"] if not (r.get("cut") and not r["tokens"])]
    return cut


def finish(state: dict, run) -> dict:
    cut = cut_at_close(state)
    result = serve_lfm2_moe.finish(state, run)
    k0, k1 = state["ticks0"], state["ticks1"]
    t0, t1 = result["window"]
    ticks = k1["ticks"] - k0["ticks"]
    firsts = sum(1 for r in state["records"] if r["first"] is not None and t0 <= r["first"] <= t1)
    lanes = (run.counters["generated_tokens"] - firsts) / max(ticks, 1)
    run.counters.update(live_lanes_mean=lanes)
    result["notes"].update(cut_at_close=cut, live_lanes_mean=lanes)
    return result


# -- the comparison ----------------------------------------------------------------

numbers = serve_lfm2_moe.numbers


def answered(records, length: int) -> dict:
    """The exact counts over the requests that ended before the close: those
    that got no answer, and answers that are neither as long as asked for nor
    ended by the engine's buffer."""
    ended = [r for r in records if not r.get("cut")]
    return {"unanswered": sum(1 for r in ended if r["error"] is not None or r["status"] != 200),
            "short_answers": sum(
                1 for r in ended if r["error"] is None and r["status"] == 200
                and not min(r["max_tokens"], length - len(r["prompt_ids"]) - 1)
                <= len(r["tokens"]) <= r["max_tokens"])}


def forced(cfg: dict, base, adapters: dict, rec: dict, length: int, tail: int, quant=None) -> dict:
    """One request through the reference in a buffer of ``length``: for each
    served token its gap in units of the position's logit spread and the
    position's routing margin; for each lower precision in ``quant`` the gap of
    the token it puts first."""
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
    for name in ref.LOWER.values():
        if name in out:
            res[name + "s"] = np.asarray(out[name])[span] / spread
    return res


def reference_weights(cfg: dict, seed: int, names) -> tuple:
    base = weights.make_base(cfg, seed)
    adapters = {name: weights.make_lora(cfg, seed, index=traffic.adapter_index(name) + 1)
                for name in sorted(set(n for n in names if n))}
    return base, adapters


def stop_program(state: dict) -> None:
    """Close the callers' connections and stop the server and its engine,
    once.  When this returns the engine's thread has ended, every token the
    device produced has been delivered, and the pool and the slots' state on
    the device are as the last program left them."""
    if state.get("stopped"):
        return
    state.pop("client").close()
    engine = state["engine"] = state["srv"]._engine        # the server lets go of it
    state["srv"].stop()
    engine._thread.join(STOP_WAIT_S)
    state["stopped"] = True


def _projections_fn(lcfg, layer: int):
    """The program's own projections ``q k v b a`` of linear layer ``layer``
    over a whole sequence, by the model cut after that layer; jitted."""
    import jax
    from fedml_tpu.llm.model import LlamaLM
    name = f"layer_{layer}"
    model = LlamaLM(dataclasses.replace(lcfg, n_layers=layer + 1,
                                        layer_types=tuple(lcfg.layer_types[:layer + 1])))

    def kept(mdl, method):
        path = mdl.scope.path if mdl.scope is not None else ()
        return method == "__call__" and path[:2] == (name, "linear_attn") and len(path) == 3 \
            and path[2] in ("q", "k", "v", "b", "a")

    def f(params, lora, tokens):
        _, got = model.apply({"params": params, "lora": lora}, tokens, return_hidden=True,
                             capture_intermediates=kept, mutable=["intermediates"])
        mods = got["intermediates"][name]["linear_attn"]
        return {p: mods[p]["__call__"][0] for p in ("q", "k", "v", "b", "a")}

    return jax.jit(f)


def read_state(state: dict, run) -> dict:
    """Stop the program, then hold the matrix state it keeps to the rule
    (``state_gaps`` over ``check.state_slots`` slots)."""
    stop_program(state)
    return state_gaps(state["engine"], state["slot_log"].slots, run.cfg, run.seed,
                      int(run.cell["check"]["state_slots"]), int(run.cell["engine"]["buf_len"]))


def state_gaps(engine, log: dict, cfg: dict, seed: int, count: int, length: int) -> dict:
    """Of the slots of a stopped ``engine`` whose request reached its first
    token (``log``: ``SlotLog.slots``), ``count`` drawn from the seed: the
    matrix state of the first linear layer as the slot's row holds it, against
    the reference's recurrence (``ref.state_after``, float32, step by step)
    fed the program's own projections of the positions that row has absorbed
    (the prompt and the tokens the ticks fed back: as many as the slot's
    position on the device says), computed again by the program cut after that
    layer in a buffer of ``length``.  Both sides then read the model's own
    numbers for the projections, so the bfloat16 of the activations, which
    hides a state kept in bfloat16 from the logits, is not between them; what
    is left is how the program carries the state (in the chunked form, step by
    step, between calls) and where the recomputation rounds a projection
    otherwise than the tick did.  That last part is read apart:
    ``conv_rows_differ``, the same distance between the convolution rows the
    engine keeps (the projections of a slot's last ``taps - 1`` positions as
    its ticks computed them) and the recomputed ones.  ``state_gap``: the
    widest distance, by the Frobenius norm over the layer's value heads,
    relative to the reference's state; ``state_slots_read``,
    ``state_positions``, ``state_gaps``.  The engine's pool is let go."""
    import jax
    import jax.numpy as jnp
    lcfg = engine.model.cfg
    layer = next(i for i in range(lcfg.n_layers) if lcfg.layer_linear(i))
    poss = np.asarray(engine._dev["poss"])
    ready = sorted(i for i, (_, _, toks) in log.items() if toks)
    rng = np.random.default_rng([seed, 0x57A7E])
    count = min(count, len(ready))
    picks = sorted(int(i) for i in rng.choice(ready, size=count, replace=False)) if count else []
    mine = engine._pool[f"layer_{layer}"]["linear_attn"]
    at = jnp.asarray(picks, jnp.int32)
    held = np.asarray(mine["linear_state"][at].astype(jnp.float32)) if picks else []
    convs = np.asarray(mine["linear_conv"][at].astype(jnp.float32)) if picks else []
    del mine
    engine._pool = None         # the pool is not read again
    params = engine.raw_params
    kept = {k: params[k] for k in ("tok_embed", "final_norm")}
    kept.update({f"layer_{i}": params[f"layer_{i}"] for i in range(layer + 1)})
    project = _projections_fn(lcfg, layer)
    taps = lcfg.linear_conv_kernel
    gaps, differ, positions = [], [], []
    for i, got, conv in zip(picks, held, convs):
        prompt, adapter, toks = log[i]
        n = int(poss[i])
        if not len(prompt) <= n < len(prompt) + len(toks):     # the row is not what the log says
            gaps.append(float("inf"))
            continue
        seq = np.zeros((1, length), np.int32)
        seq[0, :n] = (prompt + toks)[:n]
        lora = weights.make_lora(cfg, seed, index=traffic.adapter_index(adapter) + 1 if adapter else 1)
        if not adapter:         # the bank's row 0: no adapter
            lora = jax.tree_util.tree_map(jnp.zeros_like, lora)
        proj = project(kept, {f"layer_{j}": lora[f"layer_{j}"] for j in range(layer + 1)
                              if f"layer_{j}" in lora}, jnp.asarray(seq))
        mixed = jnp.concatenate([proj["q"], proj["k"], proj["v"]], axis=-1)
        want = np.asarray(ref.state_after(params[f"layer_{layer}"]["linear_attn"], mixed,
                                          proj["b"], proj["a"], n, cfg)[0], np.float64)
        gaps.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
        last = np.asarray(mixed[0, n - taps + 1:n].astype(jnp.float32))
        differ.append(float(np.linalg.norm(last - conv) / np.linalg.norm(conv)))
        positions.append(n)
        del lora, proj, mixed
    return {"state_gap": max(gaps) if gaps else 1e30, "state_slots_read": len(gaps),
            "state_positions": positions, "state_gaps": gaps, "conv_rows_differ": differ}


def free_program(state: dict, run) -> list:
    """Stop the program and free what it holds on the device; the window's
    records."""
    import jax
    records = state.pop("records")
    stop_program(state)
    srv = state.pop("srv")
    # a handler thread still unwinding a cut request holds the server, and
    # the server the base: drop what it holds, whatever points at it
    vars(srv).clear()
    del srv
    state.clear()
    gc.collect()
    jax.clear_caches()
    run.note(device_bytes_live_before_reference=sum(a.nbytes for a in jax.live_arrays()))
    return records


def forced_sample(state: dict, run, quant=None) -> tuple:
    """Stop the program and free its state, then the sampled requests through
    the reference: ``(records, picks, rows, seconds)``."""
    records = free_program(state, run)
    spec = run.cell["check"]
    picks = sample(records, int(spec["sample"]), run.seed)
    base, adapters = reference_weights(run.cfg, run.seed, [r["adapter"] for r in picks])
    length, tail = int(run.cell["engine"]["buf_len"]), int(spec["answer_tail"])
    t0 = time.perf_counter()
    rows = [forced(run.cfg, base, adapters, r, length, tail, quant) for r in picks]
    return records, picks, rows, time.perf_counter() - t0


def check(state: dict, run, result: dict) -> dict:
    spec = run.cell["check"]
    t0 = time.perf_counter()
    held = read_state(state, run)
    state_s = time.perf_counter() - t0
    records, picks, rows, reference_s = forced_sample(state, run)
    out = {**numbers(rows, float(spec["near_tie_margin"])),
           **answered(records, int(run.cell["engine"]["buf_len"])),
           "state_gap": held["state_gap"]}
    run.note(checked_requests=len(picks), checked_tokens=sum(len(r["tokens"]) for r in picks),
             checked_cut=sum(1 for r in picks if r.get("cut")), reference_s=reference_s,
             state_s=state_s, **{k: v for k, v in held.items() if k != "state_gap"},
             **readings(rows), **{k: v for k, v in out.items() if k not in spec["limits"]})
    return held_to(out, spec["limits"])
