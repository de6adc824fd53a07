#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py               # one TPU chip: every one-chip phase
    python chip_smoke.py --four-chips  # four chips: the two mesh comparisons

One process, no network, data and weights from ``--seed``.  With no argument
it drives, through the entry points a user calls:

- ``engine``: ``fedml_tpu.run_simulation(backend="sp")`` and
  ``backend="mesh"`` on the README quick-start job — same seed, same loss
  curve, same parameters;
- ``kernels``: the Pallas flash-attention forward and backward against the
  blockwise scan and its VJP;
- ``fedllm``: ``FedLLMAPI`` federated LoRA rounds at the flagship's widths
  (``tools/llm_scale_run.py``: 1.075B parameters), finite and falling loss,
  nothing compiled after the first round;
- ``server``: ``OpenAICompatServer`` over the same base with the paged
  batching engine and two adapters — ``/v1/completions`` over HTTP, every
  greedy token an argmax of the plain forward to bf16 tolerance, compared
  with single-request ``generate``.

With ``--four-chips`` it runs only what exists across chips: the mesh engine
on ``client=4`` against ``sp``, and ``FedLLMAPI`` on ``client=2 x model=2``
against the one-device ``FedLLMAPI``.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A platform other than ``tpu`` prints ``"ok": false`` and exits 1; a phase
that fails raises, so the process exits non-zero with the traceback and no
result line.  The phases are plain functions of their sizes:
``tests/test_chip_smoke.py`` calls them small on the CPU.
"""

from __future__ import annotations

import argparse
import gc
import http.client
import json
import logging
import math
import statistics
import sys
import threading
import time

#: the flagship of ``tools/llm_scale_run.py``: ~1.075B parameters, bf16 base
#: with fp32 LoRA adapters
FLAGSHIP = dict(dim=2048, n_layers=20, n_heads=16, n_kv_heads=8,
                ffn_dim=5632, vocab=32000, seq=256, lora_rank=16)
#: the four-chip comparison keeps every width and cuts the depth: two
#: programs of it are compiled in one call that costs four chips a second
FOUR_CHIP_LAYERS = 4
#: (batch, q heads, kv heads, seq, head_dim): the flagship's attention as a
#: two-client cohort traces it, and the one shape the tile table holds
KERNEL_SHAPES = ((2, 16, 8, 256, 128), (4, 12, 12, 1024, 64))


class SmokeFailure(Exception):
    """A phase ran to its end and what came out is wrong."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- what a window of work did ------------------------------------------------

_cache_hits = [0]
_listening = [False]


def _count_cache_hits() -> None:
    """Count persistent-compile-cache hits for the life of the process (jax
    has no public way to take a listener off again)."""
    if _listening[0]:
        return
    import jax

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _cache_hits[0] += 1

    jax.monitoring.register_event_listener(on_event)
    _listening[0] = True


class _AttentionPaths(logging.Handler):
    """Counts ``fedml_tpu.ops.attention``'s trace-time records by the
    implementation they name."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.paths: dict = {}

    def emit(self, record: logging.LogRecord) -> None:
        if record.args and record.msg.startswith("attention trace"):
            impl = str(record.args[0])
            self.paths[impl] = self.paths.get(impl, 0) + 1


class Window:
    """Seconds, compile requests (a persistent-cache hit is one too),
    persistent-cache hits and traced attention calls between enter and
    exit."""

    def __enter__(self) -> "Window":
        from fedml_tpu.analysis.runtime import JaxRuntimeAudit
        _count_cache_hits()
        self._log = logging.getLogger("fedml_tpu.ops.attention")
        self._level = self._log.level
        self._paths = _AttentionPaths()
        self._log.addHandler(self._paths)
        self._log.setLevel(logging.INFO)
        self._audit = JaxRuntimeAudit().__enter__()
        self._hits0 = _cache_hits[0]
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._audit.__exit__(*exc)
        self._log.removeHandler(self._paths)
        self._log.setLevel(self._level)
        self.compilations = self._audit.compilations
        self.cache_hits = _cache_hits[0] - self._hits0
        self.attention = dict(self._paths.paths)

    @property
    def compilations_now(self) -> int:
        return self._audit.compilations


def peak_bytes() -> list:
    """``peak_bytes_in_use`` of each device since the process started
    (``None`` where the backend keeps no such statistic)."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def live_bytes_per_device() -> dict:
    """Bytes of every live array's shards, by the device that holds them
    (from the shardings: reading ``addressable_shards`` would itself make
    new live arrays)."""
    import jax
    out: dict = {}
    for a in jax.live_arrays():
        n = math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
        for d in a.sharding.addressable_devices:
            out[d.id] = out.get(d.id, 0) + n
    return out


def emit(report: dict) -> dict:
    print(json.dumps(report), flush=True)
    return report


# -- engine: the README quick start, sp against mesh --------------------------

def _run_quick_start(backend: str, rounds: int, seed: int, clients: int,
                     cohort: int, train_size: int):
    """One ``run_simulation`` of the quick-start job; returns the final
    parameters, the per-round records and, per round, the compile requests
    seen up to that record."""
    import fedml_tpu
    from fedml_tpu import mlops
    from fedml_tpu.arguments import load_arguments

    args = load_arguments()
    args.update(dataset="synthetic", num_classes=10, input_shape=(28, 28, 1),
                train_size=train_size, test_size=512, model="lr",
                client_num_in_total=clients, client_num_per_round=cohort,
                comm_round=rounds, batch_size=16, learning_rate=0.1,
                frequency_of_the_test=1, partition_method="homo",
                random_seed=seed)
    marks = []
    with Window() as w, mlops.capture_events() as records:
        def mark(record):
            if record.get("type") == "round":
                marks.append(w.compilations_now)
        mlops.register_exporter(mark)
        try:
            params = fedml_tpu.run_simulation(backend=backend, args=args)
        finally:
            mlops.unregister_exporter(mark)
    curve = [r for r in records if r.get("type") == "round"]
    return params, curve, marks, w


def phase_engine(rounds: int = 6, seed: int = 0, clients: int = 32,
                 cohort: int = 8, train_size: int = 2048) -> dict:
    """``run_simulation`` on ``sp`` and on ``mesh`` (every device of this
    process on the client axis): the same seed gives the same loss curve
    and the same parameters — the repo's parity invariant
    (``tests/test_mesh.py::test_mesh_matches_sp``), to a tolerance that
    depends on whether a reduction crosses devices (below)."""
    import jax
    import numpy as np

    check(rounds >= 4, "engine phase needs two warm rounds after round 1")
    out = {}
    for backend in ("sp", "mesh"):
        params, curve, marks, w = _run_quick_start(
            backend, rounds, seed, clients, cohort, train_size)
        check(len(curve) == rounds, f"{backend}: {len(curve)} round records")
        losses = [float(r["train_loss"]) for r in curve]
        check(all(np.isfinite(losses)), f"{backend}: loss not finite")
        check(losses[-1] < losses[0], f"{backend}: loss did not fall")
        # rounds 0 and 1 compile (round, then the first evaluation); every
        # later record must see the same count
        warm = marks[1]
        out[backend] = {
            "params": params, "losses": losses,
            "test_acc": float(curve[-1]["test_acc"]),
            "compile_round_s": float(curve[0]["round_time"]),
            # the engine's own clock around a round, which ends in its
            # read-back of the round's metrics
            "warm_round_s": statistics.median(
                float(r["round_time"]) for r in curve[2:]),
            "warm_compilations": marks[-1] - warm,
            "total_s": w.seconds, "cache_hits": w.cache_hits,
        }
        check(out[backend]["warm_compilations"] == 0,
              f"{backend}: {out[backend]['warm_compilations']} compilations "
              "after round 1")
    # One device: no reduction is reassociated, and the two backends give
    # the same numbers (the repo's own tolerance for the parameters, the
    # float's print precision for the curve).  Several devices:
    # psum_scatter sums in another order, which moves a weight by an ULP —
    # and the chip's default f32 matmul rounds its inputs to bf16, so now
    # and then that ULP becomes a bf16 step (2^-8) of one product.
    one = jax.device_count() == 1
    p_atol, p_rtol = (2e-5, 1e-4) if one else (1e-4, 1e-3)
    l_atol, l_rtol = (1e-6, 1e-5) if one else (1e-4, 1e-3)
    a = jax.tree_util.tree_leaves(out["sp"].pop("params"))
    b = jax.tree_util.tree_leaves(out["mesh"].pop("params"))
    max_ulp = max_abs = 0.0
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        check(bool(np.allclose(x, y, atol=p_atol, rtol=p_rtol)),
              f"sp and mesh parameters differ by {np.max(np.abs(x - y))}")
        ulp = np.spacing(np.maximum(np.abs(x), np.abs(y)))
        max_ulp = max(max_ulp, float(np.max(np.abs(x - y) / ulp)))
        max_abs = max(max_abs, float(np.max(np.abs(x - y))))
    check(bool(np.allclose(out["sp"]["losses"], out["mesh"]["losses"],
                           atol=l_atol, rtol=l_rtol)),
          f"loss curves differ: {out['sp']['losses']} vs "
          f"{out['mesh']['losses']}")
    return emit({
        "phase": "engine", "rounds": rounds, "devices": jax.device_count(),
        "params_max_ulp": max_ulp, "params_max_abs_diff": max_abs,
        "curves_bit_equal": out["sp"]["losses"] == out["mesh"]["losses"],
        "sp": out["sp"], "mesh": out["mesh"],
        "peak_bytes_in_use": peak_bytes()})


# -- kernels: Pallas flash attention against the blockwise scan ---------------

def phase_kernels(shapes=KERNEL_SHAPES, seed: int = 0,
                  interpret: bool = False) -> dict:
    """The Pallas forward and backward kernels, called directly, against
    ``blockwise_attention`` and its VJP on bf16 inputs.  (Which of the two
    ``flash_attention`` picks inside a model is the gate's business; the
    other phases report it.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fedml_tpu.ops import attention as A

    rows = []
    for (b, h, h_kv, s, d) in shapes:
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, h_kv, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, h_kv, s, d), jnp.bfloat16)
        do = jax.random.normal(ks[3], (b, h, s, d), jnp.bfloat16)

        @jax.jit
        def pallas(q, k, v, do):
            out, lse = A.flash_attention_fwd_pallas(
                q, k, v, True, None, return_lse=True, interpret=interpret)
            return out, A.flash_attention_bwd_pallas(
                q, k, v, out, lse, do, True, None, interpret=interpret)

        @jax.jit
        def blockwise(q, k, v, do):
            out, vjp = jax.vjp(
                lambda q, k, v: A.blockwise_attention(q, k, v, True), q, k, v)
            return out, vjp(do)

        t0 = time.perf_counter()
        got = jax.block_until_ready(pallas(q, k, v, do))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(pallas(q, k, v, do))
        warm_s = time.perf_counter() - t0
        want = jax.block_until_ready(blockwise(q, k, v, do))
        errs = {}
        for name, g, w in zip(("out", "dq", "dk", "dv"),
                              (got[0], *got[1]), (want[0], *want[1])):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            check(bool(np.all(np.isfinite(g))), f"{name} not finite")
            # bf16 outputs of two differently-blocked softmaxes: a few
            # bf16 steps relative to the tensor's own scale
            errs[name] = float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
            check(errs[name] < 4e-2, f"shape {(b, h, h_kv, s, d)}: {name} "
                  f"differs from blockwise by {errs[name]:.3g}")
        rows.append({"shape": [b, h, h_kv, s, d], "rel_err": errs,
                     "first_call_s": compile_s, "warm_call_s": warm_s})
    return emit({"phase": "kernels", "interpret": interpret, "rows": rows,
                 "peak_bytes_in_use": peak_bytes()})


# -- fedllm: federated LoRA rounds at the flagship's widths -------------------

def _fedllm_api(*, dim, n_layers, n_heads, n_kv_heads, ffn_dim, vocab, seq,
                lora_rank, clients, local_steps, batch, rounds, seed,
                mesh=None):
    """Enter ``FedLLMAPI`` the way ``tools/llm_scale_run.py`` does.  Every
    client's data is exactly what one round consumes, so the rounds see the
    same sequences and a working trainer's loss falls."""
    import numpy as np

    import fedml_tpu
    from fedml_tpu import data as data_mod
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu.llm.fedllm import FedLLMAPI

    args = load_arguments()
    args.update(
        dataset="stackoverflow_nwp",
        train_size=clients * local_steps * batch, test_size=32,
        seq_len=seq, model="llama", llm_dim=dim, llm_n_layers=n_layers,
        llm_n_heads=n_heads, llm_n_kv_heads=n_kv_heads, llm_ffn_dim=ffn_dim,
        llm_max_seq_len=seq, client_num_in_total=clients,
        client_num_per_round=clients, comm_round=rounds, batch_size=batch,
        llm_max_local_steps=local_steps, lora_rank=lora_rank,
        learning_rate=2e-3, random_seed=seed, streaming_xent_chunk=8192,
        llm_remat="full")
    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, _ = data_mod.load(args)
    # the synthetic generator draws below its own vocabulary; the model
    # gets the flagship's
    for name in ("train_x", "train_y", "test_x", "test_y"):
        setattr(dataset, name, np.minimum(getattr(dataset, name), vocab - 1))
    dataset.num_classes = vocab
    return FedLLMAPI(args, dataset, mesh=mesh)


def _fedllm_rounds(api, rounds: int) -> dict:
    """``rounds`` rounds: the first compiles, the rest are the warm window
    (timed to ``block_until_ready``), which must compile nothing."""
    import jax
    import numpy as np

    check(rounds >= 3, "one round that compiles and two warm ones")
    losses, warm_s = [], []
    with Window() as first:
        losses.append(api.train_one_round(0)["train_loss"])
        jax.block_until_ready(api.global_lora)
    with Window() as warm:
        for r in range(1, rounds):
            t0 = time.perf_counter()
            losses.append(api.train_one_round(r)["train_loss"])
            jax.block_until_ready(api.global_lora)
            warm_s.append(time.perf_counter() - t0)
    check(all(np.isfinite(losses)), f"train_loss not finite: {losses}")
    check(losses[-1] < losses[0], f"train_loss did not fall: {losses}")
    check(warm.compilations == 0,
          f"{warm.compilations} compilations in the warm rounds")
    return {
        "train_loss": losses,
        "first_round_s": first.seconds,
        "warm_round_s": statistics.median(warm_s),
        "compile_s": first.seconds - statistics.median(warm_s),
        "first_round_compilations": first.compilations,
        "first_round_cache_hits": first.cache_hits,
        "warm_compilations": warm.compilations,
        # every traced flash_attention call, by the path the gate gave it
        "attention_paths": first.attention,
        "pallas_in_step": first.attention.get("pallas", 0) > 0,
    }


def _n_params(tree) -> int:
    import jax
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def phase_fedllm(*, rounds: int = 3, clients: int = 2, local_steps: int = 4,
                 batch: int = 2, seed: int = 0, **widths):
    """Returns ``(report, api)``: the server phase serves this base."""
    t0 = time.perf_counter()
    api = _fedllm_api(clients=clients, local_steps=local_steps, batch=batch,
                      rounds=rounds, seed=seed, **widths)
    init_s = time.perf_counter() - t0
    report = {"phase": "fedllm", "widths": widths,
              "n_params": _n_params(api.base_params),
              "n_lora_params": _n_params(api.global_lora),
              "cohort": {"clients": clients, "local_steps": local_steps,
                         "batch": batch, "seq": widths["seq"]},
              "init_s": init_s, **_fedllm_rounds(api, rounds),
              "peak_bytes_in_use": peak_bytes()}
    return emit(report), api


# -- server: the paged batching engine behind the OpenAI endpoint -------------

class IdTokenizer:
    """Token ids as decimal text, so that an answer over HTTP shows exactly
    which ids the server generated (the byte tokenizer drops every id above
    255 — nearly all of a 32000-token vocabulary)."""

    def encode(self, text: str) -> list:
        return [int(t) for t in text.split()]

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def phase_server(api, *, buf_len: int = 128, slots: int = 4,
                 page_tokens: int = 16, chunk_tokens: int = 32,
                 pool_pages: int = 0, max_tokens: int = 16,
                 seed: int = 0) -> dict:
    """``OpenAICompatServer`` over ``api``'s base with two registered
    adapters: a handful of ``/v1/completions`` requests, alone and at once.

    What decides: the server gives one request the same tokens every time,
    and each token it chose is — teacher-forced through the plain full
    forward, which shares no code with the engine's cache — an argmax of the
    reference to bf16 tolerance.  Single-request ``generate`` is held to the
    same reference and compared token for token: bit-equal on the CPU; on
    the chip two differently fused bf16 programs may break a near-tie
    differently, and the report says how far they agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fedml_tpu.llm.model import LlamaLM
    from fedml_tpu.serving.templates.openai_compat import (
        OpenAICompatServer, generate)

    model = LlamaLM(api.cfg)
    zero = jax.tree_util.tree_map(jnp.zeros_like, api.global_lora)
    adapters = {"fed": api.global_lora,
                "fed_x3": jax.tree_util.tree_map(lambda a: a * 3.0,
                                                 api.global_lora)}

    def apply_fn(params, tokens):
        return model.apply({"params": params, "lora": zero}, tokens)

    rng = np.random.default_rng(seed)
    tok = IdTokenizer()
    # prompts shorter and longer than one prefill chunk
    lengths = (5, chunk_tokens + 7, 2 * chunk_tokens + 3)
    check(max(lengths) + max_tokens < buf_len, "prompts do not fit buf_len")
    cases = [(tok.decode(rng.integers(1, api.cfg.vocab_size, size=n)), name)
             for n, name in zip(lengths, (None, "fed", "fed_x3"))]

    srv = OpenAICompatServer(
        apply_fn, api.base_params, tokenizer=tok, model=model,
        buf_len=buf_len, batch_slots=slots, adapters=adapters,
        adapter_slots=4, kv_page_tokens=page_tokens,
        kv_pool_pages=pool_pages, prefill_chunk_tokens=chunk_tokens)
    port = srv.start()

    def ask(case):
        prompt, adapter = case
        body = {"prompt": prompt, "max_tokens": max_tokens}
        if adapter:
            body["adapter"] = adapter
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        try:
            t0 = time.perf_counter()
            conn.request("POST", "/v1/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            seconds = time.perf_counter() - t0
        finally:
            conn.close()
        check(resp.status == 200, f"HTTP {resp.status}: {payload}")
        return payload["choices"][0]["text"], seconds

    try:
        with Window() as cold:          # compiles the chunk and tick programs
            answers = [ask(c)[0] for c in cases]
        with Window() as warm:
            alone = [ask(c) for c in cases]
            together = [None] * len(cases)

            def worker(i):
                together[i] = ask(cases[i])

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(cases))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            check(not any(t.is_alive() for t in threads)
                  and all(r is not None for r in together),
                  "a concurrent request did not come back")
        kv = srv._engine.kv_stats()
    finally:
        srv.stop()

    @jax.jit
    def forced_margins(params, lora, tokens):
        """For each position of ``tokens`` (1, buf_len): how far below the
        plain forward's best logit the NEXT token's logit lies, and the
        spread (best minus median) of that position's logits."""
        logits = model.apply({"params": params, "lora": lora},
                             tokens)[0, :-1].astype(jnp.float32)
        best = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, tokens[0, 1:, None], axis=-1)
        return best - chosen[:, 0], best - jnp.median(logits, axis=-1)

    def worst_margin(prompt_ids, answer_ids, lora):
        """Largest margin/spread over the answer's tokens."""
        seq = np.zeros((1, buf_len), np.int32)
        seq[0, :len(prompt_ids) + len(answer_ids)] = prompt_ids + answer_ids
        margin, spread = forced_margins(api.base_params, lora,
                                        jnp.asarray(seq))
        span = slice(len(prompt_ids) - 1,
                     len(prompt_ids) - 1 + len(answer_ids))
        return float(np.max(np.asarray(margin)[span]
                            / np.asarray(spread)[span]))

    agreement = []
    for i, (prompt, adapter) in enumerate(cases):
        lora = adapters[adapter] if adapter else zero
        prompt_ids = tok.encode(prompt)
        got = tok.encode(answers[i])
        check(len(got) == max_tokens,
              f"adapter {adapter}: the server answered {len(got)} tokens")
        check(answers[i] == alone[i][0] == together[i][0],
              f"adapter {adapter}: the server's answers to one request "
              f"differ: {answers[i]!r} / {alone[i][0]!r} / "
              f"{together[i][0]!r}")
        want = generate(apply_fn, api.base_params, prompt_ids,
                        max_new_tokens=max_tokens, buf_len=buf_len,
                        model=model, lora=lora)
        check(len(want) == max_tokens, "generate's answer is short")
        same = next((j for j, (a, b) in enumerate(zip(got, want)) if a != b),
                    max_tokens)
        row = {"adapter": adapter, "prompt_tokens": len(prompt_ids),
               "tokens_equal_to_generate": same,
               "server_worst_margin": worst_margin(prompt_ids, got, lora),
               "generate_worst_margin": worst_margin(prompt_ids, want, lora)}
        agreement.append(row)
        # 5% of a position's logit spread: what bf16 activations through
        # the whole depth can move a logit by, far below what a wrong
        # cache page or a wrong adapter moves it by
        for who in ("server", "generate"):
            check(row[f"{who}_worst_margin"] <= 0.05,
                  f"adapter {adapter}: a token {who} chose lies "
                  f"{row[f'{who}_worst_margin']:.3f} of the logit spread "
                  f"below the plain forward's argmax")
    check(warm.compilations == 0,
          f"{warm.compilations} compilations while serving warm requests")
    check(kv["pages_free"] == kv["pool_pages"] - 1,
          f"KV pages leaked: {kv}")
    return emit({
        "phase": "server", "requests": len(cases) * 3,
        "adapters": sorted(adapters), "buf_len": buf_len, "slots": slots,
        "kv_page_tokens": page_tokens, "prefill_chunk_tokens": chunk_tokens,
        "kv_pool_pages": kv["pool_pages"],
        "prefill_chunks": kv["prefill_chunks"],
        "cold_s": cold.seconds, "cold_compilations": cold.compilations,
        "cold_cache_hits": cold.cache_hits,
        "warm_request_s": statistics.median(s for _, s in alone),
        "warm_request_s_all": [s for _, s in alone],
        "concurrent_request_s": [s for _, s in together],
        "tokens_per_request": max_tokens,
        "warm_compilations": warm.compilations,
        "agreement": agreement,
        "greedy_equals_generate": all(
            r["tokens_equal_to_generate"] == max_tokens for r in agreement),
        # the decode path attends over the cache in plain XLA: no
        # flash_attention call is traced while serving
        "attention_paths": {**cold.attention, **warm.attention},
        "peak_bytes_in_use": peak_bytes()})


# -- four chips: the sharded FedLLM round against the one-device round --------

def phase_fedllm_sharded(*, client: int = 2, model: int = 2, rounds: int = 3,
                         local_steps: int = 4, batch: int = 2, seed: int = 0,
                         **widths) -> dict:
    """``FedLLMAPI(mesh=make_mesh(client, model))`` against the one-device
    ``FedLLMAPI`` at the same widths: the same loss to bf16 tolerance, and
    the sharded run's bytes spread evenly — no unsharded base copy on the
    first device."""
    import jax
    import numpy as np
    from fedml_tpu.core.mesh import make_mesh

    kw = dict(clients=client, local_steps=local_steps, batch=batch,
              rounds=rounds, seed=seed, **widths)
    mesh = make_mesh(client=client, model=model,
                     devices=jax.devices()[:client * model])
    gc.collect()         # so that nothing counted now is freed meanwhile
    before = live_bytes_per_device()
    sharded_api = _fedllm_api(mesh=mesh, **kw)
    sharded = _fedllm_rounds(sharded_api, rounds)
    # what this API added to each device
    per_device = {d: n - before.get(d, 0)
                  for d, n in live_bytes_per_device().items()}
    sharded["live_bytes_per_device"] = per_device
    sharded["peak_bytes_in_use"] = peak_bytes()   # before the one-device run
    check(len(per_device) == client * model,
          f"arrays live on {len(per_device)} of {client * model} devices")
    check(max(per_device.values()) <= 1.10 * min(per_device.values()),
          f"per-device bytes unbalanced: {per_device}")
    base_bytes = sum(x.nbytes
                     for x in jax.tree_util.tree_leaves(
                         sharded_api.base_params))
    # an unsharded copy would put the whole base on one device
    check(max(per_device.values()) < 0.75 * base_bytes,
          f"a device holds {max(per_device.values())} of a "
          f"{base_bytes}-byte base")
    n_params = _n_params(sharded_api.base_params)
    del sharded_api

    single = _fedllm_rounds(_fedllm_api(**kw), rounds)
    # bf16 activations under another matmul partitioning: 2^-7 of the loss
    check(bool(np.allclose(sharded["train_loss"], single["train_loss"],
                           rtol=1e-2, atol=0)),
          f"losses differ: {sharded['train_loss']} vs "
          f"{single['train_loss']}")
    return emit({"phase": "fedllm_sharded", "widths": widths,
                 "n_params": n_params, "base_bytes": base_bytes,
                 "mesh": {"client": client, "model": model},
                 "sharded": sharded, "single": single,
                 "peak_bytes_in_use": peak_bytes()})


# -- entry --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the two comparisons that need four chips")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    import jax

    import fedml_tpu  # noqa: F401  (places the compile cache before any use)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    need = 4 if opts.four_chips else 1
    if device["platform"] != "tpu" or len(devices) < need:
        print(json.dumps({
            "ok": False, "device": device,
            "error": f"needs {need} tpu device(s)"}), flush=True)
        return 1
    emit({"phase": "start", "device": device, "jax": jax.__version__,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})

    if opts.four_chips:
        phase_engine(seed=opts.seed)
        phase_fedllm_sharded(
            seed=opts.seed, **{**FLAGSHIP, "n_layers": FOUR_CHIP_LAYERS})
    else:
        phase_engine(seed=opts.seed)
        phase_kernels(seed=opts.seed)
        _, api = phase_fedllm(seed=opts.seed, **FLAGSHIP)
        phase_server(api, seed=opts.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
