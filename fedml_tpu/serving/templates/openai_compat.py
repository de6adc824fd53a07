"""OpenAI-compatible chat/completions endpoint over a flax causal LM
(reference ``python/fedml/serving/templates/hf_template/main_openai.py`` —
the HF chatbot template exposing ``/v1/chat/completions``).

TPU-native serving decisions:

- **KV-cached decode.** When the server is built from a model exposing the
  flax "cache" collection (``LlamaLM(decode=True)``), generation is a
  one-shot prefill over the padded prompt buffer followed by a jitted
  single-token step against a static-length KV cache — O(S) per token
  instead of the O(S²) full-buffer re-forward.  All shapes static, so both
  programs compile once per (buffer length, batch) and are cached across
  requests.
- **Fixed-shape fallback.** Any bare ``apply_fn(params, tokens) -> logits``
  still works: the token buffer is padded to a static length and each step
  re-runs the full forward (the round-1 behavior, kept as the generic
  path).
- **Deterministic sampling.** threefry key per request; temperature 0 ⇒
  argmax.
- **Zero extra deps.** stdlib HTTP server (FastAPI isn't in the image),
  byte-level tokenizer fallback so no tokenizer download is needed; any
  object with encode/decode can be plugged in instead.
"""

from __future__ import annotations

import collections
import functools
import json
import logging
import queue
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...obs.context import parse_traceparent
from ...obs.tracer import get_tracer

log = logging.getLogger(__name__)


class ByteTokenizer:
    """UTF-8 byte tokenizer: ids 0..255 = bytes, 256 = BOS, 257 = EOS."""

    vocab_size = 258
    bos_id = 256
    eos_id = 257

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids = list(text.encode("utf-8"))
        return ([self.bos_id] if add_bos else []) + ids

    def decode(self, ids) -> str:
        data = bytes(i for i in ids if 0 <= int(i) < 256)
        return data.decode("utf-8", errors="replace")


def _sample_live(live, key, temp, top_k: int, top_p: float = 1.0):
    """live: (V,) logits → sampled token id (greedy at temp 0).

    ``top_k``/``top_p`` are static (compile-time) filters like the
    reference HF template's generation kwargs: top-k keeps the k highest
    logits, nucleus top-p keeps the smallest prefix of the sorted
    distribution with cumulative probability ≥ p (always ≥ 1 token)."""
    if (top_k and top_k > 0) or top_p < 1.0:
        # one descending sort serves both filters; top-k is a prefix mask
        # on the sorted array, and top-p renormalizes over what top-k kept
        # (HF generation semantics: k first, then p)
        sorted_desc = jnp.sort(live)[::-1]
        if top_k and top_k > 0:
            idx = jnp.arange(sorted_desc.shape[0])
            sorted_desc = jnp.where(idx < top_k, sorted_desc, -jnp.inf)
        if top_p < 1.0:
            probs = jax.nn.softmax(sorted_desc)
            cum = jnp.cumsum(probs)
            # keep token i iff the mass BEFORE it is < p; the argmax is
            # always kept, so top_p <= 0 degrades to greedy, not to
            # an all-masked distribution
            keep = (cum - probs < top_p).at[0].set(True)
            sorted_desc = jnp.where(keep, sorted_desc, -jnp.inf)
        kth = jnp.min(jnp.where(jnp.isfinite(sorted_desc), sorted_desc,
                                jnp.inf))
        live = jnp.where(live < kth, -jnp.inf, live)
    greedy = jnp.argmax(live)
    sampled = jax.random.categorical(key, live / jnp.maximum(temp, 1e-6))
    return jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)


def _keeps_state(model) -> bool:
    """Does the model keep per-request state that is not K/V (the rows of
    ``llm/model.py::ShortConv``), of which no snapshot can be taken?"""
    return bool(getattr(getattr(model, "cfg", None), "conv_layers", 0))


@functools.lru_cache(maxsize=32)
def _build_plain_step(apply_fn: Callable, top_k: int, top_p: float):
    """Jitted full-buffer step, cached across requests (a per-request
    ``@jax.jit`` would re-trace every call — the jit cache is keyed on the
    function object)."""

    @jax.jit
    def step(params, buf, pos, key, temp):
        logits = apply_fn(params, buf)  # (1, L, V)
        # logits at pos-1 predict token at pos
        live = jax.lax.dynamic_index_in_dim(logits[0], pos - 1, axis=0,
                                            keepdims=False)
        return _sample_live(live, key, temp, top_k, top_p)

    return step


@functools.lru_cache(maxsize=32)
def _build_cached_decode(model, top_k: int, top_p: float):
    """Jitted (prefill, step) pair for a flax model supporting
    ``decode=True`` with a "cache" collection (``llm.model.LlamaLM``).

    Both functions take ``lora`` as their second argument: a LoRA tree
    (the "lora" collection of LoRADense layers) for per-request
    personalization — a traced argument, so ONE compiled program serves
    every adapter of a given shape — or ``None`` (an empty pytree; the
    presence/absence is part of the jit cache key) for models without
    adapters.  int8-quantized param trees (``llm/quantization.py``) pass
    through transparently: the dequantize runs inside the traced
    program, so the weights stay int8 in HBM and the per-matmul dequant
    fuses."""
    from ...llm.quantization import dequantize_params, weight_dtype
    wdtype = weight_dtype(model)

    def _vars(params, lora):
        v = {"params": dequantize_params(params, wdtype)}
        if lora is not None:        # trace-time: None is an empty pytree
            v["lora"] = lora
        return v

    # a model that keeps state by rows (llm/model.py::ShortConv) is told how
    # many of a call's positions are real: the rest is the buffer's padding
    stateful = _keeps_state(model)

    def _real(n):
        return {"seq_lens": jnp.reshape(n, (1,))} if stateful else {}

    @jax.jit
    def prefill(params, lora, buf, n, key, temp):
        logits, mut = model.apply(
            _vars(params, lora), buf, decode=True,
            start_pos=jnp.zeros((), jnp.int32), mutable=["cache"],
            **_real(n))
        live = jax.lax.dynamic_index_in_dim(logits[0], n - 1, axis=0,
                                            keepdims=False)
        return _sample_live(live, key, temp, top_k, top_p), mut["cache"]

    @jax.jit
    def step(params, lora, cache, tok, pos, key, temp):
        logits, mut = model.apply(
            {**_vars(params, lora), "cache": cache}, tok[None, None],
            decode=True, start_pos=pos, mutable=["cache"])
        return _sample_live(logits[0, 0], key, temp, top_k,
                            top_p), mut["cache"]

    @jax.jit
    def tail_block(params, lora, cache, padded_buf, start, n, key, temp):
        """Replay prompt positions start..n-1 in ONE dispatch (prefix-cache
        partial hits: a per-token tail replay costs one host round-trip
        per token, which inverts the caching win on dispatch-bound
        targets — round-4 advisor).  ``padded_buf`` is the prompt buffer
        right-padded with TAIL_BLOCK zeros so the dynamic slice never
        clamps; the block writes K/V for a fixed TAIL_BLOCK window whose
        stale positions >= n progressively self-heal (each later decode
        step overwrites position p's K/V before any query attends it —
        the same mask-discipline argument the speculative verify blocks
        rely on).  Logits are read at the last REAL position (n-1)."""
        block = jax.lax.dynamic_slice(padded_buf, (0, start),
                                      (1, TAIL_BLOCK))
        logits, mut = model.apply(
            {**_vars(params, lora), "cache": cache}, block,
            decode=True, start_pos=start, mutable=["cache"],
            **_real(n - start))
        live = jax.lax.dynamic_index_in_dim(logits[0], n - 1 - start,
                                            axis=0, keepdims=False)
        return _sample_live(live, key, temp, top_k, top_p), mut["cache"]

    return prefill, step, tail_block


#: fixed width of the one-dispatch tail-replay block (compiled once; a
#: partial prefix hit with an uncached tail up to this long replays as a
#: single device program instead of per-token steps)
TAIL_BLOCK = 32


def _replay_tail(step_fn, tail_fn, cache, buf_j, ids, start, n, max_seq,
                 key, temp):
    """Replay prompt positions ``start..n-1`` onto a cached KV state —
    :func:`generate`'s prefix-hit replay discipline.  Multi-token tails
    that fit the
    fixed block AND the context window replay as one tail_block dispatch;
    everything else (exact hits, tails longer than TAIL_BLOCK under a
    custom admission bound, the window's very end) takes the bounded
    per-token path.  Returns ``(tok, cache, key)``."""
    tail = n - start
    if 1 < tail <= TAIL_BLOCK and start + TAIL_BLOCK <= max_seq:
        padded = jnp.concatenate(
            [buf_j, jnp.zeros((1, TAIL_BLOCK), jnp.int32)], axis=1)
        key, sub = jax.random.split(key)
        tok, cache = tail_fn(cache, padded, jnp.int32(start),
                             jnp.int32(n), sub, temp)
        return tok, cache, key
    tok = None
    for j in range(start, n):
        key, sub = jax.random.split(key)
        tok, cache = step_fn(cache, jnp.int32(ids[j]), jnp.int32(j), sub,
                             temp)
    return tok, cache, key


class RequestError(ValueError):
    """Client-side request mistake -> HTTP 4xx (a 500 would be counted
    against server error budgets and retried by OpenAI-style clients)."""

    def __init__(self, message: str, status: int = 400):
        super().__init__(message)
        self.status = int(status)


class PrefixCache:
    """LRU cache of prefill KV states keyed by prompt token prefix.

    Serving workloads re-send shared prefixes constantly (a system
    prompt, a federated-eval template) — this skips prefill work for the
    longest cached prefix: an exact hit replays one idempotent decode
    step (re-writing the last position with identical K/V) instead of
    the whole prefill; a prefix hit continues from the cached state
    through only the unseen tail tokens.  vLLM calls the idea automatic
    prefix caching; the reference's serving path
    (/root/reference/python/fedml/serving/) re-forwards every request
    from scratch.

    Greedy outputs are BIT-IDENTICAL with or without the cache (pinned
    by test).  Sampled requests draw a different-but-equally-distributed
    key sequence (the prefill split is skipped), so seeds don't
    reproduce across cache states — same caveat vLLM documents.

    Memory: ``capacity`` x one full KV buffer (layers x 2 x B x H_kv x
    buf_len x head_dim in the model's KV dtype); size capacity to HBM.
    Entries are immutable jax arrays, so sharing them across requests
    and threads is safe; the dict itself is guarded by a lock.
    """

    def __init__(self, capacity: int = 8, max_tail: int = TAIL_BLOCK):
        self.capacity = int(capacity)
        #: partial-hit admission bound, in TOKENS of uncached tail.  Tails
        #: up to TAIL_BLOCK replay as ONE tail_block dispatch —
        #: dispatch-parity with the miss path's single prefill while
        #: skipping the cached prefix's FLOPs — so the default bound is
        #: TAIL_BLOCK.  Longer tails would fall back to one dispatch PER
        #: token, so they miss instead.
        self.max_tail = int(max_tail)
        self._entries = collections.OrderedDict()   # tuple(ids) -> cache
        self._lock = threading.Lock()
        #: the params tree the cached KV was computed under — held by
        #: STRONG reference so identity comparison is exact (an id() of a
        #: freed tree could be reused); entries are invalidated wholesale
        #: when a different tree shows up (federated serving swaps
        #: weights every round — old-weight KV must never mix with
        #: new-weight decode).  NOTE the strong ref keeps the OLD tree
        #: alive until the first post-swap request arrives; weight-swap
        #: paths should call :meth:`clear` eagerly (the server's
        #: ``update_params`` does) so the old weights + stale KV free
        #: immediately instead of squatting on HBM through the idle gap
        self._params_ref = None
        self._lora_ref = None
        self.stats = {"hits": 0, "exact_hits": 0, "misses": 0,
                      "insertions": 0, "invalidations": 0,
                      "prefill_tokens_skipped": 0}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._params_ref = None
            self._lora_ref = None

    def _sync_params(self, params, lora=None) -> None:
        """Caller holds the lock.  Drop every entry when the weights OR
        the adapter the cache was built under are replaced — prefix KV is
        (params, lora)-specific, so uniform-adapter traffic caches
        normally while a change of either tree invalidates wholesale."""
        if self._params_ref is not params or self._lora_ref is not lora:
            if self._entries:
                self.stats["invalidations"] += 1
                self._entries.clear()
            self._params_ref = params
            self._lora_ref = lora

    def lookup(self, ids: List[int], params=None, lora=None):
        """Longest COMMON prefix between ``ids`` and any cached entry →
        (c, cache) or (0, None).  A cached buffer whose prompt diverges
        after position c is still valid for the first c tokens: decode
        steps attend only positions <= their own, and each step writes
        its position's K/V before attending, so the stale tail
        progressively self-heals (the same mask-discipline argument the
        speculative verify blocks rely on).  ``params`` (the weight tree
        the caller will decode with) invalidates the cache on change."""
        t = tuple(ids)
        with self._lock:
            if params is not None:
                self._sync_params(params, lora)
            best, best_key = 0, None
            for key in self._entries:
                c = 0
                for a, b in zip(key, t):
                    if a != b:
                        break
                    c += 1
                if c > best:
                    best, best_key = c, key
            # hit policy: the uncached tail replays as single-token steps
            # (one dispatch each) while a miss costs ONE prefill dispatch,
            # so admission is gated on an ABSOLUTE tail bound (max_tail
            # tokens) — dispatch count, not FLOPs, is the serving cost
            # model; exact hits (1 idempotent replay step) always win
            if best_key is not None and len(t) - best <= self.max_tail:
                self._entries.move_to_end(best_key)   # LRU recency
                cache = self._entries[best_key]
                self.stats["hits"] += 1
                if best == len(t):
                    self.stats["exact_hits"] += 1
                # positions genuinely not re-forwarded: an exact hit still
                # replays the last prompt position, a prefix hit replays
                # best..n-1 — so min(best, n-1), not the matched length
                self.stats["prefill_tokens_skipped"] += min(best, len(t) - 1)
                return best, cache
            self.stats["misses"] += 1
            return 0, None

    def insert(self, ids: List[int], cache, params=None,
               lora=None) -> None:
        t = tuple(ids)
        with self._lock:
            if params is not None:
                self._sync_params(params, lora)
            if t in self._entries:
                self._entries.move_to_end(t)
                return
            self._entries[t] = cache
            self.stats["insertions"] += 1
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)


def generate(apply_fn: Callable, params, prompt_ids: List[int],
             max_new_tokens: int = 64, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 1.0, seed: int = 0,
             buf_len: int = 256,
             eos_id: Optional[int] = None,
             on_token: Optional[Callable[[int], None]] = None,
             model=None, prefix_cache: Optional[PrefixCache] = None,
             lora=None) -> List[int]:
    """Sample ``max_new_tokens`` continuations of ``prompt_ids``.

    ``apply_fn(params, tokens)`` must return logits of shape (B, T, V).
    With ``model`` given (a flax module supporting ``decode=True`` whose
    ``cfg.max_seq_len >= buf_len``), decode uses the KV cache: prefill
    once, then O(1)-context single-token steps.  All shapes are static, so
    each program compiles once per buffer size regardless of
    prompt/generation length.
    """
    prompt_ids = list(prompt_ids)[-(buf_len - 1):]
    buf = np.zeros((1, buf_len), np.int32)
    n = len(prompt_ids)
    buf[0, :n] = prompt_ids
    buf_j = jnp.asarray(buf)
    key = jax.random.PRNGKey(seed)
    temp = float(temperature)
    out: List[int] = []

    if model is not None:
        raw_params = params.get("params", params) if isinstance(params, dict) \
            else params
        prefill_p, step_p, tail_p = _build_cached_decode(model, int(top_k),
                                                         float(top_p))
        prefill = functools.partial(prefill_p, raw_params, lora)
        step = functools.partial(step_p, raw_params, lora)
        tail_blk = functools.partial(tail_p, raw_params, lora)
        # prefix KV is adapter-specific: the cache keys validity on
        # (params, lora) identity, so uniform-adapter traffic (e.g. the
        # server's shared zero adapter) caches normally while a CHANGE of
        # adapter invalidates wholesale — stale cross-adapter KV can
        # never serve
        if prefix_cache is not None and _keeps_state(model):
            raise ValueError(
                "prefix_cache with a model that has convolution layers: the "
                "state at the end of a shared prefix is not kept")
        hit_len, hit_cache = (prefix_cache.lookup(prompt_ids, raw_params,
                                                  lora)
                              if prefix_cache is not None and n > 0
                              else (0, None))
        if hit_cache is not None:
            # continue from the cached state through the unseen tail; an
            # exact hit replays only the LAST prompt token — position
            # n-1's K/V rewrite is idempotent (same deterministic apply),
            # and its logits equal the prefill's, so greedy output is
            # bit-identical to the uncached path.  Multi-token tails
            # replay as ONE tail_block dispatch (vs one dispatch per
            # token) whenever the fixed block fits inside the context
            # window; at the window's very end the bounded per-token
            # fallback runs instead.
            cache = hit_cache
            start = min(hit_len, n - 1)
            max_seq = getattr(getattr(model, "cfg", None), "max_seq_len",
                              buf_len)
            tok, cache, key = _replay_tail(step, tail_blk, cache, buf_j,
                                           prompt_ids, start, n, max_seq,
                                           key, temp)
        else:
            key, sub = jax.random.split(key)
            tok, cache = prefill(buf_j, n, sub, temp)
        if prefix_cache is not None and n > 0:
            prefix_cache.insert(prompt_ids, cache, raw_params, lora)
        pos = n
        while pos < buf_len and len(out) < max_new_tokens:
            t = int(tok)
            if eos_id is not None and t == eos_id:
                break
            out.append(t)
            if on_token is not None:
                on_token(t)
            key, sub = jax.random.split(key)
            tok, cache = step(cache, jnp.int32(t), jnp.int32(pos), sub,
                              temp)
            pos += 1
        return out

    step = _build_plain_step(apply_fn, int(top_k), float(top_p))
    pos = n
    for _ in range(max_new_tokens):
        if pos >= buf_len:
            break
        key, sub = jax.random.split(key)
        tok = int(step(params, buf_j, pos, sub, temp))
        if eos_id is not None and tok == eos_id:
            break
        out.append(tok)
        if on_token is not None:
            on_token(tok)
        buf_j = buf_j.at[0, pos].set(tok)
        pos += 1
    return out


def _render_chat(messages: List[dict]) -> str:
    """Minimal chat template (the reference delegates to the HF tokenizer's
    chat template; the byte tokenizer needs an explicit one)."""
    parts = [f"<|{m.get('role', 'user')}|>\n{m.get('content', '')}"
             for m in messages]
    return "\n".join(parts) + "\n<|assistant|>\n"


class OpenAICompatServer:
    """Serves /v1/models, /v1/completions, /v1/chat/completions (+ SSE
    streaming) over a (model_apply, params) pair."""

    def __init__(self, apply_fn: Callable, params, tokenizer=None,
                 model_name: str = "fedml-tpu-llm", host: str = "127.0.0.1",
                 port: int = 0, buf_len: int = 256, model=None,
                 batch_slots: int = 0, draft_model=None, draft_params=None,
                 decode_horizon: int = 1,
                 prefix_cache_slots: int = 0,
                 prefix_max_tail: int = TAIL_BLOCK,
                 adapters=None, adapter_slots: int = 0,
                 metrics_port: Optional[int] = None,
                 slo_rules: Optional[List[dict]] = None,
                 kv_page_tokens: Optional[int] = None,
                 kv_pool_pages: int = 0,
                 prefill_chunk_tokens: int = 0, prefill_lanes: int = 1,
                 adapter_cache_slots: int = 0,
                 adapter_store_dir: Optional[str] = None,
                 kv_window_pool_pages: int = 0):
        """``host`` defaults to loopback — the endpoint is unauthenticated,
        so exposing it on all interfaces requires an explicit
        ``host="0.0.0.0"``.  ``model`` (optional): flax module supporting
        ``decode=True`` → KV-cached decode (see :func:`generate`).
        ``batch_slots`` > 0 (requires ``model``) routes requests through the
        :class:`~fedml_tpu.serving.batching.ContinuousBatchingEngine` so
        concurrent requests share one batched decode program; sampled
        requests that ALSO ask for ``top_k``/``top_p`` fall through to the
        single-request path (one compiled program per distinct filter
        pair) so the fields are honored, never silently ignored.  ``decode_horizon`` > 1 (engine mode only) generates that
        many tokens per device dispatch — same outputs, H-fold fewer host
        round-trips; streaming granularity coarsens to H tokens.

        Memory-plane knobs (engine mode only; docs/SERVING.md):
        ``kv_page_tokens`` is the page size of the engine's KV page pool
        (None = the engine's default; ``kv_pool_pages`` sizes the pool,
        0 = auto; ``kv_window_pool_pages`` the window layers' pool of a
        model that has full layers too), a prompt enters in chunks
        (``prefill_chunk_tokens``/``prefill_lanes``);
        ``adapter_cache_slots`` > 0 demotes the adapter bank to an N-row
        cache over a host/disk store (``adapter_store_dir`` spills cold
        rows to disk) — use it INSTEAD of ``adapter_slots`` to register
        adapters past HBM."""
        self.apply_fn = apply_fn
        self.params = params
        self.tokenizer = tokenizer or ByteTokenizer()
        self.model_name = model_name
        self.host, self.port = host, port
        # fedmon live export: a sibling /metrics + /healthz endpoint over
        # the tracer's serve.* gauges (started/stopped with the server)
        self.metrics_port = metrics_port
        self.metrics_server = None
        # fedslo: objective-style SLO rules ride into the engine (per-
        # request burn-rate streams) and the metrics endpoint (/healthz
        # multi-window evaluation) — see docs/OBSERVABILITY.md
        self.slo_rules = slo_rules
        self.buf_len = buf_len
        self.model = model
        # speculative decode (requires model + a draft, and no engine;
        # greedy requests only — sampled requests take the plain paths)
        self.draft_model = draft_model
        self.draft_params = draft_params
        if draft_model is not None and model is None:
            raise ValueError("draft_model requires `model` (KV-cached "
                             "target) — speculative decode is cache-based")
        if draft_model is not None and draft_params is None:
            raise ValueError("draft_model requires draft_params")
        if draft_model is not None and (_keeps_state(model)
                                        or _keeps_state(draft_model)):
            raise ValueError(
                "draft_model with a model that has convolution layers: a "
                "rejected draft token has already moved the layers' state, "
                "and no snapshot is kept to take it back")
        # prefix_cache_slots > 0 (requires ``model``): reuse prefill KV
        # for shared prompt prefixes.  Non-engine path: one PrefixCache
        # consulted by generate(); engine path: the engine shares pages
        # through a cache of its own, consulted at admission
        # (self.prefix_cache aliases it below so stats stay reachable
        # either way; the fall-through around the engine uses none).
        self.prefix_cache = None
        if prefix_cache_slots and _keeps_state(model):
            raise ValueError(
                "prefix_cache_slots with a model that has convolution "
                "layers: a cached prefix would need the layers' state at "
                "the end of the part that is shared, of which no snapshot "
                "is kept")
        if prefix_cache_slots and model is None:
            raise ValueError("prefix_cache_slots requires `model` "
                             "(prefix caching is KV-cache-based)")
        if prefix_cache_slots and not batch_slots:
            self.prefix_cache = PrefixCache(prefix_cache_slots,
                                            max_tail=int(prefix_max_tail))
        # adapters: {name: LoRA tree} over ONE shared base — per-request
        # personalization for federated clients (request field
        # {"adapter": name} or {"model": name}; neither = the zero adapter
        # = base behavior).  Requires a lora_rank>0 model config; one
        # compiled program serves every adapter (the tree is a traced
        # argument).  With ``batch_slots`` the adapters live in a
        # device-resident bank (serving/adapters.AdapterRegistry) of
        # ``adapter_slots`` rows and requests for DIFFERENT adapters share
        # one batched decode program; without an engine each request
        # carries its tree through the single-request path.  The reference
        # serves one full model copy per personalized endpoint.
        # serializes hot-swap writers (update_params / add_adapter /
        # evict_adapter on the training/promotion thread) against HTTP
        # worker threads snapshotting a coherent (params, draft_params,
        # prefix_cache, adapter) set at the top of _complete
        self._swap_lock = threading.Lock()
        self.adapters = None
        self._zero_lora = None
        self.registry = None
        # page-size / adapter-cache knobs are engine-mode only (the memory
        # plane they shape IS the engine's) — reject up front instead of
        # silently ignoring them
        if (kv_page_tokens is not None or adapter_cache_slots) \
                and not batch_slots:
            raise ValueError(
                "kv_page_tokens / adapter_cache_slots shape the "
                "batching engine's memory plane — set batch_slots too")
        if batch_slots and draft_model is not None:
            from ..batching import PagedKVUnsupportedError
            raise PagedKVUnsupportedError(
                "draft_model with batch_slots: the batching engine's KV "
                "cache is a page pool, and the speculative verify blocks "
                "write multi-token windows into contiguous per-request "
                "caches — speculation serves single requests "
                "(serving/speculative.py::speculative_generate: build the "
                "server without batch_slots)")
        if adapter_cache_slots and adapter_slots:
            raise ValueError(
                "adapter_cache_slots and adapter_slots are mutually "
                "exclusive: the cache mode replaces the fixed bank")
        if adapters is not None or adapter_slots or adapter_cache_slots:
            if model is None:
                raise ValueError("adapters require `model` (KV-cached "
                                 "decode carries the lora collection)")
            if getattr(getattr(model, "cfg", None), "lora_rank", 0) <= 0:
                raise ValueError("adapters require a lora_rank>0 model "
                                 "config (LoRADense layers)")
            if batch_slots and not adapter_cache_slots:
                from ..adapters import AdapterRegistry
                cap = int(adapter_slots) or len(adapters or {}) + 8
                self.registry = AdapterRegistry(model, capacity=cap)
                for name, tree in (adapters or {}).items():
                    self.registry.register(name, tree)
            elif not batch_slots:
                # (draft_model + adapters is fine here: greedy requests
                # route through speculative_generate, which carries the
                # lora tree — parity-tested)
                self.adapters = dict(adapters or {})
                # zero A/B -> the adapter term vanishes: base behavior.
                # eval_shape + zeros, NOT model.init: init would
                # materialize a full base-parameter tree (and trace a
                # forward) just to read the lora collection — a transient
                # full-model allocation a box sized for int8-quantized
                # weights may not survive
                shapes = jax.eval_shape(
                    lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
                    jax.random.PRNGKey(0))["lora"]
                self._zero_lora = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        self._engine = None
        if batch_slots:
            if model is None:
                raise ValueError(
                    "batch_slots requires `model` (a flax module supporting "
                    "decode=True) — the batching engine is KV-cache based")
            from ..batching import ContinuousBatchingEngine
            page = ({} if kv_page_tokens is None
                    else {"kv_page_tokens": int(kv_page_tokens)})
            self._engine = ContinuousBatchingEngine(
                model, params, slots=int(batch_slots), buf_len=buf_len,
                horizon=int(decode_horizon),
                prefix_cache_slots=int(prefix_cache_slots),
                adapter_registry=self.registry,
                slo_rules=slo_rules,
                kv_pool_pages=int(kv_pool_pages),
                kv_window_pool_pages=int(kv_window_pool_pages),
                prefill_chunk_tokens=int(prefill_chunk_tokens),
                prefill_lanes=int(prefill_lanes),
                adapter_cache_slots=int(adapter_cache_slots),
                adapter_store_dir=adapter_store_dir, **page)
            self.prefix_cache = self._engine.prefix_cache
            if adapter_cache_slots:
                # the engine owns the store-backed registry; alias it
                # so add_adapter/evict_adapter and the fall-through
                # path route through the same cache
                self.registry = self._engine.registry
                for name, tree in (adapters or {}).items():
                    self.registry.register(name, tree)
        self._server: Optional[ThreadingHTTPServer] = None

    # -- request handling --------------------------------------------------
    def _complete(self, prompt: str, req: dict,
                  on_text: Optional[Callable[[str], None]] = None,
                  traceparent: Optional[str] = None) -> str:
        """Run generation; ``on_text`` (if given) receives incremental text
        deltas on UTF-8 boundaries — a raw per-token decode would shred
        multi-byte characters with the byte tokenizer.  ``traceparent``
        (validated W3C header value) joins the request's span tree to the
        caller's fedscope trace."""
        tok = self.tokenizer
        ids: List[int] = []
        sent = 0
        t_submit = time.monotonic()

        def emit(t: int):
            nonlocal sent
            ids.append(t)
            text = tok.decode(ids)
            # trailing replacement chars mark an incomplete UTF-8 sequence;
            # hold those bytes back until the sequence completes
            clean = text.rstrip("�")
            if len(clean) > sent:
                on_text(clean[sent:])
                sent = len(clean)

        # adapter routing: an explicit {"adapter": name} field, or —
        # multi-tenant OpenAI convention — {"model": name} naming anything
        # other than the server's base model id (so a federated client
        # points its stock OpenAI SDK at its own cohort's adapter)
        adapter_name = req.get("adapter")
        # one coherent weight snapshot per request: update_params /
        # add_adapter / evict_adapter swap these under _swap_lock on the
        # promotion thread, so grab (params, draft_params, prefix_cache,
        # lora) together — a mid-request swap then serves entirely-old or
        # entirely-new weights, never a torn mix
        with self._swap_lock:
            if not adapter_name:
                m = req.get("model")
                if (isinstance(m, str) and m and m != self.model_name
                        and (self.adapters is not None
                             or self.registry is not None)):
                    adapter_name = m
            params = self.params
            draft_params = self.draft_params
            prefix_cache = self.prefix_cache
            lora = None
            if self.registry is not None:
                pass  # resolved (and pinned) per-path below
            elif self.adapters is not None:
                if adapter_name:
                    if adapter_name not in self.adapters:
                        raise RequestError(
                            f"unknown adapter {adapter_name!r}; have "
                            f"{sorted(self.adapters)}", status=404)
                    lora = self.adapters[adapter_name]
                else:
                    lora = self._zero_lora
            elif adapter_name:
                raise RequestError("server has no adapters configured")

        # per-request top_k/top_p cannot ride the engine (its sampler is
        # one compiled program for the pool) — rather than silently
        # IGNORING the fields, such requests fall through to the
        # single-request path, whose builder compiles one program per
        # distinct (top_k, top_p) pair (lru-cached); greedy requests are
        # filter-independent, so they stay on the engine either way
        # None-safe field parsing: OpenAI-style clients serialize unset
        # optionals as explicit JSON nulls, and dict.get's default does
        # not apply to a present null
        temp = float(req.get("temperature") or 0.0)
        req_top_k = int(req.get("top_k") or 0)
        req_top_p = float(1.0 if req.get("top_p") is None
                          else req.get("top_p"))
        wants_filters = (temp != 0.0
                         and (req_top_k > 0 or req_top_p < 1.0))
        if self._engine is not None and not wants_filters:
            try:
                q = self._engine.submit(
                    tok.encode(prompt),
                    max_new_tokens=int(req.get("max_tokens", 64)),
                    temperature=temp,
                    seed=int(req.get("seed", 0)),
                    eos_id=getattr(tok, "eos_id", None),
                    adapter=adapter_name,
                    traceparent=traceparent)
            except KeyError as e:
                # unknown adapter — resolved at submit so the 404 happens
                # before any slot/queue state is touched
                raise RequestError(str(e.args[0] if e.args else e),
                                   status=404)
            out = []
            while True:
                try:
                    t = q.get(timeout=300)
                except queue.Empty:
                    break  # engine wedged/crashed — fail the request open
                if t is None:
                    break
                out.append(t)
                if on_text:
                    emit(t)
        else:
            release_row = None
            if self.registry is not None:
                # fall-through around the MT engine (per-request
                # top_k/top_p filters): pin the bank row for the whole
                # generation so an eviction can't reclaim it mid-request.
                # Cache-mode misses (row paging in from the store) block-
                # retry here — this path has a thread to park, unlike the
                # engine loop
                from ..adapters import AdapterMissError
                deadline = time.monotonic() + 30.0
                while True:
                    try:
                        release_row, _atok = self.registry.acquire(
                            adapter_name)
                        break
                    except AdapterMissError:
                        if time.monotonic() >= deadline:
                            raise RequestError(
                                f"adapter {adapter_name!r} did not page "
                                "in within 30s", status=503)
                        time.sleep(0.02)
                    except KeyError as e:
                        raise RequestError(
                            str(e.args[0] if e.args else e), status=404)
                lora = self.registry.lora_for_row(release_row)
            try:
                if self.draft_model is not None and temp == 0.0:
                    from ..speculative import speculative_generate
                    out, _spec_stats = speculative_generate(
                        self.model, params, self.draft_model,
                        draft_params, tok.encode(prompt),
                        max_new_tokens=int(req.get("max_tokens", 64)),
                        buf_len=self.buf_len,
                        eos_id=getattr(tok, "eos_id", None),
                        on_token=emit if on_text else None,
                        lora=lora)
                else:
                    out = generate(
                        self.apply_fn, params, tok.encode(prompt),
                        max_new_tokens=int(req.get("max_tokens", 64)),
                        temperature=temp,
                        top_k=req_top_k,
                        top_p=min(max(req_top_p, 0.0), 1.0),
                        seed=int(req.get("seed", 0)),
                        buf_len=self.buf_len,
                        eos_id=getattr(tok, "eos_id", None),
                        on_token=emit if on_text else None,
                        model=self.model,
                        prefix_cache=(prefix_cache
                                      if self._engine is None else None),
                        lora=lora)
            finally:
                if release_row is not None:
                    self.registry.release(release_row)
            # the engine emits its own request span tree at _finish; the
            # single-request fall-through emits one here (HTTP-thread
            # lane, host clocks) so every served request has a
            # serve.request span regardless of path
            tracer = get_tracer()
            if tracer.enabled:
                e2e_s = time.monotonic() - t_submit
                tracer.complete(
                    "serve.request", e2e_s, cat="serve",
                    tid=threading.get_ident(),
                    adapter=adapter_name or "base",
                    output_tokens=len(out), e2e_s=round(e2e_s, 6),
                    traceparent=traceparent, path="fallthrough")
        text = tok.decode(out)
        if on_text and len(text) > sent:
            on_text(text[sent:])  # flush any held-back tail
        return text

    def _make_handler(self):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def _send_json(self, code: int, payload: dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/v1/models":
                    names = [outer.model_name]
                    if outer.registry is not None:
                        names += outer.registry.names()
                    elif outer.adapters is not None:
                        names += sorted(outer.adapters)
                    self._send_json(200, {"object": "list", "data": [
                        {"id": n, "object": "model",
                         "owned_by": "fedml_tpu"} for n in names]})
                elif self.path in ("/ready", "/health"):
                    self._send_json(200, {"ready": True})
                else:
                    self._send_json(404, {"error": "not found"})

            def _sse_stream(self, make_chunk, run):
                """True streaming: chunks are flushed as generation emits
                them (``run`` is called with the per-delta writer)."""
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.end_headers()

                def write_piece(piece: str):
                    data = json.dumps(make_chunk(piece))
                    self.wfile.write(f"data: {data}\n\n".encode())
                    self.wfile.flush()

                with get_tracer().span("serve.stream", cat="serve"):
                    run(write_piece)
                self.wfile.write(b"data: [DONE]\n\n")

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                try:
                    req = json.loads(self.rfile.read(n) or b"{}")
                except json.JSONDecodeError:
                    self._send_json(400, {"error": "bad json"})
                    return
                rid = f"cmpl-{uuid.uuid4().hex[:24]}"
                now = int(time.time())
                # fedscope trace context: a valid W3C traceparent header
                # joins this request's span tree to the caller's trace
                # (malformed values are dropped, not propagated)
                tp_raw = self.headers.get("traceparent")
                tparent = tp_raw if (tp_raw and
                                     parse_traceparent(tp_raw)) else None
                try:
                    if self.path == "/v1/chat/completions":
                        prompt = _render_chat(req.get("messages", []))
                        if req.get("stream"):
                            self._sse_stream(
                                lambda p: {
                                    "id": rid, "object":
                                        "chat.completion.chunk",
                                    "created": now, "model": outer.model_name,
                                    "choices": [{"index": 0, "delta":
                                                 {"content": p},
                                                 "finish_reason": None}]},
                                lambda writer: outer._complete(
                                    prompt, req, on_text=writer,
                                    traceparent=tparent))
                            return
                        text = outer._complete(prompt, req,
                                               traceparent=tparent)
                        self._send_json(200, {
                            "id": rid, "object": "chat.completion",
                            "created": now, "model": outer.model_name,
                            "choices": [{"index": 0, "message":
                                         {"role": "assistant",
                                          "content": text},
                                         "finish_reason": "stop"}]})
                    elif self.path == "/v1/completions":
                        text = outer._complete(str(req.get("prompt", "")),
                                               req, traceparent=tparent)
                        self._send_json(200, {
                            "id": rid, "object": "text_completion",
                            "created": now, "model": outer.model_name,
                            "choices": [{"index": 0, "text": text,
                                         "finish_reason": "stop"}]})
                    else:
                        self._send_json(404, {"error": "not found"})
                except RequestError as e:
                    # client mistake (unknown adapter, bad field) — 4xx,
                    # not a retryable server fault
                    self._send_json(e.status, {"error": str(e)})
                except Exception as e:
                    log.exception("generation failed")
                    self._send_json(500, {"error": str(e)})

            def log_message(self, fmt, *args):
                log.debug("openai-compat: " + fmt, *args)

        return Handler

    def add_adapter(self, name: str, lora_tree) -> None:
        """Register/replace a personalization adapter (e.g. a client's
        trained LoRA from a federated round).  No recompile: the adapter
        tree is a traced argument of the shared decode program.  In
        multi-tenant engine mode this hot-swaps a bank row (in-flight
        requests on the old version finish on it — copy-on-write)."""
        if self.registry is not None:
            self.registry.register(str(name), lora_tree)
            return
        with self._swap_lock:
            if self.adapters is None:
                raise ValueError("server built without adapters= — construct "
                                 "with adapters={} (or batch_slots + "
                                 "adapter_slots) to enable personalization")
            self.adapters[str(name)] = lora_tree

    def evict_adapter(self, name: str) -> None:
        """Stop routing ``name``.  Engine mode delegates to the registry
        (in-flight requests drain on their pinned row); dict mode just
        drops the entry."""
        if self.registry is not None:
            self.registry.evict(str(name))
            return
        with self._swap_lock:
            if self.adapters is None or str(name) not in self.adapters:
                raise KeyError(f"unknown adapter {name!r}")
            del self.adapters[str(name)]

    def update_params(self, params, draft_params=None,
                      timeout: float = 60.0) -> None:
        """Swap the serving weights (federated round boundary).

        Engine mode: the swap is delegated to the batching engine, which
        applies it once in-flight requests drain (its admission pauses
        meanwhile) and clears its prefix cache atomically with the swap —
        so the engine path and the sampled fall-through path serve the
        SAME weight version once this returns.  Non-engine mode: swaps
        ``self.params`` and clears the prefix cache eagerly (its strong
        params ref would otherwise keep the old tree + stale KV resident
        until the next request).  ``draft_params`` also swaps the
        speculative draft of a server built with ``draft_model``
        (optional: a stale draft only lowers acceptance rate; greedy
        verification keeps outputs exact).  ``timeout``
        bounds the engine drain — size it to the slowest legal request
        (roughly ``buf_len`` x per-dispatch latency); on ``TimeoutError``
        NOTHING has been mutated, so the caller can simply retry.
        """
        if draft_params is not None and self.draft_model is None:
            # validate BEFORE mutating: a failed call must not leave the
            # fall-through path on new weights with the engine on old
            raise ValueError("draft_params given but the server was "
                             "built without draft_model")
        # engine swap FIRST, for the same reason: it can raise on a drain
        # timeout, and a failed call must leave the server fully on the
        # old version — assigning self.params before the engine landed
        # would split the sampled fall-through (new) from the engine (old)
        if self._engine is not None:
            self._engine.update_params(params, timeout=timeout)
        # the engine drain above can block for seconds — only the final
        # pointer swap runs under _swap_lock, paired with the coherent
        # snapshot HTTP workers take at the top of _complete
        with self._swap_lock:
            self.params = params
            if draft_params is not None:
                self.draft_params = draft_params
            if self._engine is None and self.prefix_cache is not None:
                self.prefix_cache.clear()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> int:
        self._server = ThreadingHTTPServer((self.host, self.port),
                                           self._make_handler())
        self.port = self._server.server_address[1]
        threading.Thread(target=self._server.serve_forever,
                         daemon=True).start()
        if self.metrics_port is not None and self.metrics_server is None:
            from ...obs.metricsd import MetricsServer
            extra, objectives = [], None
            if self._engine is not None:
                # the engine's request-lifecycle histograms append to
                # /metrics; its objective windows drive /healthz burn rates
                extra = [self._engine.serve_hists.render_prometheus]
                objectives = self._engine.slo_windows or None
            self.metrics_server = MetricsServer(
                port=int(self.metrics_port), host=self.host,
                slo_rules=self.slo_rules, extra_text=extra,
                objectives=objectives)
            self.metrics_server.start()
        log.info("openai-compatible endpoint on %s:%d", self.host, self.port)
        return self.port

    def stop(self):
        if self._server is not None:
            self._server.shutdown()
            self._server = None
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        if self._engine is not None:
            self._engine.stop()
            self._engine = None
