"""Continuous-batching decode engine for the serving plane.

The reference's serving stack handles concurrency by running one request per
FastAPI worker against an HF ``generate`` call (``serving/templates/
hf_template/main_openai.py``) — concurrent requests time-share the
accelerator, each paying a full decode pass.  TPU-natively the accelerator
wants one BATCHED program: this engine keeps a fixed pool of decode slots,
runs a single jitted ``vmap``-ed KV-cache step for all live slots per tick,
and admits waiting requests into freed slots between ticks ("continuous
batching" — requests join/leave the batch at token granularity, so short
requests aren't held hostage by long ones and the MXU sees batch-B matmuls
instead of B sequential batch-1 passes).

Engine states are static-shaped throughout (slot count, window, pool), so
exactly two programs compile: the batched tick and the prefill chunk.  The
KV cache is ONE page pool per layer (:mod:`fedml_tpu.serving.paged_kv`,
docs/SERVING.md "Memory plane"): every slot addresses its own pages through
a block table carried as traced data, admission reserves pages on the host,
and a prompt enters in fixed-size chunks that share the tick with decode.
A layer whose decode needs state that is not K/V (the short convolution's
last rows of input; a linear-attention layer's matrix state) keeps it beside
the pool, a row a slot, with no reservation (docs/SERVING.md "State that is
not pages").

Multi-tenant LoRA (``adapter_slots``/``adapter_registry``, see
:mod:`fedml_tpu.serving.adapters` and docs/SERVING.md): N adapters live
stacked in a device-resident bank next to the ONE shared base; each slot
carries an ``adapter_id`` and the batched step computes ``base(x) +
gather(bank, slot_adapter_ids) @ x`` via grouped (slot-batched) adapter
einsums — bank capacity is static, membership is data, so serving a new
or different adapter never recompiles.

The loop runs one tick ahead (docs/SERVING.md, "The loop"): the slot state
(token, position, key, temperature, adapter row, block table, steps left)
stays on the device and the tick program carries it from launch to launch,
so tick k+1 is launched before tick k is read and the host's delivery and
book-keeping run under the device's work.

Greedy (temp=0) output is bit-identical to the single-request
:func:`fedml_tpu.serving.templates.openai_compat.generate` path (tested);
the per-request threefry key splits follow the same sequence as that path,
so sampling streams match it too.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import queue
import threading
import time
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import get_tracer
from ..obs import programs as obs_programs
from ..obs.histogram import ServeHistograms
from .adapters import AdapterMissError, AdapterRegistry
from .paged_kv import PagedBlockPool, PagedPrefixCache, PageExhaustedError
from .templates.openai_compat import _sample_live


class PagedKVUnsupportedError(ValueError):
    """What the page pool cannot serve: a model that carries no
    ``LlamaConfig`` for the engine to rebuild with the pool's geometry
    (raised at engine construction), and a speculative draft (raised by
    the server for ``draft_model`` with ``batch_slots``: the draft/target
    verify blocks write multi-token windows into contiguous per-request
    caches, :mod:`fedml_tpu.serving.speculative`)."""


class _UnservableError(Exception):
    """A request whose page reservation can NEVER succeed on this pool
    (need exceeds total non-trash pages) — failed open instead of parked,
    or parking would deadlock the engine."""


#: what a staged slot row asks of ``slot_rows``: write the whole row, only
#: end the lane (``left`` := 0), or only write the window table
_ROW_PUT, _ROW_MASK, _ROW_TABLE = 1, 2, 3

#: how the TPU's compiler is asked to build the paged tick and chunk
#: programs.  By default it prefetches every weight matrix and adapter bank
#: in four slices, each a start and a done operation of its own: 2.3 of the
#: tick's 3.6 thousand device operations were those halves.  One slice a
#: prefetch moves the same bytes in 0.55 of the operations
#: (tests/test_chip_compile.py holds both to no slice; PERF.md section 6,
#: PR 32, has the device times).
PAGED_TPU_COMPILER_OPTIONS = {"xla_tpu_sliced_prefetch_max_slices": 1}


def _unwrap_params(params):
    """Accept either a raw param tree or a ``{"params": tree}`` wrapper
    (the flax ``init`` convention) — one place, used by construction and
    weight-swap paths alike."""
    return params.get("params", params) if isinstance(params, dict) \
        else params


def _moe_counters(mut):
    """What the sparse layers of one apply sowed (``llm/moe.py``:
    ``[pairs, experts_hit, load_max, tiles]`` a layer), one row a layer;
    None for a model without such layers."""
    from ..llm.moe import COUNTERS
    leaves = jax.tree_util.tree_leaves(mut.get(COUNTERS, {}))
    return _fold_counters(jnp.stack(leaves)) if leaves else None


def _fold_counters(rows):
    """Rows of ``[pairs, experts_hit, load_max, tiles]`` as one: pairs,
    experts hit and the kernels' row tiles summed, the largest load of any."""
    return jnp.stack([rows[:, 0].sum(), rows[:, 1].sum(), rows[:, 2].max(),
                      rows[:, 3].sum()])


def _with_counters(tokens, rows):
    """The program's int32 result: the tokens, and behind them the experts'
    counters (``rows`` folded) where the model has any — one array, so they
    ride the read-back of the tokens."""
    if rows is None:
        return tokens
    return jnp.concatenate([tokens.reshape(-1), _fold_counters(rows)])


class _Slot:
    __slots__ = ("live", "q", "pos", "remaining", "eos_id", "cur_tok",
                 "adapter_row",
                 # tick steps still to be dispatched for the request: what
                 # budget and buffer end leave (eos is learned later).  The
                 # device counts the same down (``left``); ``pos`` and
                 # ``remaining`` follow delivery, a dispatch behind
                 "steps",
                 # prefill state machine (free → prefilling → live):
                 # prompt ids + replay cursor for the chunked
                 # prefill lanes, the admission-split sample key, and the
                 # slot's block-table reservation size
                 "prefilling", "pf_ids", "pf_next", "pf_n", "pf_sub",
                 "pf_atok", "pf_acc", "n_blocks",
                 # the window pool (a model with window and full layers):
                 # the slot holds the pages of blocks ``w_first`` ..
                 # ``w_next - 1``, at most ``w_keep`` of the request's
                 # ``w_end``; ``dpos`` is the position the next tick to be
                 # dispatched writes (``pos`` follows delivery, behind it)
                 "w_first", "w_next", "w_keep", "w_end", "dpos",
                 # fedslo request-lifecycle telemetry (host monotonic
                 # clocks, engine-thread-confined like the decode state)
                 "t_submit", "t_admit", "t_prefill_end", "t_first",
                 "prompt_tokens", "out_tokens", "adapter_label",
                 "traceparent",
                 # the running integer submit() gave the request: every
                 # span of one request carries it
                 "request")

    def __init__(self):
        self.live = False
        self.q: Optional[queue.Queue] = None
        self.pos = 0
        self.remaining = 0
        self.eos_id: Optional[int] = None
        self.cur_tok = 0
        self.adapter_row = 0
        self.steps = 0
        self.prefilling = False
        self.pf_ids: Optional[List[int]] = None
        self.pf_next = 0
        self.pf_n = 0
        self.pf_sub = None
        self.pf_atok = None
        self.pf_acc = None
        self.n_blocks = 0
        self.w_first = self.w_next = self.w_keep = self.w_end = 0
        self.dpos = 0
        self.t_submit = 0.0
        self.t_admit: Optional[float] = None
        self.t_prefill_end = 0.0
        self.t_first: Optional[float] = None
        self.prompt_tokens = 0
        self.out_tokens = 0
        self.adapter_label = "base"
        self.traceparent: Optional[str] = None
        self.request: Optional[int] = None


class ContinuousBatchingEngine:
    """``submit()`` returns a queue that yields generated token ids and then
    ``None``; a daemon thread drives the batched decode loop."""

    def __init__(self, model, params, slots: int = 4, buf_len: int = 256,
                 top_k: int = 0, top_p: float = 1.0, horizon: int = 1,
                 prefix_cache_slots: int = 0,
                 adapter_registry: Optional[AdapterRegistry] = None,
                 adapter_slots: int = 0,
                 metrics_port: Optional[int] = None,
                 hist_labels: int = 8,
                 slo_rules: Optional[List[Dict[str, Any]]] = None,
                 kv_page_tokens: int = 16, kv_pool_pages: int = 0,
                 kv_window_pool_pages: int = 0,
                 prefill_chunk_tokens: int = 0, prefill_lanes: int = 1,
                 adapter_cache_slots: int = 0,
                 adapter_store_dir: Optional[str] = None):
        # refused before anything is started or allocated
        self.kv_page_tokens = int(kv_page_tokens)
        if self.kv_page_tokens <= 0:
            raise ValueError(
                f"kv_page_tokens={kv_page_tokens}: the page size of the "
                "engine's KV cache, in tokens, is positive (default 16)")
        cfg = getattr(model, "cfg", None)
        if cfg is None or not hasattr(cfg, "kv_page_tokens"):
            raise PagedKVUnsupportedError(
                "paged KV needs a LlamaLM-style model carrying a "
                "LlamaConfig (engine rebuilds it with the pool "
                "geometry)")
        self.model = model
        # fedslo (docs/OBSERVABILITY.md): per-request lifecycle histograms
        # (TTFT / e2e / queue wait / phase times / decode rate) with
        # bounded per-adapter labels (first-K + "other", hist_labels caps
        # the series count), and optional burn-rate objective streams fed
        # per finished request — host floats only, recorded on the engine
        # thread at request finish, never inside the jitted step
        self.serve_hists = ServeHistograms(max_labels=int(hist_labels))
        self.slo_windows: Dict[str, Any] = {}
        if slo_rules:
            from ..obs.slo import windows_for_rules
            self.slo_windows = windows_for_rules(slo_rules)
        # fedmon live export (docs/OBSERVABILITY.md): metrics_port serves
        # /metrics + /healthz over the global tracer's serve.* gauges
        # (0 = ephemeral; None = off); closed by stop().  The serve
        # histograms append to /metrics; the objective windows make
        # /healthz evaluate multi-window burn rates, not just point rules
        self.metrics_server = None
        if metrics_port is not None:
            from ..obs.metricsd import MetricsServer
            self.metrics_server = MetricsServer(
                port=int(metrics_port), slo_rules=slo_rules,
                extra_text=[self.serve_hists.render_prometheus],
                objectives=self.slo_windows or None)
            self.metrics_server.start()
        self.raw_params = _unwrap_params(params)
        self.n_slots = int(slots)
        self.buf_len = int(buf_len)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        # multi-tenant LoRA (serving/adapters.py): an adapter bank stacked
        # on a leading axis next to the ONE shared base; each slot carries
        # an adapter_id and the batched step gathers its bank row inside
        # the compiled program — bank capacity is static, membership is
        # data, so requests landing on different adapters never recompile.
        # ``adapter_slots=N`` builds a capacity-N registry; passing
        # ``adapter_registry`` shares one bank across engines.
        # adapter cache mode (serving/adapter_store.py, docs/SERVING.md):
        # ``adapter_cache_slots=N`` demotes the bank to an N-row HBM
        # cache over a host/disk adapter store — registered adapters
        # scale past HBM like client state did (fedstore), misses page
        # in asynchronously and the request requeues.  Pins are deferred
        # to admission (the engine thread owns install/evict).
        self.registry = adapter_registry
        self._owns_registry = False
        if adapter_cache_slots and self.registry is None:
            from .adapter_store import AdapterStore
            store = AdapterStore(
                model, spill_dir=adapter_store_dir,
                max_resident_pages=(16 if adapter_store_dir else 0))
            self.registry = AdapterRegistry(
                model, capacity=int(adapter_cache_slots), store=store)
            self._owns_registry = True
        elif adapter_slots and self.registry is None:
            self.registry = AdapterRegistry(model, capacity=int(adapter_slots))
            self._owns_registry = True
        self._store_mode = (self.registry is not None
                            and self.registry.store is not None)
        if self._store_mode:
            self.registry.on_fetch_done = self._on_adapter_fetched
        # decode horizon: tokens generated per device dispatch.  horizon=1 is
        # token-granularity admission (lowest queueing latency); horizon=H
        # runs H steps as one lax.scan on-device so per-token host round-trip
        # cost (dominant over a network-attached TPU) amortizes H-fold.  The
        # per-step computation is the identical scanned body, so outputs are
        # bit-equal to horizon=1 for every request; requests only join the
        # batch every H tokens, and a slot that hits eos/budget mid-horizon
        # burns its remaining lanes (discarded on host, cache overwritten at
        # next admission).
        self.horizon = max(1, int(horizon))

        # the KV cache (serving/paged_kv.py, docs/SERVING.md memory plane):
        # ONE page pool per layer + a per-slot block table carried as
        # traced data.  Admission reserves ceil(min(n+max_new, buf_len)/P)
        # pages host-side (parking the request when the pool is dry);
        # prefill runs in fixed prefill_chunk_tokens chunks on a per-tick
        # lane budget so long prompts stop head-of-line-blocking decode.
        ptok = self.kv_page_tokens
        self._chunks_total = 0
        self._pages_shared = 0
        self._pages_private = 0
        self.prefill_chunk = int(prefill_chunk_tokens) or \
            min(64, self.buf_len)
        self.prefill_lanes = max(1, int(prefill_lanes))
        # per-slot block-table width: the window covers buf_len plus
        # the worst chunk-padding / horizon-burn overhang, so every
        # out-of-reservation write lands on a real (trash) table
        # entry instead of index-clamping into a live page
        overhang = max(self.prefill_chunk, self.horizon)
        self.max_blocks = math.ceil((self.buf_len + overhang) / ptok)
        # pages a single slot may ever RESERVE (positions < buf_len)
        self.blocks_cap = math.ceil(self.buf_len / ptok)
        # 0: a page for every position of every slot, and the trash page —
        # no admitted request ever parks for pages
        pool_pages = int(kv_pool_pages) or \
            (1 + self.n_slots * self.blocks_cap)
        self.kv_pool_pages = pool_pages
        # a pool per kind of layer (docs/SERVING.md, "A pool per kind of
        # layer"): a model with window and full layers in one stack keeps
        # the full layers' K/V in ``page_pool`` under the rule above, and
        # the window layers' in ``window_pool``.  There a slot holds at
        # most ``window_blocks`` pages (window + overhang + a page of
        # tokens), its table is a ring (block j at entry j % window_blocks),
        # and before every chunk and tick the pages wholly behind
        # ``position - window + 1`` go back to the free list and as many
        # are taken for the blocks ahead (``_slide_window``).  A model of
        # one kind of layer has the one pool.
        self.window = int(cfg.sliding_window) \
            if getattr(cfg, "mixed_attention", False) else 0
        self.window_pool: Optional[PagedBlockPool] = None
        self.window_blocks = 0
        geometry = {"kv_page_tokens": ptok, "kv_pool_pages": pool_pages}
        if self.window:
            if prefix_cache_slots:
                raise PagedKVUnsupportedError(
                    "prefix pages are not shared over a window pool: a "
                    "lent page would be taken back behind the first "
                    "sharer's window while a later one still reads it "
                    "(prefix_cache_slots=0 for a model with window and "
                    "full layers)")
            self.window_blocks = math.ceil(
                (self.window + overhang) / ptok) + 1
            window_pages = int(kv_window_pool_pages) or (
                1 + self.n_slots * min(self.window_blocks, self.blocks_cap))
            geometry["kv_window_pool_pages"] = window_pages
            self.window_pool = PagedBlockPool(window_pages)
        self._window_pages_freed = 0
        # state beside pages (docs/SERVING.md, "State that is not pages"): a
        # layer that mixes by the short convolution keeps, for every slot,
        # the last rows of its input, and a linear-attention layer its matrix
        # state and its convolution's rows, in one buffer a leaf, a row a
        # slot and a trash row.  A slot owns its row for as long as the
        # engine lives: nothing is reserved and no request parks for state.
        # The programs keep three rules: a request's first chunk starts from
        # zeros (its first position is 0: no upload, no host write), every
        # chunk and tick writes its lanes' rows back, and a lane that is not
        # live addresses the trash row, so a slot between two chunks rides a
        # tick and keeps what its last chunk left.
        self._stateful = bool(getattr(cfg, "state_layers", 0))
        if self._stateful:
            if prefix_cache_slots:
                raise PagedKVUnsupportedError(
                    "prefix pages are not shared by a model with "
                    "convolution layers or linear-attention layers: a lent "
                    "prefix would need the layers' state at its end, of "
                    "which no snapshot is kept (prefix_cache_slots=0 for a "
                    "model with such layers)")
            geometry["state_slots"] = self.n_slots
        self.paged_model = type(model)(dataclasses.replace(cfg, **geometry))
        self.page_pool = PagedBlockPool(pool_pages)
        self._btabs = np.zeros((self.n_slots, self.max_blocks), np.int32)
        self._wtabs = np.zeros((self.n_slots, self.window_blocks), np.int32)

        # prefix_cache_slots > 0: admission shares *pages* for shared
        # prompt prefixes: PagedPrefixCache (LRU, longest common prefix in
        # whole pages, params-identity invalidation) lends refcounted full
        # pages into the new slot's block table, and the chunk replay
        # starts past the shared span, so lent pages stay read-only under
        # sharers.  Only the engine thread touches it during admission, but
        # the cache carries its own lock anyway.
        self.prefix_cache = None
        if prefix_cache_slots:
            self.prefix_cache = PagedPrefixCache(
                prefix_cache_slots, ptok, self.page_pool)

        from ..llm.quantization import dequantize_params, weight_dtype
        wdtype = weight_dtype(model)

        from ..llm.moe import COUNTERS
        pm = self.paged_model
        C = self.prefill_chunk
        horizon = self.horizon
        # only the TPU's compiler knows the options
        options = (PAGED_TPU_COMPILER_OPTIONS
                   if jax.default_backend() == "tpu" else None)
        key_words = int(np.asarray(jax.random.PRNGKey(0)).size)
        two_pools = self.window_pool is not None
        stateful = self._stateful
        n_slots = self.n_slots

        def tables(state, pick):
            """What the model takes as ``block_tables``: ``pick`` of the
            carried table, or of one a kind of layer where there are two
            pools."""
            if not two_pools:
                return pick(state["btabs"])
            return {"full": pick(state["btabs"]),
                    "window": pick(state["wtabs"])}

        def paged_tick(params, lora_slots, pool, state):
            """``horizon`` scanned steps of every lane from the slot state
            the last program left on the device, and the state the next
            program takes.  Each step is ONE batched apply against the
            shared pool — no vmap: every slot addresses its own pages via
            the traced block tables, per-slot depths ride the (b,)
            start_pos vector, and the per-slot key splits follow the
            single-request path's sequence exactly (split[0]=carry,
            split[1]=sample).  A lane is live while it has steps ``left``;
            only a live lane's token, position and key advance (a
            prefilling slot's admission key must not move with the splits
            its lane rides along for), and the count runs down here as it
            does on the host, so a budget's end uploads nothing.  A lane
            that is not live (free, prefilling, finished) sees an all-trash
            table, so its burn write lands in garbage and never in a page
            another slot is reading."""
            # int8-quantized trees dequantize inside the trace (stays int8
            # in HBM; per-matmul dequant fuses) — no-op for plain trees
            params = dequantize_params(params, wdtype)
            live = state["left"] > 0
            btabs = tables(state, lambda t: jnp.where(live[:, None], t, 0))
            # the rows of state, for a model that keeps any: a lane that is
            # not live addresses the trash row, the twin of its all-trash
            # table
            rows = {"state_rows": jnp.where(live, jnp.arange(n_slots),
                                            n_slots)} if stateful else {}

            def body(carry, _):
                pool, toks, poss, keys = carry
                variables = {"params": params, "cache": pool}
                if lora_slots is not None:
                    variables["lora"] = lora_slots
                logits, mut = pm.apply(
                    variables, toks[:, None], decode=True,
                    start_pos=poss, block_tables=btabs,
                    mutable=["cache", COUNTERS], **rows)
                split = jax.vmap(jax.random.split)(keys)
                nxt = jax.vmap(
                    lambda lg, sub, temp: _sample_live(
                        lg, sub, temp, self.top_k, self.top_p)
                )(logits[:, 0], split[:, 1], state["temps"])
                return ((mut["cache"], nxt, poss + 1, split[:, 0]),
                        (nxt, _moe_counters(mut)))

            (pool, toks, poss, keys), (hist, counts) = jax.lax.scan(
                body, (pool, state["toks"], state["poss"], state["keys"]),
                None, length=horizon)
            state = dict(
                state, toks=jnp.where(live, toks, state["toks"]),
                poss=jnp.where(live, poss, state["poss"]),
                keys=jnp.where(live[:, None], keys, state["keys"]),
                left=jnp.maximum(state["left"] - horizon, 0))
            # hist: (horizon, n_slots) → host iterates per-slot rows
            return _with_counters(hist.T, counts), pool, state

        @partial(jax.jit, donate_argnums=(1, 2),
                 compiler_options=options)
        def paged_step(params, pool, state):
            return paged_tick(params, None, pool, state)

        @partial(jax.jit, donate_argnums=(2, 3),
                 compiler_options=options)
        def paged_step_mt(params, bank, pool, state):
            return paged_tick(params, jax.tree_util.tree_map(
                lambda b: b[state["aids"]], bank), pool, state)

        @partial(jax.jit, donate_argnums=(2, 3),
                 compiler_options=options)
        def paged_chunk(params, lora, pool, state, chunk, acc):
            # one fixed-shape (1, C) prefill chunk for one slot.
            # ``chunk`` is everything the host knows of it, one
            # upload: the C token ids, then ``start idx slot pos
            # left``, the sample key's words and, where the window
            # layers have a pool of their own, the slot's window table
            # as the host has just slid it.  The sample index is
            # TRACED so intermediate chunks (token discarded) and the
            # final chunk (token at n-1-chunk_start) ride one compiled
            # program; the slot's block table and temperature are its
            # row of the carried state.  A final chunk (``left`` >= 0)
            # hands the slot over on the device: the sampled token,
            # the position ``pos`` and the steps ``left`` go into its
            # row, so the slot joins the next tick with no read-back.
            # ``acc`` is the last chunk's result for the same request
            # (None for a model without sparse layers): the experts'
            # counters add up behind the token from chunk to chunk,
            # and the final chunk's one read-back brings the request's
            params = dequantize_params(params, wdtype)
            start, idx, slot, pos, left = (chunk[C + j] for j in range(5))
            key = jax.lax.bitcast_convert_type(
                chunk[C + 5:C + 5 + key_words], jnp.uint32)
            if two_pools:       # the table before the slot's row is read
                state = dict(state, wtabs=state["wtabs"].at[slot].set(
                    chunk[C + 5 + key_words:]))
            variables = {"params": params, "cache": pool}
            if lora is not None:
                variables["lora"] = lora
            # the slot's own row of state; of a final chunk's C positions
            # those up to the prompt's last are real
            rows = {"state_rows": slot[None], "seq_lens": jnp.where(
                left >= 0, idx + 1, C)[None]} if stateful else {}
            logits, mut = pm.apply(
                variables, chunk[None, :C], decode=True,
                start_pos=start[None],
                block_tables=tables(state, lambda t: t[slot][None]),
                mutable=["cache", COUNTERS], **rows)
            tok = _sample_live(logits[0, idx], key, state["temps"][slot],
                               self.top_k, self.top_p)
            def handed(vec, new):
                return vec.at[slot].set(
                    jnp.where(left >= 0, new, vec[slot]))

            state = dict(state, toks=handed(state["toks"], tok),
                         poss=handed(state["poss"], pos),
                         left=handed(state["left"], left))
            counts = _moe_counters(mut)
            if counts is not None:      # added to the request's so far
                counts = jnp.stack([acc[1:], counts])
            return _with_counters(tok, counts), mut["cache"], state

        self._step = paged_step if self.registry is None \
            else paged_step_mt
        self._chunk = paged_chunk
        # each program's registration with obs/programs.py, made at its
        # first launch (``program_ops``); None until then
        self._programs: Dict[str, Any] = {
            "step": None, "chunk": None, "slot_rows": None}

        # the slot state the tick program carries from launch to launch
        # (docs/SERVING.md, "The loop"): one row a slot, on the device.
        # The host writes a row only where an admission or a late finish
        # changed it, all of an iteration's changes in one staged array
        # ``[block table | window table | tok pos left temp aid | key
        # words | op]``.
        blocks = self.max_blocks
        tabs = blocks + self.window_blocks

        @partial(jax.jit, donate_argnums=(0,))
        def slot_rows(state, rows):
            op = rows[:, -1]
            put = op == _ROW_PUT
            cols = rows[:, tabs:]
            new = {"toks": cols[:, 0], "poss": cols[:, 1],
                   "left": cols[:, 2],
                   "temps": jax.lax.bitcast_convert_type(
                       cols[:, 3], jnp.float32),
                   "aids": cols[:, 4],
                   "keys": jax.lax.bitcast_convert_type(
                       cols[:, 5:5 + key_words], jnp.uint32),
                   "btabs": rows[:, :blocks]}
            if two_pools:
                new["wtabs"] = rows[:, blocks:tabs]
            out = {name: jnp.where(put.reshape((-1,) + (1,) * (old.ndim - 1)),
                                   new[name], old)
                   for name, old in state.items()}
            out["left"] = jnp.where(op == _ROW_MASK, 0, out["left"])
            if two_pools:
                out["wtabs"] = jnp.where((op == _ROW_TABLE)[:, None],
                                         new["wtabs"], out["wtabs"])
            return out

        self._slot_rows = slot_rows
        n = self.n_slots
        self._dev = {"toks": jnp.zeros(n, jnp.int32),
                     "poss": jnp.zeros(n, jnp.int32),
                     "left": jnp.zeros(n, jnp.int32),
                     "temps": jnp.zeros(n, jnp.float32),
                     "keys": jnp.zeros((n, key_words), jnp.uint32),
                     "btabs": jnp.zeros((n, blocks), jnp.int32)}
        if two_pools:
            self._dev["wtabs"] = jnp.zeros((n, self.window_blocks), jnp.int32)
        if self.registry is not None:
            self._dev["aids"] = jnp.zeros(n, jnp.int32)
        self._rows = np.zeros((n, tabs + 6 + key_words), np.int32)
        self._rows_staged = False
        # the dispatch whose results the host has not read yet, as
        # ``[tokens, [(slot, queue)] of its lanes, first tokens]``, and the
        # first tokens of this pass's final chunks ``(slot, queue, token)``:
        # they ride the next dispatch's record
        self._unread: Optional[list] = None
        self._firsts: List[tuple] = []
        self._ticks_ahead = 0
        self._lanes_burned = 0
        self._flushes = 0

        dummy_lora = (self.registry.lora_for_row(0)
                      if self.registry is not None else None)
        # materialize the page pool from the chunk program's shape
        # (eval_shape only)
        chunk0 = jnp.zeros((1, self.prefill_chunk), jnp.int32)
        btab0 = tables({"btabs": jnp.zeros((1, self.max_blocks), jnp.int32),
                        "wtabs": jnp.zeros((1, self.window_blocks),
                                           jnp.int32)}, lambda t: t)

        def _shape_probe(p):
            variables = {"params": dequantize_params(p, wdtype)}
            if dummy_lora is not None:
                variables["lora"] = dummy_lora
            return self.paged_model.apply(
                variables, chunk0, decode=True,
                start_pos=jnp.zeros((1,), jnp.int32),
                block_tables=btab0, mutable=["cache"])

        _, shapes = jax.eval_shape(_shape_probe, self.raw_params)
        self._pool = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])
        # what one cached token costs over all layers, whatever a page
        # holds (K and V rows of every kv head; one latent row) and
        # whichever pool the layer's pages lie in; a leaf of state has no
        # pages and is counted apart, whole
        from ..llm.model import LINEAR_LEAVES, STATE_LEAVES
        paged, state, linear = [], [], []
        for path, p in jax.tree_util.tree_leaves_with_path(self._pool):
            leaf = getattr(path[-1], "key", None)
            (state if leaf in STATE_LEAVES else paged).append(p)
            if leaf in LINEAR_LEAVES:
                linear.append(p)
        self._kv_bytes_per_token = sum(
            p.nbytes // (p.shape[0] * ptok) for p in paged)
        self._state_bytes = sum(p.nbytes for p in state)
        # a row of every linear-attention layer's state (its matrix state and
        # its convolution's rows): what a lane of a call reads and writes back
        self._linear_row_bytes = sum(p.nbytes // p.shape[0] for p in linear)
        # sparse layers: the chunk program's first accumulator
        self._moe_layers = sum(
            cfg.sparse_layer(i) for i in range(cfg.n_layers))
        self._chunk_acc0 = jnp.zeros((5,), jnp.int32) \
            if self._moe_layers else None
        self._chunk_words = self.prefill_chunk + 5 + key_words \
            + self.window_blocks
        # the layers whose paged read is a kernel over each lane's live
        # pages in this process's programs, for the tick's lanes and for a
        # chunk's rows: what ``attn_pages`` counts (none where the ``jnp``
        # forms run), for the models that have such a kernel
        self._counts_attn_pages = two_pools or cfg.kv_lora_rank > 0
        self._kernel_reads = {
            "tick": self._reads_by_kernel(self.n_slots, 1),
            "chunk": self._reads_by_kernel(1, self.prefill_chunk)} \
            if self._counts_attn_pages else {}
        self._attn_pages = 0
        self._host_device = jax.devices("cpu")[0]

        self._slots = [_Slot() for _ in range(self.n_slots)]
        self._waiting: "queue.Queue[dict]" = queue.Queue()
        # requests pulled off _waiting but not admittable yet (adapter
        # page-in in flight, page pool dry) — engine-thread-confined,
        # retried at the top of every iteration before new admissions
        self._parked: List[dict] = []
        # set (under _cond) by the adapter fetch worker; cleared by the
        # engine's parked-retry pass
        self._fetch_ready = False
        # engine-thread flag: a slot finish released an adapter pin (or
        # pages) — a parked request whose install lost to an all-pinned
        # cache must retry now, even with nothing live to keep the loop
        # ticking.  Cleared with _fetch_ready by the retry pass.
        self._pin_released = False
        self._cond = threading.Condition()
        self._stopped = False
        # weight swap staged by update_params(); applied by the engine
        # thread once live slots drain (admission pauses meanwhile)
        self._pending_params = None
        self._ticks = 0  # batched steps executed (observability)
        # what the sparse layers did (kv_stats): pairs the
        # held experts computed in ticks and finished prefills, and the
        # row tiles the grouped-matmul kernels visited for them; held
        # experts that got a token and sparse layers run, over the ticks
        self._expert_pairs = 0
        self._expert_tiles = 0
        self._experts_hit = 0
        self._moe_layers_ticked = 0
        self._expert_load_max = 0
        self._iters = 0  # engine-loop passes (the serve.iter span's index)
        self._requests = 0  # requests submitted; the next one's id
        # host-side serving telemetry (always maintained; mirrored onto
        # fedtrace counters when tracing is on — host ints only, the
        # engine never adds a device sync for observability)
        self.serve_stats: Dict[str, Any] = {
            "admits": 0, "tokens": 0, "requests": {}}
        self._tok_window = [time.monotonic(), 0]
        # guards serve_stats/_tok_window (engine thread increments, HTTP
        # submit() and metrics scrapes read).  Strictly innermost: taken
        # with nothing else held, or nested inside _cond — never the
        # reverse, so it can never extend the lock-order graph into a cycle
        self._stats_lock = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # -- public api --------------------------------------------------------
    def submit(self, prompt_ids: List[int], max_new_tokens: int = 64,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None,
               adapter: Optional[str] = None,
               traceparent: Optional[str] = None) -> "queue.Queue":
        """Enqueue a request; returns a queue yielding token ids then
        ``None``.  ``adapter`` names a registered bank row (multi-tenant
        engines only; ``KeyError`` for unknown names) — the row is pinned
        until the request finishes, so an eviction or re-registration
        mid-stream can never change the weights under an in-flight slot.
        ``traceparent`` (W3C header value) joins the request's span tree
        to the caller's fedscope trace."""
        out: "queue.Queue" = queue.Queue()
        row, atok = 0, None
        if self._store_mode:
            # cache mode: validate the name against the store here (so
            # unknown adapters still fail the caller) but defer the PIN
            # to admission — the engine thread owns page-in/install, and
            # a miss parks the request instead of blocking submit
            if adapter is not None and adapter not in self.registry:
                raise KeyError(f"unknown adapter {adapter!r}; have "
                               f"{self.registry.names()}")
        elif self.registry is not None:
            # resolve at submit so unknown adapters fail the caller, not
            # the engine thread; the pin travels with the request
            row, atok = self.registry.acquire(adapter)
        elif adapter:
            raise ValueError("engine built without an adapter registry "
                             f"(adapter_slots=0) — cannot route {adapter!r}")
        # the put happens under _cond so it cannot interleave with the
        # shutdown/crash drain (which also holds _cond): either the request
        # lands before the drain and receives its sentinel, or the stopped
        # flag is already visible here and we raise
        try:
            with self._cond:
                if self._stopped or not self._thread.is_alive():
                    raise RuntimeError("engine stopped")
                name = adapter if adapter is not None else "base"
                self._requests += 1
                self._waiting.put({
                    "request": self._requests,
                    "prompt_ids": list(prompt_ids)[-(self.buf_len - 1):],
                    "max_new_tokens": int(max_new_tokens),
                    "temperature": float(temperature),
                    "seed": int(seed),
                    "eos_id": eos_id,
                    "adapter": adapter,
                    "adapter_row": row,
                    "adapter_token": atok,
                    "adapter_label": name,
                    "traceparent": traceparent,
                    "t_submit": time.monotonic(),
                    "q": out,
                })
                with self._stats_lock:   # _cond -> _stats_lock, never reversed
                    reqs = self.serve_stats["requests"]
                    reqs[name] = reqs.get(name, 0) + 1
                    nreq = reqs[name]
                # bounded-cardinality request counter: ONE metric with an
                # adapter label (capped at hist_labels + "other"), replacing
                # PR 9's per-adapter metric NAMES which grew one series per
                # registered adapter.  The old names re-appear only behind
                # the deprecation flag, kept for one release.
                label, label_n = self.serve_hists.labels.resolve(name)
                tracer = get_tracer()
                if tracer.enabled:
                    tracer.counter("serve.requests_by_adapter", label_n,
                                   adapter=label)
                    if os.environ.get(
                            "FEDML_SERVE_LEGACY_ADAPTER_COUNTERS") == "1":
                        tracer.counter(f"serve.requests.{name}", nreq)
                self._cond.notify()
        except BaseException:
            if self.registry is not None:
                self.registry.release(row)
            raise
        return out

    def generate(self, prompt_ids: List[int], **kw) -> List[int]:
        """Blocking convenience wrapper over :meth:`submit`."""
        q = self.submit(prompt_ids, **kw)
        out: List[int] = []
        while True:
            t = q.get()
            if t is None:
                return out
            out.append(t)

    def update_params(self, params, wait: bool = True,
                      timeout: float = 60.0) -> None:
        """Swap the serving weights (federated round boundary).

        The swap is staged and applied by the engine thread only once the
        in-flight slots drain — admission pauses while a swap is pending —
        so every request is served end-to-end by exactly one weight
        version (no mid-stream weight change, no old-weights engine vs
        new-weights fall-through split).  The engine's prefix cache is
        cleared atomically with the swap.  Same-structure trees reuse the
        compiled programs (params are traced arguments).  ``wait=True``
        blocks until the swap lands; the drain is bounded by in-flight
        ``max_new_tokens`` budgets.
        """
        raw = _unwrap_params(params)
        with self._cond:
            if self._stopped or not self._thread.is_alive():
                raise RuntimeError("engine stopped")
            self._pending_params = raw
            self._cond.notify_all()
            if not wait:
                return
            deadline = time.monotonic() + timeout
            while self._pending_params is not None:
                if self._stopped or not self._thread.is_alive():
                    raise RuntimeError("engine stopped during weight swap")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        "weight swap did not land within "
                        f"{timeout}s (in-flight requests still draining)")
                self._cond.wait(timeout=min(0.5, remaining))

    def _on_adapter_fetched(self, name: str) -> None:
        """Fetch-worker callback (cache mode): wake the engine so parked
        adapter-miss requests retry immediately."""
        with self._cond:
            self._fetch_ready = True
            self._cond.notify()

    def stop(self):
        self._stopped = True
        with self._cond:
            self._cond.notify()
        self._thread.join(timeout=10)
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        if self._owns_registry and self.registry is not None:
            self.registry.close()
        # obs/programs.py holds the programs weakly; an engine that is
        # stopped but still referenced drops out of it here
        obs_programs.unregister(*self._programs.values())

    def _register_program(self, key: str, fn, *args) -> None:
        """``fn``'s first launch: its name and this launch's shapes go to
        ``obs/programs.py``, which keeps no array and no reference to the
        engine.  Written on the engine's thread only, once a key;
        ``program_ops`` and ``stop`` read a handle or None."""
        # fedrace: disable-next-line=unguarded-shared-write
        self._programs[key] = obs_programs.register(fn.__name__, fn, args)

    def program_ops(self) -> Dict[str, Optional[dict]]:
        """The instruction -> module maps of this engine's compiled programs
        by program name (``paged_step`` or ``paged_step_mt``, ``paged_chunk``,
        ``slot_rows``, and the bank's ``gather_row``), each ``{instruction:
        {"path", "phase", "kernel", "op"}}``, or None for a program not
        launched yet: what joins a device trace's operations to the model's
        modules (docs/OBSERVABILITY.md, "Device time by module").  Lowers and
        compiles each program again (a load where a persistent compile cache
        is set): seconds.  Not for the engine's own thread."""
        if threading.current_thread() is self._thread:
            raise RuntimeError("program_ops() compiles: not on the engine's thread")
        handles = {fn.__name__: self._programs[key] for key, fn in (
            ("step", self._step), ("chunk", self._chunk),
            ("slot_rows", self._slot_rows))}
        out = obs_programs.program_ops(handles)
        if self.registry is not None:
            out.update(self.registry.program_ops())
        return out

    def step_programs(self):
        """fedverify hook (ISSUE 10, docs/FEDVERIFY.md): the engine's
        compiled programs as ``(name, jitted_fn, args, donate_argnums)``
        on their resting buffer shapes, so the contract checker can
        AOT-lower them without serving a request.  ``decode_step`` is the
        per-tick batched decode ``_dispatch`` launches: it donates the
        page pool and the carried slot state (the two arguments after
        params[/bank]).  ``prefill_chunk`` is the other compiled citizen —
        both pinned so a page-geometry change shows up as a contract
        diff, not a silent regression."""
        bank = () if self.registry is None else (self.registry.bank,)
        state_arg = 2 + len(bank)
        lora = (self.registry.lora_for_row(0)
                if self.registry is not None else None)
        chunk = jnp.zeros((self._chunk_words,), jnp.int32)
        return [
            ("decode_step", self._step,
             (self.raw_params, *bank, self._pool, self._dev),
             (state_arg - 1, state_arg)),
            ("prefill_chunk", self._chunk,
             (self.raw_params, lora, self._pool, self._dev, chunk,
              self._chunk_acc0), (2, 3)),
        ]

    # -- engine loop -------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if not s.live and not s.prefilling:
                return i
        return None

    # the decode state (the page pool _pool, the carried slot state _dev
    # and the rows staged for it) is engine-thread-confined: written only
    # on the engine thread, never touched by submit()/HTTP threads, so it
    # needs no lock despite living next to shared state
    def _stage_row(self, slot: int, key, temp: float, aid: int,
                   tok: int = 0, pos: int = 0, left: int = 0) -> None:
        """Stage ``slot``'s whole row for the next ``_sync_rows``: what an
        admission knows (it leaves token, position and steps to the
        request's final chunk, which writes them on the device)."""
        row = self._rows[slot]
        tabs = self.max_blocks + self.window_blocks
        row[:self.max_blocks] = self._btabs[slot]
        row[self.max_blocks:tabs] = self._wtabs[slot]
        row[tabs:-1] = (
            tok, pos, left, np.float32(temp).view(np.int32), aid,
            *np.asarray(key).view(np.int32))
        row[-1] = _ROW_PUT
        self._rows_staged = True  # fedrace: disable=unguarded-shared-write

    def _stage_table(self, slot: int) -> None:
        """Stage ``slot``'s window table alone, as ``_slide_window`` has
        just left it: the tick's own token, position and key stay the
        device's."""
        row = self._rows[slot]
        row[self.max_blocks:self.max_blocks + self.window_blocks] = \
            self._wtabs[slot]
        if row[-1] == 0:
            row[-1] = _ROW_TABLE
        self._rows_staged = True  # fedrace: disable=unguarded-shared-write

    def _mask_lane(self, slot: int) -> None:
        """End ``slot``'s lane on the device where the device cannot know
        (eos, an abort): a lane that ran out of budget or buffer has
        counted itself down."""
        self._rows[slot, -1] = _ROW_MASK
        self._rows_staged = True  # fedrace: disable=unguarded-shared-write

    def _sync_rows(self) -> None:
        """Write the staged rows into the carried state: one upload and one
        small program, and nothing at all in a pass that staged none."""
        if not self._rows_staged:
            return
        # the array goes to the runtime for good (a CPU client may alias
        # it); staging goes on in a new one
        rows, self._rows = self._rows, np.zeros_like(self._rows)  # fedrace: disable=unguarded-shared-write
        self._rows_staged = False  # fedrace: disable=unguarded-shared-write
        rows = jax.device_put(rows)
        if self._programs["slot_rows"] is None:
            self._register_program("slot_rows", self._slot_rows,
                                   self._dev, rows)
        # fedrace: disable-next-line=unguarded-shared-write
        self._dev = self._slot_rows(self._dev, rows)

    def _finish(self, i: int, aborted: bool = False):
        s = self._slots[i]
        if not aborted and s.t_admit is not None:
            self._observe_finish(i, s)
        if s.steps:
            s.steps = 0
            self._mask_lane(i)
        s.t_admit = None
        s.live = False
        s.prefilling = False
        s.pf_ids = None
        s.pf_sub = None
        if s.n_blocks:
            # drop the slot's hold on its block-table pages (shared
            # prefix pages survive under the cache / other sharers)
            self.page_pool.release(
                [int(p) for p in self._btabs[i, :s.n_blocks]])
            self._btabs[i, :] = 0  # fedrace: disable=unguarded-shared-write
            s.n_blocks = 0
        if s.w_next > s.w_first:      # what the window still holds
            held = self._wtabs[i][self._wtabs[i] != 0]
            self.window_pool.release([int(p) for p in held])
            self._wtabs[i, :] = 0  # fedrace: disable=unguarded-shared-write
            s.w_first = s.w_next = 0
        if s.q is not None:
            s.q.put(None)
        s.q = None
        if self.registry is not None and s.adapter_row:
            self.registry.release(s.adapter_row)
            s.adapter_row = 0
        # fedrace: disable-next-line=unguarded-shared-write
        self._pin_released = True

    def _observe_finish(self, i: int, s: "_Slot") -> None:
        """fedslo request-lifecycle telemetry at natural completion
        (engine thread, host clocks only — the jitted step is untouched):
        the phase breakdown lands in the serve histograms, the objective
        windows, and — when tracing is on — a retroactive span tree on a
        synthetic lane of the request's own (a lane per slot crossed: a
        request that waited while its slot still served another begins,
        with its queue span, before that one ends, and B/E pairing after
        the export's timestamp sort then depended on which thread ran
        first).  That tree is one request's lifetime, not what this thread
        was doing: the live ``serve.iter`` tree (``_run_loop``) says that."""
        now = time.monotonic()
        queue_s = max(s.t_admit - s.t_submit, 0.0)
        prefill_s = max(s.t_prefill_end - s.t_admit, 0.0)
        e2e_s = max(now - s.t_submit, 0.0)
        decode_s = max(now - s.t_prefill_end, 0.0)
        ttft_s = max(s.t_first - s.t_submit, 0.0) \
            if s.t_first is not None else None
        self.serve_hists.record_request(
            s.adapter_label, queue_s=queue_s, prefill_s=prefill_s,
            e2e_s=e2e_s, ttft_s=ttft_s, decode_s=decode_s,
            output_tokens=s.out_tokens)
        for win in self.slo_windows.values():
            v = {"serve_ttft_seconds": ttft_s,
                 "serve_e2e_seconds": e2e_s,
                 "serve_queue_wait_seconds": queue_s,
                 "serve_prefill_seconds": prefill_s,
                 "serve_decode_seconds": decode_s}.get(win.metric)
            if v is not None:
                win.observe(v)
        tracer = get_tracer()
        if not tracer.enabled:
            return
        # clear of COMPILE_TID; 4096 requests later a lane is free again
        lane = -16 - (s.request or 0) % 4096
        # each call reads the clock anew, a little later: the child that
        # ends with its parent is written first, so that it ends inside it
        tracer.complete("serve.decode", decode_s, cat="serve", tid=lane,
                        slot=i, request=s.request)
        tracer.complete(
            "serve.request", e2e_s, cat="serve", tid=lane,
            adapter=s.adapter_label, slot=i, request=s.request,
            prompt_tokens=s.prompt_tokens, output_tokens=s.out_tokens,
            queue_s=round(queue_s, 6), prefill_s=round(prefill_s, 6),
            ttft_s=round(ttft_s, 6) if ttft_s is not None else None,
            decode_s=round(decode_s, 6), e2e_s=round(e2e_s, 6),
            traceparent=s.traceparent)
        tracer.complete("serve.queue", queue_s, cat="serve", tid=lane,
                        end_s_ago=max(e2e_s - queue_s, 0.0), slot=i,
                        request=s.request)

    def _emit(self, i: int, tok: int) -> bool:
        """Deliver one sampled token; returns False when the slot is done
        (eos / budget / buffer end).  Delivery rules mirror ``generate()``
        exactly: eos is not delivered, nor is a token whose successor
        position would fall outside the buffer window."""
        s = self._slots[i]
        if s.remaining <= 0 or s.pos >= self.buf_len:
            return False
        if s.eos_id is not None and tok == s.eos_id:
            return False
        s.q.put(tok)
        s.remaining -= 1
        s.cur_tok = tok
        if s.t_first is None:
            s.t_first = time.monotonic()
        s.out_tokens += 1
        with self._stats_lock:
            self.serve_stats["tokens"] += 1
            self._tok_window[1] += 1
        return s.remaining > 0 and s.pos < self.buf_len

    # -- admission ---------------------------------------------------------
    def _reserve_pages(self, req: dict, slot: int) -> None:
        """Wire ``slot``'s block table: longest shareable prefix pages
        (incref'd) + fresh private pages for the rest of the request's
        worst-case window; in the window pool, where the model has one, the
        first ``min(that, window_blocks)`` blocks' pages: room in both, or
        nothing is taken.  Raises :class:`PageExhaustedError` when a
        pool is dry (caller parks) and :class:`_UnservableError` when the
        reservation can never fit (caller fails the request open)."""
        ids = req["prompt_ids"]
        n = len(ids)
        ptok = self.kv_page_tokens
        need = min(n + req["max_new_tokens"], self.buf_len)
        need_blocks = max(1, math.ceil(need / ptok))
        if need_blocks > self.page_pool.n_pages - 1:
            raise _UnservableError(
                f"request needs {need_blocks} pages; pool has "
                f"{self.page_pool.n_pages - 1} usable")
        keep = min(need_blocks, self.window_blocks)
        wpool = self.window_pool
        if wpool is not None:
            if keep > wpool.n_pages - 1:
                raise _UnservableError(
                    f"request needs {keep} window pages; pool has "
                    f"{wpool.n_pages - 1} usable")
            if not wpool.can_reserve(keep):   # before the other pool gives
                wpool.stats["exhausted"] += 1
                raise PageExhaustedError(
                    f"need {keep} window pages, {wpool.pages_free} free")
        atok = req.get("adapter_token")
        full, shared = (self.prefix_cache.lookup(ids, self.raw_params, atok)
                        if self.prefix_cache is not None and n > 0
                        else (0, []))
        # incref the lent pages FIRST: evict_for_pages below may drop the
        # very entry we matched, and only our hold keeps its pages alive
        self.page_pool.share(shared)
        priv = need_blocks - full
        try:
            if not self.page_pool.can_reserve(priv) \
                    and self.prefix_cache is not None:
                self.prefix_cache.evict_for_pages(priv)
            pages = self.page_pool.reserve(priv)
        except PageExhaustedError:
            self.page_pool.release(shared)
            raise
        self._btabs[slot, :] = 0  # fedrace: disable=unguarded-shared-write
        self._btabs[slot, :full] = shared
        self._btabs[slot, full:need_blocks] = pages
        if wpool is not None:       # blocks 0 .. keep - 1, entry j for j
            self._wtabs[slot, :] = 0  # fedrace: disable=unguarded-shared-write
            self._wtabs[slot, :keep] = wpool.reserve(keep)
        req["_kv"] = (full, need_blocks, keep)
        with self._stats_lock:  # kv_stats() reads from caller threads
            self._pages_shared += full
            self._pages_private += priv

    def _admit(self, req: dict, slot: int) -> None:
        """Enter the prefilling state (free → prefilling): block table is
        already wired by ``_reserve_pages``; the chunk lanes in
        ``_prefill_tick`` replay the prompt from the shared-page boundary
        and flip the slot live on the final chunk."""
        t_admit = time.monotonic()
        ids = req["prompt_ids"]
        n = len(ids)
        full, need_blocks, keep = req.pop("_kv")
        # same split sequence as the single-request path: sub samples the
        # first token (on the final chunk), key carries into decode.  On
        # the host's own device: the same integers, and no wait behind the
        # tick the accelerator is running
        with jax.default_device(self._host_device):
            key, sub = jax.random.split(jax.random.PRNGKey(req["seed"]))
            key, sub = np.asarray(key), np.asarray(sub)
        s = self._slots[slot]
        s.prefilling = True
        s.live = False
        s.q = req["q"]
        s.pos = 0
        s.remaining = req["max_new_tokens"]
        s.eos_id = req["eos_id"]
        s.cur_tok = 0
        s.adapter_row = req.get("adapter_row", 0)
        s.steps = 0
        s.pf_ids = ids
        s.pf_n = n
        s.pf_next = full * self.kv_page_tokens
        s.pf_sub = sub
        s.pf_atok = req.get("adapter_token")
        s.pf_acc = self._chunk_acc0
        s.n_blocks = need_blocks
        if self.window_pool is not None:
            s.w_first, s.w_next, s.w_keep, s.w_end = 0, keep, keep, need_blocks
        s.t_submit = req.get("t_submit", t_admit)
        s.t_admit = t_admit
        s.t_prefill_end = t_admit
        s.t_first = None
        s.prompt_tokens = n
        s.out_tokens = 0
        s.adapter_label = req.get("adapter_label", "base")
        s.traceparent = req.get("traceparent")
        s.request = req.get("request")
        self._stage_row(slot, key, req["temperature"], s.adapter_row)

    def _reads_by_kernel(self, b: int, s: int) -> List[tuple]:
        """``(layers, window, ring, entries)`` a kind of layer: those of the
        paged model whose read, in a program of ``b`` lanes of ``s``
        positions, walks its table and whose walk is the kernel of
        ``ops/paged_attention.py`` here; of a model with latent attention,
        every layer where its read is ``ops/latent_attention.py``'s."""
        from ..llm.model import paged_read_walks
        from ..ops import paged_attention as pa
        cfg = self.paged_model.cfg
        head_dim = cfg.head_dim or cfg.dim // cfg.n_heads

        def shape(*dims, dtype=cfg.dtype):
            return jax.ShapeDtypeStruct(dims, dtype)

        if cfg.kv_lora_rank:    # one pool a layer, no window, no ring
            from ..llm.mla import pool_row_width
            from ..ops import latent_attention as la
            row = pool_row_width(cfg)
            if la.engages(
                    shape(b, cfg.n_heads, s, row),
                    shape(cfg.kv_pool_pages, cfg.kv_page_tokens, row),
                    shape(b, self.max_blocks, dtype=jnp.int32),
                    cfg.kv_lora_rank):
                return [(cfg.n_layers, 0, False, self.max_blocks)]
            return []
        q = shape(b, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, s,
                  head_dim)
        kinds: Dict[tuple, int] = {}
        for i in range(cfg.n_layers):
            window = cfg.layer_window(i)
            ring = window > 0 and cfg.kv_window_pool_pages > 0
            entries, pages = (self.window_blocks, cfg.kv_window_pool_pages) \
                if ring else (self.max_blocks, cfg.kv_pool_pages)
            pool = shape(pages, cfg.kv_page_tokens, cfg.n_kv_heads, head_dim,
                         dtype=jnp.int8 if cfg.kv_cache_dtype == "int8"
                         else cfg.dtype)
            if paged_read_walks(cfg, window, b, s, entries) and pa.engages(
                    q, pool, pool, shape(b, entries, dtype=jnp.int32)):
                kinds[window, ring, entries] = \
                    kinds.get((window, ring, entries), 0) + 1
        return [(n, *kind) for kind, n in kinds.items()]

    def _count_attn_pages(self, program: str, pos: np.ndarray) -> int:
        """Pages the kernel visits over all layers in one call of
        ``program`` whose live lanes stand at ``pos`` (lanes, s)."""
        from ..ops.paged_attention import visited_pages
        live = np.ones(len(pos), np.int64)
        pages = sum(
            layers * visited_pages(pos, live, window=window, ring=ring,
                                   entries=entries,
                                   ptok=self.kv_page_tokens)
            for layers, window, ring, entries in self._kernel_reads[program])
        with self._stats_lock:
            self._attn_pages += pages
        return pages

    def _slide_window(self, i: int, s: "_Slot", lo: int):
        """Slide slot ``i``'s window table to a program whose lowest query
        position is ``lo`` (a chunk's start, a tick's position): the pages
        of the blocks wholly behind ``lo - window + 1``, which no later
        program of the slot reads, go back to the free list and their ring
        entries to the trash page, and as many pages are taken for the
        blocks ahead, up to the request's last, so that the slot holds
        ``w_keep`` blocks or all that are left.  It never holds more than
        it did, so the take cannot fail.  Host book-keeping in dispatch
        order: the device runs the programs in that order too, so a page
        is written by its next holder only after every program that read
        it for this one.  Returns whether the table changed, and the pages
        that went back."""
        ptok, ring = self.kv_page_tokens, self.window_blocks
        tab = self._wtabs[i]
        first = min(max((lo - self.window + 1) // ptok, s.w_first), s.w_next)
        behind = [j % ring for j in range(s.w_first, first)]
        if behind:
            self.window_pool.release([int(tab[e]) for e in behind])
            tab[behind] = 0
            with self._stats_lock:
                self._window_pages_freed += len(behind)
        upto = min(first + s.w_keep, s.w_end)
        ahead = [j % ring for j in range(s.w_next, upto)]
        if ahead:
            tab[ahead] = self.window_pool.reserve(len(ahead))
        s.w_first, s.w_next = first, max(upto, s.w_next)
        return bool(behind or ahead), len(behind)

    def _prefill_tick(self) -> None:
        """Run up to ``prefill_lanes`` fixed-shape prefill chunks, one per
        prefilling slot — chunked prefill shares the tick with decode, so
        a 4k-token prompt costs each tick one chunk, not a stall."""
        lanes = self.prefill_lanes
        C = self.prefill_chunk
        tracer = get_tracer()
        for i, s in enumerate(self._slots):
            if lanes <= 0:
                break
            if not s.prefilling:
                continue
            lanes -= 1
            cs = s.pf_next
            n = s.pf_n
            final = cs + C >= n
            with tracer.span("serve.chunk", cat="engine", slot=i,
                             request=s.request, start=cs,
                             tokens=min(C, n - cs), final=int(final)) as span:
                freed = self._prefill_chunk(tracer, i, s, cs, final)
                if self._stateful:
                    # the row the chunk wrote back, and whether it read it
                    # from an earlier chunk (a first chunk starts from zeros)
                    span.set(state_rows=1, state_carried=int(cs > 0))
                if self._linear_row_bytes:
                    # the linear-attention layers' row it wrote back
                    span.set(linear_state_bytes=self._linear_row_bytes)
                if self.window_pool is not None:
                    span.set(window_pages_freed=freed)
                if self._counts_attn_pages:
                    span.set(attn_pages=self._count_attn_pages(
                        "chunk", cs + np.arange(C)[None]))

    def _prefill_chunk(self, tracer, i: int, s: "_Slot", cs: int,
                       final: bool) -> int:
        """One chunk of slot ``i``'s prompt from position ``cs``.  The
        final one flips the slot live: its sampled token goes into the
        slot's row on the device, so the slot joins the tick of this same
        pass, and the host reads the token (and, behind it, what the
        experts computed over the request's chunks) with that tick's.
        Returns the pages the slot's window gave back for this chunk."""
        C = self.prefill_chunk
        n = s.pf_n
        freed = 0
        with tracer.span("serve.chunk.gather", cat="engine",
                         adapter_row=s.adapter_row):
            lora = (self.registry.lora_for_row(s.adapter_row)
                    if self.registry is not None else None)
        with tracer.span("serve.chunk.dispatch", cat="engine"):
            self._sync_rows()       # the chunk reads its slot's row
            if final:
                # what _emit will say of the first token, eos apart: the
                # steps that budget and buffer end leave the ticks
                s.steps = min(s.remaining - 1, self.buf_len - n) \
                    if s.remaining > 0 else 0
            chunk = np.zeros(self._chunk_words, np.int32)
            seg = s.pf_ids[cs:cs + C]
            chunk[:len(seg)] = seg
            # sample index is traced: intermediate chunks discard token 0,
            # the final chunk samples at the prompt's last position
            chunk[C:C + 5] = (cs, max(n - 1 - cs, 0) if final else 0, i, n,
                              s.steps if final else -1)
            words = C + 5 + s.pf_sub.size
            chunk[C + 5:words] = s.pf_sub.view(np.int32)
            if self.window_pool is not None:
                # the table as this chunk finds it rides its upload
                _, freed = self._slide_window(i, s, cs)
                chunk[words:] = self._wtabs[i]
            chunk = jax.device_put(chunk)
            if self._programs["chunk"] is None:
                self._register_program(
                    "chunk", self._chunk, self.raw_params, lora,
                    self._pool, self._dev, chunk, s.pf_acc)
            # the page pool is engine-thread-confined like the other
            # decode state (see _stage_row); step_programs reads it at rest
            # fedrace: disable-next-line=unguarded-shared-write
            tok, self._pool, self._dev = self._chunk(
                self.raw_params, lora, self._pool, self._dev, chunk,
                s.pf_acc)
        with self._stats_lock:
            self._chunks_total += 1
        if not final:
            s.pf_next = cs + C
            if s.pf_acc is not None:
                s.pf_acc = tok
            return freed
        self._firsts.append((i, s.q, tok))
        s.pf_acc = None
        s.prefilling = False
        s.live = True
        s.pos = s.dpos = n
        s.t_prefill_end = time.monotonic()
        if self.prefix_cache is not None and n > 0:
            fullpages = n // self.kv_page_tokens
            if fullpages:
                # the cache object is internally locked; the reference
                # itself is set once in the ctor and never rebound
                # fedrace: disable-next-line=unguarded-shared-write
                self.prefix_cache.insert(
                    s.pf_ids,
                    [int(p) for p in self._btabs[i, :fullpages]],
                    self.raw_params, s.pf_atok)
        s.pf_ids = None
        s.pf_sub = None
        return freed

    def _admit_one(self, req: dict, slot: int, tracer) -> bool:
        """Admission front door: cache-mode adapter pin (deferred from
        submit) + page reservation, then the real admit.  Returns False
        when the request parked (adapter page-in in flight / pool dry) or
        failed open — the slot stays free."""
        try:
            if (self._store_mode and req.get("adapter") is not None
                    and req.get("adapter_token") is None):
                row, atok = self.registry.acquire(req["adapter"])
                req["adapter_row"], req["adapter_token"] = row, atok
            self._reserve_pages(req, slot)
        except AdapterMissError:
            req["_park_reason"] = "adapter"
            self._parked.append(req)
            return False
        except PageExhaustedError:
            # drop a just-taken pin so the row isn't held while parked
            if self._store_mode and req.get("adapter_row"):
                self.registry.release(req["adapter_row"])
                req["adapter_row"], req["adapter_token"] = 0, None
            req["_park_reason"] = "pages"
            self._parked.append(req)
            return False
        except (_UnservableError, KeyError, RuntimeError):
            # unservable reservation, adapter evicted between submit and
            # admission, or a fetch failure re-raised from take(): fail
            # this request open, keep the engine alive
            if self._store_mode and req.get("adapter_row"):
                self.registry.release(req["adapter_row"])
            req["q"].put(None)
            return False
        with tracer.span("serve.admit", cat="serve", slot=slot,
                         adapter_row=req.get("adapter_row", 0),
                         request=req.get("request")):
            self._admit(req, slot)
        with self._stats_lock:
            self.serve_stats["admits"] += 1
        return True

    def _parked_actionable(self) -> bool:
        """Caller holds ``_cond``: is a parked retry worth waking for?
        Page-parked requests retry whenever pages may have freed (any
        finish notifies); adapter-parked ones only once a fetch landed."""
        if not self._parked:
            return False
        if self._fetch_ready or self._pin_released:
            return True
        return any(r.get("_park_reason") == "pages" for r in self._parked)

    def kv_stats(self) -> Dict[str, Any]:
        """Host-side memory-plane stats (bench + tests): pool occupancy,
        chunk counts, prefix page-sharing, adapter cache counters."""
        with self._stats_lock:
            # ticks launched while the one before was still unread, lanes
            # that ran for a slot eos had already ended, and times the
            # loop read back with nothing to launch (drain, stop, swap)
            out: Dict[str, Any] = {"ticks": self._ticks,
                                   "ticks_ahead": self._ticks_ahead,
                                   "lanes_burned": self._lanes_burned,
                                   "flushes": self._flushes}
            chunks = self._chunks_total
            shared, private = self._pages_shared, self._pages_private
            pairs, hit = self._expert_pairs, self._experts_hit
            tiles = self._expert_tiles
            layers = self._moe_layers_ticked
            freed_early = self._window_pages_freed
            attn_pages = self._attn_pages
        out["pool"] = dict(self.page_pool.stats)
        out["pages_free"] = self.page_pool.pages_free
        out["pool_pages"] = self.page_pool.n_pages
        out["prefill_chunks"] = chunks
        out["pages_shared"] = shared
        out["pages_private"] = private
        out["kv_bytes_per_token"] = self._kv_bytes_per_token
        if self._stateful:
            # state that is not pages: every layer's buffer, whole, and its
            # rows (a row a slot and the trash row)
            out["state_bytes"] = self._state_bytes
            out["state_rows"] = self.n_slots + 1
        if self.window_pool is not None:
            # the window layers' pool beside the full layers' (``pool``):
            # reserved, released and refused (``exhausted``) of its own,
            # and the pages that went back behind a window, early
            out["window_pool"] = dict(self.window_pool.stats)
            out["window_pages_free"] = self.window_pool.pages_free
            out["window_pool_pages"] = self.window_pool.n_pages
            out["window_blocks"] = self.window_blocks
            out["window_pages_freed"] = freed_early
        if self._counts_attn_pages:
            # pages the reads' kernel visited over all layers, in ticks and
            # chunks; 0 where the reads ran in ``jnp``
            out["attn_pages"] = attn_pages
        out["expert_pairs"] = pairs
        out["experts_hit"] = hit
        out["expert_tiles"] = tiles
        out["moe_layers_ticked"] = layers
        if self.prefix_cache is not None:
            out["prefix"] = dict(self.prefix_cache.stats)
        if self.registry is not None:
            out["adapter"] = dict(self.registry.stats)
        return out

    def _drain_waiting(self):
        """Fail-open every queued AND parked request (caller holds
        ``_cond``), dropping adapter pins so evicted rows can still
        reclaim."""
        while not self._waiting.empty():
            req = self._waiting.get()
            req["q"].put(None)
            if self.registry is not None and req.get("adapter_row"):
                self.registry.release(req["adapter_row"])
        for req in self._parked:
            req["q"].put(None)
            if self.registry is not None and req.get("adapter_row"):
                self.registry.release(req["adapter_row"])
        self._parked.clear()

    def _iter_args(self, tracer) -> Dict[str, int]:
        """The ``serve.iter`` span's args; counted only when tracing."""
        if not tracer.enabled:
            return {}
        return {"iter": self._iters,
                "live": sum(s.live for s in self._slots),
                "prefilling": sum(s.prefilling for s in self._slots),
                "queued": self._waiting.qsize() + len(self._parked)}

    def _busy(self) -> bool:
        """A request holds a slot, or a dispatch is still unread (its
        lanes may all belong to requests eos has ended since)."""
        return (self._unread is not None or bool(self._firsts)
                or any(s.live or s.prefilling for s in self._slots))

    def _run(self):
        try:
            self._run_loop()
        except Exception:  # noqa: BLE001 — a dead engine must not hang HTTP
            logging.getLogger(__name__).exception(
                "continuous-batching engine crashed; failing open")
            with self._cond:  # excludes concurrent submit() puts
                self._stopped = True
                try:        # what the device had produced still goes out
                    self._flush()
                except Exception:  # noqa: BLE001 — the device may be the cause
                    logging.getLogger(__name__).exception(
                        "the outstanding read-back was lost")
                for i, s in enumerate(self._slots):
                    if s.live:
                        self._finish(i, aborted=True)
                self._drain_waiting()
                self._cond.notify_all()  # wake update_params waiters

    def _run_loop(self):
        while True:
            with self._cond:
                while (not self._stopped and self._waiting.empty()
                       and self._pending_params is None
                       and not self._busy()
                       and not self._parked_actionable()):
                    with get_tracer().span("serve.wait", cat="engine"):
                        self._cond.wait(timeout=0.5)
                if self._stopped:
                    self._flush()   # every token already produced goes out
                    for i, s in enumerate(self._slots):
                        if s.live or s.prefilling:
                            self._finish(i, aborted=True)
                    self._drain_waiting()
                    self._cond.notify_all()
                    return
                # apply a staged weight swap once in-flight slots drain
                # (prefilling counts — its KV is half-written under the
                # old weights — and so does an unread dispatch: the pass
                # that finds nothing to launch has flushed it by then);
                # the prefix cache clears atomically with it
                # (its old entries are keyed by the old params identity
                # anyway — clearing frees the old tree + stale KV eagerly)
                swap_pending = self._pending_params is not None
                if swap_pending and not self._busy():
                    # raw_params is swapped only here on the engine thread
                    # (update_params merely STAGES via _pending_params under
                    # _cond); all other raw_params uses are engine-thread
                    # dispatch reads, so the write needs no extra guard
                    # fedrace: disable-next-line=unguarded-shared-write
                    self.raw_params = self._pending_params
                    self._pending_params = None
                    if self.prefix_cache is not None:
                        self.prefix_cache.clear()
                    swap_pending = False
                    self._cond.notify_all()
                retry_parked = bool(self._parked) and not swap_pending
                if retry_parked:
                    self._fetch_ready = False
                    self._pin_released = False

            # admit into free slots (token-granularity join) — paused
            # while a swap waits for the drain, so no request straddles
            # the weight boundary.  Parked requests retry first (their
            # adapter may have paged in / pages may have freed); a parked
            # head never blocks fresh admissions behind it — _admit_one
            # re-parks and the loop moves on.
            tracer = get_tracer()
            # one live span tree per pass (admit, chunk, tick): what this
            # thread is doing, on the clock a profiler trace ties to
            self._iters += 1
            with tracer.span("serve.iter", cat="engine",
                             **self._iter_args(tracer)):
                worked = self._iterate(tracer, swap_pending, retry_parked)
            if worked and tracer.enabled:
                self._gauges(tracer)

    def _iterate(self, tracer, swap_pending: bool,
                 retry_parked: bool) -> bool:
        """One pass of the loop outside ``_cond``: admissions, prefill
        chunks, then a tick or the flush of the outstanding one.  False
        when there was nothing to launch, read or prefill."""
        if retry_parked:
            retry, self._parked = self._parked, []
            for j, req in enumerate(retry):
                slot = self._free_slot()
                if slot is None:
                    self._parked.extend(retry[j:])
                    break
                self._admit_one(req, slot, tracer)
        while not swap_pending and not self._waiting.empty():
            slot = self._free_slot()
            if slot is None:
                break
            req = self._waiting.get()
            self._admit_one(req, slot, tracer)
        if tracer.enabled:
            tracer.counter("serve.queue_depth",
                           self._waiting.qsize() + len(self._parked))

        self._prefill_tick()
        # who is in the next tick is decided without the last one's
        # tokens: a slot whose budget or buffer ends with a tick already
        # launched has no steps left and waits, live, for the read-back
        # that finishes it
        live = [i for i, s in enumerate(self._slots)
                if s.live and s.steps > 0]
        if live:
            self._dispatch(live)
            with self._stats_lock:
                self._ticks += 1
        elif self._unread is not None or self._firsts:
            self._flush()
        else:
            return any(s.prefilling for s in self._slots)
        return True

    def _gauges(self, tracer) -> None:
        """The serving gauges, once a pass, when tracing."""
        now = time.monotonic()
        rolled = None
        with self._stats_lock:
            t0, ntok = self._tok_window
            if now - t0 >= 0.5:
                rolled = (ntok, self.serve_stats["tokens"])
                self._tok_window = [now, 0]
        if rolled is not None:   # counter emits outside _stats_lock
            tracer.counter("serve.tokens_per_s", rolled[0] / (now - t0))
            tracer.counter("serve.tokens_total", rolled[1])
        with self._stats_lock:
            shared = self._pages_shared
            tot = shared + self._pages_private
            chunks = self._chunks_total
        tracer.counter("serve.kv_pages_free", self.page_pool.pages_free)
        if self.window_pool is not None:
            tracer.counter("serve.kv_pages_free.full",
                           self.page_pool.pages_free)
            tracer.counter("serve.kv_pages_free.window",
                           self.window_pool.pages_free)
        tracer.counter("serve.kv_page_hit_rate",
                       shared / tot if tot else 0.0)
        tracer.counter("serve.prefill_chunks", chunks)
        tracer.counter("serve.kv_bytes_per_token",
                       self._kv_bytes_per_token)
        if self._stateful:
            tracer.counter("serve.state_bytes", self._state_bytes)
        if self._moe_layers:
            tracer.counter("serve.expert_load_max",
                           self._expert_load_max)
        if self._store_mode:
            st = self.registry.stats
            tracer.counter("serve.adapter_cache_hits", st["cache_hits"])
            tracer.counter("serve.adapter_cache_misses", st["cache_misses"])
            tracer.counter("serve.adapter_cache_evictions",
                           st["cache_evictions"])
            tot = st["cache_hits"] + st["cache_misses"]
            tracer.counter("serve.adapter_miss_rate",
                           st["cache_misses"] / tot if tot else 0.0)

    def _tick_span(self, tracer, live, tracing: bool):
        """The ``serve.tick`` span ``_dispatch`` opens; what needs a sum
        over the slots is summed only when tracing."""
        args = {}
        if self._stateful and tracing:
            # rows of state the tick writes back (its live lanes), and the
            # slots between two chunks of a prompt, whose rows it leaves
            # alone (their lanes address the trash row)
            args = {"state_rows": len(live), "state_held": sum(
                s.prefilling and s.pf_next > 0 for s in self._slots)}
            if self._linear_row_bytes:
                # the linear-attention layers' rows the tick writes back
                args["linear_state_bytes"] = len(live) * self._linear_row_bytes
        return tracer.span(
            "serve.tick", cat="engine", live=len(live),
            live_kv_tokens=(sum(self._slots[i].pos for i in live)
                            if tracing else None), **args)

    def _dispatch(self, live):
        """One device tick for the slots with a lane in it, launched
        before the tick before it is read: ``stage`` and ``dispatch`` put
        tick k on the device's queue,
        then ``readback``, ``emit`` and ``free`` serve dispatch k-1 (its
        tokens and the first tokens of its pass's final chunks) while the
        device runs k.  The slot state stays on the device from program to
        program; a pass that changed no slot uploads nothing."""
        tracer = get_tracer()
        tracing = tracer.enabled
        ahead = int(self._unread is not None)
        with self._tick_span(tracer, live, tracing) as tick:
            with tracer.span("serve.tick.stage", cat="engine"):
                if self.window_pool is not None:
                    self._slide_lanes(tick, live)
                if self._counts_attn_pages:
                    self._advance_lanes(tick, live)
                self._sync_rows()
            with tracer.span("serve.tick.dispatch", cat="engine"):
                if self.registry is not None:
                    # snapshot + dispatch under the registry lock so a
                    # concurrent register()'s donated row write cannot
                    # invalidate the bank buffer between the read and the
                    # launch (the dispatch itself is async and fast;
                    # registration is the rare path)
                    with self.registry.lock:
                        if self._programs["step"] is None:
                            self._register_program(
                                "step", self._step, self.raw_params,
                                self.registry.bank, self._pool, self._dev)
                        toks, self._pool, self._dev = self._step(
                            self.raw_params, self.registry.bank,
                            self._pool, self._dev)
                else:
                    if self._programs["step"] is None:
                        self._register_program(
                            "step", self._step, self.raw_params,
                            self._pool, self._dev)
                    toks, self._pool, self._dev = self._step(
                        self.raw_params, self._pool, self._dev)
                lanes = []
                for i in live:
                    s = self._slots[i]
                    s.steps = max(s.steps - self.horizon, 0)
                    lanes.append((i, s.q))
                rec, self._unread = self._unread, [toks, lanes, self._firsts]
                self._firsts = []
                del toks
            with self._stats_lock:
                self._ticks_ahead += ahead
            tick.set(ahead=ahead)
            self._collect(tracer, tick, rec)

    def _slide_lanes(self, tick, live) -> None:
        """Before a tick is dispatched: every lane's window table slid to
        the position the tick writes, the changed tables staged, and on the
        span what one layer of each kind holds for the tick's lanes once it
        has written: everything up to the position, and what the window's
        pages do."""
        ptok = self.kv_page_tokens
        full = held = freed = 0
        for i in live:
            s = self._slots[i]
            changed, behind = self._slide_window(i, s, s.dpos)
            if changed:
                self._stage_table(i)
            freed += behind
            full += s.dpos + 1
            held += s.dpos + 1 - s.w_first * ptok
        tick.set(window_pages_freed=freed, live_full_tokens=full,
                 live_window_tokens=held)

    def _advance_lanes(self, tick, live) -> None:
        """Before a tick is dispatched: on the span the pages its reads
        visit where they are a kernel's, and every lane's ``dpos`` moved to
        what the next tick writes."""
        pos = np.array([self._slots[i].dpos for i in live],
                       np.int64).reshape(-1, 1)
        tick.set(attn_pages=sum(self._count_attn_pages("tick", pos + step)
                                for step in range(self.horizon)))
        for i in live:
            self._slots[i].dpos += self.horizon

    def _flush(self) -> None:
        """Read back what is outstanding when there is nothing to launch
        over it: the last dispatch of a drain (before the loop waits, a
        staged swap is applied, or the engine stops) and the first tokens
        of final chunks that no tick followed."""
        rec, self._unread = self._unread, None
        if self._firsts:
            rec = rec or [None, [], []]
            rec[2] = rec[2] + self._firsts
            self._firsts = []
        if rec is None:
            return
        with self._stats_lock:    # before a caller has its last token
            self._flushes += 1
        tracer = get_tracer()
        with tracer.span("serve.flush", cat="engine") as span:
            self._collect(tracer, span, rec)

    def _collect(self, tracer, span, rec: Optional[list]) -> None:
        """The ``readback``, ``emit`` and ``free`` phases over one
        dispatch's record (None: nothing was outstanding), under the tick
        or the flush that is open as ``span``.  Delivery is ``_emit``'s
        for every token; a lane whose request eos ended after the launch
        ran for nothing and is counted as burned."""
        toks, lanes, firsts = rec or (None, (), ())
        with tracer.span("serve.tick.readback", cat="engine"):
            # one transfer: (n_slots, horizon) tokens with, behind them,
            # the experts' counters where the model has sparse layers; and
            # the final chunks' first tokens, which ran before the tick
            got, first_got = jax.device_get(
                (toks, [t for _, _, t in firsts])) if rec else (None, [])
        flat = got.reshape(-1) if got is not None else ()
        first_got = [out.reshape(-1) for out in first_got]
        counters = flat[self.n_slots * self.horizon:]
        # a request's first token brings what the experts computed over
        # its prompt's chunks
        prefill_pairs = sum(int(out[1]) for out in first_got if out.size > 1)
        prefill_tiles = sum(int(out[4]) for out in first_got if out.size > 1)
        pairs = hit = tiles = ticked = 0
        if len(counters):
            pairs, hit, most, tiles = (int(c) for c in counters)
            ticked = self._moe_layers * self.horizon
            self._expert_load_max = most  # fedrace: disable=unguarded-shared-write
        if ticked or prefill_pairs:
            # counted before a token goes out, so that a caller who has
            # its last token finds the tick that made it in kv_stats()
            with self._stats_lock:
                self._expert_pairs += pairs + prefill_pairs
                self._expert_tiles += tiles + prefill_tiles
                self._experts_hit += hit
                self._moe_layers_ticked += ticked
        with tracer.span("serve.tick.emit", cat="engine") as emit:
            finished = tokens = burned = 0
            for (i, q, _), out in zip(firsts, first_got):
                if self._slots[i].q is q and not self._emit(i, int(out[0])):
                    self._finish(i)
                    finished += 1
            for i, q in lanes:
                s = self._slots[i]
                if s.q is not q:
                    burned += 1
                    continue
                had = s.out_tokens
                for tok in flat[i * self.horizon:(i + 1) * self.horizon]:
                    s.pos += 1
                    if not self._emit(i, int(tok)):
                        self._finish(i)
                        finished += 1
                        break
                tokens += s.out_tokens - had
            emit.set(finished=finished)
        if burned:
            with self._stats_lock:
                self._lanes_burned += burned
        with tracer.span("serve.tick.free", cat="engine"):
            # the record's device arrays go here, by name, and not at the
            # return: the first one freed after its program has run blocks
            # for milliseconds on the TPU runtime
            if rec:
                rec.clear()
            del toks, firsts
        if tracer.enabled:
            # ``tokens`` are the ticks' own: a request's first is counted
            # apart, with its prompt's pairs where the model has experts
            args = {"tokens": tokens, "burned": burned}
            if ticked:
                args.update(expert_pairs=pairs, experts_hit=hit,
                            expert_load_max=most, expert_tiles=tiles)
            if first_got:
                args["first_tokens"] = len(first_got)
                if first_got[0].size > 1:
                    args["prefill_expert_pairs"] = prefill_pairs
            span.set(**args)
