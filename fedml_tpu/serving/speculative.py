"""Speculative (draft-assisted) greedy decoding.

A small draft model proposes ``k`` tokens with cheap cached steps; the
target model verifies all of them in ONE k-token cached forward and accepts
the longest matching prefix plus its own correction token.  Output is
**bit-identical to target-only greedy decode** (verified in tests) — the
draft only changes how many target forwards are spent, not what they
produce.  With an aligned draft, one target forward yields up to ``k``
tokens; on TPU a k-token decode block costs barely more than a 1-token step
(the MXU is idle at s=1), so acceptance rate translates almost directly
into decode speedup.

Cache-correctness argument (why rejected tokens need no rollback): the
decode-mode attention masks every key/value slot at a position greater than
the query's (``llm/model.py::_decode_attend``), so K/V written for rejected
draft tokens are never attended until the decode frontier reaches their
positions again — at which point the verify block of a later round
overwrites them.  Both the target and draft caches self-heal this way.

Reference note: the reference serving stack has no speculative path (its
HF template predates assisted generation); this is a beyond-parity serving
feature. Greedy (temperature 0) only, like early HF assisted generation.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..llm.quantization import dequantize_params, weight_dtype


def _vars(params, lora):
    """Variable dict with an optional "lora" collection — ``None`` is a
    trace-time constant, so adapter-blind callers compile the exact
    pre-lora programs."""
    v = {"params": params}
    if lora is not None:
        v["lora"] = lora
    return v


def propose_block(model, params, cache, sync, slen, fd, m, lora=None):
    """Un-jitted fused draft round: catch-up sync + m-token greedy
    proposal — the single source of truth for the draft-side cache
    position logic, shared by :func:`speculative_generate` (jitted per
    depth) and the vmapped :class:`~.batching.SpeculativeBatchingEngine`.

    ``params`` must already be dequantized.  ``sync``: (Kpad,) canonical
    tokens at positions ``fd..``; only the first ``slen`` are real (the
    padding's speculative writes self-heal — module docstring).  Returns
    ``(d_tokens (m,), cache)``; d_tokens[j] sits at position
    ``fd + slen + j``.
    """
    logits, mut = model.apply(
        {**_vars(params, lora), "cache": cache}, sync[None, :], decode=True,
        start_pos=fd, mutable=["cache"])
    cache = mut["cache"]
    pos = fd + slen - 1                  # last canonical position
    first = jnp.argmax(jax.lax.dynamic_index_in_dim(
        logits[0], slen - 1, axis=0, keepdims=False)).astype(jnp.int32)

    def body(carry, j):
        tok, cache = carry               # tok sits at position pos+j
        lg, mut = model.apply(
            {**_vars(params, lora), "cache": cache}, tok[None, None],
            decode=True, start_pos=pos + j, mutable=["cache"])
        nxt = jnp.argmax(lg[0, 0]).astype(jnp.int32)
        return (nxt, mut["cache"]), nxt

    # m is the host-static draft block length (engine config, never a
    # tracer); the branch just picks the scan-free shape for m == 1
    # fedlint: disable-next-line=recompile-hazard
    if m > 1:
        (_, cache), rest = jax.lax.scan(body, (first, cache),
                                        jnp.arange(1, m))
        return jnp.concatenate([first[None], rest]), cache
    return first[None], cache


def verify_greedy_block(model, params, cache, block, pos, lora=None):
    """Un-jitted target verify: ``block`` (k,) tokens written at positions
    ``pos..pos+k-1``; returns the target's greedy prediction for each next
    position.  ``params`` must already be dequantized."""
    logits, mut = model.apply(
        {**_vars(params, lora), "cache": cache}, block[None, :], decode=True,
        start_pos=pos, mutable=["cache"])
    return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), mut["cache"]


@functools.lru_cache(maxsize=16)
def _build_spec_fns(model):
    # not k-specialized: verify_block handles any block length via jit
    # retracing, so the cache keys on the model alone.  Every function
    # takes ``lora`` as its second argument — a LoRA tree for per-request
    # personalization (traced, so one compiled program serves every
    # adapter of a given shape) or None for adapter-blind decode — the
    # same convention as openai_compat._build_cached_decode.
    wdtype = weight_dtype(model)

    @jax.jit
    def prefill(params, lora, buf, n):
        logits, mut = model.apply(
            _vars(dequantize_params(params, wdtype), lora), buf, decode=True,
            start_pos=jnp.zeros((), jnp.int32), mutable=["cache"])
        live = jax.lax.dynamic_index_in_dim(logits[0], n - 1, axis=0,
                                            keepdims=False)
        return jnp.argmax(live).astype(jnp.int32), mut["cache"]

    @jax.jit
    def step(params, lora, cache, tok, pos):
        logits, mut = model.apply(
            {**_vars(dequantize_params(params, wdtype), lora),
             "cache": cache},
            tok[None, None], decode=True, start_pos=pos, mutable=["cache"])
        return jnp.argmax(logits[0, 0]).astype(jnp.int32), mut["cache"]

    @jax.jit
    def verify_block(params, lora, cache, block, pos):
        return verify_greedy_block(model, dequantize_params(params, wdtype),
                                   cache, block, pos, lora)

    @functools.partial(jax.jit, static_argnames=("m",))
    def propose(params, lora, cache, sync_buf, sync_len, start, m):
        """Fused draft round: catch-up sync + m-token proposal, ONE
        dispatch (body shared with the batched engine: propose_block)."""
        return propose_block(model, dequantize_params(params, wdtype),
                             cache, sync_buf, sync_len, start, m, lora)

    return prefill, step, verify_block, propose


def speculative_generate(model, params, draft_model, draft_params,
                         prompt_ids: List[int], max_new_tokens: int = 64,
                         buf_len: int = 256, k: int = 4,
                         eos_id: Optional[int] = None,
                         on_token=None, adaptive_k: bool = True,
                         lora=None, draft_lora=None
                         ) -> Tuple[List[int], Dict[str, float]]:
    """Greedy decode of ``max_new_tokens`` with draft-model speculation.

    Returns ``(tokens, stats)``; ``stats['target_forwards']`` counts the
    expensive model's invocations and ``stats['acceptance_rate']`` the
    fraction of draft proposals the target agreed with.

    ``adaptive_k`` (default on, the HF assisted-generation heuristic):
    the verify-block size starts at 2 (= 1 draft proposal + the current
    token), doubles toward ``k`` (= up to ``k - 1`` proposals) after a
    fully-accepted round, and halves after a rejection — a misaligned
    draft stops burning draft forwards while an aligned one still reaches
    the full depth.  Output is unaffected (verified: any depth schedule
    yields the target-greedy stream).

    ``lora`` applies a LoRA adapter tree to the TARGET's prefill and
    verify (same argument the cached-decode builders take), so the output
    is bit-identical to ``generate(..., lora=lora)`` at temperature 0 —
    speculative + LoRA serves the adapter, not the base.  ``draft_lora``
    optionally personalizes the draft too; leaving the draft adapter-blind
    only lowers the acceptance rate, never changes output.
    """
    raw = params.get("params", params) if isinstance(params, dict) else params
    draw = draft_params.get("params", draft_params) \
        if isinstance(draft_params, dict) else draft_params
    t_prefill, _, t_verify, _ = _build_spec_fns(model)
    d_prefill, _, _, d_propose = _build_spec_fns(draft_model)

    prompt_ids = list(prompt_ids)[-(buf_len - 1):]
    n = len(prompt_ids)
    buf = np.zeros((1, buf_len), np.int32)
    buf[0, :n] = prompt_ids
    buf_j = jnp.asarray(buf)

    # both models prefill the prompt; target's greedy next-token is the
    # first "cur" (identical to generate()'s prefill output at temp 0)
    cur, t_cache = t_prefill(raw, lora, buf_j, jnp.int32(n))
    _, d_cache = d_prefill(draw, draft_lora, buf_j, jnp.int32(n))
    pos = n
    out: List[int] = []
    f_d = n  # draft CONFIRMED frontier: positions < f_d hold canonical K/V
    stats = {"target_forwards": 1, "draft_forwards": 1,
             "proposed": 0, "accepted": 0}

    def emit(tok: int) -> bool:
        if eos_id is not None and tok == eos_id:
            return False
        if pos_holder[0] >= buf_len or len(out) >= max_new_tokens:
            return False
        out.append(tok)
        if on_token is not None:
            on_token(tok)
        return len(out) < max_new_tokens

    pos_holder = [pos]
    cur = int(cur)
    if not emit(cur):
        return out, _finalize(stats)

    # adaptive depth stays a power of two (capped by k), so the verify
    # block only ever takes ~log2(k) distinct shapes — each novel shape is
    # a fresh XLA compile mid-request, which the schedule must not amplify
    depth = min(2, k) if adaptive_k else k
    while True:
        pos = pos_holder[0]
        block_k = min(depth, k, buf_len - pos)
        if block_k < 1:
            break
        # fused draft round: catch-up sync (every canonical token the draft
        # hasn't confirmed, f_d..pos — speculative writes from earlier
        # rounds are overwritten) + (block_k-1)-token proposal scan, all in
        # ONE device dispatch (the old host loop paid one dispatch per
        # draft token)
        d_tokens = []
        # near the buffer end the fixed (k+1) padded sync would clamp its
        # cache write (dynamic_update_slice) and silently corrupt canonical
        # draft K/V below the frontier — fall back to verify-only rounds
        # for the last few positions instead
        if block_k >= 2 and f_d + k + 1 <= buf_len:
            sync = [(prompt_ids[p] if p < n else out[p - n])
                    for p in range(f_d, pos + 1)]
            assert len(sync) <= k + 1, (len(sync), k)  # f_d trails pos by <= k
            sync_buf = np.zeros(k + 1, np.int32)
            sync_buf[:len(sync)] = sync
            d_jax, d_cache = d_propose(draw, draft_lora, d_cache,
                                       jnp.asarray(sync_buf),
                                       jnp.int32(len(sync)), jnp.int32(f_d),
                                       block_k - 1)
            stats["draft_forwards"] += block_k - 1
            f_d = pos + 1
            d_tokens = [int(t) for t in np.asarray(d_jax)]
        stats["proposed"] += len(d_tokens)
        block_k = len(d_tokens) + 1  # actual block length (guard may skip)

        # one target forward verifies cur + all proposals
        block = jnp.asarray([cur] + d_tokens, jnp.int32)
        greedy, t_cache = t_verify(raw, lora, t_cache, block, jnp.int32(pos))
        stats["target_forwards"] += 1
        greedy_host = np.asarray(greedy)

        done = False
        rejected = False
        for i, d in enumerate(d_tokens):
            g = int(greedy_host[i])
            if d != g:
                # first disagreement: the target's own token replaces it
                rejected = True
                pos_holder[0] = pos + i + 1
                cur = g
                done = not emit(g)
                break
            stats["accepted"] += 1
            pos_holder[0] = pos + i + 1
            if not emit(d):
                done = True
                break
            cur = d
        else:
            # every proposal accepted: the block's last greedy token is the
            # target's continuation of the final draft token
            g = int(greedy_host[block_k - 1])
            pos_holder[0] = pos + block_k
            cur = g
            done = not emit(g)
        if done:
            break
        if adaptive_k:
            depth = max(2, depth // 2) if rejected else \
                (depth * 2 if depth < k else depth)
    return out, _finalize(stats)


def _finalize(stats: Dict[str, int]) -> Dict[str, float]:
    stats = dict(stats)
    stats["acceptance_rate"] = (stats["accepted"] / stats["proposed"]
                                if stats["proposed"] else 0.0)
    return stats


__all__ = ["speculative_generate"]
