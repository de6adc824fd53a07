"""Fused attention for the FedLLM path.

The reference delegates long-sequence attention wholesale to HF flash-attn
monkey-patches (``train/llm/models/attention.py:30``) — nothing in-repo.
Here attention is first-class (SURVEY §5 "long-context" requirement):

- :func:`blockwise_attention` — streaming-softmax attention as a
  ``lax.scan`` over KV blocks.  O(S·block) memory, differentiable by XLA
  autodiff, runs on any backend.  This is the semantic reference.
- :func:`flash_attention` — Pallas TPU kernel forward (VMEM-tiled, MXU
  matmuls, running max/sum in scratch) with a ``custom_vjp`` whose backward
  is the blockwise implementation's VJP — identical math, no S×S
  materialization on either pass.
- :func:`ring_attention` (``ring_attention.py``) — sequence parallelism over
  the mesh ``seq`` axis: KV shards rotate around the ICI ring via
  ``ppermute`` while each device's queries accumulate streaming softmax.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp

log = logging.getLogger(__name__)

NEG_INF = -1e30


def _block_scores(q, k, sm_scale):
    # preferred_element_type keeps the MXU's f32 accumulation instead of
    # rounding the dot back to bf16 — round-3 root cause of the TPU-bf16
    # gradient NaN (a bf16 score matrix through the transposed scan NaNs)
    return jnp.einsum("...qd,...kd->...qk", q, k,
                      preferred_element_type=jnp.float32) * sm_scale


def blockwise_attention(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        block_k: int = 256, q_positions=None,
                        window: int = 0):
    """Streaming-softmax attention.

    q, k: (..., S, D); v: (..., S, Dv), Dv = D unless the values have a
    width of their own (latent attention: q/k 192, v 128).  Scans KV in
    blocks of ``block_k``, carrying the running max m, normalizer l, and
    unnormalized accumulator — the flash attention recurrence expressed in
    XLA.

    GQA: 4-D inputs where k/v carry fewer heads than q are handled by
    broadcasting a grouped view — no repeated-KV materialization.

    ``q_positions`` (broadcastable to q's ``(..., S)``): the position of
    every query row among the keys, where the queries are not the keys'
    own first S rows (a prefill chunk over a cache window); the causal
    mask is then ``key index <= position``.

    ``window`` > 0 (causal only): a query at position i sees key j iff
    ``0 <= i - j < window``.
    """
    if (q.ndim == 4 and k.ndim == 4 and k.shape[1] != q.shape[1]):
        b, h, s_q_, d_ = q.shape
        h_kv = k.shape[1]
        assert h % h_kv == 0, (h, h_kv)
        rep = h // h_kv
        qg = q.reshape(b, h_kv, rep, s_q_, d_)
        if q_positions is not None:
            q_positions = jnp.broadcast_to(
                q_positions, (b, h, s_q_)).reshape(b, h_kv, rep, s_q_)
        out = blockwise_attention(qg, k[:, :, None], v[:, :, None],
                                  causal=causal, sm_scale=sm_scale,
                                  block_k=block_k, q_positions=q_positions,
                                  window=window)
        return out.reshape(b, h, s_q_, v.shape[-1])
    if window and not causal:
        raise ValueError("a window is defined for causal attention only")
    *lead, s_q, d = q.shape
    s_k, d_v = k.shape[-2], v.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    block_k = min(block_k, s_k)
    n_blocks = -(-s_k // block_k)
    pad = n_blocks * block_k - s_k
    if pad:
        kp = jnp.pad(k, [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)])
        vp = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(0, pad), (0, 0)])
    else:
        kp, vp = k, v
    # reshape by K's OWN leading dims (grouped-query calls pass a size-1
    # group axis that broadcasts against q's rep axis)
    klead = kp.shape[:-2]
    kb = kp.reshape(*klead, n_blocks, block_k, d)
    vb = vp.reshape(*klead, n_blocks, block_k, d_v)
    # move block axis to front for scan
    perm = (len(lead),) + tuple(range(len(lead))) + (len(lead) + 1, len(lead) + 2)
    kb = jnp.transpose(kb, perm)
    vb = jnp.transpose(vb, perm)

    q_pos = jnp.arange(s_q) if q_positions is None else q_positions

    def body(carry, inp):
        m, l, acc, blk = carry[0], carry[1], carry[2], carry[3]
        kblk, vblk = inp
        scores = _block_scores(q, kblk, sm_scale)          # (..., s_q, block_k)
        kv_pos = blk * block_k + jnp.arange(block_k)
        valid = kv_pos < s_k
        if causal:
            valid = valid[None, :] & (kv_pos[None, :] <= q_pos[..., None])
            if window:
                valid = valid & (q_pos[..., None] - kv_pos[None, :] < window)
            scores = jnp.where(valid, scores, NEG_INF)
        else:
            scores = jnp.where(valid, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        # a block that lies wholly behind a row's window leaves its running
        # max at NEG_INF: the row's own key, in a later block, is what
        # wipes the ones counted here (alpha = 0)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "...qk,...kd->...qd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new, blk + 1), None

    m0 = jnp.full((*lead, s_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((*lead, s_q), jnp.float32)
    acc0 = jnp.zeros((*lead, s_q, d_v), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, acc0, 0), (kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


# -- Pallas TPU forward kernel ------------------------------------------------
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_ref, l_ref, acc_ref, *,
                      block_q: int, block_k: int, sm_scale: float,
                      causal: bool, seq_k: int, window: int = 0):
    """Grid: (batch*heads, q_blocks, k_blocks); k innermost ("arbitrary").
    Scratch m/l/acc persist across the k dimension for one (bh, qi) pair.
    Also emits the per-row logsumexp (m + log l) for the backward pass.

    Layout note (Mosaic): per-row stats are kept 2-D ``(block_q, 1)`` and the
    lse output is ``(bh, s_q, 1)`` blocked ``(1, block_q, 1)`` — a block's
    last two dims must be (divisible by 8, divisible by 128) or equal the
    array dims, so a flat ``(bh, s_q)`` lse with ``(1, block_q)`` blocks does
    not lower on real TPUs (interpret mode never enforces this)."""
    import jax.experimental.pallas as pl

    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[:] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[:] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    # causal: a KV block strictly below the diagonal band is fully masked —
    # skip its matmuls entirely (halves the work for causal attention)
    if causal:
        live = kj * block_k <= qi * block_q + block_q - 1
        if window:      # nor does one wholly behind the block's first window
            live &= kj * block_k + block_k - 1 > qi * block_q - window
    else:
        live = kj >= 0

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                # (block_q, d)
        # OOB rows of a partially-out-of-bounds block are undefined (NaN in
        # interpret mode): zero them, else 0·NaN poisons the contractions
        kv_rows = (kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        k = jnp.where(kv_rows, k_ref[0], 0.0)       # (block_k, d)
        v = jnp.where(kv_rows, v_ref[0], 0.0)
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kv_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kv_pos < seq_k
        if causal:
            mask = mask & (kv_pos <= q_pos)
            if window:
                mask = mask & (q_pos - kv_pos < window)
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[:]                           # (block_q, 1)
        m_new = jnp.maximum(m_prev,
                            jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:], 1e-30)                # (block_q, 1)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _kv_head_map(b: int, h: int, h_kv: int):
    """Program-id → KV-row mapping for grouped-query attention: q head
    ``h_q`` reads kv head ``h_q // (h // h_kv)`` — the kernel never
    materializes repeated KV (the ``jnp.repeat`` the naive path needs
    costs h/h_kv × KV HBM traffic)."""
    rep = h // h_kv

    def kv_row(bh):
        return (bh // h) * h_kv + (bh % h) // rep

    return kv_row


# Tiles from a tools/tpu_flash_tune.py sweep on TPU v5e.  Keyed by
# (seq_k, head_dim); callers that pass explicit blocks bypass the table.
#
# AUTOTUNE-OR-FALLBACK POLICY: an entry is a shape where a sweep timed the
# Pallas forward + dq pass + dK/dV pass, at the entered tile, faster than
# the blockwise scan's forward + backward (what a training step runs of
# either), and the tile is the one with the least such total.
# ``flash_attention`` uses Pallas only for tuned shapes; untuned shapes take
# the blockwise path.  Which of the two a traced call took is logged at
# trace time (``_note_impl``), so "flash" never quietly means "blockwise".
# Override with env FEDML_TPU_FLASH_MODE = "force" (always Pallas; an error
# off the TPU) | "off" (always blockwise) | "auto" (default policy).
_TUNED_BLOCKS = {
    (1024, 64): (256, 1024),
    # b8 h32 kv8 s1024 d128 (fedlora-round.mistral-7b-d12's round), TPU v5
    # lite: forward + dq + dK/dV 4.27 ms a call (forward 0.98) against the
    # scan's forward + backward 35.95 ms (forward 6.43); (512, 512) 5.97 ms
    (1024, 128): (1024, 1024),
}
# untuned shapes keep the round-2 tile — only measured shapes change
_DEFAULT_BLOCKS = (512, 512)


def register_tuned_blocks(seq_k: int, head_dim: int,
                          block_q: int, block_k: int) -> None:
    """Record a measured-faster tile for (seq_k, head_dim).  Shapes already
    traced under jit keep their compiled choice; new traces see the entry."""
    _TUNED_BLOCKS[(int(seq_k), int(head_dim))] = (int(block_q), int(block_k))


def load_tuned_blocks(path: str) -> int:
    """Merge tuned tiles from a tools/tpu_flash_tune.py artifact (the file
    may contain progress lines; the JSON payload is the last '{' line).
    Only entries whose sweep measured flash >= blockwise are registered —
    losing shapes stay on the fallback path.  Returns entries added."""
    import json as _json
    if not os.path.exists(path):
        return 0
    # the tune tool is resumable per shape index, so an appended log can
    # hold MULTIPLE payload lines — merge results from all of them
    results = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                try:
                    payload = _json.loads(line)
                except ValueError:
                    continue
                results.extend(payload.get("results") or [])
    added = 0
    for res in results:
        best = res.get("best")
        if not best or best.get("vs_blockwise", 0) < 1.0:
            continue
        # shape key format: b{b}_h{h}_kv{kv}_s{s}_d{d}
        try:
            toks = res["shape"].split("_")
            s = int([t for t in toks if t.startswith("s")][0][1:])
            d = int([t for t in toks if t.startswith("d")][0][1:])
        except (IndexError, ValueError):
            continue
        register_tuned_blocks(s, d, best["bq"], best["bk"])
        added += 1
    return added


def _pick_blocks(s_k: int, d: int, block_q, block_k):
    tq, tk = _TUNED_BLOCKS.get((s_k, d), _DEFAULT_BLOCKS)
    return (tq if block_q is None else block_q,
            tk if block_k is None else block_k)


def flash_attention_fwd_pallas(q, k, v, causal: bool = True,
                               sm_scale: Optional[float] = None,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               return_lse: bool = False,
                               interpret: bool = False, window: int = 0):
    """q: (B, H, S, D); k, v: (B, H_kv, S, D) with H_kv | H (GQA served by
    index-mapping, no KV repeat) → (B, H, S, D) [+ logsumexp (B, H, S)].
    ``window`` > 0: key j is visible to query i iff ``0 <= i - j < window``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_q, d = q.shape
    h_kv = k.shape[1]
    assert h % h_kv == 0, (h, h_kv)
    s_k = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    block_q, block_k = _pick_blocks(s_k, d, block_q, block_k)
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    qr = q.reshape(b * h, s_q, d)
    kr = k.reshape(b * h_kv, s_k, d)
    vr = v.reshape(b * h_kv, s_k, d)
    nq = -(-s_q // block_q)
    nk = -(-s_k // block_k)
    kv_row = _kv_head_map(b, h, h_kv)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        sm_scale=float(sm_scale), causal=causal, seq_k=s_k,
        window=int(window))

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kj: (kv_row(bh), kj, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kj: (kv_row(bh), kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    out = out.reshape(b, h, s_q, d)
    if return_lse:
        return out, lse.reshape(b, h, s_q)
    return out



# -- Pallas TPU backward kernels ---------------------------------------------
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, block_q: int, block_k: int,
                         sm_scale: float, causal: bool, seq_k: int,
                         window: int = 0):
    """dQ pass.  Grid: (bh, q_blocks, k_blocks), k innermost; dq accumulates
    in scratch across k for one (bh, qi)."""
    import jax.experimental.pallas as pl

    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if causal:
        live = kj * block_k <= qi * block_q + block_q - 1
        if window:      # nor does one wholly behind the block's first window
            live &= kj * block_k + block_k - 1 > qi * block_q - window
    else:
        live = kj >= 0

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        kv_rows = (kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        k = jnp.where(kv_rows, k_ref[0], 0.0)
        v = jnp.where(kv_rows, v_ref[0], 0.0)
        do = do_ref[0]
        lse = lse_ref[0]                            # (block_q, 1)
        delta = delta_ref[0]                        # (block_q, 1)
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kv_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kv_pos < seq_k
        if causal:
            mask = mask & (kv_pos <= q_pos)
            if window:
                mask = mask & (q_pos - kv_pos < window)
        p = jnp.where(mask, jnp.exp(scores - lse), 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                          block_k: int, sm_scale: float, causal: bool,
                          seq_k: int, seq_q: int, window: int = 0):
    """dK/dV pass.  Grid: (bh, k_blocks, q_blocks), q innermost; dk/dv
    accumulate in scratch across q for one (bh, kj)."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    kj = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        # q blocks strictly above the diagonal band see none of this k block
        live = qi * block_q + block_q - 1 >= kj * block_k
        if window:      # nor do q blocks wholly past this k block's windows
            live &= qi * block_q - (kj * block_k + block_k - 1) < window
    else:
        live = qi >= 0

    @pl.when(live)
    def _compute():
        q_rows = (qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < seq_q
        q = jnp.where(q_rows, q_ref[0], 0.0)
        do = jnp.where(q_rows, do_ref[0], 0.0)
        lse = jnp.where(q_rows, lse_ref[0], 0.0)    # (block_q, 1)
        delta = jnp.where(q_rows, delta_ref[0], 0.0)
        kv_rows = (kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        k = jnp.where(kv_rows, k_ref[0], 0.0)
        v = jnp.where(kv_rows, v_ref[0], 0.0)
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kv_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # padded q rows (q_pos >= seq_q) would pollute the dk/dv sums with
        # whatever the out-of-bounds q/do/lse blocks contain — mask them
        mask = (kv_pos < seq_k) & (q_pos < seq_q)
        if causal:
            mask = mask & (kv_pos <= q_pos)
            if window:
                mask = mask & (q_pos - kv_pos < window)
        p = jnp.where(mask, jnp.exp(scores - lse), 0.0)
        dv_acc[:] += jnp.dot(p.astype(do.dtype).T, do,
                             preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[:] += jnp.dot(ds.astype(q.dtype).T, q,
                             preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, out, lse, do, causal: bool = True,
                               sm_scale: Optional[float] = None,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               interpret: bool = False, window: int = 0):
    """Flash-attention backward: (dq, dk, dv), no S×S materialization and no
    forward recompute beyond the score blocks (reference capability target:
    the HF flash-attn patch at ``train/llm/models/attention.py:30``).

    GQA: k/v may carry H_kv < H heads (read via index mapping, never
    repeated); dk/dv are computed per q-head then group-summed."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_q, d = q.shape
    h_kv = k.shape[1]
    assert h % h_kv == 0, (h, h_kv)
    s_k = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    block_q, block_k = _pick_blocks(s_k, d, block_q, block_k)
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    qr = q.reshape(b * h, s_q, d)
    kr = k.reshape(b * h_kv, s_k, d)
    vr = v.reshape(b * h_kv, s_k, d)
    dor = do.reshape(b * h, s_q, d)
    lser = lse.reshape(b * h, s_q, 1)
    # delta = rowsum(dO * O) — cheap elementwise, stays in XLA
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * h, s_q, 1)
    nq = -(-s_q // block_q)
    nk = -(-s_k // block_k)
    kv_row = _kv_head_map(b, h, h_kv)

    common = dict(block_q=block_q, block_k=block_k, sm_scale=float(sm_scale),
                  causal=causal, seq_k=s_k, window=int(window))
    common_kv = dict(common, seq_q=s_q)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    k_spec = pl.BlockSpec((1, block_k, d),
                          lambda bh, i, j: (kv_row(bh), j, 0))
    r_spec = pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(b * h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(qr, kr, vr, dor, lser, delta)

    # dkv pass: grid over k blocks, scan q
    qs_spec = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0))
    ks_spec = pl.BlockSpec((1, block_k, d),
                           lambda bh, j, i: (kv_row(bh), j, 0))
    rs_spec = pl.BlockSpec((1, block_q, 1), lambda bh, j, i: (bh, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common_kv),
        grid=(b * h, nk, nq),
        in_specs=[qs_spec, ks_spec, ks_spec, qs_spec, rs_spec, rs_spec],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(qr, kr, vr, dor, lser, delta)
    dq = dq.reshape(b, h, s_q, d)
    dk = dk.reshape(b, h, s_k, d)
    dv = dv.reshape(b, h, s_k, d)
    if h_kv != h:
        rep = h // h_kv
        dk = dk.reshape(b, h_kv, rep, s_k, d).sum(2)
        dv = dv.reshape(b, h_kv, rep, s_k, d).sum(2)
    return dq, dk, dv


# -- public entry with custom vjp --------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None, window: int = 0):
    """Fused attention: Pallas forward + Pallas flash backward on TPU
    (logsumexp saved from the forward, no S×S materialization and no full
    recompute), blockwise-scan semantics + blockwise VJP everywhere else.
    ``window`` > 0 (causal): key j is visible to query i iff
    ``0 <= i - j < window``."""
    return _fa_fwd(q, k, v, causal, sm_scale, window)[0]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(s_k: int, d: int) -> bool:
    """Autotune-or-fallback gate: Pallas only where a sweep measured it
    faster than the blockwise scan (see _TUNED_BLOCKS note)."""
    mode = os.environ.get("FEDML_TPU_FLASH_MODE", "auto")
    if mode == "force":
        if not _on_tpu():
            raise RuntimeError(
                "FEDML_TPU_FLASH_MODE=force asks for the Pallas TPU kernel, "
                f"but this process runs on {jax.default_backend()!r}")
        return True
    if mode == "off":
        return False
    return _on_tpu() and (s_k, d) in _TUNED_BLOCKS


def _note_impl(impl: str, q, k) -> None:
    """One INFO record per TRACED ``flash_attention`` call (this python runs
    only while tracing), ``args = (impl, q.shape, k.shape)`` with ``impl``
    ``"pallas"`` or ``"blockwise"`` — what ``chip_smoke.py`` counts and
    prints for each phase."""
    log.info("attention trace: impl=%s q=%s kv=%s", impl, tuple(q.shape),
             tuple(k.shape))


def _fa_fwd(q, k, v, causal, sm_scale, window=0):
    # the kernels take one head width: values of another take the scan
    if v.shape[-1] == q.shape[-1] and _use_pallas(k.shape[2], k.shape[3]):
        _note_impl("pallas", q, k)
        out, lse = flash_attention_fwd_pallas(q, k, v, causal, sm_scale,
                                              return_lse=True, window=window)
        return out, (q, k, v, out, lse)
    _note_impl("blockwise", q, k)
    out = blockwise_attention(q, k, v, causal, sm_scale, window=window)
    return out, (q, k, v, None, None)


def _fa_bwd(causal, sm_scale, window, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        return flash_attention_bwd_pallas(q, k, v, out, lse, g, causal,
                                          sm_scale, window=window)
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, causal, sm_scale,
                                            window=window),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
