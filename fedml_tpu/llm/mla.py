"""Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434 §2.1) beside
``model.py::Attention``: queries through a low-rank pair ``q_a``/``q_b``,
keys and values through one latent ``c_kv`` (``kv_lora_rank`` wide, RMS-
normalised) and one rotary key ``k_r`` (``qk_rope_head_dim`` wide) that all
heads share; ``kv_b`` expands the latent to every head's ``k_nope`` and ``v``.

What is cached is the row ``[c_kv ; k_r]`` — ``kv_lora_rank +
qk_rope_head_dim`` numbers a token and layer, whatever the head count — in ONE
page pool a layer, ``(pool_pages, page_tokens, row)``, written by a row scatter
on the donated buffer as the dense model's K and V pools are
(``model.py::Attention._paged_decode_attend``).  ``row`` is that width rounded
up to the chip's 128 lanes (:func:`pool_row_width`; 576 -> 640, the rest
zeros): an array whose last axis is not a whole number of lane tiles gets a
transposed device layout on the TPU, a token's row is then no contiguous
run, and the compiler copies every pool at the entry and at the exit of every
program (``tests/test_chip_compile.py`` holds both programs to "no op moves a
whole pool").  Row-major tiles would pad 576 to 640 in memory anyway.

Three entries share the projections:

- full sequence (training, the single-request path): the expanded form
  through ``ops/attention`` with q/k ``nope + rope`` wide and v ``v_head_dim``;
- paged, lowered for a TPU with a bfloat16 pool at widths that tile
  (:func:`_read_pool`, ``ops/latent_attention.py::kernel_can_run``): the
  **absorbed** form as one Pallas kernel that reads the pool where it lies,
  each lane's own live pages under a running softmax, for the decode tick
  and the prefill chunk alike (:func:`attend_pool`: ``W_UK`` folded into the
  query before it, ``W_UV`` applied after it, one fetch of a page serving
  key and value);
- paged, everywhere else (the CPU, a float32 model): every lane's whole
  table gathered into a window (:func:`attend_window`), then whichever of two
  ``jnp`` forms of the same mathematics costs fewer operations at the call's
  shapes (:func:`absorbed_is_cheaper`): **absorbed** — the products run over
  the latent itself (the decode tick, one query row a slot) — or **expanded**
  — the window's latent multiplied out to per-head keys and values once (a
  prefill chunk, hundreds of query rows).

``kv_b`` carries no adapter: the absorbed form reads its matrix, not its
product with an input.
"""

from __future__ import annotations

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import latent_attention as la
from ..ops.attention import blockwise_attention, flash_attention
from .model import (LlamaConfig, RMSNorm, _attn_impl, _projection, _rope,
                    yarn_mscale)


def softmax_scale(cfg: LlamaConfig) -> float:
    """``(nope + rope) ** -0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim) ** 2`` where the rotary is scaled."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def latent_width(cfg: LlamaConfig) -> int:
    """The numbers cached a token and layer: ``[c_kv ; k_r]``."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def pool_row_width(cfg: LlamaConfig) -> int:
    """A pool row: the latent, padded with zeros to whole lane tiles."""
    return -(-latent_width(cfg) // 128) * 128


def absorbed_is_cheaper(cfg: LlamaConfig, rows: int) -> bool:
    """``rows`` query rows against one window: absorbed pays
    ``2·rank − nope − v`` more a row, head and window position; expanded
    pays ``rank · (nope + v)`` a head and window position, once."""
    rank, nope, v = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    return rows * (2 * rank - nope - v) <= rank * (nope + v)


def expand(c_kv, k_r, w_kvb, nope: int):
    """The latent multiplied out: ``c_kv`` (b, W, rank) and ``k_r``
    (b, W, rope) -> every head's keys (b, h, W, nope + rope), the rotary
    part shared, and values (b, h, W, v)."""
    kv = jnp.einsum("bwr,rhd->bhwd", c_kv, w_kvb,
                    preferred_element_type=jnp.float32).astype(c_kv.dtype)
    k_r = jnp.broadcast_to(k_r[:, None], kv.shape[:3] + k_r.shape[-1:])
    return jnp.concatenate([kv[..., :nope], k_r], axis=-1), kv[..., nope:]


def absorb_query(q_nope, q_rope, w_uk, width: int, dtype):
    """``W_UK`` folded into the query: ``q_nope`` (b, h, s, nope) and
    ``q_rope`` (b, h, s, rope) -> (b, h, s, width) rows ``[q_nope · W_UK
    (rank) ; q_rope ; zeros]`` in ``dtype``, one product with a pool row:
    the query is zero where the row is."""
    q_lat = jnp.einsum("bhsn,rhn->bhsr", q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(dtype)
    pad = width - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate([q_lat, q_rope.astype(dtype),
                            jnp.zeros(q_lat.shape[:-1] + (pad,), dtype)],
                           axis=-1)


def unabsorb(o_lat, w_uv):
    """``W_UV`` after the weighted sum: ``o_lat`` (b, h, s, rank) ->
    (b, h, s, v)."""
    return jnp.einsum("bhsr,rhv->bhsv", o_lat, w_uv,
                      preferred_element_type=jnp.float32).astype(o_lat.dtype)


def attend_absorbed(q_nope, q_rope, window, w_kvb, pos, scale: float,
                    split: Tuple[int, int]):
    """``q_nope`` (b, h, s, nope), ``q_rope`` (b, h, s, rope), ``window``
    (b, W, row) of rows ``[c_kv (rank) ; k_r (rope) ; zeros]`` by position,
    ``w_kvb`` (rank, h, nope + v), ``pos`` (b, s) the queries' positions
    -> (b, h, s, v)."""
    rank, nope = split
    q = absorb_query(q_nope, q_rope, w_kvb[..., :nope], window.shape[-1],
                     window.dtype)
    scores = jnp.einsum("bhsc,bwc->bhsw", q, window,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(window.shape[1])[None, None, :] <= pos[:, :, None]
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(window.dtype)
    o_lat = jnp.einsum("bhsw,bwr->bhsr", probs, window[..., :rank],
                       preferred_element_type=jnp.float32).astype(window.dtype)
    return unabsorb(o_lat, w_kvb[..., nope:])


def attend_pool(q_nope, q_rope, pool, tables, w_kvb, pos, scale: float,
                split: Tuple[int, int], interpret: bool = False):
    """:func:`attend_absorbed` over the pages ``tables`` (b, entries) names
    in ``pool`` (pages, P, row), read where they lie by the kernel of
    ``ops/latent_attention.py``: each lane's own live pages and no window
    gathered."""
    rank, nope = split
    q = absorb_query(q_nope, q_rope, w_kvb[..., :nope], pool.shape[-1],
                     pool.dtype)
    o_lat = la.latent_attention(q, pool, tables, pos, rank=rank,
                                sm_scale=scale, interpret=interpret)
    return unabsorb(o_lat, w_kvb[..., nope:])


def attend_expanded(q_nope, q_rope, window, w_kvb, pos, scale: float,
                    split: Tuple[int, int]):
    """The same arguments and result as :func:`attend_absorbed`, by the
    window's per-head keys and values and a streaming softmax over them
    (hundreds of query rows against thousands of positions: the scores
    never stand whole)."""
    rank, nope = split
    k, v = expand(window[..., :rank],
                  window[..., rank:rank + q_rope.shape[-1]], w_kvb, nope)
    q = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], axis=-1)
    return blockwise_attention(q.astype(window.dtype), k, v, True, scale,
                               q_positions=pos[:, None, :])


def attend_window(cfg: LlamaConfig, q_nope, q_rope, pool, tables, w_kvb, pos,
                  scale: float):
    """The paged read in plain ``jnp``: every lane's whole table gathered
    into a window (b, entries · P, row), then whichever form costs fewer
    operations at the call's rows (:func:`absorbed_is_cheaper`)."""
    b, s = pos.shape
    window = pool[tables].reshape(b, -1, pool.shape[-1])
    attend = attend_absorbed if absorbed_is_cheaper(cfg, s) \
        else attend_expanded
    return attend(q_nope, q_rope, window, w_kvb, pos, scale,
                  (cfg.kv_lora_rank, cfg.qk_nope_head_dim))


def _read_pool(cfg: LlamaConfig, q_nope, q_rope, pool, tables, w_kvb, pos,
               scale: float):
    """Attention of a call's queries over the pool, the rows of the call
    already written: where the program is lowered for a TPU and the
    operands allow (``ops/latent_attention.py::kernel_can_run``: a bfloat16
    pool, a row and a rank of whole lanes, pages and rows that tile) the
    kernel over each lane's live pages (:func:`attend_pool`); everywhere
    else (the CPU, a float32 model, odd widths) the gathered window
    (:func:`attend_window`)."""
    gathered = functools.partial(attend_window, cfg, scale=scale)
    q = jax.ShapeDtypeStruct(q_nope.shape[:-1] + pool.shape[-1:], pool.dtype)
    if not la.kernel_can_run(q, pool, tables, cfg.kv_lora_rank):
        return gathered(q_nope, q_rope, pool, tables, w_kvb, pos)
    kernel = functools.partial(
        attend_pool, scale=scale,
        split=(cfg.kv_lora_rank, cfg.qk_nope_head_dim))
    return jax.lax.platform_dependent(q_nope, q_rope, pool, tables, w_kvb,
                                      pos, tpu=kernel, default=gathered)


class _Matrix(nn.Module):
    """A bare matrix under ``<name>/kernel``, where ``nn.Dense`` keeps its."""
    shape: Tuple[int, int]
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape, self.param_dtype)


class MLA(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, decode: bool = False,
                 block_tables=None):
        cfg = self.cfg
        h, rank = cfg.n_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        dense = _projection(cfg)
        b, s, _ = x.shape
        c_q = RMSNorm(cfg.norm_eps, name="q_a_norm")(
            dense(cfg.q_lora_rank, "q_a")(x))
        q = dense(h * (nope + rope), "q_b")(c_q)
        q = q.reshape(b, s, h, nope + rope).transpose(0, 2, 1, 3)
        q_nope = q[..., :nope]
        q_rope = _rope(q[..., nope:], positions, cfg.rope_theta,
                       cfg.rope_scaling)
        kv = dense(rank + rope, "kv_a")(x)
        c_kv = RMSNorm(cfg.norm_eps, name="kv_a_norm")(kv[..., :rank])
        k_r = _rope(kv[:, None, :, rank:], positions, cfg.rope_theta,
                    cfg.rope_scaling)[:, 0]                  # (b, s, rope)
        w_kvb = _Matrix((rank, h * (nope + dv)), cfg.store_dtype,
                        name="kv_b")().astype(cfg.dtype)
        w_kvb = w_kvb.reshape(rank, h, nope + dv)
        scale = softmax_scale(cfg)

        if decode:
            if block_tables is None:
                raise NotImplementedError(
                    "latent attention keeps its cache in the paged pool "
                    "only: decode through the batching engine with "
                    "kv_page_tokens > 0 (or run the full sequence)")
            out = self._paged_attend(q_nope, q_rope, c_kv, k_r, w_kvb,
                                     positions, block_tables, scale)
        else:
            impl = _attn_impl(cfg)
            if impl == "ring":
                raise NotImplementedError("ring attention takes one head "
                                          "width; latent attention has two")
            k, v = expand(c_kv, k_r, w_kvb, nope)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            attend = flash_attention if impl == "flash" \
                else blockwise_attention
            out = attend(q, k, v, True, scale)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
        return dense(cfg.dim, "o")(out)

    def _paged_attend(self, q_nope, q_rope, c_kv, k_r, w_kvb, positions,
                      block_tables, scale):
        """Write this call's latent rows into the pool, then attend over
        each slot's block-table window (``kv_pos <= position``), as the
        dense model's paged path does; the trash page and the mask
        discipline are the same."""
        cfg = self.cfg
        ptok, width = cfg.kv_page_tokens, pool_row_width(cfg)
        pool = self.variable("cache", "latent", jnp.zeros,
                             (cfg.kv_pool_pages, ptok, width), cfg.dtype)
        pos = positions.astype(jnp.int32)                       # (b, s)
        page = jnp.take_along_axis(block_tables, pos // ptok, axis=1)
        pad = jnp.zeros(c_kv.shape[:-1] + (width - latent_width(cfg),),
                        c_kv.dtype)
        rows = jnp.concatenate([c_kv, k_r, pad], axis=-1).astype(cfg.dtype)
        pool.value = pool.value.at[page, pos % ptok].set(rows)
        return _read_pool(cfg, q_nope, q_rope, pool.value, block_tables,
                          w_kvb, pos, scale)


__all__ = ["MLA", "attend_absorbed", "attend_expanded", "attend_pool",
           "attend_window", "absorb_query", "unabsorb", "expand",
           "absorbed_is_cheaper", "softmax_scale", "latent_width",
           "pool_row_width"]
