"""Llama-family causal LM in flax — the FedLLM flagship model
(capability target of reference ``python/fedml/train/llm/``: HF +
DeepSpeed fine-tuning, rebuilt TPU-first).

Architecture: RMSNorm, rotary embeddings, grouped-query attention, SwiGLU
MLP — computed in bfloat16 with fp32 accumulations, attention via the fused
ops in :mod:`fedml_tpu.ops` (``blockwise``/``flash``/``ring`` selected by
``attn_impl``; ring requires running inside shard_map with a ``seq`` axis).

Sharding: :func:`param_sharding_rules` maps every parameter to a
PartitionSpec over the canonical mesh — embeddings and FFN sharded on
``model`` (tensor parallel), everything FSDP-sharded on the largest
divisible axis as fallback — the jax/pjit equivalent of the reference's
delegated DeepSpeed ZeRO-3 (``train/llm/distributed.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.mesh import MODEL_AXIS, SEQ_AXIS
from ..models.base import FlaxModel
from ..ops.attention import blockwise_attention, flash_attention
from ..ops.grouped_matmul import swiglu_act


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A published ``rope_scaling`` of type ``yarn`` (arXiv:2309.00071)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


#: what a layer may mix by (``LlamaConfig.layer_types``)
LAYER_KINDS = frozenset({"full_attention", "sliding_attention", "conv",
                         "linear_attention"})
#: the leaves of the ``cache`` collection that are a linear-attention layer's
#: rows of state: its matrix state and its convolution's rows
LINEAR_LEAVES = frozenset({"linear_state", "linear_conv"})
#: the leaves of the ``cache`` collection that are rows of state a slot (no
#: pages): the short convolution's and the linear attention's
STATE_LEAVES = frozenset({"conv_state"}) | LINEAR_LEAVES


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: storage dtype for matmul weights/embeddings; ``None`` = same as
    #: ``dtype``.  The base is frozen under LoRA, so bf16 STORAGE (not just
    #: bf16 compute over f32 masters, the flax default) halves weight HBM
    #: and weight-stream bandwidth — and avoids ever materializing an f32
    #: copy at init (a 7B model must never allocate 27 GiB of f32 masters
    #: on a 16 GiB chip).  RMSNorm scales stay f32 regardless: negligible
    #: bytes, and bf16 norms were implicated in the round-3 bf16-gradient
    #: sensitivity work.
    param_dtype: Any = None
    attn_impl: str = "auto"     # auto | blockwise | flash | ring
    #: Rematerialization policy for transformer blocks on the training path:
    #: "full" recomputes everything in backward (lowest HBM — the
    #: memory_estimate upper bounds assume this), "dots" saves matmul
    #: outputs and recomputes only elementwise ops (~25-30% faster step
    #: when activations fit), "none" disables remat.
    remat: str = "full"         # full | dots | none
    #: LoRA rank; 0 = dense fine-tuning.  When >0, attention projections
    #: carry low-rank adapters in the separate "lora" variable collection —
    #: base weights stay frozen/shared, per-client state is adapters only
    #: (the memory key to 512-client 7B federation, SURVEY §7 hard parts).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    #: Mixture-of-Experts: >0 replaces the dense FFN with n_experts SwiGLU
    #: experts, top-k routed, expert-parallel over the ``model`` mesh axis
    #: (llm/moe.py — EP has no reference counterpart, SURVEY §2.9).
    n_experts: int = 0
    moe_top_k: int = 2
    #: the experts' width; 0 = ``ffn_dim``.  The first
    #: ``first_dense_layers`` layers keep the dense SwiGLU of ``ffn_dim``.
    moe_ffn_dim: int = 0
    first_dense_layers: int = 0
    #: shared experts: one SwiGLU of ``n_shared_experts * moe_ffn_dim``
    #: beside the routed ones, for every token
    n_shared_experts: int = 0
    moe_scoring: str = "softmax"        # softmax | sigmoid
    #: group-limited routing: the ``moe_topk_group`` best of ``moe_n_group``
    #: groups of experts stay (one group: plain top-k)
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    #: (first, count): the routed experts whose weights live here — the
    #: router keeps ``n_experts`` outputs, the layer computes its own
    #: experts' part (llm/moe.py).  None = all.
    experts_held: Optional[Tuple[int, int]] = None
    #: latent attention (llm/mla.py): ``kv_lora_rank`` > 0 replaces the
    #: grouped-query attention by MLA with these published widths
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[YarnScaling] = None
    #: a head's width; 0 = ``dim // n_heads``
    head_dim: int = 0
    #: the kind of every layer's mixer: ``"full_attention"`` (causal over
    #: everything before), ``"sliding_attention"`` (key j visible to query
    #: i iff ``0 <= i - j < sliding_window``), ``"conv"`` (the gated short
    #: convolution, :class:`ShortConv`, of ``conv_kernel`` taps) or
    #: ``"linear_attention"`` (the gated delta rule,
    #: ``llm/linear_attention.py::GatedDeltaNet``); None = all full
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 0
    conv_kernel: int = 0
    #: the linear-attention layers' widths: key and value heads and their
    #: widths, the taps of the convolution before the rule, the eps of the
    #: output's norm and what its sigmoid gate is multiplied by
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0
    linear_norm_eps: float = 1e-6
    linear_gate_scale: float = 2.0
    #: latent attention: ``sigmoid(W_g n)`` multiplies every head's output
    #: before ``o``
    gated_attention: bool = False
    #: norms after the mixer and after the feed-forward too:
    #: ``h = x + N2(Mix(N1 x))``, ``y = h + N4(FF(N3 h))``
    sandwich_norm: bool = False
    #: what the gate of ``norm_kind="gated"`` is multiplied by
    norm_gate_scale: float = 2.0
    #: > 0: every SwiGLU (dense, shared, routed) computes
    #: ``silu(min(gate, limit)) * clip(up, -limit, limit)``
    swiglu_limit: float = 0.0
    #: RMS norms on q and k, over each head's numbers with one learned scale
    #: of ``head_dim``, before the rotary embedding
    qk_norm: bool = False
    #: the experts are chosen by ``scores + bias`` (a float32 buffer beside
    #: the router, ``select_bias``); the gates are the chosen experts' scores
    moe_select_bias: bool = False
    #: False: the full layers carry no positional embedding, the window
    #: layers keep the rotary one
    rope_full_layers: bool = True
    #: ``x + Attn(n) + FFN(n)`` with one norm ``n`` for both, in place of
    #: the sequential residual
    parallel_block: bool = False
    #: ``rms``, ``layer`` (the mean is subtracted first; scale, no bias) or
    #: ``gated`` (``x / rms(x) * norm_gate_scale * sigmoid(w)``, ``w`` zero
    #: at init: the zero-centred gated norm)
    norm_kind: str = "rms"
    #: the head is the embedding, transposed; the logits times ``logit_scale``
    tie_embeddings: bool = False
    logit_scale: float = 1.0
    #: what the shared experts' sum is multiplied by (1 / their number where
    #: a model averages them)
    shared_expert_scale: float = 1.0
    #: >0 fuses the lm_head matmul into a vocab-chunked streaming softmax
    #: cross-entropy on the training path (ops/xent.py) — peak activation
    #: memory O(B*S*chunk) instead of the O(B*S*V) logit tensor.
    streaming_xent_chunk: int = 0
    #: KV-cache storage dtype for the decode path: "native" keeps
    #: ``dtype``; "int8" stores K/V rows as int8 with one f32 scale per
    #: (batch, kv_head, position) — halves decode HBM traffic (the TPU
    #: decode bottleneck) at ~1% attention-output error.  Dequantization
    #: folds into the score/output einsums, so HBM reads stay int8.
    kv_cache_dtype: str = "native"  # native | int8
    #: Paged KV cache (serving): >0 switches the decode path to a single
    #: shared page pool of ``kv_pool_pages`` pages of ``kv_page_tokens``
    #: tokens each per layer, addressed through a per-slot block table
    #: passed as TRACED data — slot admission/eviction never recompiles,
    #: and slots share prefix pages copy-on-write.  Page 0 is the
    #: reserved trash page: unallocated block-table entries point at it,
    #: and mask discipline (every attended position <= the query's own
    #: position was written by the owning slot first) keeps its garbage
    #: out of every softmax.  0 = dense per-slot caches (training and
    #: the single-request paths are always dense).
    kv_page_tokens: int = 0
    kv_pool_pages: int = 0
    #: >0 (the engine sets it for a model with layers of both kinds): the
    #: window layers' pools have this many pages and are addressed through
    #: a table of their own, a ring: block j of a slot is entry ``j %
    #: entries``, and the serving engine takes the pages wholly behind the
    #: window back while the request runs (docs/SERVING.md).  0: every
    #: layer's pool has ``kv_pool_pages`` and one table addresses them all.
    kv_window_pool_pages: int = 0
    #: >0 (the engine sets it for a model with ``"conv"`` or
    #: ``"linear_attention"`` layers): on the paged path such a layer's state
    #: is one buffer a leaf of this many rows and a trash row, a row a slot,
    #: addressed by the call's ``state_rows``.  0: a row for each row of the
    #: batch (the single-request path).
    state_slots: int = 0

    def __post_init__(self):
        # typos must fail loudly — a silently-defaulted knob produces
        # measurements the user attributes to the value they typed
        if self.remat not in ("full", "dots", "none"):
            raise ValueError(f"remat={self.remat!r}: must be "
                             "'full', 'dots', or 'none'")
        if self.kv_cache_dtype not in ("native", "int8"):
            raise ValueError(f"kv_cache_dtype={self.kv_cache_dtype!r}: "
                             "must be 'native' or 'int8'")
        if self.attn_impl not in ("auto", "blockwise", "flash", "ring"):
            raise ValueError(f"attn_impl={self.attn_impl!r}: must be "
                             "'auto', 'blockwise', 'flash', or 'ring'")
        if self.kv_page_tokens < 0 or self.kv_pool_pages < 0:
            raise ValueError("kv_page_tokens/kv_pool_pages must be >= 0")
        if (self.kv_pool_pages > 0) != (self.kv_page_tokens > 0):
            raise ValueError(
                "paged KV needs BOTH kv_page_tokens and kv_pool_pages "
                f"(got {self.kv_page_tokens}/{self.kv_pool_pages})")
        if self.kv_pool_pages == 1:
            raise ValueError("kv_pool_pages=1 is only the reserved trash "
                             "page — need at least 2")
        if self.norm_kind not in ("rms", "layer", "gated"):
            raise ValueError(f"norm_kind={self.norm_kind!r}: must be 'rms', "
                             "'layer' or 'gated'")
        if self.sandwich_norm and self.parallel_block:
            raise ValueError("sandwich_norm with parallel_block: norms after "
                             "the mixer and the feed-forward of a parallel "
                             "block are not computed here")
        kinds = self.layer_types
        if kinds is not None:
            if len(kinds) != self.n_layers or set(kinds) - LAYER_KINDS:
                raise ValueError(
                    f"layer_types={kinds!r}: one of 'full_attention', "
                    f"'sliding_attention', 'conv' and 'linear_attention' for "
                    f"each of {self.n_layers} layers")
            if "sliding_attention" in kinds and self.sliding_window <= 0:
                raise ValueError("sliding_attention layers need "
                                 "sliding_window > 0")
            if "conv" in kinds and self.conv_kernel < 2:
                raise ValueError("conv layers need conv_kernel >= 2 (the "
                                 "taps of the short convolution)")
            if "linear_attention" in kinds and (
                    min(self.linear_key_heads, self.linear_value_heads,
                        self.linear_key_head_dim,
                        self.linear_value_head_dim) <= 0
                    or self.linear_conv_kernel < 2
                    or self.linear_value_heads % self.linear_key_heads):
                raise ValueError(
                    "linear_attention layers need linear_key_heads, "
                    "linear_value_heads (a multiple of the key heads), "
                    "linear_key_head_dim, linear_value_head_dim and "
                    "linear_conv_kernel >= 2")
        if self.gated_attention and self.kv_lora_rank <= 0:
            raise ValueError("gated_attention is computed for latent "
                             "attention only")
        if self.qk_norm and self.kv_lora_rank > 0:
            raise ValueError("qk_norm is computed for grouped-query "
                             "attention, not for latent attention")
        if self.windowed and (self.kv_lora_rank > 0
                              or self.kv_cache_dtype == "int8"):
            raise ValueError(
                "a sliding window is computed for grouped-query attention "
                "over a cache of the model's own type: not for latent "
                "attention, nor over an int8 cache")
        if self.tie_embeddings and self.streaming_xent_chunk:
            raise ValueError("streaming_xent_chunk reads lm_head/kernel: "
                             "not with tie_embeddings")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring={self.moe_scoring!r}: must be "
                             "'softmax' or 'sigmoid'")
        if self.n_experts > 0:
            first, count = self.experts_held or (0, self.n_experts)
            if not (0 <= first and count >= 1
                    and first + count <= self.n_experts):
                raise ValueError(
                    f"experts_held={self.experts_held}: not a run of the "
                    f"{self.n_experts} routed experts")
            if self.n_experts % self.moe_n_group \
                    or not 1 <= self.moe_topk_group <= self.moe_n_group:
                raise ValueError(
                    f"{self.n_experts} experts do not form "
                    f"{self.moe_n_group} groups of which "
                    f"{self.moe_topk_group} stay")
        if self.kv_lora_rank > 0:
            if min(self.q_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim) <= 0:
                raise ValueError(
                    "latent attention needs q_lora_rank, qk_nope_head_dim, "
                    "qk_rope_head_dim and v_head_dim beside kv_lora_rank")
            if self.kv_cache_dtype == "int8":
                raise ValueError(
                    "kv_cache_dtype='int8' is not defined for latent "
                    "attention: the cached row [c_kv ; k_r] is one "
                    "normalised latent shared by every head and read twice, "
                    "as keys through W_UK and as values through W_UV; the "
                    "int8 pools hold one scale per kv head and position, "
                    "and no int8 layout of the latent has been compared "
                    "with the reference")

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def windowed(self) -> bool:
        """Does any layer look back a fixed window only?"""
        return bool(self.layer_types) \
            and "sliding_attention" in self.layer_types

    @property
    def mixed_attention(self) -> bool:
        """Window and full layers in one stack."""
        return self.windowed and "full_attention" in self.layer_types

    @property
    def conv_layers(self) -> int:
        """How many layers mix by the short convolution and keep its state."""
        return sum(k == "conv" for k in self.layer_types or ())

    @property
    def linear_layers(self) -> int:
        """How many layers mix by the gated delta rule and keep its state."""
        return sum(k == "linear_attention" for k in self.layer_types or ())

    @property
    def state_layers(self) -> int:
        """How many layers keep state by rows (no pages)."""
        return self.conv_layers + self.linear_layers

    def layer_conv(self, i: int) -> bool:
        return bool(self.layer_types) and self.layer_types[i] == "conv"

    def layer_linear(self, i: int) -> bool:
        return bool(self.layer_types) \
            and self.layer_types[i] == "linear_attention"

    def layer_window(self, i: int) -> int:
        """Layer ``i``'s window; 0 = everything before."""
        return self.sliding_window if self.windowed \
            and self.layer_types[i] == "sliding_attention" else 0

    def layer_rope(self, i: int) -> bool:
        return self.rope_full_layers or self.layer_window(i) > 0

    def sparse_layer(self, i: int) -> bool:
        """Does layer ``i`` carry routed experts?"""
        return self.n_experts > 0 and i >= self.first_dense_layers

    @property
    def store_dtype(self):
        return self.dtype if self.param_dtype is None else self.param_dtype


TINY = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                   dtype=jnp.float32)
LLAMA2_7B = LlamaConfig()


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, y: YarnScaling):
    """YaRN's inverse frequencies for a rotary width ``d``: pairs that turn
    more than ``beta_fast`` times within the original context keep their
    frequency, those that turn less than ``beta_slow`` times are slowed by
    ``factor``, a linear ramp between.  The blend holds at every position."""
    def turns_at(turns):        # the pair that makes ``turns`` turns
        return d * math.log(y.original_max_position_embeddings
                            / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))
    low = max(math.floor(turns_at(y.beta_fast)), 0)
    high = min(math.ceil(turns_at(y.beta_slow)), d - 1)
    extra = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def _rope(x, positions, theta: float, scaling: Optional[YarnScaling] = None):
    """Rotary position embedding; x: (B, H, S, D_head).  ``positions`` is
    (S,) shared across the batch, or (B, S) per-row (the paged serving
    step, where every slot sits at its own depth)."""
    d = x.shape[-1]
    if scaling is not None:
        freqs = yarn_inv_freq(d, theta, scaling)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) \
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim)
        cos, sin = cos * m, sin * m
    if positions.ndim == 2:          # (B, S, d/2) -> (B, 1, S, d/2)
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


class LayerNorm(nn.Module):
    """``(x - mean x) / sqrt(var x + eps) * scale``, no bias."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


class GatedNorm(nn.Module):
    """The zero-centred gated norm: ``x / sqrt(mean x^2 + eps) * gate_scale *
    sigmoid(w)``, ``w`` (float32) zero at init, so that the norm starts as a
    plain RMS norm."""
    eps: float = 1e-6
    gate_scale: float = 2.0

    @nn.compact
    def __call__(self, x):
        w = self.param("gate", nn.initializers.zeros, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) \
            * (self.gate_scale * jax.nn.sigmoid(w))


def _norm(cfg: "LlamaConfig", name: str):
    if cfg.norm_kind == "gated":
        return GatedNorm(cfg.norm_eps, cfg.norm_gate_scale, name=name)
    return (LayerNorm if cfg.norm_kind == "layer" else RMSNorm)(
        cfg.norm_eps, name=name)


class LoRADense(nn.Module):
    """Dense with an optional low-rank adapter in the "lora" collection:
    y = x·W + (α/r)·(x·A)·B.  W lives in "params" (frozen for FedLoRA);
    A, B live in "lora" so a cohort of clients can vmap over adapters while
    sharing one copy of W.

    Grouped apply: adapter leaves carrying one EXTRA leading axis aligned
    with x's batch — A (B, in, r), B (B, r, out), e.g. a per-sample gather
    out of the serving adapter bank (``gather(bank, slot_adapter_ids)``) —
    run as a pair of batched einsums, so a mixed-adapter batch costs one
    grouped matmul instead of per-adapter dispatches."""

    features: int
    rank: int
    alpha: float
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = nn.Dense(self.features, use_bias=False, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="base")(x)
        if self.rank > 0:
            # structure initialized to zeros; lora_init() randomizes A
            # externally (B stays zero so the adapter starts as identity)
            a = self.variable(
                "lora", "A",
                lambda: jnp.zeros((x.shape[-1], self.rank), jnp.float32))
            b = self.variable(
                "lora", "B",
                lambda: jnp.zeros((self.rank, self.features), jnp.float32))
            scale = self.alpha / self.rank
            av, bv = a.value, b.value
            xf = x.astype(jnp.float32)
            if av.ndim == 3:
                delta = jnp.einsum("b...i,bir->b...r", xf, av)
                delta = jnp.einsum("b...r,bro->b...o", delta, bv)
            else:
                delta = xf @ av @ bv
            y = y + (delta * scale).astype(y.dtype)
        return y


def _projection(cfg: "LlamaConfig"):
    """``(features, name) -> module``: an attention projection, with its
    LoRA adapter where the configuration has a rank."""
    if cfg.lora_rank > 0:
        return lambda feats, name: LoRADense(
            feats, cfg.lora_rank, cfg.lora_alpha, dtype=cfg.dtype,
            param_dtype=cfg.store_dtype, name=name)
    return lambda feats, name: nn.Dense(
        feats, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.store_dtype, name=name)


def _attn_impl(cfg: "LlamaConfig") -> str:
    """``cfg.attn_impl`` with ``auto`` resolved: the Pallas kernel on the
    TPU, the scan elsewhere."""
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    return "flash" if jax.default_backend() == "tpu" else "blockwise"


#: the paged read walks a block table, under a running softmax, where what
#: the whole-window gather would hold at once (K and V of every row's whole
#: table, or the scores over them) passes this many bytes; a window layer
#: always walks
WALK_MIN_BYTES = 1 << 28
#: the scores of one slab of the ``jnp`` walk, in elements
WALK_SLAB_SCORES = 1 << 26


def paged_read_walks(cfg: "LlamaConfig", window: int, b: int, s: int,
                     entries: int) -> bool:
    """Whether the paged read of a layer with this ``window``, for ``b``
    lanes of ``s`` positions over tables of ``entries`` pages, walks its
    table (:func:`_walk_pages`) and does not gather it whole: a window layer
    always, a full layer where K and V of every row's whole table, or the
    scores over them, pass ``WALK_MIN_BYTES`` (an int8 cache has no walk)."""
    head_dim = cfg.head_dim or cfg.dim // cfg.n_heads
    int8_kv = cfg.kv_cache_dtype == "int8"
    itemsize = 1 if int8_kv else jnp.dtype(cfg.dtype).itemsize
    whole = b * entries * cfg.kv_page_tokens * max(
        2 * cfg.n_kv_heads * head_dim * itemsize, 4 * cfg.n_heads * s)
    return bool(window or (whole > WALK_MIN_BYTES and not int8_kv))


@functools.partial(jax.jit,
                   static_argnames=("window", "ring", "sm_scale", "dtype"))
def _walk_pages(q, pool_k, pool_v, tables, pos, window: int, ring: bool,
                sm_scale: float, dtype):
    """Attention of ``q`` (b, h_kv, rep, s, d) at positions ``pos`` (b, s)
    over the pages ``tables`` (b, entries) names in ``pool_k``/``pool_v``
    (pages, P, h_kv, d), under a running softmax.

    Entry ``e`` of a table stands for block ``e`` of the sequence, or, in a
    ``ring``, for the one block ``j`` in ``(last - entries, last]`` with
    ``j % entries == e``, ``last`` the block of the row's highest position
    in this call.  A key at position j is visible to the query at i iff
    ``0 <= i - j`` (``< window``, where the layer has one).  A row whose
    table is all trash (a lane that is not live) is walked over not at
    all, and what it reads is unspecified.

    Where the program is lowered for a TPU and the operands allow
    (``ops/paged_attention.py::kernel_can_run``: bfloat16, whole lanes of
    head_dim, rows that tile) it is one Pallas kernel in which every lane
    visits its own live pages; everywhere else (the CPU, a float32 model,
    odd widths) the ``jnp`` loop of :func:`_walk_pages_jnp`."""
    from ..ops import paged_attention as pa
    walk = functools.partial(_walk_pages_jnp, window=window, ring=ring,
                             sm_scale=sm_scale, dtype=dtype)
    # fedlint: disable-next-line=recompile-hazard -- shapes and dtypes only
    if q.dtype != dtype or not pa.kernel_can_run(q, pool_k, pool_v, tables):
        return walk(q, pool_k, pool_v, tables, pos)
    kernel = functools.partial(pa.paged_attention, window=window, ring=ring,
                               sm_scale=sm_scale)
    return jax.lax.platform_dependent(q, pool_k, pool_v, tables, pos,
                                      tpu=kernel, default=walk)


def _walk_pages_jnp(q, pool_k, pool_v, tables, pos, *, window: int,
                    ring: bool, sm_scale: float, dtype):
    """:func:`_walk_pages` in plain ``jnp``: a slab of entries at a time, as
    far as the longest row of the batch reaches: the trip count is data, and
    no step holds more than a slab of every row."""
    b, g, rep, s, d = q.shape
    ptok, entries = pool_k.shape[1], tables.shape[1]
    slab = min(max(WALK_SLAB_SCORES // (b * g * rep * s * ptok), 8), 64,
               entries)
    padded = -(-entries // slab) * slab
    tables = jnp.pad(tables, ((0, 0), (0, padded - entries)))
    last = pos[:, -1] // ptok                               # (b,)
    reach = jnp.minimum(last + 1, entries) if ring else last + 1
    reach = jnp.where(jnp.any(tables != 0, axis=1), reach, 0)
    trips = (jnp.max(reach) + slab - 1) // slab

    def body(i, carry):
        m, l, acc = carry
        e = i * slab + jnp.arange(slab)                     # entries
        tab = jax.lax.dynamic_slice_in_dim(tables, i * slab, slab, axis=1)
        kblk = pool_k[tab].reshape(b, slab * ptok, g, d)
        vblk = pool_v[tab].reshape(b, slab * ptok, g, d)
        if ring:
            block = last[:, None] - jnp.mod(last[:, None] - e[None, :],
                                            entries)
        else:
            block = jnp.broadcast_to(e[None, :], (b, slab))
        kv_pos = (block[:, :, None] * ptok + jnp.arange(ptok)).reshape(
            b, 1, slab * ptok)
        ahead = pos[:, :, None] - kv_pos                    # (b, s, K)
        valid = (ahead >= 0) & (kv_pos >= 0) \
            & jnp.repeat(e < entries, ptok)[None, None, :]
        if window:
            valid &= ahead < window
        scores = jnp.einsum("bgrqd,bkgd->bgrqk", q, kblk.astype(q.dtype),
                            preferred_element_type=jnp.float32) * sm_scale
        scores = jnp.where(valid[:, None, None], scores, -1e30)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        # a slab wholly outside a row's window leaves its running max at
        # -1e30; the row's own key, in a later slab, wipes what is counted
        # here (alpha = 0)
        p = jnp.exp(scores - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(dtype), vblk.astype(dtype),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((b, g, rep, s), -1e30, jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, trips, body, (m0, jnp.zeros_like(m0),
                         jnp.zeros((b, g, rep, s, d), jnp.float32)))
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(dtype)


class Attention(nn.Module):
    cfg: LlamaConfig
    #: > 0: key j is visible to query i iff ``0 <= i - j < window``
    window: int = 0
    #: rotary embedding of q and k (False: no positional embedding)
    rope: bool = True

    @nn.compact
    def __call__(self, x, positions, decode: bool = False,
                 block_tables=None):
        cfg = self.cfg
        head_dim = cfg.head_dim or cfg.dim // cfg.n_heads
        dense = _projection(cfg)
        q = dense(cfg.n_heads * head_dim, "wq")(x)
        k = dense(cfg.n_kv_heads * head_dim, "wk")(x)
        v = dense(cfg.n_kv_heads * head_dim, "wv")(x)
        b, s, _ = x.shape
        q = q.reshape(b, s, cfg.n_heads, head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, cfg.n_kv_heads, head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, cfg.n_kv_heads, head_dim).transpose(0, 2, 1, 3)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        if self.rope:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)

        if decode:
            if block_tables is not None:
                return self._paged_decode_attend(q, k, v, positions,
                                                 block_tables, b, s,
                                                 head_dim, dense)
            return self._decode_attend(q, k, v, positions, b, s, head_dim,
                                       dense)

        impl = _attn_impl(cfg)
        if impl == "ring":
            from ..ops.ring_attention import ring_attention
            if self.window:
                raise NotImplementedError(
                    "ring attention has no sliding window")
            if cfg.n_kv_heads != cfg.n_heads:  # ring path still repeats
                rep = cfg.n_heads // cfg.n_kv_heads
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
            out = ring_attention(q, k, v, axis_name=SEQ_AXIS, causal=True)
        elif impl == "flash":
            # flash + blockwise consume grouped KV natively (index-mapped
            # heads — no h/h_kv × HBM blow-up from jnp.repeat)
            out = flash_attention(q, k, v, True, None, self.window)
        else:
            out = blockwise_attention(q, k, v, causal=True,
                                      window=self.window)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * head_dim)
        return dense(cfg.dim, "wo")(out)

    def _decode_attend(self, q, k, v, positions, b, s, head_dim, dense):
        """KV-cached attention for autoregressive serving (the reference
        streams from HF's incremental generator,
        ``serving/templates/hf_template/main_openai.py``; here the cache is
        a static ``max_seq_len`` buffer in the flax "cache" collection so
        the single-token step jits once).

        ``positions[0]`` is the sequence position of the first new token;
        the new K/V are written into the cache at that offset and q attends
        to every cache slot ``<= `` its own position (stale slots beyond
        the live prefix are masked, so a full-buffer prefill that wrote
        garbage past the prompt length is harmless).
        """
        cfg = self.cfg
        cache_len = cfg.max_seq_len
        int8_kv = cfg.kv_cache_dtype == "int8"
        store_dtype = jnp.int8 if int8_kv else cfg.dtype
        ck = self.variable("cache", "k", jnp.zeros,
                           (b, cfg.n_kv_heads, cache_len, head_dim),
                           store_dtype)
        cv = self.variable("cache", "v", jnp.zeros,
                           (b, cfg.n_kv_heads, cache_len, head_dim),
                           store_dtype)
        start = positions[0].astype(jnp.int32)
        if int8_kv:
            cks = self.variable("cache", "k_scale", jnp.zeros,
                                (b, cfg.n_kv_heads, cache_len), jnp.float32)
            cvs = self.variable("cache", "v_scale", jnp.zeros,
                                (b, cfg.n_kv_heads, cache_len), jnp.float32)

            def quant_rows(x):
                xf = x.astype(jnp.float32)
                scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
                q8 = jnp.clip(jnp.round(xf / scale[..., None]),
                              -127, 127).astype(jnp.int8)
                return q8, scale

            k8, ks = quant_rows(k)
            v8, vs = quant_rows(v)
            ck.value = jax.lax.dynamic_update_slice(ck.value, k8,
                                                    (0, 0, start, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v8,
                                                    (0, 0, start, 0))
            cks.value = jax.lax.dynamic_update_slice(cks.value, ks,
                                                     (0, 0, start))
            cvs.value = jax.lax.dynamic_update_slice(cvs.value, vs,
                                                     (0, 0, start))
        else:
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(cfg.dtype), (0, 0, start, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(cfg.dtype), (0, 0, start, 0))
        kf, vf = ck.value, cv.value                 # (b, h_kv, L, d)
        rep = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(b, cfg.n_kv_heads, rep, s, head_dim)
        # f32 accumulation (same convention as ops/attention._block_scores:
        # bf16-accumulated score dots caused the round-3 gradient NaNs, and
        # int8-dequantized K carries magnitudes up to 127)
        scores = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kf.astype(qg.dtype),
                            preferred_element_type=jnp.float32)
        # grouped, no KV repeat
        if int8_kv:
            # exact dequant: q·(k8*scale) == (q·k8)*scale (scale is
            # per-position) — the HBM read stays int8
            scores = scores * cks.value[:, :, None, None, :]
        scores = scores / (head_dim ** 0.5)
        kv_pos = jnp.arange(cache_len)
        ahead = positions[:, None] - kv_pos[None, :]      # (s, cache_len)
        mask = ahead >= 0
        if self.window:
            mask &= ahead < self.window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        if int8_kv:
            # fold v's per-position scale into probs, keep vf int8 in HBM
            probs = probs * cvs.value[:, :, None, None, :]
        probs = probs.astype(cfg.dtype)
        out = jnp.einsum("bgrqk,bgkd->bgrqd", probs, vf.astype(cfg.dtype),
                         preferred_element_type=jnp.float32
                         ).astype(cfg.dtype)
        out = out.reshape(b, cfg.n_heads, s, head_dim)
        out = out.transpose(0, 2, 1, 3).reshape(
            b, s, cfg.n_heads * head_dim)
        return dense(cfg.dim, "wo")(out)

    def _paged_decode_attend(self, q, k, v, positions, block_tables, b, s,
                             head_dim, dense):
        """Paged KV attention: one page pool per layer SHARED across all
        slots (no batch axis — the chunked-prefill program at b=1 and the
        batched decode step at b=slots mutate the same buffers), addressed
        through a per-slot ``block_tables`` (b, max_blocks) int32 carried
        as traced data.  ``positions`` is (b, s) — every slot at its own
        depth.  Reads gather the slot's whole block-table window and mask
        ``kv_pos <= position``.  The window index of a gathered token IS
        its logical position, so the softmax (masked to -1e30, exp -> 0.0
        exactly in f32) is bitwise what the dense cache computes over the
        same prefix.

        The pool is ``(pool_pages, page_tokens, h_kv, d)``: one token's K
        (or V) for all kv heads is ONE contiguous row, so the write
        ``pool[table[pos // P], pos % P] = row`` is a scatter of whole
        trailing slices that XLA performs in place on the donated buffer.
        A decode program never moves a whole pool
        (``tests/test_chip_compile.py`` holds the compiled tick and chunk
        programs to that): a head axis between the two indexed axes makes
        the chip's compiler re-lay every pool out to this order at entry
        and back at the aliased output, four pool copies a layer.

        Unallocated block-table entries are 0 — the trash page.  Writes
        past a slot's reservation (chunk padding, horizon burn-out) land
        there; reads of it are always masked because a reserved prefix
        covers every window position <= the slot's own position.

        A window layer of a model that has full layers too
        (``kv_window_pool_pages``) has a pool of that size and a short
        table, a ring: position p is written through entry ``(p // P) %
        entries``, and the engine, which takes the pages wholly behind the
        window back while the request runs, has zeroed the entries of the
        blocks it took and of those it has not given yet.  Its read, and
        any read too large to gather whole (``WALK_MIN_BYTES``), walks the
        table (:func:`_walk_pages`: a Pallas kernel over each lane's live
        pages where the program is lowered for a TPU, a ``jnp`` loop over
        slabs elsewhere).
        """
        cfg = self.cfg
        ptok = cfg.kv_page_tokens
        ring = self.window > 0 and cfg.kv_window_pool_pages > 0
        pool_pages = cfg.kv_window_pool_pages if ring else cfg.kv_pool_pages
        int8_kv = cfg.kv_cache_dtype == "int8"
        store_dtype = jnp.int8 if int8_kv else cfg.dtype
        # heads narrower than a lane tile whose row of all kv heads is whole
        # tiles: the pool keeps the row flat, (pages, P, h_kv * d).  With a
        # trailing axis of half a tile the chip's compiler pads every page to
        # twice its size and re-lays the pool out around the scatter and the
        # gather (tests/test_chip_compile.py holds the programs to no copy)
        row = (cfg.n_kv_heads * head_dim,) if (
            head_dim % 128 and (cfg.n_kv_heads * head_dim) % 128 == 0
            and not int8_kv) else (cfg.n_kv_heads, head_dim)
        pk = self.variable("cache", "k", jnp.zeros,
                           (pool_pages, ptok) + row, store_dtype)
        pv = self.variable("cache", "v", jnp.zeros,
                           (pool_pages, ptok) + row, store_dtype)
        pos = positions.astype(jnp.int32)                   # (b, s)
        max_blocks = block_tables.shape[1]
        entry = (pos // ptok) % max_blocks if ring else pos // ptok
        page = jnp.take_along_axis(block_tables, entry, axis=1)
        offs = pos % ptok                                   # (b, s)
        k_w = k.transpose(0, 2, 1, 3).reshape((b, s) + row)  # (b, s) + row
        v_w = v.transpose(0, 2, 1, 3).reshape((b, s) + row)
        if int8_kv:
            pks = self.variable("cache", "k_scale", jnp.zeros,
                                (pool_pages, ptok, cfg.n_kv_heads),
                                jnp.float32)
            pvs = self.variable("cache", "v_scale", jnp.zeros,
                                (pool_pages, ptok, cfg.n_kv_heads),
                                jnp.float32)

            def quant_rows(x):
                xf = x.astype(jnp.float32)
                scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1),
                                    1e-8) / 127.0
                q8 = jnp.clip(jnp.round(xf / scale[..., None]),
                              -127, 127).astype(jnp.int8)
                return q8, scale

            k8, ks = quant_rows(k_w)
            v8, vs = quant_rows(v_w)
            pk.value = pk.value.at[page, offs].set(k8)
            pv.value = pv.value.at[page, offs].set(v8)
            pks.value = pks.value.at[page, offs].set(ks)
            pvs.value = pvs.value.at[page, offs].set(vs)
        else:
            pk.value = pk.value.at[page, offs].set(k_w.astype(cfg.dtype))
            pv.value = pv.value.at[page, offs].set(v_w.astype(cfg.dtype))
        # gather the slot windows AFTER the write so a chunk attends to
        # its own earlier tokens (in-chunk causality via the mask below)
        window = max_blocks * ptok
        rep = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(b, cfg.n_kv_heads, rep, s, head_dim)

        def project(out):                # (b, hkv, rep, s, d) -> (b, s, dim)
            out = out.reshape(b, cfg.n_heads, s, head_dim)
            out = out.transpose(0, 2, 1, 3).reshape(
                b, s, cfg.n_heads * head_dim)
            return dense(cfg.dim, "wo")(out)

        if paged_read_walks(cfg, self.window, b, s, max_blocks):
            return project(_walk_pages(
                qg, pk.value, pv.value, block_tables, pos, self.window, ring,
                head_dim ** -0.5, cfg.dtype))

        def gather_window(pool, per_head=()):        # -> (b, hkv, W, ...)
            g = pool[block_tables]                   # (b, MB, P, hkv, ...)
            g = g.reshape((b, window, cfg.n_kv_heads) + per_head)
            return jnp.moveaxis(g, 2, 1)

        kf = gather_window(pk.value, (head_dim,))
        vf = gather_window(pv.value, (head_dim,))
        scores = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kf.astype(qg.dtype),
                            preferred_element_type=jnp.float32)
        if int8_kv:
            scores = scores * gather_window(pks.value)[:, :, None, None]
        scores = scores / (head_dim ** 0.5)
        kv_pos = jnp.arange(window)
        mask = kv_pos[None, None, :] <= pos[:, :, None]    # (b, s, W)
        scores = jnp.where(mask[:, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        if int8_kv:
            probs = probs * gather_window(pvs.value)[:, :, None, None]
        probs = probs.astype(cfg.dtype)
        out = jnp.einsum("bgrqk,bgkd->bgrqd", probs, vf.astype(cfg.dtype),
                         preferred_element_type=jnp.float32
                         ).astype(cfg.dtype)
        return project(out)


class ShortConv(nn.Module):
    """The gated short convolution, a mixer in place of attention
    (``layer_types[i] == "conv"``): ``[B | C | z] = in_proj(x)``, ``u = B * z``,
    ``c_t = sum_j w[:, j] * u_{t - (K-1) + j}`` (depthwise and causal over
    ``K = conv_kernel`` taps, ``u`` zero before the sequence, no bias),
    ``out_proj(C * c)``.

    What a later call needs of the past is the last ``K - 1`` rows of ``u``:
    on the decode paths they live in the ``cache`` collection as
    ``conv_state``, a buffer shaped by **rows of state** and not by pages:
    ``(state_slots + 1, K - 1, dim)`` for the engine's paged model (a row a
    slot and the trash row), else a row for each row of the batch.
    ``state = (rows, lens)``: the (b,) rows this call's lanes address
    (None: ``arange(b)``) and how many of each lane's ``s`` positions are real
    (None: all; the positions behind them are a chunk's padding).  Three rules,
    all inside the call: a lane whose first position is 0 starts from zeros,
    whatever its row holds (nothing precedes position 0: a request's first
    chunk uploads and clears nothing); every call writes each lane's last
    ``K - 1`` real rows back to its row; and a lane that must leave a slot's
    state alone is given the trash row by its caller.  A call of ``s``
    positions computes its convolution from the carried rows and its own ``u``
    in one shifted sum."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, state=None):
        cfg = self.cfg
        taps = cfg.conv_kernel
        dense = _projection(cfg)
        b, s, d = x.shape
        gate_in, gate_out, z = jnp.split(dense(3 * d, "in_proj")(x), 3, axis=-1)
        u = gate_in * z
        w = self.param("conv_weight", nn.initializers.normal(taps ** -0.5),
                       (d, taps), cfg.store_dtype).astype(jnp.float32)
        if decode:
            rows, lens = state if state is not None else (None, None)
            carried = self.variable(
                "cache", "conv_state", jnp.zeros,
                (cfg.state_slots + 1 if cfg.state_slots else b, taps - 1, d),
                cfg.dtype)
            if rows is None:
                rows = jnp.arange(b)
            fresh = jnp.reshape(positions[..., 0] == 0, (-1, 1, 1))
            prev = jnp.where(fresh, 0, carried.value[rows]).astype(u.dtype)
        else:
            prev = jnp.zeros((b, taps - 1, d), u.dtype)
        ext = jnp.concatenate([prev, u], axis=1)        # (b, K-1+s, d)
        c = sum(ext[:, j:j + s].astype(jnp.float32) * w[:, j]
                for j in range(taps)).astype(u.dtype)
        if decode:
            if lens is None:
                last = ext[:, s:]
            else:       # u of positions lens-K+1 .. lens-1, in ext's rows
                at = lens[:, None] + jnp.arange(taps - 1)[None, :]
                last = jnp.take_along_axis(ext, at[:, :, None], axis=1)
            carried.value = carried.value.at[rows].set(last.astype(cfg.dtype))
        return dense(d, "out_proj")(gate_out * c)


class MLP(nn.Module):
    cfg: LlamaConfig
    #: 0 = ``cfg.ffn_dim``; a shared expert passes its own
    width: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        width = self.width or cfg.ffn_dim
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.store_dtype, name=name)
        gate = dense(width, "w_gate")(x)
        up = dense(width, "w_up")(x)
        return dense(cfg.dim, "w_down")(swiglu_act(gate, up, cfg.swiglu_limit))


class Block(nn.Module):
    cfg: LlamaConfig
    #: routed experts (and the shared one) in place of the dense SwiGLU
    sparse: bool = False
    #: the attention's window (0: everything before) and whether it rotates
    #: q and k: what differs by layer is data of the configuration
    window: int = 0
    rope: bool = True
    #: the mixer is the gated short convolution, not attention
    conv: bool = False
    #: the mixer is the gated delta rule, not attention
    linear: bool = False

    @nn.compact
    def __call__(self, x, positions, decode: bool = False,
                 block_tables=None, state=None):
        cfg = self.cfg
        n = _norm(cfg, "attn_norm")(x)
        if self.conv:
            attn = ShortConv(cfg, name="conv")(n, positions, decode, state)
        elif self.linear:
            from .linear_attention import GatedDeltaNet
            attn = GatedDeltaNet(cfg, name="linear_attn")(
                n, positions, decode, state)
        elif cfg.latent_attention:
            from .mla import MLA
            attn = MLA(cfg, name="attention")(
                n, positions, decode=decode, block_tables=block_tables)
        else:
            attn = Attention(cfg, window=self.window, rope=self.rope,
                             name="attention")(
                n, positions, decode=decode, block_tables=block_tables)
        if cfg.parallel_block:       # one norm for both
            return x + attn + _feed_forward(cfg, self.sparse, n)
        if cfg.sandwich_norm:
            attn = _norm(cfg, "attn_post_norm")(attn)
        h = x + attn
        ff = _feed_forward(cfg, self.sparse, _norm(cfg, "mlp_norm")(h))
        if cfg.sandwich_norm:
            ff = _norm(cfg, "mlp_post_norm")(ff)
        return h + ff


def _feed_forward(cfg: LlamaConfig, sparse: bool, hn):
    """A block's feed-forward over its normed input: the dense SwiGLU, or the
    routed experts beside the shared ones.  Called inside ``Block``'s own
    scope (a method would put its name into every scope under it, the
    kernels' among them)."""
    if not sparse:
        return MLP(cfg, name="mlp")(hn)
    from .moe import MoEMLP
    width = cfg.moe_ffn_dim or cfg.ffn_dim
    y = MoEMLP(dim=cfg.dim, ffn_dim=width, n_experts=cfg.n_experts,
               top_k=cfg.moe_top_k, scoring=cfg.moe_scoring,
               n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
               norm_topk=cfg.moe_norm_topk,
               routed_scale=cfg.moe_routed_scale,
               select_bias=cfg.moe_select_bias, held=cfg.experts_held,
               swiglu_limit=cfg.swiglu_limit,
               dtype=cfg.dtype, param_dtype=cfg.store_dtype,
               name="moe_mlp")(hn)
    if cfg.n_shared_experts:
        shared = MLP(cfg, width=cfg.n_shared_experts * width,
                     name="shared_expert")(hn)
        if cfg.shared_expert_scale != 1.0:
            shared = shared * jnp.asarray(cfg.shared_expert_scale,
                                          shared.dtype)
        y = y + shared
    return y


class LlamaLM(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False,
                 start_pos=None, return_hidden: bool = False,
                 block_tables=None, state_rows=None, seq_lens=None):
        """``decode=True`` switches attention to the KV-cached path: the
        flax "cache" collection must be mutable in ``apply``, and
        ``start_pos`` (scalar int array — or a (B,) vector on the paged
        path, one depth per slot) gives the sequence position of
        ``tokens[:, 0]`` — the caller owns position bookkeeping so the
        jitted single-token step stays stateless.  ``block_tables``
        ((B, max_blocks) int32, traced) selects the paged-pool decode
        path (``kv_page_tokens``/``kv_pool_pages`` on the config); for a
        model with ``kv_window_pool_pages`` it is ``{"full": ...,
        "window": ...}``, a table per kind of layer.  A model with
        ``"conv"`` or ``"linear_attention"`` layers keeps their state by
        rows (:class:`ShortConv`, ``linear_attention.GatedDeltaNet``):
        ``state_rows`` ((B,) int32, traced) says which row each lane of the
        call addresses (None: lane i its own row i) and ``seq_lens`` ((B,))
        how many of a lane's positions are real (None: all of them).
        ``return_hidden=True`` returns final-norm hidden states without
        the lm_head projection (the streaming cross-entropy path)."""
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=cfg.store_dtype, name="tok_embed")
        x = embed(tokens)
        positions = jnp.arange(tokens.shape[-1])
        if start_pos is not None:
            start_pos = jnp.asarray(start_pos)
            if start_pos.ndim == 1:      # per-slot depths -> (B, T)
                positions = positions[None, :] + start_pos[:, None]
            else:
                positions = positions + start_pos
        if cfg.remat == "none":
            mk_block = Block
        elif cfg.remat == "dots":
            # save MXU outputs, recompute elementwise only — faster backward
            # than full remat wherever the saved dots fit in HBM
            mk_block = functools.partial(
                nn.remat, static_argnums=(3,),
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )(Block)
        else:   # "full": recompute block activations in backward — HBM for
            mk_block = nn.remat(Block, static_argnums=(3,))  # FLOPs
        for i in range(cfg.n_layers):
            window = cfg.layer_window(i)
            if cfg.layer_conv(i) or cfg.layer_linear(i):
                block = mk_block(cfg, sparse=cfg.sparse_layer(i),
                                 conv=cfg.layer_conv(i),
                                 linear=cfg.layer_linear(i),
                                 name=f"layer_{i}")
                x = block(x, positions, decode, None, (state_rows, seq_lens))
                continue
            block = mk_block(cfg, sparse=cfg.sparse_layer(i), window=window,
                             rope=cfg.layer_rope(i), name=f"layer_{i}")
            tables = block_tables
            if isinstance(tables, dict):
                tables = tables["window" if window else "full"]
            x = block(x, positions, decode, tables)
        x = _norm(cfg, "final_norm")(x)
        if return_hidden:
            # streaming cross-entropy path (ops/xent.py): the caller fuses
            # the lm_head matmul into a vocab-chunked loss instead of
            # materializing (B, S, V) logits.  Only valid under apply —
            # init must run the default path so lm_head params exist.
            return x
        # kernel stored in store_dtype, compute still f32 (logit precision)
        if cfg.tie_embeddings:
            logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                                embed.embedding.astype(jnp.float32))
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False,
                              dtype=jnp.float32, param_dtype=cfg.store_dtype,
                              name="lm_head")(x)
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
        return logits


#: published ``config.json`` key -> ``LlamaConfig`` field
_PUBLISHED_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "n_routed_experts": "n_experts", "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_ffn_dim",
    "first_k_dense_replace": "first_dense_layers",
    "n_shared_experts": "n_shared_experts", "scoring_func": "moe_scoring",
    "n_group": "moe_n_group", "topk_group": "moe_topk_group",
    "norm_topk_prob": "moe_norm_topk",
    "routed_scaling_factor": "moe_routed_scale",
    # the cohere2_moe family's names
    "head_dim": "head_dim", "layer_norm_eps": "norm_eps",
    "num_experts": "n_experts", "num_shared_experts": "n_shared_experts",
    "expert_selection_fn": "moe_scoring", "sliding_window": "sliding_window",
    "use_parallel_block": "parallel_block", "logit_scale": "logit_scale",
    "tie_word_embeddings": "tie_embeddings",
    # the lfm2_moe family's names
    "norm_eps": "norm_eps", "conv_L_cache": "conv_kernel",
    "num_dense_layers": "first_dense_layers",
    "use_expert_bias": "moe_select_bias", "use_qk_norm": "qk_norm",
    # the gigachat3_5 family's names
    "linear_num_key_heads": "linear_key_heads",
    "linear_num_value_heads": "linear_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel",
    "linear_attn_o_norm_eps": "linear_norm_eps",
    "linear_sigmoid_gate_scale": "linear_gate_scale",
    "gated_attention": "gated_attention",
    "layernorm_gating_weight": "norm_gate_scale",
    "swiglu_limit": "swiglu_limit",
}


def config_from_published(published) -> dict:
    """``LlamaConfig`` fields from a published ``config.json`` (a path, or
    the object it holds).  Keys that say nothing about the shape are passed
    over; a key that asks for mathematics this model does not compute
    raises."""
    if not isinstance(published, dict):
        with open(published) as f:
            published = json.load(f)
    out = {field: type(getattr(LlamaConfig, field))(published[key])
           for key, field in _PUBLISHED_KEYS.items()
           if published.get(key) is not None}
    scaling = published.get("rope_scaling")
    if scaling:
        kind = scaling.get("type", scaling.get("rope_type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling of type {kind!r}: only 'yarn' "
                             "is computed here")
        names = {f.name for f in dataclasses.fields(YarnScaling)}
        out["rope_scaling"] = YarnScaling(
            **{k: v for k, v in scaling.items() if k in names})
    method = published.get("topk_method", "none")
    if method == "noaux_tc":
        # selection by scores plus a bias that is not in the gates, the
        # groups scored over the same sum: ``route``'s rule with a bias
        out["moe_select_bias"] = True
    elif method not in ("none", "greedy", "group_limited_greedy"):
        raise ValueError(f"topk_method {method!r} is not computed here "
                         "(llm/moe.py::route chooses the k best of the best "
                         "groups, with or without a selection bias)")
    if published.get("attention_bias") or published.get(
            "hidden_act", "silu") != "silu":
        raise ValueError("attention_bias and activations other than silu "
                         "are not computed here")
    out.update(_linear_fields(published))
    if published.get("layer_types"):
        out["layer_types"] = tuple(published["layer_types"])
        unknown = set(out["layer_types"]) - LAYER_KINDS
        if unknown:
            raise ValueError(
                f"layer_types entries {sorted(unknown)!r}: a layer mixes by "
                "'full_attention', 'sliding_attention', 'conv' or "
                "'linear_attention' here")
        if "sliding_attention" not in out["layer_types"]:
            out.pop("sliding_window", None)
    else:       # a window no layer is said to have
        out.pop("sliding_window", None)
    if "conv" not in out.get("layer_types", ()):    # taps no layer has
        out.pop("conv_kernel", None)
    if "linear_attention" in out.get("layer_types", ()) \
            and not published.get("linear_attention_type"):
        raise ValueError(
            "layer_types entries 'linear_attention' without "
            "linear_attention_type: which rule such a layer computes is "
            "not said")
    out.update(_unnamed_fields(published))
    return out


#: what a ``gigachat3_5`` config.json may name and is computed here
_LINEAR_TYPE = "GigaChat35GatedDeltaNet"
_LINEAR_GATING = "gated_rmsnorm_sigmoid_zero_centered"


def _linear_fields(published: dict) -> dict:
    """The fields of the keys the ``gigachat3_5`` family adds: linear-attention
    layers wherever ``full_attention_layers`` leaves them, the layer's norms
    and the latent attention's options.  What is not computed raises by name:
    another ``linear_attention_type`` or ``linear_gating_type``, a
    ``layernorm_type`` other than ``pre_post``, a ``norm_type`` other than
    ``ZeroCenteredGatedNorm``, multi-token prediction modules
    (``num_nextn_predict_layers``), a sigmoid on the shared expert, rotary
    pairs that are not interleaved, a ``qk_head_dim`` that is not nope + rope,
    and YaRN's ``mscale_all_dim`` without ``use_mla_scaling_factor``."""
    fields = {}
    kind = published.get("linear_attention_type")
    if kind is not None and kind != _LINEAR_TYPE:
        raise ValueError(f"linear_attention_type {kind!r}: only {_LINEAR_TYPE!r} "
                         "(the gated delta rule) is computed here")
    gating = published.get("linear_gating_type")
    if gating is not None and gating != _LINEAR_GATING:
        raise ValueError(f"linear_gating_type {gating!r}: only {_LINEAR_GATING!r} "
                         "is computed here")
    full = published.get("full_attention_layers")
    if kind is not None:
        if full is None or published.get("layer_types"):
            raise ValueError(
                "linear_attention_type needs full_attention_layers, and no "
                "layer_types beside it: which layers are linear is not said")
        n = int(published["num_hidden_layers"])
        if any(not 0 <= int(i) < n for i in full):
            raise ValueError(f"full_attention_layers {full!r}: not layers of {n}")
        fields["layer_types"] = tuple(
            "full_attention" if i in {int(j) for j in full}
            else "linear_attention" for i in range(n))
    elif full is not None:
        raise ValueError("full_attention_layers without linear_attention_type: "
                         "what the other layers compute is not said")
    sandwich = published.get("layernorm_type")
    if sandwich is not None:
        if sandwich != "pre_post":
            raise ValueError(f"layernorm_type {sandwich!r}: only 'pre_post' (norms "
                             "before and after the mixer and the feed-forward) "
                             "is computed here")
        fields["sandwich_norm"] = True
    norm = published.get("norm_type")
    if norm is not None:
        if norm != "ZeroCenteredGatedNorm":
            raise ValueError(f"norm_type {norm!r}: only 'ZeroCenteredGatedNorm' is "
                             "computed here")
        fields["norm_kind"] = "gated"
    if int(published.get("num_nextn_predict_layers") or 0) > 0:
        raise ValueError(
            f"num_nextn_predict_layers {published['num_nextn_predict_layers']}: "
            "multi-token prediction modules are not built here (they draft "
            "tokens and leave the main model's logits alone; the engine does "
            "not speculate)")
    if published.get("use_shared_expert_sigmoid"):
        raise ValueError("use_shared_expert_sigmoid true: a sigmoid gate on the "
                         "shared expert is not computed here")
    if published.get("rope_interleave") is False:
        raise ValueError("rope_interleave false: _rope turns interleaved pairs")
    head = published.get("qk_head_dim")
    if head is not None and int(head) != int(published.get("qk_nope_head_dim", 0)) \
            + int(published.get("qk_rope_head_dim", 0)):
        raise ValueError(f"qk_head_dim {head}: not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if published.get("use_mla_scaling_factor") is False and (
            published.get("rope_scaling") or {}).get("mscale_all_dim"):
        raise ValueError(
            "use_mla_scaling_factor false with rope_scaling.mscale_all_dim: "
            "latent attention's softmax scale takes YaRN's mscale squared here")
    return fields


def _unnamed_fields(published: dict) -> dict:
    """The fields that a ``config.json`` states by more than one key's name,
    read from the keys it carries and from no family's name: a
    ``layer_norm_eps`` with no ``rms_norm_eps`` is a norm that subtracts the
    mean; ``shared_expert_combination_strategy`` says how the shared experts
    join the routed sum; ``rope_full_layers`` (no published key: a
    configuration states it as assumed) says whether the full-attention layers
    carry the rotary embedding.  A key that asks for what is not computed
    raises by name; the ``prefix_dense_*`` keys do nothing without leading
    dense layers and are passed over."""
    if published.get("conv_bias"):
        raise ValueError("conv_bias true: the short convolution and its "
                         "projections are computed without a bias here")
    if published.get("use_expert_bias") and not published.get(
            "norm_topk_prob", True):
        raise ValueError(
            "use_expert_bias with norm_topk_prob false: gates that are the "
            "chosen experts' scores, not renormalised over the chosen, have "
            "not been compared with a reference under a selection bias")
    if float(published.get("rotary_pct", 1)) != 1.0:
        raise ValueError(f"rotary_pct {published['rotary_pct']!r}: the rotary "
                         "embedding turns every pair of a head, or none")
    if not published.get("use_gated_activation", True):
        raise ValueError("use_gated_activation false: only the gated "
                         "(SwiGLU) feed-forward is computed here")
    if published.get("position_embedding_type", "rope_gptj") != "rope_gptj":
        raise ValueError(
            f"position_embedding_type "
            f"{published['position_embedding_type']!r}: _rope turns "
            "interleaved pairs (rope_gptj)")
    if int(published.get("first_k_dense_replace") or 0) > 0 and any(
            k.startswith("prefix_dense_") for k in published):
        raise ValueError(
            "first_k_dense_replace > 0 with prefix_dense_* keys: leading "
            "dense layers of a width and a window pattern of their own are "
            "not computed here")
    fields = {}
    if (published.get("layer_norm_eps") is not None
            and published.get("rms_norm_eps") is None):
        fields["norm_kind"] = "layer"
    if published.get("rope_full_layers") is not None:
        fields["rope_full_layers"] = bool(published["rope_full_layers"])
    how = published.get("shared_expert_combination_strategy")
    if how is not None:
        if how != "average":
            raise ValueError(
                f"shared_expert_combination_strategy {how!r}: only "
                "'average' (the mean of the shared experts' outputs, added "
                "to the routed sum) is computed here")
        if published.get("num_shared_experts"):
            fields["shared_expert_scale"] = 1.0 / int(
                published["num_shared_experts"])
    return fields


def config_from_args(args, vocab: Optional[int] = None) -> LlamaConfig:
    name = str(getattr(args, "model", "tiny_llama")).lower()
    if name in ("llama", "llama2_7b", "llama-2-7b"):
        base = LLAMA2_7B
    else:
        base = TINY
    overrides = {}
    # a published config.json first: the arguments below override it
    published = getattr(args, "llm_config_json", None)
    if published:
        overrides.update(config_from_published(published))
    for field in ("dim", "n_layers", "n_heads", "n_kv_heads", "ffn_dim",
                  "max_seq_len"):
        v = getattr(args, f"llm_{field}", None)
        if v is not None:
            overrides[field] = int(v)
    for field in ("rope_theta", "norm_eps"):
        v = getattr(args, f"llm_{field}", None)
        if v is not None:
            overrides[field] = float(v)
    held = getattr(args, "llm_experts_held", None)
    if held:        # "first,count" or a pair
        first, count = (held.split(",") if isinstance(held, str) else held)
        overrides["experts_held"] = (int(first), int(count))
    if vocab:
        overrides["vocab_size"] = int(vocab)
    impl = getattr(args, "attn_impl", None)
    if impl:
        overrides["attn_impl"] = str(impl)
    remat = getattr(args, "llm_remat", None)
    if remat:
        overrides["remat"] = str(remat)
    kvd = getattr(args, "llm_kv_cache_dtype", None)
    if kvd:
        overrides["kv_cache_dtype"] = str(kvd)
    dt = getattr(args, "model_dtype", None)
    if dt:
        overrides["dtype"] = jnp.dtype(str(dt)).type
    sx = getattr(args, "streaming_xent_chunk", None)
    if sx is not None:
        overrides["streaming_xent_chunk"] = int(sx)
    n_experts = getattr(args, "n_experts", None)
    if n_experts is not None:
        overrides["n_experts"] = int(n_experts)
        overrides["moe_top_k"] = int(getattr(args, "moe_top_k", 2))
    return dataclasses.replace(base, **overrides)


def build_causal_lm(args, vocab: Optional[int] = None) -> FlaxModel:
    cfg = config_from_args(args, vocab)
    if cfg.lora_rank == 0 and cfg.param_dtype is None:
        # the generic trainers behind FlaxModel train the WHOLE param tree
        # (FlaxModel.init drops the "lora" collection, so dense training is
        # the only mode here) — keep f32 masters: bf16-stored weights lose
        # adamw updates below ~2^-9 relative. bf16 storage stays for the
        # frozen-base paths (FedLLMAPI / LoRA CausalLMTrainer / serving).
        cfg = dataclasses.replace(cfg, param_dtype=jnp.float32)
    seq = int(getattr(args, "seq_len", min(cfg.max_seq_len, 512)))
    return FlaxModel(LlamaLM(cfg), (seq,), input_dtype=jnp.int32, task="lm")


def causal_nll(logits, targets):
    """Mean token NLL — THE loss both the federated (fedllm.py) and
    centralized (trainer.py) paths share; fp32 softmax regardless of compute
    dtype."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def per_sequence_loglik(logits, targets):
    """Mean per-sequence token log-likelihood (for masked eval sums)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(ll, axis=-1)


def param_sharding_rules(params, mesh) -> Any:
    """PartitionSpec per parameter: embeddings/FFN tensor-sharded on
    ``model``; 2-D kernels FSDP-sharded on their largest divisible dim;
    small vectors replicated."""
    msize = mesh.shape[MODEL_AXIS]

    def rule(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        if leaf.ndim == 1:
            return P()
        if "tok_embed" in names or "lm_head" in names:
            # shard vocab dim
            dim = 0 if leaf.shape[0] % msize == 0 else (
                1 if leaf.shape[-1] % msize == 0 else None)
        elif any(n in names for n in ("w_gate", "w_up")):
            dim = 1 if leaf.shape[1] % msize == 0 else None
        elif "w_down" in names:
            dim = 0 if leaf.shape[0] % msize == 0 else None
        elif any(n in names for n in ("wq", "wk", "wv")):
            dim = 1 if leaf.shape[1] % msize == 0 else None
        elif "wo" in names:
            dim = 0 if leaf.shape[0] % msize == 0 else None
        else:  # FSDP fallback: largest divisible dim
            dim = None
            for d in sorted(range(leaf.ndim), key=lambda d: -leaf.shape[d]):
                if leaf.shape[d] % msize == 0:
                    dim = d
                    break
        if dim is None:
            return P()
        spec = [None] * leaf.ndim
        spec[dim] = MODEL_AXIS
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, params)
