"""Transformer text classifier — the DistilBERT-class FedNLP workload
(reference app zoo: ``python/examples/federate/prebuilt_jobs/fednlp``
fine-tunes HF DistilBERT for 20news/agnews classification; here the encoder
is built from this repo's own attention ops, TPU-first).

Bidirectional (non-causal) encoder blocks reuse the fused attention in
:mod:`fedml_tpu.ops.attention`; pooling is masked mean over non-pad tokens;
everything static-shaped for one compiled step.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import blockwise_attention, flash_attention


class EncoderBlock(nn.Module):
    dim: int
    n_heads: int
    ffn_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, pad_mask):
        # pre-norm attention; pad keys excluded by masking scores via a
        # large negative bias folded into v? — simplest correct route:
        # zero pad positions after attention and renormalize via the mask
        h = nn.LayerNorm(dtype=self.dtype)(x)
        b, s, _ = h.shape
        head_dim = self.dim // self.n_heads
        dense = lambda name: nn.Dense(self.dim, use_bias=False,
                                      dtype=self.dtype, name=name)
        q = dense("wq")(h).reshape(b, s, self.n_heads, head_dim)
        k = dense("wk")(h).reshape(b, s, self.n_heads, head_dim)
        v = dense("wv")(h).reshape(b, s, self.n_heads, head_dim)
        # zero out pad keys/values so they contribute nothing but a uniform
        # additive term, then drop pad queries on the way out
        key_mask = pad_mask[:, :, None, None]
        k = (k * key_mask).transpose(0, 2, 1, 3)
        v = (v * key_mask).transpose(0, 2, 1, 3)
        q = q.transpose(0, 2, 1, 3)
        if jax.default_backend() == "tpu":
            att = flash_attention(q, k, v, False, None)
        else:
            att = blockwise_attention(q, k, v, causal=False)
        att = att.transpose(0, 2, 1, 3).reshape(b, s, self.dim)
        x = x + dense("wo")(att) * pad_mask[:, :, None]
        h = nn.LayerNorm(dtype=self.dtype)(x)
        ff = nn.Dense(self.ffn_dim, dtype=self.dtype, name="ff_up")(h)
        ff = nn.Dense(self.dim, dtype=self.dtype, name="ff_down")(
            nn.gelu(ff))
        return x + ff * pad_mask[:, :, None]


class TextTransformerClassifier(nn.Module):
    """Token ids (B, S) int32, 0 = padding → class logits (B, C)."""

    vocab_size: int
    num_classes: int
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    ffn_dim: int = 512
    max_len: int = 512
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        pad_mask = (tokens > 0).astype(self.dtype)          # (B, S)
        x = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     name="tok_embed")(tokens)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (self.max_len, self.dim))
        x = x + pos[: tokens.shape[1]][None].astype(self.dtype)
        for i in range(self.n_layers):
            x = EncoderBlock(self.dim, self.n_heads, self.ffn_dim,
                             self.dtype, name=f"layer_{i}")(x, pad_mask)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        denom = jnp.maximum(pad_mask.sum(-1, keepdims=True), 1.0)
        pooled = (x * pad_mask[:, :, None]).sum(1) / denom  # masked mean
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="classifier")(pooled)
