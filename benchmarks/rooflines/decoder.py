"""Operations a dense decoder requires, from shapes only.  Recomputation
(remat) and padding are not counted: this is what the mathematics needs,
whatever implements it."""

from __future__ import annotations

from . import attention


def _dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, cfg["num_key_value_heads"], hd, cfg["intermediate_size"], \
        cfg["vocab_size"], cfg["num_hidden_layers"]


def layer_matmul_params(cfg) -> int:
    d, h, kv, hd, f, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def matmul_params(cfg) -> int:
    """Parameters that take part in a matrix product per token: the layers and
    the head.  The embedding table is a gather and does none."""
    d, _, _, _, _, v, n = _dims(cfg)
    return n * layer_matmul_params(cfg) + d * v


def total_params(cfg) -> int:
    d, _, _, _, _, v, n = _dims(cfg)
    return matmul_params(cfg) + v * d + (2 * n + 1) * d


def lora_params(cfg) -> int:
    d, h, kv, hd, _, _, n = _dims(cfg)
    r = cfg["lora"]["rank"]
    shapes = ((d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d))
    return n * sum(r * (a + b) for a, b in shapes)


def train_flops_per_token(cfg, cell) -> float:
    """Forward and backward of one trained token with the base frozen: 2N
    forward, 2N for the activations' gradients, none for the base's weights;
    LoRA's own 2n forward and 4n backward; causal attention at the cell's
    sequence length."""
    s = int(cell["traffic"]["seq_len"])
    att = attention.causal_flops(cfg, s, backward=True) / s
    return 4.0 * matmul_params(cfg) + 6.0 * lora_params(cfg) + att


def forward_flops_per_token(cfg, cell) -> float:
    """One token through the served model: 2N and LoRA's 2n.  Attention over
    the cache is left out (it depends on each request's depth, and at these
    lengths is under a tenth of 2N)."""
    return 2.0 * matmul_params(cfg) + 2.0 * lora_params(cfg)
