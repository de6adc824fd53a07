"""One decode tick of a batch of slots: the bytes it has to read.  Every
matmul weight once, the keys and values of the live tokens, and the adapters
of the rows in use.  Decode at these batch sizes is bound by bytes."""

from __future__ import annotations

from . import decoder


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * hd * itemsize * cfg["num_hidden_layers"]


def weight_bytes(cfg, itemsize: int = 2) -> int:
    return decoder.matmul_params(cfg) * itemsize


def tick_bytes(cfg, live_tokens: float) -> float:
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * live_tokens


def least_seconds(cfg, cell, counters, peak) -> float:
    live = counters.get("live_kv_tokens_mean")
    if live is None:
        return 0.0
    slots = int(cell["engine"]["slots"])
    flops = decoder.forward_flops_per_token(cfg, cell) * slots
    return max(tick_bytes(cfg, live) / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops_per_s"])
