"""The least seconds the chip could take for one kernel's calls in one run of
a program, from the counts each family's roofline file already has: what the
mathematics needs, whatever implements it (a pool row's padding to whole
lanes, rows computed for idle slots and recomputation are not counted).

Each function takes (configuration, cell, the run's counters, the chip's
peaks), as ``readers/xplane.py``'s ``module_roofline_pct`` gives them, and
returns 0.0 where a counter it needs is missing."""

from __future__ import annotations

from . import attention, cohere2_moe, lfm2_moe, mla_moe


def _hit_experts_seconds(sparse_layers: int, expert_params: int, counters, peak) -> float:
    """The grouped-matmul kernels of one tick: the weights of the held experts
    that got a token (``experts_hit_mean`` a sparse layer), bf16, once.  At a
    few rows an expert the products are far under the bytes."""
    hit = counters.get("experts_hit_mean")
    if hit is None:
        return 0.0
    return 2.0 * sparse_layers * hit * expert_params / peak["hbm_bytes_per_s"]


def expert_gmm_mla_moe(cfg, cell, counters, peak) -> float:
    m = mla_moe._dims(cfg)
    return _hit_experts_seconds(m["layers"] - m["dense"], mla_moe.expert_params(cfg), counters, peak)


def expert_gmm_cohere2_moe(cfg, cell, counters, peak) -> float:
    m = cohere2_moe._dims(cfg)
    return _hit_experts_seconds(m["layers"], cohere2_moe.expert_params(cfg), counters, peak)


def expert_gmm_lfm2_moe(cfg, cell, counters, peak) -> float:
    m = lfm2_moe._dims(cfg)
    return _hit_experts_seconds(m["sparse_layers"], lfm2_moe.expert_params(cfg), counters, peak)


def attn_read_mla_moe(cfg, cell, counters, peak) -> float:
    """The ``latent_attention`` calls of one tick: the live latent, every
    layer's (``live_kv_tokens_mean`` rows of rank + rope numbers, bf16)."""
    live = counters.get("live_kv_tokens_mean")
    if live is None:
        return 0.0
    return mla_moe.latent_bytes_per_token(cfg) * live / peak["hbm_bytes_per_s"]


def attn_read_cohere2_moe(cfg, cell, counters, peak) -> float:
    """The ``paged_attention`` calls of one tick: the live K and V by pool
    kind (a full layer reads ``live_kv_tokens_mean``, a window layer
    ``live_window_tokens_mean``: each request's depth capped at the window)."""
    live, held = counters.get("live_kv_tokens_mean"), counters.get("live_window_tokens_mean")
    if live is None or held is None:
        return 0.0
    m = cohere2_moe._dims(cfg)
    cached = m["full_layers"] * live + m["window_layers"] * held
    return cohere2_moe.kv_bytes_per_token_and_layer(cfg) * cached / peak["hbm_bytes_per_s"]


def round_attention(cfg, cell, counters, peak) -> float:
    """The attention of one round, all layers: scores and weighted values over
    the causal half, forward and backward (``attention.causal_flops``), for the
    round's sequences (clients x local steps x batch).  The forward pass that
    ``remat`` computes again is not required work."""
    t = cell["traffic"]
    sequences = int(t["clients_per_round"]) * int(t["local_steps"]) * int(t["batch"])
    flops = sequences * attention.causal_flops(cfg, int(t["seq_len"]), backward=True)
    return flops / peak["bf16_flops_per_s"]
