"""Operations and bytes an ``lfm2_moe`` decoder requires (gated
short-convolution layers and grouped-query attention layers in one stack,
leading dense SwiGLU layers and routed experts after them, a tied head), from
shapes only.  What the mathematics needs, whatever implements it: padding,
recomputation, rows computed for idle slots and keys gathered only to be
masked are not counted."""

from __future__ import annotations


def _dims(cfg):
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    conv = sum(k == "conv" for k in kinds)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dense = min(cfg["num_dense_layers"], len(kinds))
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], hd=d // h, f=cfg["intermediate_size"],
                fe=cfg["moe_intermediate_size"], e=cfg["num_experts"], k=cfg["num_experts_per_tok"],
                taps=cfg["conv_L_cache"], v=cfg["vocab_size"], layers=len(kinds), conv_layers=conv,
                attn_layers=len(kinds) - conv, dense_layers=dense, sparse_layers=len(kinds) - dense)


def attention_params(cfg) -> int:
    """wq, wk, wv, wo."""
    m = _dims(cfg)
    return 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]


def conv_params(cfg) -> int:
    """in_proj (d -> 3d) and out_proj: the matrices; the taps are elementwise."""
    m = _dims(cfg)
    return 4 * m["d"] * m["d"]


def expert_params(cfg) -> int:
    m = _dims(cfg)
    return 3 * m["d"] * m["fe"]


def fixed_matmul_params(cfg) -> int:
    """Every matrix a token meets whatever its routing: the mixers, the dense
    feed-forwards, the routers, and the head (the embedding, transposed; as an
    embedding it is a gather)."""
    m = _dims(cfg)
    return (m["attn_layers"] * attention_params(cfg) + m["conv_layers"] * conv_params(cfg)
            + m["dense_layers"] * 3 * m["d"] * m["f"] + m["sparse_layers"] * m["d"] * m["e"]
            + m["d"] * m["v"])


def all_expert_params(cfg) -> int:
    m = _dims(cfg)
    return m["sparse_layers"] * m["e"] * expert_params(cfg)


def total_params(cfg) -> int:
    """The tied embedding once; two norms a layer, the final one, the q and k
    scales, the taps and the selection bias."""
    m = _dims(cfg)
    small = ((2 * m["layers"] + 1) * m["d"] + m["attn_layers"] * 2 * m["hd"]
             + m["conv_layers"] * m["d"] * m["taps"] + m["sparse_layers"] * m["e"])
    return fixed_matmul_params(cfg) + all_expert_params(cfg) + small


def lora_params(cfg) -> int:
    m = _dims(cfg)
    r = cfg["lora"]["rank"]
    attn = ((m["d"], m["h"] * m["hd"]), (m["d"], m["kv"] * m["hd"]), (m["d"], m["kv"] * m["hd"]),
            (m["h"] * m["hd"], m["d"]))
    conv = ((m["d"], 3 * m["d"]), (m["d"], m["d"]))
    return (m["attn_layers"] * sum(r * (a + b) for a, b in attn)
            + m["conv_layers"] * sum(r * (a + b) for a, b in conv))


def kv_bytes_per_token_and_layer(cfg, itemsize: int = 2) -> int:
    m = _dims(cfg)
    return 2 * m["kv"] * m["hd"] * itemsize


def state_bytes_per_row_and_layer(cfg, itemsize: int = 2) -> int:
    """A slot's convolution state in one layer: the last ``taps - 1`` rows of
    the gated input."""
    m = _dims(cfg)
    return (m["taps"] - 1) * m["d"] * itemsize


def forward_flops_per_token(cfg, cell) -> float:
    """One token: 2 flops a parameter of every fixed matrix, of LoRA and of
    the ``num_experts_per_tok`` experts of every sparse layer.  Attention over
    the cache and the convolution's taps are left out, as the other
    configurations' functions leave the former: the share reads low, never
    high."""
    m = _dims(cfg)
    routed = m["sparse_layers"] * m["k"] * expert_params(cfg)
    return 2.0 * (fixed_matmul_params(cfg) + routed + lora_params(cfg))


def tick_least_seconds(cfg, cell, counters, peak) -> float:
    """One decode tick of the cell's slots.  Bytes: every fixed matrix once
    (the router in float32), the experts that got a token
    (``experts_hit_mean`` a layer), the live K/V of the attention layers
    (``live_kv_tokens_mean``), a row of state read and written for every slot
    and convolution layer, the adapters in the bank.  Operations: the matrices
    at one row a slot, the experts at ``num_experts_per_tok`` pairs a slot and
    layer, the scores and the weighted sum over the live keys.  The larger of
    the two times."""
    live = counters.get("live_kv_tokens_mean")
    hit = counters.get("experts_hit_mean")
    if live is None or hit is None:
        return 0.0
    m = _dims(cfg)
    slots = int(cell["engine"]["slots"])
    adapters = int(cell["traffic"]["adapters"]["count"]) * lora_params(cfg) * 4
    state = 2 * slots * m["conv_layers"] * state_bytes_per_row_and_layer(cfg)
    nbytes = (2 * fixed_matmul_params(cfg) + 2 * m["sparse_layers"] * m["d"] * m["e"]
              + 2 * m["sparse_layers"] * hit * expert_params(cfg)
              + kv_bytes_per_token_and_layer(cfg) * m["attn_layers"] * live + state + adapters)
    pairs = slots * m["sparse_layers"] * m["k"]
    flops = (2.0 * slots * (fixed_matmul_params(cfg) + lora_params(cfg)) + 2.0 * pairs * expert_params(cfg)
             + 4.0 * m["h"] * m["hd"] * m["attn_layers"] * live)
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])


def chunk_least_seconds(cfg, cell, counters, peak) -> float:
    """One prefill chunk of ``prefill_chunk_tokens`` rows of one request, the
    mean over the chunks of the mix's mean prompt.  Bytes: every fixed matrix
    and every expert once (a chunk's rows reach them all), the slot's K/V so
    far, its rows of state read and written, one adapter.  Operations: the
    matrices at the chunk's real rows with the head at one, the experts at
    ``num_experts_per_tok`` a row, the scores and the weighted sum over the
    pairs a causal mask leaves."""
    m = _dims(cfg)
    rows = int(cell["engine"]["prefill_chunk_tokens"])
    p = cell["traffic"]["prompt"]
    n = (int(p["lo"]) + int(p["hi"])) // 2
    chunks = [(min(rows, n - cs), cs) for cs in range(0, n, rows)]
    real = sum(r for r, _ in chunks) / len(chunks)
    keys = sum(cs + r for r, cs in chunks) / len(chunks)
    pairs = sum(r * cs + r * (r + 1) / 2 for r, cs in chunks) / len(chunks)
    nbytes = (2 * (fixed_matmul_params(cfg) + all_expert_params(cfg)) + 2 * m["sparse_layers"] * m["d"] * m["e"]
              + kv_bytes_per_token_and_layer(cfg) * m["attn_layers"] * keys
              + 2 * m["conv_layers"] * state_bytes_per_row_and_layer(cfg) + lora_params(cfg) * 4)
    routed = m["sparse_layers"] * m["k"] * expert_params(cfg)
    flops = (2.0 * real * (fixed_matmul_params(cfg) - m["d"] * m["v"] + routed + lora_params(cfg))
             + 2.0 * m["d"] * m["v"] + 4.0 * m["h"] * m["hd"] * m["attn_layers"] * pairs)
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])
