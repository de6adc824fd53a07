"""Operations and bytes a ``cohere2_moe`` decoder requires (window and full
attention layers in one stack, a parallel block, routed experts beside shared
ones, a tied head), from shapes only, for the share of it that one chip holds
(``experts_held``).  What the mathematics needs, whatever implements it:
padding, recomputation, rows computed for idle slots and keys gathered only to
be masked are not counted."""

from __future__ import annotations


def _dims(cfg):
    share = cfg.get("experts_held") or {"count": cfg["num_experts"], "of": cfg["num_experts"]}
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    window = sum(k == "sliding_attention" for k in kinds)
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], fe=cfg["intermediate_size"], shared=cfg["num_shared_experts"],
                k=cfg["num_experts_per_tok"], v=cfg["vocab_size"], layers=len(kinds),
                window_layers=window, full_layers=len(kinds) - window, w=cfg["sliding_window"],
                count=share["count"], of=share["of"])


def attention_params(cfg) -> int:
    """wq, wk, wv, wo."""
    m = _dims(cfg)
    return m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"] + m["h"] * m["hd"] * m["d"]


def expert_params(cfg) -> int:
    m = _dims(cfg)
    return 3 * m["d"] * m["fe"]


def fixed_matmul_params(cfg) -> int:
    """Every matrix a token meets whatever its routing: attention, the shared
    experts and the router of every layer, and the head (the embedding,
    transposed; as an embedding it is a gather)."""
    m = _dims(cfg)
    return (m["layers"] * (attention_params(cfg) + m["shared"] * expert_params(cfg) + m["d"] * m["of"])
            + m["d"] * m["v"])


def held_expert_params(cfg) -> int:
    m = _dims(cfg)
    return m["layers"] * m["count"] * expert_params(cfg)


def total_params(cfg) -> int:
    """The tied embedding once; a norm a layer and the final one."""
    m = _dims(cfg)
    return fixed_matmul_params(cfg) + held_expert_params(cfg) + (m["layers"] + 1) * m["d"]


def lora_params(cfg) -> int:
    m = _dims(cfg)
    r = cfg["lora"]["rank"]
    shapes = ((m["d"], m["h"] * m["hd"]), (m["d"], m["kv"] * m["hd"]), (m["d"], m["kv"] * m["hd"]),
              (m["h"] * m["hd"], m["d"]))
    return m["layers"] * sum(r * (a + b) for a, b in shapes)


def kv_bytes_per_token_and_layer(cfg, itemsize: int = 2) -> int:
    m = _dims(cfg)
    return 2 * m["kv"] * m["hd"] * itemsize


def forward_flops_per_token(cfg, cell) -> float:
    """One token through the share: 2 flops a parameter of every fixed matrix
    and of LoRA, and of the routed experts at their EXPECTED number a token
    here, ``num_experts_per_tok * count / of`` (8 x 16/128 = one expert:
    uniform routing; what a run's routing really sent here is the counter
    ``expert_pairs``).  Attention over the cache is left out, as the other
    configurations' functions leave it: at these lengths its products would add
    a quarter (a window layer's context capped at the window), so this share
    reads low, never high."""
    m = _dims(cfg)
    routed = m["layers"] * m["k"] * m["count"] / m["of"] * expert_params(cfg)
    return 2.0 * (fixed_matmul_params(cfg) + routed + lora_params(cfg))


def tick_least_seconds(cfg, cell, counters, peak) -> float:
    """One decode tick of the cell's slots.  Bytes: every fixed matrix once
    (the router in float32), the held experts that got a token
    (``experts_hit_mean`` a layer), the live K/V by pool kind (a full layer
    reads ``live_kv_tokens_mean``, a window layer ``live_window_tokens_mean``:
    each request's depth capped at the window), the adapters in the bank.
    Operations: the matrices at one row a slot, the routed experts at their
    expected pairs, the scores and the weighted sum over the live keys
    (``4 * h * head_dim`` a cached token and layer).  The larger of the two
    times."""
    live = counters.get("live_kv_tokens_mean")
    held = counters.get("live_window_tokens_mean")
    hit = counters.get("experts_hit_mean")
    if live is None or held is None or hit is None:
        return 0.0
    m = _dims(cfg)
    slots = int(cell["engine"]["slots"])
    adapters = int(cell["traffic"]["adapters"]["count"]) * lora_params(cfg) * 4
    cached = m["full_layers"] * live + m["window_layers"] * held        # token-layers
    nbytes = (2 * fixed_matmul_params(cfg) + 2 * m["layers"] * m["d"] * m["of"]
              + 2 * m["layers"] * hit * expert_params(cfg)
              + kv_bytes_per_token_and_layer(cfg) * cached + adapters)
    pairs = slots * m["layers"] * m["k"] * m["count"] / m["of"]
    flops = (2.0 * slots * (fixed_matmul_params(cfg) + lora_params(cfg)) + 2.0 * pairs * expert_params(cfg)
             + 4.0 * m["h"] * m["hd"] * cached)
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])


def chunk_contexts(cfg, cell):
    """The mix's chunks, each as (keys a full layer holds for it, query-key
    pairs in a full layer, keys a window layer holds for it, query-key pairs
    in a window layer), one for every chunk of every class's mean prompt,
    weighted by the class's share of a block."""
    m = _dims(cfg)
    rows = int(cell["engine"]["prefill_chunk_tokens"])
    out = []
    for c in cell["traffic"]["classes"]:
        n = (int(c["prompt"]["lo"]) + int(c["prompt"]["hi"])) // 2
        for cs in range(0, n, rows):
            real = min(rows, n - cs)
            # query i of the chunk (position cs + i) sees cs + i + 1 keys, or the window's
            full_pairs = real * cs + real * (real + 1) / 2
            window_pairs = sum(min(cs + i + 1, m["w"]) for i in range(real))
            out.append((int(c["per_block"]), cs + real, full_pairs,
                        min(cs + real, m["w"] + real - 1), window_pairs))
    return out


def chunk_least_seconds(cfg, cell, counters, peak) -> float:
    """One prefill chunk of ``prefill_chunk_tokens`` rows of one request, the
    mean over the mix's chunks (``chunk_contexts``).  Bytes: every fixed
    matrix and every held expert once (a thousand rows reach them all), the
    slot's K/V so far by pool kind, one adapter.  Operations: the matrices at
    the chunk's rows with the head at one, the routed experts at their
    expected share, the scores and the weighted sum over the pairs a causal
    (and, in a window layer, windowed) mask leaves."""
    m = _dims(cfg)
    rows = int(cell["engine"]["prefill_chunk_tokens"])
    chunks = chunk_contexts(cfg, cell)
    weight = sum(c[0] for c in chunks)
    full_keys = sum(c[0] * c[1] for c in chunks) / weight
    full_pairs = sum(c[0] * c[2] for c in chunks) / weight
    window_keys = sum(c[0] * c[3] for c in chunks) / weight
    window_pairs = sum(c[0] * c[4] for c in chunks) / weight
    nbytes = (2 * (fixed_matmul_params(cfg) + held_expert_params(cfg)) + 2 * m["layers"] * m["d"] * m["of"]
              + kv_bytes_per_token_and_layer(cfg) * (m["full_layers"] * full_keys
                                                      + m["window_layers"] * window_keys)
              + lora_params(cfg) * 4)
    routed = m["layers"] * m["k"] * m["count"] / m["of"] * expert_params(cfg)
    flops = (2.0 * rows * (fixed_matmul_params(cfg) - m["d"] * m["v"] + routed + lora_params(cfg))
             + 2.0 * m["d"] * m["v"]
             + 4.0 * m["h"] * m["hd"] * (m["full_layers"] * full_pairs + m["window_layers"] * window_pairs))
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])
