"""Operations and bytes a latent-attention, sparse-expert decoder requires,
from shapes only, for the share of it that one chip holds (``experts_held``).
What the mathematics needs, whatever implements it: padding, recomputation
and rows computed for idle slots are not counted."""

from __future__ import annotations


def _dims(cfg):
    share = cfg.get("experts_held") or {"count": cfg["n_routed_experts"], "of": cfg["n_routed_experts"]}
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
                rkv=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
                shared=cfg.get("n_shared_experts", 0), k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
                count=share["count"], of=share["of"])


def mla_params(cfg) -> int:
    """q_a, q_b, kv_a, kv_b, o."""
    m = _dims(cfg)
    return (m["d"] * m["rq"] + m["rq"] * m["h"] * (m["nope"] + m["rope"]) + m["d"] * (m["rkv"] + m["rope"])
            + m["rkv"] * m["h"] * (m["nope"] + m["dv"]) + m["h"] * m["dv"] * m["d"])


def expert_params(cfg) -> int:
    m = _dims(cfg)
    return 3 * m["d"] * m["fe"]


def fixed_matmul_params(cfg) -> int:
    """Every matrix a token meets whatever its routing: attention, the dense
    layers' SwiGLU, the shared expert and the router of the sparse ones, the
    head.  (The embedding is a gather.)"""
    m = _dims(cfg)
    sparse = m["layers"] - m["dense"]
    return (m["layers"] * mla_params(cfg) + m["dense"] * 3 * m["d"] * m["f"]
            + sparse * (m["shared"] * expert_params(cfg) + m["d"] * m["of"]) + m["d"] * m["v"])


def held_expert_params(cfg) -> int:
    m = _dims(cfg)
    return (m["layers"] - m["dense"]) * m["count"] * expert_params(cfg)


def total_params(cfg) -> int:
    m = _dims(cfg)
    norms = m["layers"] * (2 * m["d"] + m["rq"] + m["rkv"]) + m["d"]
    return fixed_matmul_params(cfg) + held_expert_params(cfg) + m["v"] * m["d"] + norms


def lora_params(cfg) -> int:
    m = _dims(cfg)
    r = cfg["lora"]["rank"]
    shapes = ((m["d"], m["rq"]), (m["rq"], m["h"] * (m["nope"] + m["rope"])),
              (m["d"], m["rkv"] + m["rope"]), (m["h"] * m["dv"], m["d"]))
    return m["layers"] * sum(r * (a + b) for a, b in shapes)


def latent_bytes_per_token(cfg, itemsize: int = 2) -> int:
    m = _dims(cfg)
    return (m["rkv"] + m["rope"]) * itemsize * m["layers"]


def forward_flops_per_token(cfg, cell) -> float:
    """One token through the share: 2 flops a parameter of every fixed matrix
    and of LoRA, and of the routed experts at their EXPECTED number a token
    here, ``num_experts_per_tok * count / of`` (8 x 12/192 = 1/2 of an expert:
    uniform routing; what a run's routing really sent here is the counter
    ``expert_pairs``).  Attention over the cache is left out, as
    ``decoder.forward_flops_per_token`` leaves it: at 2k tokens the expanded
    form's products would add a fifth, so this share reads low, never high."""
    m = _dims(cfg)
    routed = (m["layers"] - m["dense"]) * m["k"] * m["count"] / m["of"] * expert_params(cfg)
    return 2.0 * (fixed_matmul_params(cfg) + routed + lora_params(cfg))


def tick_least_seconds(cfg, cell, counters, peak) -> float:
    """One decode tick of the cell's slots.  Bytes: every fixed matrix once
    (the router in float32), the held experts that got a token
    (``experts_hit_mean`` a sparse layer), the live latent, the adapters in
    the bank.  Operations: the matrices at one row a slot, the routed experts
    at the pairs counted, the absorbed products over the live latent
    (``2 * h * (2 * rank + rope)`` a cached token and layer).  The larger of
    the two times."""
    live, hit = counters.get("live_kv_tokens_mean"), counters.get("experts_hit_mean")
    if live is None or hit is None:
        return 0.0
    m = _dims(cfg)
    slots = int(cell["engine"]["slots"])
    sparse = m["layers"] - m["dense"]
    adapters = int(cell["traffic"]["adapters"]["count"]) * lora_params(cfg) * 4
    nbytes = (2 * fixed_matmul_params(cfg) + 2 * sparse * m["d"] * m["of"]
              + 2 * sparse * hit * expert_params(cfg) + latent_bytes_per_token(cfg) * live + adapters)
    pairs = slots * sparse * m["k"] * m["count"] / m["of"]
    flops = (2.0 * slots * (fixed_matmul_params(cfg) + lora_params(cfg)) + 2.0 * pairs * expert_params(cfg)
             + 2.0 * m["h"] * (2 * m["rkv"] + m["rope"]) * m["layers"] * live)
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])


def chunk_least_seconds(cfg, cell, counters, peak) -> float:
    """One prefill chunk of ``prefill_chunk_tokens`` rows of one request.
    Bytes: every fixed matrix and every held expert once (hundreds of rows
    reach them all), the slot's latent so far.  Operations: the matrices at
    the chunk's rows with the head at one, the routed experts at their
    expected share, the expanded attention's products over the context, which
    for a chunk drawn anywhere in a prompt is half the mix's mean prompt."""
    m = _dims(cfg)
    rows = int(cell["engine"]["prefill_chunk_tokens"])
    prompt = cell["traffic"]["prompt"]
    context = (prompt["lo"] + prompt["hi"]) / 4.0
    sparse = m["layers"] - m["dense"]
    nbytes = (2 * (fixed_matmul_params(cfg) + held_expert_params(cfg)) + 2 * sparse * m["d"] * m["of"]
              + latent_bytes_per_token(cfg) * context + lora_params(cfg) * 4)
    routed = sparse * m["k"] * m["count"] / m["of"] * expert_params(cfg)
    flops = (2.0 * rows * (fixed_matmul_params(cfg) - m["d"] * m["v"] + routed + lora_params(cfg))
             + 2.0 * m["d"] * m["v"]
             + 2.0 * rows * m["h"] * (m["nope"] + m["rope"] + m["dv"]) * context * m["layers"])
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])
