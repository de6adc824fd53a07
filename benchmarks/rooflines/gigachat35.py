"""Operations and bytes a ``gigachat3_5`` decoder requires (gated delta-rule
linear-attention layers beside gated latent attention, a leading dense SwiGLU
layer, routed experts and a shared one after it), from shapes only, for the
share of it that one chip holds (``experts_held``).  What the mathematics
needs, whatever implements it: padding, recomputation, rows computed for idle
slots and the trash row are not counted."""

from __future__ import annotations


def _dims(cfg):
    share = cfg.get("experts_held") or {"count": cfg["n_routed_experts"], "of": cfg["n_routed_experts"]}
    layers = cfg["num_hidden_layers"]
    full = {int(i) for i in cfg["full_attention_layers"]}
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
                rkv=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                dv_mla=cfg["v_head_dim"], hk=hk, hv=hv, dk=dk, dvl=dv,
                taps=cfg["linear_conv_kernel_dim"], conv=2 * hk * dk + hv * dv,
                f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
                shared=cfg.get("n_shared_experts", 0), k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
                layers=layers, dense=cfg["first_k_dense_replace"], sparse=layers - cfg["first_k_dense_replace"],
                mla_layers=len(full & set(range(layers))), linear_layers=layers - len(full & set(range(layers))),
                count=share["count"], of=share["of"])


def mla_params(cfg) -> int:
    """q_a, q_b, kv_a, kv_b, o and the heads' gate."""
    m = _dims(cfg)
    return (m["d"] * m["rq"] + m["rq"] * m["h"] * (m["nope"] + m["rope"]) + m["d"] * (m["rkv"] + m["rope"])
            + m["rkv"] * m["h"] * (m["nope"] + m["dv_mla"]) + 2 * m["h"] * m["dv_mla"] * m["d"])


def linear_params(cfg) -> int:
    """q, k, v, z, b, a and o: the matrices; the taps are elementwise."""
    m = _dims(cfg)
    return m["d"] * (2 * m["hk"] * m["dk"] + 2 * m["hv"] * m["dvl"] + 2 * m["hv"]) + m["hv"] * m["dvl"] * m["d"]


def expert_params(cfg) -> int:
    m = _dims(cfg)
    return 3 * m["d"] * m["fe"]


def fixed_matmul_params(cfg) -> int:
    """Every matrix a token meets whatever its routing: the mixers, the dense
    layer's SwiGLU, the shared expert and the router of the sparse ones, the
    head.  (The embedding is a gather.)"""
    m = _dims(cfg)
    return (m["mla_layers"] * mla_params(cfg) + m["linear_layers"] * linear_params(cfg)
            + m["dense"] * 3 * m["d"] * m["f"]
            + m["sparse"] * (m["shared"] * expert_params(cfg) + m["d"] * m["of"]) + m["d"] * m["v"])


def held_expert_params(cfg) -> int:
    m = _dims(cfg)
    return m["sparse"] * m["count"] * expert_params(cfg)


def total_params(cfg) -> int:
    """The embedding and the head, every matrix, the held experts; the norms'
    gates, the latent norms, the taps, A_log, dt_bias, the output norms and
    the selection bias."""
    m = _dims(cfg)
    small = ((4 * m["layers"] + 1) * m["d"] + m["mla_layers"] * (m["rq"] + m["rkv"])
             + m["linear_layers"] * (m["conv"] * m["taps"] + 2 * m["hv"] + m["dvl"]) + m["sparse"] * m["of"])
    return fixed_matmul_params(cfg) + held_expert_params(cfg) + m["v"] * m["d"] + small


def lora_params(cfg) -> int:
    m = _dims(cfg)
    r = cfg["lora"]["rank"]
    mla = ((m["d"], m["rq"]), (m["rq"], m["h"] * (m["nope"] + m["rope"])), (m["d"], m["rkv"] + m["rope"]),
           (m["h"] * m["dv_mla"], m["d"]))
    lin = ((m["d"], m["hk"] * m["dk"]), (m["d"], m["hk"] * m["dk"]), (m["d"], m["hv"] * m["dvl"]),
           (m["hv"] * m["dvl"], m["d"]))
    return (m["mla_layers"] * sum(r * (a + b) for a, b in mla)
            + m["linear_layers"] * sum(r * (a + b) for a, b in lin))


def latent_bytes_per_token(cfg, itemsize: int = 2) -> int:
    m = _dims(cfg)
    return (m["rkv"] + m["rope"]) * itemsize * m["mla_layers"]


def state_bytes_per_row_and_layer(cfg) -> int:
    """A slot's state in one linear layer: the float32 matrix state of every
    value head, and the convolution's last ``taps - 1`` rows of ``[q; k; v]``
    in bfloat16."""
    m = _dims(cfg)
    return m["hv"] * m["dk"] * m["dvl"] * 4 + (m["taps"] - 1) * m["conv"] * 2


def state_seconds(cfg, lanes: float, peak) -> float:
    """The rows of state ``lanes`` lanes read and write in every linear layer."""
    m = _dims(cfg)
    return lanes * m["linear_layers"] * 2 * state_bytes_per_row_and_layer(cfg) / peak["hbm_bytes_per_s"]


def forward_flops_per_token(cfg, cell) -> float:
    """One token through the share: 2 flops a parameter of every fixed matrix
    and of LoRA, and of the routed experts at their EXPECTED number a token
    here, ``num_experts_per_tok * count / of`` (8 x 16/256 = 1/2 of an
    expert).  Attention over the cache and the delta rule's own products are
    left out: the share reads low, never high."""
    m = _dims(cfg)
    routed = m["sparse"] * m["k"] * m["count"] / m["of"] * expert_params(cfg)
    return 2.0 * (fixed_matmul_params(cfg) + routed + lora_params(cfg))


def tick_least_seconds(cfg, cell, counters, peak) -> float:
    """One decode tick of the cell's slots.  Bytes: every fixed matrix once
    (the router in float32), the held experts that got a token
    (``experts_hit_mean`` a sparse layer), the live latent, every live lane's
    rows of linear state read and written (``live_lanes_mean``), the adapters
    in the bank.  Operations: the matrices at one row a slot, the routed
    experts at their expected pairs, the absorbed products over the live
    latent.  The larger of the two times."""
    live, hit = counters.get("live_kv_tokens_mean"), counters.get("experts_hit_mean")
    lanes = counters.get("live_lanes_mean")
    if live is None or hit is None or lanes is None:
        return 0.0
    m = _dims(cfg)
    slots = int(cell["engine"]["slots"])
    adapters = int(cell["traffic"]["adapters"]["count"]) * lora_params(cfg) * 4
    nbytes = (2 * fixed_matmul_params(cfg) + 2 * m["sparse"] * m["d"] * m["of"]
              + 2 * m["sparse"] * hit * expert_params(cfg) + latent_bytes_per_token(cfg) * live + adapters)
    pairs = slots * m["sparse"] * m["k"] * m["count"] / m["of"]
    flops = (2.0 * slots * (fixed_matmul_params(cfg) + lora_params(cfg)) + 2.0 * pairs * expert_params(cfg)
             + 2.0 * m["h"] * (2 * m["rkv"] + m["rope"]) * m["mla_layers"] * live)
    return max(nbytes / peak["hbm_bytes_per_s"] + state_seconds(cfg, lanes, peak),
               flops / peak["bf16_flops_per_s"])


def chunk_least_seconds(cfg, cell, counters, peak) -> float:
    """One prefill chunk of ``prefill_chunk_tokens`` rows of one request.
    Bytes: every fixed matrix and every held expert once (hundreds of rows
    reach them all), the slot's latent so far, its rows of linear state read
    and written.  Operations: the matrices at the chunk's rows with the head
    at one, the routed experts at their expected share, the expanded
    attention's products over the context (half the mix's mean prompt)."""
    m = _dims(cfg)
    rows = int(cell["engine"]["prefill_chunk_tokens"])
    prompt = cell["traffic"]["prompt"]
    context = (prompt["lo"] + prompt["hi"]) / 4.0
    nbytes = (2 * (fixed_matmul_params(cfg) + held_expert_params(cfg)) + 2 * m["sparse"] * m["d"] * m["of"]
              + latent_bytes_per_token(cfg) * context + lora_params(cfg) * 4)
    routed = m["sparse"] * m["k"] * m["count"] / m["of"] * expert_params(cfg)
    flops = (2.0 * rows * (fixed_matmul_params(cfg) - m["d"] * m["v"] + routed + lora_params(cfg))
             + 2.0 * m["d"] * m["v"]
             + 2.0 * rows * m["h"] * (m["nope"] + m["rope"] + m["dv_mla"]) * context * m["mla_layers"])
    return max(nbytes / peak["hbm_bytes_per_s"] + state_seconds(cfg, 1, peak),
               flops / peak["bf16_flops_per_s"])


# -- the least seconds of one kind of instructions of a tick (``readers/ops.py``'s
# ``kernel_roofline_pct``), beside ``kernels.py``'s functions of the other families

def linear_state_kernel(cfg, cell, counters, peak) -> float:
    """The linear layers' state in one tick, whatever implements it: every live
    lane's matrix state and convolution rows read once and written once, in
    every linear layer (``live_lanes_mean``).  The rule's products are far
    under the bytes (2 x 4 d_k d_v flops a head and lane against 8 d_k d_v
    bytes)."""
    lanes = counters.get("live_lanes_mean")
    return 0.0 if lanes is None else state_seconds(cfg, lanes, peak)


def expert_gmm_kernel(cfg, cell, counters, peak) -> float:
    """The grouped-matmul kernels of one tick: the weights of the held experts
    that got a token (``experts_hit_mean`` a sparse layer), bf16, once."""
    hit = counters.get("experts_hit_mean")
    if hit is None:
        return 0.0
    return 2.0 * _dims(cfg)["sparse"] * hit * expert_params(cfg) / peak["hbm_bytes_per_s"]


def attn_read_kernel(cfg, cell, counters, peak) -> float:
    """The ``latent_attention`` calls of one tick: the live latent of the
    latent layers alone (``live_kv_tokens_mean`` rows of rank + rope numbers,
    bf16, a latent layer)."""
    live = counters.get("live_kv_tokens_mean")
    return 0.0 if live is None else latent_bytes_per_token(cfg) * live / peak["hbm_bytes_per_s"]
