"""Weights of a ``gigachat3_5`` decoder (``reference/gigachat35_decoder.py``),
drawn from ``--seed`` by the benchmark, as ``weights_mla_moe.py`` draws a
latent-attention decoder's: handed to the program as a checkpoint would be,
and made again for the plain reference.  One jitted call on the device, in the
type the weights are served in (bf16 matrices and convolution taps; float32
norm weights, gates, ``A_log``, ``dt_bias``, router, selection bias and LoRA,
the router's values rounded to bf16).

The tree has the layout ``fedml_tpu.llm.model.LlamaLM`` reads for a stack of
``"linear_attention"`` and ``"full_attention"`` (latent) layers with sandwich
gated norms, ``lora_rank > 0`` and ``experts_held``: a layer's mixer is
``linear_attn`` (``q k v o`` with adapters, ``z b a``, ``conv_weight`` of
(2 x key heads x d_k + value heads x d_v, taps), ``A_log``, ``dt_bias``,
``o_norm``) or ``attention`` (``q_a q_b kv_a o`` with adapters, ``kv_b``,
``gate``, the two latent norms); every norm of the block and the final one is
the zero-centred gated norm (``<name>/gate``).  The routed experts' matrices
are stacked on a leading axis of the experts held here, ``count`` from
``first`` of the router's ``of``: expert ``e`` has the same weights whichever
share holds it, so the shares of a layer are slices of the uncut one.

**Scales that decide what can be seen.**  ``W_a`` is drawn at a tenth of a
fan-in scale and ``dt_bias`` so that ``softplus(dt_bias)`` spans 1e-3 .. 1e-1
(the family's initialisation of the step): a value head keeps ``e^g`` = 0.2 ..
0.999 of its state a step, so what a state carries reaches hundreds of
positions on and a state that is lost or rounded shows.  The gated norms'
weights are drawn at ``GATE_STD`` and the selection bias at ``BIAS_STD``, so a
program that leaves a gate or the bias out is told apart.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from weights import LORA_STD, _normal, _scale, root_key, same_layout  # noqa: F401
from weights_lfm2_moe import _swiglu
from weights_mla_moe import held

#: scale of the zero-centred gates' weights (a norm's gate is 2 sigmoid(w))
GATE_STD = 0.3
#: scale of the selection bias's entries (the scores are sigmoids of logits of
#: about unit spread)
BIAS_STD = 0.012
#: what ``W_a``'s entries are drawn at, times the fan-in scale
ALPHA_SCALE = 0.1
#: the step ``softplus(dt_bias)`` is drawn log-uniform over this range
DT_RANGE = (1e-3, 1e-1)


def kinds(cfg: dict) -> list:
    """The layers' kinds: latent attention where ``full_attention_layers``
    says, the gated delta rule elsewhere."""
    full = {int(i) for i in cfg["full_attention_layers"]}
    return ["full_attention" if i in full else "linear_attention"
            for i in range(int(cfg["num_hidden_layers"]))]


def dims(cfg: dict) -> dict:
    first, count, of = held(cfg)
    hk, hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    return dict(d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
                rq=int(cfg["q_lora_rank"]), rkv=int(cfg["kv_lora_rank"]),
                nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
                dv_mla=int(cfg["v_head_dim"]), hk=hk, hv=hv, dk=dk, dvl=dv,
                taps=int(cfg["linear_conv_kernel_dim"]), conv=2 * hk * dk + hv * dv,
                f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
                shared=int(cfg.get("n_shared_experts", 0)), v=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]), dense=int(cfg["first_k_dense_replace"]),
                first=first, count=count, of=of)


def mixer_name(kind: str) -> str:
    return "linear_attn" if kind == "linear_attention" else "attention"


def projection_shapes(cfg: dict, kind: str) -> dict:
    """The mixer's projections that carry adapters, by the layer's kind."""
    m = dims(cfg)
    if kind == "linear_attention":
        return {"q": (m["d"], m["hk"] * m["dk"]), "k": (m["d"], m["hk"] * m["dk"]),
                "v": (m["d"], m["hv"] * m["dvl"]), "o": (m["hv"] * m["dvl"], m["d"])}
    return {"q_a": (m["d"], m["rq"]), "q_b": (m["rq"], m["h"] * (m["nope"] + m["rope"])),
            "kv_a": (m["d"], m["rkv"] + m["rope"]), "o": (m["h"] * m["dv_mla"], m["d"])}


def _gate(key, n):
    return {"gate": GATE_STD * jax.random.normal(key, (n,), jnp.float32)}


def _linear(ks, cfg: dict, dtype) -> dict:
    m = dims(cfg)
    mixer = {name: {"base": {"kernel": _normal(k, shape, shape[0] ** -0.5, dtype)}}
             for k, (name, shape) in zip(ks[:4], projection_shapes(cfg, "linear_attention").items())}
    mixer["z"] = {"kernel": _normal(ks[4], (m["d"], m["hv"] * m["dvl"]), m["d"] ** -0.5, dtype)}
    mixer["b"] = {"kernel": _normal(ks[5], (m["d"], m["hv"]), m["d"] ** -0.5, dtype)}
    mixer["a"] = {"kernel": _normal(ks[6], (m["d"], m["hv"]), ALPHA_SCALE * m["d"] ** -0.5, dtype)}
    mixer["conv_weight"] = _normal(ks[7], (m["conv"], m["taps"]), m["taps"] ** -0.5, dtype)
    mixer["A_log"] = jnp.log(jax.random.uniform(ks[8], (m["hv"],), jnp.float32, 1.0, 16.0))
    lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
    dt = jnp.exp(jax.random.uniform(ks[9], (m["hv"],), jnp.float32, lo, hi))
    mixer["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))            # softplus^-1(dt)
    mixer["o_norm"] = 0.1 * jax.random.normal(ks[10], (m["dvl"],), jnp.float32)
    return mixer


def _mla(ks, cfg: dict, dtype) -> dict:
    m = dims(cfg)
    att = {name: {"base": {"kernel": _normal(k, shape, shape[0] ** -0.5, dtype)}}
           for k, (name, shape) in zip(ks[:4], projection_shapes(cfg, "full_attention").items())}
    att["kv_b"] = {"kernel": _normal(ks[4], (m["rkv"], m["h"] * (m["nope"] + m["dv_mla"])),
                                     m["rkv"] ** -0.5, dtype)}
    att["gate"] = {"kernel": _normal(ks[5], (m["d"], m["h"] * m["dv_mla"]), m["d"] ** -0.5, dtype)}
    att["q_a_norm"] = {"scale": _scale(ks[6], m["rq"])}
    att["kv_a_norm"] = {"scale": _scale(ks[7], m["rkv"])}
    return att


def _layer(key, cfg: dict, kind: str, sparse: bool, dtype):
    m = dims(cfg)
    ks = jax.random.split(key, 24)
    mixer = _linear(ks[:11], cfg, dtype) if kind == "linear_attention" else _mla(ks[:8], cfg, dtype)
    out = {mixer_name(kind): mixer,
           **{name: _gate(k, m["d"]) for k, name in zip(
               ks[11:15], ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"))}}
    kernel = lambda w: {"kernel": w}  # noqa: E731
    if not sparse:
        out["mlp"] = _swiglu(ks[15:18], m["d"], m["f"], dtype, kernel)
        return out
    router = _normal(ks[15], (m["d"], m["of"]), m["d"] ** -0.5, dtype).astype(jnp.float32)

    def expert(e):          # expert e's weights, whichever share holds it
        return _swiglu(jax.random.split(jax.random.fold_in(ks[16], e), 3), m["d"], m["fe"], dtype,
                       lambda w: w)

    out["moe_mlp"] = {"router": {"kernel": router},
                      "select_bias": _normal(ks[17], (m["of"],), BIAS_STD, jnp.float32),
                      **jax.vmap(expert)(m["first"] + jnp.arange(m["count"]))}
    if m["shared"]:
        out["shared_expert"] = _swiglu(ks[18:21], m["d"], m["shared"] * m["fe"], dtype, kernel)
    return out


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "linear_num_key_heads",
               "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim", "intermediate_size", "moe_intermediate_size",
               "n_shared_experts", "vocab_size", "num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "experts_held", "full_attention_layers")


def _frozen(cfg: dict) -> str:
    return json.dumps({k: cfg.get(k) for k in _SHAPE_KEYS}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _base_fn(frozen: str, dtype_name: str):
    cfg = json.loads(frozen)
    dtype = jnp.dtype(dtype_name)
    m = dims(cfg)

    def make(key):
        out = {"tok_embed": {"embedding": _normal(
            jax.random.fold_in(key, 1), (m["v"], m["d"]), 1.0, dtype)},
            "final_norm": _gate(jax.random.fold_in(key, 2), m["d"]),
            "lm_head": {"kernel": _normal(
                jax.random.fold_in(key, 3), (m["d"], m["v"]), m["d"] ** -0.5, dtype)}}
        for i, kind in enumerate(kinds(cfg)):
            out[f"layer_{i}"] = _layer(jax.random.fold_in(key, 100 + i), cfg, kind,
                                       i >= m["dense"], dtype)
        return out

    return jax.jit(make)


def make_base(cfg: dict, seed: int):
    """The frozen base, whole, in one jitted call, in the type the
    configuration serves it in (bfloat16 unless it states another)."""
    return _base_fn(_frozen(cfg), str(cfg.get("weight_dtype", "bfloat16")))(
        jax.random.fold_in(root_key(seed), 0xBA5E))


@functools.lru_cache(maxsize=None)
def _lora_fn(frozen: str, rank: int):
    cfg = json.loads(frozen)

    def make(key):
        out = {}
        for i, kind in enumerate(kinds(cfg)):
            lk = jax.random.fold_in(key, i)
            rows = {}
            for j, (name, (fan_in, fan_out)) in enumerate(projection_shapes(cfg, kind).items()):
                ka, kb = jax.random.split(jax.random.fold_in(lk, j))
                rows[name] = {"A": _normal(ka, (fan_in, rank), LORA_STD, jnp.float32),
                              "B": _normal(kb, (rank, fan_out), LORA_STD, jnp.float32)}
            out[f"layer_{i}"] = {mixer_name(kind): rows}
        return out

    return jax.jit(make)


def make_lora(cfg: dict, seed: int, index: int = 0):
    """One set of adapters (float32), ``A`` and ``B`` both non-zero."""
    key = jax.random.fold_in(jax.random.fold_in(root_key(seed), 0x10A), int(index))
    return _lora_fn(_frozen(cfg), int(cfg["lora"]["rank"]))(key)
