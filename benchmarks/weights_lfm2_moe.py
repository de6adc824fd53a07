"""Weights of an ``lfm2_moe`` decoder (``reference/lfm2_moe_decoder.py``),
drawn from ``--seed`` by the benchmark, as ``weights.py`` draws a dense
decoder's: handed to the program as a checkpoint would be, and made again for
the plain reference.  One jitted call on the device, in the type the weights
are served in (bf16 matrices and convolution taps; float32 norm scales,
router, selection bias and LoRA, the router's values rounded to bf16).

The tree has the layout ``fedml_tpu.llm.model.LlamaLM`` reads for a stack of
``"conv"`` and ``"full_attention"`` layers with a tied head and ``lora_rank >
0``: a layer's mixer is ``conv`` (``in_proj``, ``out_proj``, ``conv_weight`` of
(d, K)) or ``attention`` (``wq wk wv wo`` and the scales ``q_norm``,
``k_norm`` of a head's width); its feed-forward ``mlp`` (the leading dense
layers) or ``moe_mlp`` (router, ``select_bias``, the experts' matrices stacked
on a leading axis).  Adapters sit on the mixer's projections: rows of two
shapes in one tree.

**The selection bias** is drawn at ``BIAS_STD``: large enough that the experts
chosen by ``s + b`` differ from the ``k`` largest ``s`` on between a tenth and
a third of the tokens of a sparse layer (``calibrate_lfm2_moe.py`` prints the
share read; ``PERF.md`` section 2 has it), so a program that leaves the bias
out of the selection, or puts it into the gates, is told apart.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from weights import LORA_STD, _normal, _scale, root_key, same_layout  # noqa: F401

#: the embedding is the head too: at unit scale a token's own row would tower
#: over every other logit; at this scale the layers' outputs decide the logits
EMBED_STD = 0.02
#: scale of the selection bias's entries (the scores are sigmoids of logits of
#: about unit spread)
BIAS_STD = 0.012


def dims(cfg: dict) -> dict:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(d=d, h=h, kv=int(cfg["num_key_value_heads"]), hd=d // h,
                f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
                e=int(cfg["num_experts"]), taps=int(cfg["conv_L_cache"]),
                v=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
                dense=int(cfg["num_dense_layers"]))


def kinds(cfg: dict) -> list:
    """The kinds of the layers that are run: the first of ``layer_types``."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def projection_shapes(cfg: dict, kind: str) -> dict:
    """The mixer's projections that carry adapters, by the layer's kind."""
    m = dims(cfg)
    if kind == "conv":
        return {"in_proj": (m["d"], 3 * m["d"]), "out_proj": (m["d"], m["d"])}
    return {"wq": (m["d"], m["h"] * m["hd"]), "wk": (m["d"], m["kv"] * m["hd"]),
            "wv": (m["d"], m["kv"] * m["hd"]), "wo": (m["h"] * m["hd"], m["d"])}


def mixer_name(kind: str) -> str:
    return "conv" if kind == "conv" else "attention"


def _swiglu(keys, d, f, dtype, wrap):
    shapes = {"w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f)}
    return {n: wrap(_normal(k, s, fan ** -0.5, dtype)) for k, (n, (s, fan)) in zip(keys, shapes.items())}


def _layer(key, cfg: dict, kind: str, sparse: bool, dtype):
    m = dims(cfg)
    ks = jax.random.split(key, 16)
    mixer = {name: {"base": {"kernel": _normal(k, shape, shape[0] ** -0.5, dtype)}}
             for k, (name, shape) in zip(ks[:4], projection_shapes(cfg, kind).items())}
    if kind == "conv":
        mixer["conv_weight"] = _normal(ks[4], (m["d"], m["taps"]), m["taps"] ** -0.5, dtype)
    else:
        mixer["q_norm"] = {"scale": _scale(ks[4], m["hd"])}
        mixer["k_norm"] = {"scale": _scale(ks[5], m["hd"])}
    out = {mixer_name(kind): mixer, "attn_norm": {"scale": _scale(ks[6], m["d"])},
           "mlp_norm": {"scale": _scale(ks[7], m["d"])}}
    if not sparse:
        out["mlp"] = _swiglu(ks[8:11], m["d"], m["f"], dtype, lambda w: {"kernel": w})
        return out
    router = _normal(ks[8], (m["d"], m["e"]), m["d"] ** -0.5, dtype).astype(jnp.float32)

    def expert(e):
        return _swiglu(jax.random.split(jax.random.fold_in(ks[9], e), 3), m["d"], m["fe"], dtype, lambda w: w)

    out["moe_mlp"] = {"router": {"kernel": router},
                      "select_bias": _normal(ks[10], (m["e"],), BIAS_STD, jnp.float32),
                      **jax.vmap(expert)(jnp.arange(m["e"]))}
    return out


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
               "moe_intermediate_size", "num_experts", "conv_L_cache", "vocab_size",
               "num_hidden_layers", "num_dense_layers")


def _frozen(cfg: dict) -> str:
    return json.dumps({**{k: cfg.get(k) for k in _SHAPE_KEYS}, "layer_types": kinds(cfg)}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _base_fn(frozen: str, dtype_name: str):
    cfg = json.loads(frozen)
    dtype = jnp.dtype(dtype_name)
    m = dims(cfg)

    def make(key):
        out = {"tok_embed": {"embedding": _normal(
            jax.random.fold_in(key, 1), (m["v"], m["d"]), EMBED_STD, dtype)},
            "final_norm": {"scale": _scale(jax.random.fold_in(key, 2), m["d"])}}
        for i, kind in enumerate(kinds(cfg)):
            out[f"layer_{i}"] = _layer(jax.random.fold_in(key, 100 + i), cfg, kind, i >= m["dense"], dtype)
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
