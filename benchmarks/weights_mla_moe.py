"""Weights of a latent-attention, sparse-expert decoder, drawn from ``--seed``
by the benchmark, as ``weights.py`` draws a dense decoder's: handed to the
program as a checkpoint would be, and made again for the plain reference.  One
jitted call on the device, in the type the weights are served in (bf16
matrices; float32 norm scales, router and LoRA, the router's values rounded to
bf16).

The tree has the layout ``fedml_tpu.llm.model.LlamaLM`` reads with latent
attention, ``lora_rank > 0`` and ``experts_held``: the routed experts' matrices
are stacked on a leading axis of the experts held here, ``count`` from
``first`` of the router's ``of``.  Expert ``e`` has the same weights whichever
share holds it, so the shares of a layer are slices of the uncut one.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from weights import LORA_STD, _normal, _scale, root_key, same_layout  # noqa: F401

LORA_TARGETS = ("q_a", "q_b", "kv_a", "o")


def held(cfg: dict):
    """(first, count, of): the experts held here, of the router's width."""
    share = cfg.get("experts_held")
    if not share:
        return 0, int(cfg["n_routed_experts"]), int(cfg["n_routed_experts"])
    return int(share["first"]), int(share["count"]), int(share["of"])


def dims(cfg: dict) -> dict:
    h = int(cfg["num_attention_heads"])
    first, count, of = held(cfg)
    return dict(d=int(cfg["hidden_size"]), h=h, rq=int(cfg["q_lora_rank"]),
                rkv=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
                rope=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
                f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
                shared=int(cfg.get("n_shared_experts", 0)), v=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]), dense=int(cfg["first_k_dense_replace"]),
                first=first, count=count, of=of)


def projection_shapes(cfg: dict) -> dict:
    """The four adapted projections (``kv_b`` is frozen: decode absorbs it)."""
    m = dims(cfg)
    return {"q_a": (m["d"], m["rq"]), "q_b": (m["rq"], m["h"] * (m["nope"] + m["rope"])),
            "kv_a": (m["d"], m["rkv"] + m["rope"]), "o": (m["h"] * m["dv"], m["d"])}


def _swiglu(keys, d, f, dtype, wrap):
    shapes = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return {n: wrap(_normal(k, s, s[0] ** -0.5, dtype)) for k, (n, s) in zip(keys, shapes.items())}


def _layer(key, cfg: dict, dtype, sparse: bool):
    m = dims(cfg)
    ks = jax.random.split(key, 16)
    att = {name: {"base": {"kernel": _normal(k, shape, shape[0] ** -0.5, dtype)}}
           for k, (name, shape) in zip(ks[:4], projection_shapes(cfg).items())}
    att["kv_b"] = {"kernel": _normal(ks[4], (m["rkv"], m["h"] * (m["nope"] + m["dv"])),
                                     m["rkv"] ** -0.5, dtype)}
    att["q_a_norm"] = {"scale": _scale(ks[5], m["rq"])}
    att["kv_a_norm"] = {"scale": _scale(ks[6], m["rkv"])}
    out = {"attention": att, "attn_norm": {"scale": _scale(ks[7], m["d"])},
           "mlp_norm": {"scale": _scale(ks[8], m["d"])}}
    kernel = lambda w: {"kernel": w}
    if not sparse:
        out["mlp"] = _swiglu(ks[9:12], m["d"], m["f"], dtype, kernel)
        return out
    router = _normal(ks[9], (m["d"], m["of"]), m["d"] ** -0.5, dtype).astype(jnp.float32)

    def expert(e):          # expert e's weights, whichever share holds it
        return _swiglu(jax.random.split(jax.random.fold_in(ks[10], e), 3),
                       m["d"], m["fe"], dtype, lambda w: w)

    out["moe_mlp"] = {"router": {"kernel": router},
                      **jax.vmap(expert)(m["first"] + jnp.arange(m["count"]))}
    if m["shared"]:
        out["shared_expert"] = _swiglu(ks[11:14], m["d"], m["shared"] * m["fe"], dtype, kernel)
    return out


@functools.lru_cache(maxsize=None)
def _base_fn(frozen: str, dtype_name: str):
    cfg = json.loads(frozen)
    dtype = jnp.dtype(dtype_name)
    m = dims(cfg)

    def make(key):
        out = {"tok_embed": {"embedding": _normal(
            jax.random.fold_in(key, 1), (m["v"], m["d"]), 1.0, dtype)},
            "final_norm": {"scale": _scale(jax.random.fold_in(key, 2), m["d"])},
            "lm_head": {"kernel": _normal(
                jax.random.fold_in(key, 3), (m["d"], m["v"]), m["d"] ** -0.5, dtype)}}
        for i in range(m["layers"]):
            out[f"layer_{i}"] = _layer(jax.random.fold_in(key, 100 + i), cfg, dtype,
                                       sparse=i >= m["dense"])
        return out

    return jax.jit(make)


def _frozen(cfg: dict) -> str:
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "intermediate_size",
            "moe_intermediate_size", "n_shared_experts", "vocab_size", "num_hidden_layers",
            "first_k_dense_replace", "n_routed_experts", "experts_held")
    return json.dumps({k: cfg.get(k) for k in keys}, sort_keys=True)


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
        for i in range(dims(cfg)["layers"]):
            lk = jax.random.fold_in(key, i)
            att = {}
            for j, (name, (fan_in, fan_out)) in enumerate(projection_shapes(cfg).items()):
                ka, kb = jax.random.split(jax.random.fold_in(lk, j))
                att[name] = {"A": _normal(ka, (fan_in, rank), LORA_STD, jnp.float32),
                             "B": _normal(kb, (rank, fan_out), LORA_STD, jnp.float32)}
            out[f"layer_{i}"] = {"attention": att}
        return out

    return jax.jit(make)


def make_lora(cfg: dict, seed: int, index: int = 0):
    """One set of adapters (float32), ``A`` and ``B`` both non-zero."""
    key = jax.random.fold_in(jax.random.fold_in(root_key(seed), 0x10A), int(index))
    return _lora_fn(_frozen(cfg), int(cfg["lora"]["rank"]))(key)
