"""Weights of a dense decoder, drawn from ``--seed`` by the benchmark.

The benchmark makes the weights, hands them to the program (as a checkpoint
would) and makes them again for the plain reference, so the reference takes
nothing that the program has produced.  One jitted call on the device, in the
type the weights are served in (bf16 matrices, float32 norm scales and LoRA).

The tree has the layout ``fedml_tpu.llm.model.LlamaLM`` reads with
``lora_rank > 0`` (the projections are ``LoRADense``: ``<name>/base/kernel``).
The drivers check the layout against the program's own before they swap the
weights in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: scale of the adapters' entries, ``A`` and ``B`` alike
LORA_STD = 0.02


def root_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it and
    the rest is folded in (``PRNGKey`` takes 32 signed bits on this set-up)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    hd = int(cfg.get("head_dim") or d // int(cfg["num_attention_heads"]))
    return dict(d=d, hd=hd, h=int(cfg["num_attention_heads"]),
                kv=int(cfg["num_key_value_heads"]),
                f=int(cfg["intermediate_size"]), v=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]))


def projection_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    return {"wq": (m["d"], m["h"] * m["hd"]), "wk": (m["d"], m["kv"] * m["hd"]),
            "wv": (m["d"], m["kv"] * m["hd"]), "wo": (m["h"] * m["hd"], m["d"])}


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _scale(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def _layer(key, cfg: dict, dtype):
    m = dims(cfg)
    ks = jax.random.split(key, 9)
    att = {name: {"base": {"kernel": _normal(k, shape, shape[0] ** -0.5, dtype)}}
           for k, (name, shape) in zip(ks[:4], projection_shapes(cfg).items())}
    mlp = {"w_gate": {"kernel": _normal(ks[4], (m["d"], m["f"]), m["d"] ** -0.5, dtype)},
           "w_up": {"kernel": _normal(ks[5], (m["d"], m["f"]), m["d"] ** -0.5, dtype)},
           "w_down": {"kernel": _normal(ks[6], (m["f"], m["d"]), m["f"] ** -0.5, dtype)}}
    return {"attention": att, "mlp": mlp,
            "attn_norm": {"scale": _scale(ks[7], m["d"])},
            "mlp_norm": {"scale": _scale(ks[8], m["d"])}}


@functools.lru_cache(maxsize=None)
def _base_fn(cfg_items: tuple, dtype_name: str):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(dtype_name)
    m = dims(cfg)

    def make(key):
        out = {"tok_embed": {"embedding": _normal(
            jax.random.fold_in(key, 1), (m["v"], m["d"]), 1.0, dtype)},
            "final_norm": {"scale": _scale(jax.random.fold_in(key, 2), m["d"])},
            "lm_head": {"kernel": _normal(
                jax.random.fold_in(key, 3), (m["d"], m["v"]), m["d"] ** -0.5, dtype)}}
        for i in range(m["layers"]):
            out[f"layer_{i}"] = _layer(jax.random.fold_in(key, 100 + i), cfg, dtype)
        return out

    return jax.jit(make)


def _hashable(cfg: dict) -> tuple:
    keys = ("hidden_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "vocab_size",
            "num_hidden_layers")
    return tuple((k, cfg.get(k)) for k in keys)


def make_base(cfg: dict, seed: int):
    """The frozen base, whole, in one jitted call, in the type the
    configuration serves it in (bfloat16 unless it states another)."""
    return _base_fn(_hashable(cfg), str(cfg.get("weight_dtype", "bfloat16")))(
        jax.random.fold_in(root_key(seed), 0xBA5E))


@functools.lru_cache(maxsize=None)
def _lora_fn(cfg_items: tuple, rank: int):
    cfg = dict(cfg_items)
    m = dims(cfg)

    def make(key):
        out = {}
        for i in range(m["layers"]):
            lk = jax.random.fold_in(key, i)
            att = {}
            for j, (name, (fan_in, fan_out)) in enumerate(
                    projection_shapes(cfg).items()):
                ka, kb = jax.random.split(jax.random.fold_in(lk, j))
                att[name] = {
                    "A": _normal(ka, (fan_in, rank), LORA_STD, jnp.float32),
                    "B": _normal(kb, (rank, fan_out), LORA_STD, jnp.float32)}
            out[f"layer_{i}"] = {"attention": att}
        return out

    return jax.jit(make)


def make_lora(cfg: dict, seed: int, index: int = 0):
    """One set of adapters (float32): ``A`` and ``B`` both non-zero, as after
    some rounds of a federation, so that both take gradients from the first
    local step and a served adapter moves the logits."""
    rank = int(cfg["lora"]["rank"])
    key = jax.random.fold_in(jax.random.fold_in(root_key(seed), 0x10A),
                             int(index))
    return _lora_fn(_hashable(cfg), rank)(key)


def same_layout(ours, theirs) -> str:
    """Empty when two trees agree in structure, shapes and types; else the
    first difference, in words."""
    a = jax.tree_util.tree_flatten_with_path(ours)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    if len(a) != len(b):
        return f"{len(a)} leaves against the program's {len(b)}"
    for (pa, la), (pb, lb) in zip(a, b):
        if pa != pb or la.shape != lb.shape or la.dtype != lb.dtype:
            return (f"{jax.tree_util.keystr(pa)} {la.shape} {la.dtype} against "
                    f"the program's {jax.tree_util.keystr(pb)} {lb.shape} {lb.dtype}")
    return ""
