"""Weights of a ``cohere2_moe`` decoder (``reference/cohere2_moe_decoder.py``),
drawn from ``--seed`` by the benchmark, as ``weights.py`` draws a dense
decoder's: handed to the program as a checkpoint would be, and made again for
the plain reference.  One jitted call on the device, in the type the weights
are served in (bf16 matrices; float32 norm scales, router and LoRA, the
router's values rounded to bf16).

The tree has the layout ``fedml_tpu.llm.model.LlamaLM`` reads for a parallel
block with a tied head, ``lora_rank > 0`` and ``experts_held``: one norm a
layer (``attn_norm``), no ``lm_head``, the routed experts' matrices stacked on
a leading axis of the experts held here (``count`` from ``first`` of the
router's ``of``), and the ``num_shared_experts`` shared experts side by side
in one SwiGLU of that many times the width (the program and the reference
multiply its output by one over their number: the mean).  Expert ``e`` has the
same weights whichever share holds it, so the shares of a layer are slices of
the uncut one.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from weights import LORA_STD, _normal, _scale, projection_shapes, root_key, same_layout  # noqa: F401

LORA_TARGETS = ("wq", "wk", "wv", "wo")
#: the embedding is the head too: at unit scale a token's own row would tower
#: over every other logit (|E_t|^2 = d against sqrt(d) for the rest), every
#: answer would repeat its prompt's last token, and no precision could move a
#: served token.  At this scale the layers' outputs decide the logits.
EMBED_STD = 0.02


def held(cfg: dict):
    """(first, count, of): the experts held here, of the router's width."""
    share = cfg.get("experts_held")
    if not share:
        return 0, int(cfg["num_experts"]), int(cfg["num_experts"])
    return int(share["first"]), int(share["count"]), int(share["of"])


def dims(cfg: dict) -> dict:
    first, count, of = held(cfg)
    return dict(d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
                kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
                fe=int(cfg["intermediate_size"]), shared=int(cfg["num_shared_experts"]),
                v=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
                first=first, count=count, of=of)


def _swiglu(keys, d, f, down_fan_in, dtype, wrap):
    shapes = {"w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), down_fan_in)}
    return {n: wrap(_normal(k, s, fan ** -0.5, dtype)) for k, (n, (s, fan)) in zip(keys, shapes.items())}


def _layer(key, cfg: dict, dtype):
    m = dims(cfg)
    ks = jax.random.split(key, 12)
    att = {name: {"base": {"kernel": _normal(k, shape, shape[0] ** -0.5, dtype)}}
           for k, (name, shape) in zip(ks[:4], projection_shapes(cfg).items())}
    router = _normal(ks[4], (m["d"], m["of"]), m["d"] ** -0.5, dtype).astype(jnp.float32)

    def expert(e):          # expert e's weights, whichever share holds it
        return _swiglu(jax.random.split(jax.random.fold_in(ks[5], e), 3),
                       m["d"], m["fe"], m["fe"], dtype, lambda w: w)

    return {"attention": att, "attn_norm": {"scale": _scale(ks[6], m["d"])},
            "moe_mlp": {"router": {"kernel": router},
                        **jax.vmap(expert)(m["first"] + jnp.arange(m["count"]))},
            # each shared expert's down-projection has its own width as fan-in
            "shared_expert": _swiglu(ks[7:10], m["d"], m["shared"] * m["fe"], m["fe"], dtype,
                                     lambda w: {"kernel": w})}


@functools.lru_cache(maxsize=None)
def _base_fn(frozen: str, dtype_name: str):
    cfg = json.loads(frozen)
    dtype = jnp.dtype(dtype_name)
    m = dims(cfg)

    def make(key):
        out = {"tok_embed": {"embedding": _normal(
            jax.random.fold_in(key, 1), (m["v"], m["d"]), EMBED_STD, dtype)},
            "final_norm": {"scale": _scale(jax.random.fold_in(key, 2), m["d"])}}
        for i in range(m["layers"]):
            out[f"layer_{i}"] = _layer(jax.random.fold_in(key, 100 + i), cfg, dtype)
        return out

    return jax.jit(make)


def _frozen(cfg: dict) -> str:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "num_shared_experts", "vocab_size", "num_hidden_layers",
            "num_experts", "experts_held")
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
