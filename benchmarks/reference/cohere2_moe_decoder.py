"""The plain reference of a ``cohere2_moe`` decoder (Command A+, as its
``config.json`` gives it): a LayerNorm that subtracts the mean (scale, no
bias); a parallel block ``x + Attn(n) + FFN(n)`` with one norm ``n = LN(x)``;
grouped-query attention whose layers are of two kinds (``layer_types``):
``sliding_attention`` with the rotary embedding (interleaved pairs) and key j
visible to query i iff ``0 <= i - j < sliding_window``, ``full_attention``
with NO positional embedding, causal; sigmoid-scored experts, the
``num_experts_per_tok`` largest chosen, gates renormalised over the chosen,
beside ``num_shared_experts`` shared experts whose outputs are averaged; a
final LayerNorm and the embedding as the head, times ``logit_scale``; LoRA on
``wq``, ``wk``, ``wv``, ``wo``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``;
no kernel, no cache, no batching engine, and nothing imported from the program
under test.  Weights stay in the type they are served in and are widened where
a product reads them, layer by layer under ``jax.checkpoint``; attention runs
a block of heads and a block of queries at a time, so that one 12.5k-token
request fits on the chip beside the base.

**The share.**  ``held = (first, count)``: the router scores all ``E``
experts, the gates keep their denominator over all chosen experts, and the
sum runs over the chosen experts that are held.  ``held=None`` is the uncut
layer.  The shared experts are one SwiGLU of ``num_shared_experts`` times the
width (the four side by side: the sum of their outputs), times ``1 /
num_shared_experts``.  The vocabulary is whatever the embedding holds.

``quant="int8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to eight bits
(a scale per row of the contraction), the precision next below the bfloat16
the configuration states.

Departures from the published model are listed in the configuration file.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: kv heads attended at a time, and queries at a time
KV_HEAD_BLOCK = 1
QUERY_BLOCK = 512
#: rows through the feed-forward at a time
ROW_BLOCK = 2048


def _fake_int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(x, w, quant):
    """``x @ w`` over the last axis of ``x`` and the first of ``w``."""
    x, w = x.astype(F32), w.astype(F32)
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(x, w)


def layer_norm(x, scale, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """x: (..., S, D); positions: (S,).  Adjacent pairs rotate together."""
    d = x.shape[-1]
    ang = positions.astype(F32)[:, None] / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def lora_dense(x, proj, lora, scale, quant):
    y = matmul(x, proj["base"]["kernel"], quant)
    if lora is not None:
        y = y + scale * matmul(matmul(x, lora["A"], quant), lora["B"], quant)
    return y


def attention(n, layer, lora, cfg, window, quant):
    """n (B, S, d) -> (B, S, d).  ``window`` 0: a full layer, no positional
    embedding; > 0: rotary q and k, keys the last ``window`` positions."""
    b, s, _ = n.shape
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ls = float(cfg["lora"]["alpha"]) / float(cfg["lora"]["rank"])
    lo = (lambda name: lora[name]) if lora is not None else (lambda name: None)
    heads = lambda t, k: t.reshape(b, s, k, hd).transpose(0, 2, 1, 3)
    q = heads(lora_dense(n, layer["wq"], lo("wq"), ls, quant), h)         # (B, h, S, hd)
    k = heads(lora_dense(n, layer["wk"], lo("wk"), ls, quant), hkv)
    v = heads(lora_dense(n, layer["wv"], lo("wv"), ls, quant), hkv)
    pos = jnp.arange(s)
    if window:
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    if quant == "int8":
        q, k, v = (_fake_int8(t, -1) for t in (q, k, v))
    rep = h // hkv
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q.reshape(b, hkv, rep, s, hd), ((0, 0),) * 3 + ((0, pad), (0, 0)))
    qpos = jnp.pad(pos, (0, pad))
    step = math.gcd(hkv, KV_HEAD_BLOCK)
    # blocks of kv heads first, then of queries
    qs = q.reshape(b, hkv // step, step, rep, (s + pad) // qb, qb, hd).transpose(1, 4, 0, 2, 3, 5, 6)
    ks = k.reshape(b, hkv // step, step, s, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, hkv // step, step, s, hd).transpose(1, 0, 2, 3, 4)

    def head_block(args):
        qh, kh, vh = args                        # (nq, B, step, rep, qb, hd), (B, step, S, hd)

        def query_block(args):
            qq, pp = args                        # (B, step, rep, qb, hd), (qb,)
            ahead = pp[:, None] - pos[None, :]
            seen = ahead >= 0
            if window:
                seen &= ahead < window
            scores = jnp.einsum("bgrqd,bgkd->bgrqk", qq, kh) * hd ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("bgrqk,bgkd->bgrqd", probs, vh)

        return jax.lax.map(query_block, (qh, qpos.reshape(-1, qb)))

    out = jax.lax.map(head_block, (qs, ks, vs))                            # (G, nq, B, step, rep, qb, hd)
    out = out.transpose(2, 0, 3, 4, 1, 5, 6).reshape(b, h, s + pad, hd)[:, :, :s]
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return lora_dense(out, layer["wo"], lo("wo"), ls, quant)


def swiglu(x, w, quant):
    gate = matmul(x, w["w_gate"], quant)
    up = matmul(x, w["w_up"], quant)
    return matmul(jax.nn.silu(gate) * up, w["w_down"], quant)


def route(n, w_router, cfg, held, quant):
    """n (N, d) -> gates (N, k), chosen experts (N, k), and the margin (N,):
    how far the routing at this token is from the other choice that would
    change what the held experts add, the k-th and the (k+1)-th expert
    changing places with one of them held."""
    e = w_router.shape[-1]
    k = cfg["num_experts_per_tok"]
    if cfg.get("expert_selection_fn", "sigmoid") != "sigmoid":
        raise ValueError("the reference scores experts by sigmoid")
    s = jax.nn.sigmoid(matmul(n, w_router, quant))                         # (N, E)
    top, idx = jax.lax.top_k(s, k + 1)
    gates = top[:, :k]
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    first, count = held if held is not None else (0, e)
    is_held = lambda i: (i >= first) & (i < first + count)
    margin = jnp.where(is_held(idx[:, k - 1]) | is_held(idx[:, k]), top[:, k - 1] - top[:, k], jnp.inf)
    return gates, idx[:, :k], margin


def experts(n, layer, cfg, held, quant):
    """The held experts' part of the routed sum, and the margin."""
    moe = layer["moe_mlp"]
    gates, idx, margin = route(n, moe["router"]["kernel"], cfg, held, quant)
    first, count = held if held is not None else (0, moe["router"]["kernel"].shape[-1])
    y = jnp.zeros_like(n)
    for j in range(count):                      # expert first + j, whose weights are row j
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), axis=-1)      # (N,)
        w = {name: moe[name][j] for name in ("w_gate", "w_up", "w_down")}
        y = y + g[:, None] * swiglu(n, w, quant)
    return y, margin


def feed_forward(n, layer, cfg, held, quant):
    """n (N, d) -> the routed experts' part plus the mean of the shared
    experts, and the routing margin; ``ROW_BLOCK`` rows at a time (a token's
    feed-forward is its own: 14k rows of the shared experts' 16,384-wide
    products would be gigabytes)."""
    def rows(r):
        y, margin = experts(r, layer, cfg, held, quant)
        shared = {name: layer["shared_expert"][name]["kernel"] for name in ("w_gate", "w_up", "w_down")}
        return y + swiglu(r, shared, quant) / cfg["num_shared_experts"], margin

    count, d = n.shape
    if count <= ROW_BLOCK:
        return rows(n)
    pad = -count % ROW_BLOCK
    y, margin = jax.lax.map(rows, jnp.pad(n, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, d))
    return y.reshape(-1, d)[:count], margin.reshape(-1)[:count]


def block(x, layer, lora, cfg, window, held, quant):
    """``x + Attn(n) + FFN(n)``, ``n = LN(x)``; and the routing margin."""
    n = layer_norm(x, layer["attn_norm"]["scale"], cfg["layer_norm_eps"])
    attn = attention(n, layer["attention"], lora, cfg, window, quant)
    b, s, d = n.shape
    y, margin = feed_forward(n.reshape(b * s, d), layer, cfg, held, quant)
    return x + attn + y.reshape(b, s, d), margin.reshape(b, s)


def layer_window(cfg, i):
    return int(cfg["sliding_window"]) if cfg["layer_types"][i] == "sliding_attention" else 0


def forward(base, lora, tokens, cfg, held=None, quant=None, tail=None):
    """(B, S) token ids -> (B, S, V) float32 logits, and (B, S) the smallest
    routing margin over the layers.  ``tail = (start, T)``: the logits and
    margins of positions ``start .. start + T - 1`` only (``start`` may be
    traced): a 14k-token request's logits over 32k rows of vocabulary are
    1.8 GB, and only an answer's positions are compared."""
    embedding = base["tok_embed"]["embedding"]
    x = embedding[tokens].astype(F32)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    for i in range(cfg["num_hidden_layers"]):
        name = f"layer_{i}"
        lo = None if lora is None else lora[name]["attention"]
        x, m = jax.checkpoint(functools.partial(
            block, cfg=cfg, window=layer_window(cfg, i), held=held, quant=quant))(x, base[name], lo)
        margin = jnp.minimum(margin, m)
    if tail is not None:
        x = jax.lax.dynamic_slice_in_dim(x, tail[0], tail[1], axis=1)
        margin = jax.lax.dynamic_slice_in_dim(margin, tail[0], tail[1], axis=1)
    x = layer_norm(x, base["final_norm"]["scale"], cfg["layer_norm_eps"])
    return matmul(x, embedding.T, quant) * cfg.get("logit_scale", 1.0), margin


def _freeze(cfg: dict) -> str:
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
            "layer_norm_eps", "num_hidden_layers", "layer_types", "sliding_window",
            "num_experts_per_tok", "num_shared_experts", "norm_topk_prob", "expert_selection_fn",
            "logit_scale")
    out = {k: cfg[k] for k in keep if k in cfg}
    out["layer_types"] = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    out["lora"] = {"rank": cfg["lora"]["rank"], "alpha": cfg["lora"]["alpha"]}
    return json.dumps(out, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _logits_jit(frozen: str, held, quant):
    cfg = json.loads(frozen)

    def f(base, lora, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(base, lora, tokens, cfg, held, quant)

    return jax.jit(f)


def logits(base, lora, tokens, cfg, held=None, quant=None):
    """Logits and routing margins, jitted once per configuration."""
    return _logits_jit(_freeze(cfg), None if held is None else tuple(held), quant)(base, lora, tokens)


# -- a served request, teacher-forced -------------------------------------------

@functools.lru_cache(maxsize=None)
def _forced_fn(frozen: str, held, tail: int):
    cfg = json.loads(frozen)

    def f(base, lora, tokens, start):
        """tokens (1, L).  For the ``tail`` positions p from ``start``: how far
        the logit of token p+1 lies below that position's best, the
        position's spread (best minus median), position p's own routing
        margin; and the logits themselves, for the control."""
        with jax.default_matmul_precision("highest"):
            ref, margin = forward(base, lora, tokens, cfg, held, None, (start, tail))
            ref = ref[0]
            best = jnp.max(ref, axis=-1)
            following = jax.lax.dynamic_slice_in_dim(tokens[0], start + 1, tail)
            nxt = jnp.take_along_axis(ref, following[:, None], axis=-1)[:, 0]
            return {"gap": best - nxt, "spread": best - jnp.median(ref, axis=-1),
                    "margin": margin[0]}, ref

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _first_fn(frozen: str, held, quant, tail: int):
    """The token a lower precision puts first at each of the ``tail``
    positions: a program of its own, so that the two forward passes of a
    14k-token request never stand on the chip together."""
    cfg = json.loads(frozen)

    def f(base, lora, tokens, start):
        with jax.default_matmul_precision("highest"):
            low, _ = forward(base, lora, tokens, cfg, held, quant, (start, tail))
            return jnp.argmax(low[0], axis=-1)

    return jax.jit(f)


def forced_gaps(base, lora, tokens, start, tail: int, cfg, held=None, quant=None):
    """The served tokens' gaps at positions ``start .. start + tail - 1`` of
    ``tokens`` (1, L), ``L >= start + tail + 1``; under ``quant`` also
    ``control_gap``, the same gap for the token the lower precision puts
    first."""
    frozen, held = _freeze(cfg), None if held is None else tuple(held)
    start = jnp.asarray(start, jnp.int32)
    out, ref = _forced_fn(frozen, held, int(tail))(base, lora, tokens, start)
    if quant is not None:
        first = _first_fn(frozen, held, quant, int(tail))(base, lora, tokens, start)
        out["control_gap"] = jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
    return out
