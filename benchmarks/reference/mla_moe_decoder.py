"""The plain reference of a latent-attention, sparse-expert decoder (the
DeepSeek-V2/V3 family's layer, as A.X-K1 publishes it): RMSNorm, multi-head
latent attention with YaRN rotary frequencies, a leading dense SwiGLU layer,
then layers of sigmoid-routed, group-limited top-k experts beside one shared
expert, LoRA on ``q_a``, ``q_b``, ``kv_a`` and ``o``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``.
Attention in the expanded form only (every head's keys and values multiplied
out of the latent); the experts by a loop over the held experts with masks; no
kernel, no cache, no batching engine, and nothing imported from the program
under test.  Weights stay in the type they are served in and are widened where
a product reads them, layer by layer under ``jax.checkpoint``; attention runs
a few heads at a time, so that one request at the cell's length fits on the
chip beside the base.

**The share.**  The configuration holds ``count`` consecutive experts from
``first`` of the router's ``E`` (``held``): the router scores all ``E``, the
gates keep their denominator over all chosen experts, and the sum runs over
the chosen experts that are held.  ``held=None`` is the uncut layer.  The
vocabulary is whatever the embedding and the head hold.

``quant="int8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to eight bits
(a scale per row of the contraction), the precision next below the bfloat16
the configuration states.

Departures from the published model are listed in the configuration file
(interleaved rotary pairs, seeded weights, ``topk_method: "none"`` read as the
group-limited rule without a score-correction bias).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: heads attended at a time
HEAD_BLOCK = 8


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


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


# -- rotary embedding with YaRN ----------------------------------------------------

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(d, theta, scaling):
    """Inverse frequencies of the ``d // 2`` rotary pairs.  Under YaRN
    (arXiv:2309.00071) pair i keeps ``theta ** (-2i/d)`` where it turns more
    than ``beta_fast`` times within the original context, is divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, and is blended
    linearly between the two pairs where that happens; at every position."""
    base = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    if not scaling:
        return base
    orig = scaling["original_max_position_embeddings"]

    def pair_with_turns(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_with_turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_with_turns(scaling["beta_slow"])), d - 1)
    slowed = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / max(high - low, 1e-3), 0, 1)
    return base * (1 - slowed) + base / scaling["factor"] * slowed


def rope(x, positions, theta, scaling):
    """x: (..., S, D); positions: (S,).  Adjacent pairs rotate together."""
    ang = positions.astype(F32)[:, None] * inv_freq(x.shape[-1], theta, scaling)[None, :]
    m = 1.0
    if scaling:
        m = yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) \
            / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


# -- the layer ----------------------------------------------------------------------

def lora_dense(x, proj, lora, scale, quant):
    y = matmul(x, proj["base"]["kernel"], quant)
    if lora is not None:
        y = y + scale * matmul(matmul(x, lora["A"], quant), lora["B"], quant)
    return y


def mla(x, layer, lora, cfg, quant):
    """Multi-head latent attention, expanded: x (B, S, d) -> (B, S, d)."""
    b, s, _ = x.shape
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rp, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta, scaling = cfg["rms_norm_eps"], cfg["rope_theta"], cfg.get("rope_scaling")
    ls = float(cfg["lora"]["alpha"]) / float(cfg["lora"]["rank"])
    lo = (lambda n: lora[n]) if lora is not None else (lambda n: None)
    pos = jnp.arange(s)
    c_q = rms_norm(lora_dense(x, layer["q_a"], lo("q_a"), ls, quant),
                   layer["q_a_norm"]["scale"], eps)
    q = lora_dense(c_q, layer["q_b"], lo("q_b"), ls, quant)
    q = q.reshape(b, s, h, nope + rp).transpose(0, 2, 1, 3)            # (B, h, S, 192)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, theta, scaling)
    kv = lora_dense(x, layer["kv_a"], lo("kv_a"), ls, quant)
    c_kv = rms_norm(kv[..., :rank], layer["kv_a_norm"]["scale"], eps)   # (B, S, rank)
    k_r = rope(kv[..., rank:], pos, theta, scaling)                     # (B, S, rope): one for all heads
    kv_h = matmul(c_kv, layer["kv_b"]["kernel"], quant).reshape(b, s, h, nope + dv)
    kv_h = kv_h.transpose(0, 2, 1, 3)
    k_nope, v = kv_h[..., :nope], kv_h[..., nope:]
    if quant == "int8":
        q_nope, q_rope, k_nope, v, k_r = (_fake_int8(t, -1) for t in (q_nope, q_rope, k_nope, v, k_r))
    causal = pos[None, :] <= pos[:, None]
    scale = softmax_scale(cfg)

    def heads(args):
        qn, qr, kn, vv = args                                            # (B, S, ·) of HEAD_BLOCK heads
        scores = (jnp.einsum("hbqd,hbkd->hbqk", qn, kn)
                  + jnp.einsum("hbqd,bkd->hbqk", qr, k_r)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hbqk,hbkd->hbqd", probs, vv)

    step = math.gcd(h, HEAD_BLOCK)
    blocks = lambda t: t.transpose(1, 0, 2, 3).reshape((h // step, step, b) + t.shape[2:])
    out = jax.lax.map(heads, (blocks(q_nope), blocks(q_rope), blocks(k_nope), blocks(v)))
    out = out.reshape(h, b, s, dv).transpose(1, 2, 0, 3).reshape(b, s, h * dv)
    return lora_dense(out, layer["o"], lo("o"), ls, quant)


def swiglu(x, w, quant):
    gate = matmul(x, w["w_gate"], quant)
    up = matmul(x, w["w_up"], quant)
    return matmul(jax.nn.silu(gate) * up, w["w_down"], quant)


def route(hn, w_router, cfg, held, quant):
    """hn (N, d) -> gates (N, k), chosen experts (N, k), and the margin (N,):
    how far the routing at this token is from another choice that would
    change what the held experts add.  Two such choices: the k-th and the
    (k+1)-th expert among the kept groups' change places, one of them held;
    the last kept group and the first left-out one change places, one of them
    a group with held experts in it."""
    e = w_router.shape[-1]
    k, n_group, keep = cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"]
    logits = matmul(hn, w_router, quant)
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the reference scores experts by sigmoid")
    s = jax.nn.sigmoid(logits)                                           # (N, E)
    per = e // n_group
    best2, _ = jax.lax.top_k(s.reshape(-1, n_group, per), min(2, per))
    group_score = best2.sum(-1)                                          # (N, G)
    gs, gi = jax.lax.top_k(group_score, min(keep + 1, n_group))
    kept = jnp.any(gi[:, :keep, None] == jnp.arange(n_group), axis=1)    # (N, G)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), s, -jnp.inf)
    top, idx = jax.lax.top_k(masked, k + 1)
    gates = top[:, :k]
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * cfg.get("routed_scaling_factor", 1.0)
    first, count = held if held is not None else (0, e)
    is_held = lambda i: (i >= first) & (i < first + count)
    margin = jnp.where(is_held(idx[:, k - 1]) | is_held(idx[:, k]), top[:, k - 1] - top[:, k], jnp.inf)
    if keep < n_group:
        has_held = lambda g: (g * per < first + count) & ((g + 1) * per > first)
        margin = jnp.minimum(margin, jnp.where(
            has_held(gi[:, keep - 1]) | has_held(gi[:, keep]), gs[:, keep - 1] - gs[:, keep], jnp.inf))
    return gates, idx[:, :k], margin


def experts(hn, layer, cfg, held, quant):
    """The held experts' part of the routed sum, and the margin."""
    moe = layer["moe_mlp"]
    gates, idx, margin = route(hn, moe["router"]["kernel"], cfg, held, quant)
    first, count = held if held is not None else (0, moe["router"]["kernel"].shape[-1])
    y = jnp.zeros_like(hn)
    for j in range(count):                      # expert first + j, whose weights are row j
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), axis=-1)    # (N,)
        w = {n: moe[n][j] for n in ("w_gate", "w_up", "w_down")}
        y = y + g[:, None] * swiglu(hn, w, quant)
    return y, margin


def block(x, layer, lora, cfg, held, quant):
    eps = cfg["rms_norm_eps"]
    x = x + mla(rms_norm(x, layer["attn_norm"]["scale"], eps), layer["attention"], lora, cfg, quant)
    hn = rms_norm(x, layer["mlp_norm"]["scale"], eps)
    if "moe_mlp" not in layer:
        dense = {n: layer["mlp"][n]["kernel"] for n in ("w_gate", "w_up", "w_down")}
        return x + swiglu(hn, dense, quant), jnp.full(x.shape[:-1], jnp.inf, F32)
    b, s, d = hn.shape
    y, margin = experts(hn.reshape(b * s, d), layer, cfg, held, quant)
    shared = {n: layer["shared_expert"][n]["kernel"] for n in ("w_gate", "w_up", "w_down")}
    return x + y.reshape(b, s, d) + swiglu(hn, shared, quant), margin.reshape(b, s)


def forward(base, lora, tokens, cfg, held=None, quant=None):
    """(B, S) token ids -> (B, S, V) float32 logits, and (B, S) the smallest
    routing margin over the sparse layers."""
    x = base["tok_embed"]["embedding"][tokens].astype(F32)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    for i in range(cfg["num_hidden_layers"]):
        name = f"layer_{i}"
        lo = None if lora is None else lora[name]["attention"]
        x, m = jax.checkpoint(functools.partial(block, cfg=cfg, held=held, quant=quant))(
            x, base[name], lo)
        margin = jnp.minimum(margin, m)
    x = rms_norm(x, base["final_norm"]["scale"], cfg["rms_norm_eps"])
    return matmul(x, base["lm_head"]["kernel"], quant), margin


def _freeze(cfg: dict) -> str:
    keep = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_scaling", "rms_norm_eps",
            "num_hidden_layers", "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
            "routed_scaling_factor", "scoring_func")
    out = {k: cfg[k] for k in keep if k in cfg}
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
def _forced_fn(frozen: str, held, quant):
    cfg = json.loads(frozen)

    def f(base, lora, tokens):
        """tokens (1, L).  For every position p < L-1: how far the logit of
        token p+1 lies below that position's best, the position's spread
        (best minus median), the smallest routing margin of positions 0..p
        is NOT taken: ``margin`` is position p's own; and, under ``quant``,
        the same gap for the token the lower precision puts first."""
        with jax.default_matmul_precision("highest"):
            ref, margin = forward(base, lora, tokens, cfg, held, None)
            ref = ref[0, :-1]
            best = jnp.max(ref, axis=-1)
            nxt = jnp.take_along_axis(ref, tokens[0, 1:, None], axis=-1)[:, 0]
            out = {"gap": best - nxt, "spread": best - jnp.median(ref, axis=-1),
                   "margin": margin[0, :-1]}
            if quant is not None:
                low, _ = forward(base, lora, tokens, cfg, held, quant)
                first = jnp.argmax(low[0, :-1], axis=-1)
                out["control_gap"] = best - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
            return out

    return jax.jit(f)


def forced_gaps(base, lora, tokens, cfg, held=None, quant=None):
    return _forced_fn(_freeze(cfg), None if held is None else tuple(held), quant)(base, lora, tokens)
