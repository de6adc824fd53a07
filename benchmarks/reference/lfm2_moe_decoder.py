"""The plain reference of an ``lfm2_moe`` decoder (LFM2-8B-A1B, as its
``config.json`` gives it).  ``RMS(x) = x / sqrt(mean x^2 + norm_eps) * scale``;
layer i is sequential with two norms: ``h = x + Op_i(RMS_op(x))``,
``x' = h + FF_i(RMS_ffn(h))``.

- ``layer_types[i] == "conv"``, the gated short convolution: ``[B | C | z] =
  W_in n`` (d -> 3d, no bias, the thirds in that order), ``u_t = B_t * z_t``,
  ``c_t = sum_j w[:, j] * u_{t - (K-1) + j}`` (depthwise, causal, ``u`` zero
  before the sequence, ``K = conv_L_cache`` taps, ``w`` of shape (d, K), no
  bias), ``y_t = C_t * c_t``, ``Op = W_out y``.
- ``"full_attention"``: grouped-query attention without biases; RMS norms on q
  and k over each head's numbers with one learned scale of ``head_dim`` each,
  before the rotary embedding (interleaved pairs, all of a head's dimensions);
  causal, scale ``head_dim ** -0.5``.
- ``FF_i``, ``i < num_dense_layers``: SwiGLU of ``intermediate_size``.  Else
  ``s = sigmoid(W_r n)`` over all experts; the ``num_experts_per_tok`` largest
  of ``s + b`` are chosen (``use_expert_bias``: ``b`` is not in the gates);
  ``g_e = s_e / max(sum_chosen s, 1e-20)`` (``norm_topk_prob``: the floor is
  the program's own, the configuration's file says why), times
  ``routed_scaling_factor``; ``sum g_e E_e(n)``, experts of
  ``moe_intermediate_size``.  No shared expert.
- a final RMS norm and the embedding as the head; LoRA on ``wq wk wv wo`` of
  the attention layers and ``in_proj``, ``out_proj`` of the convolution layers.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``;
no kernel, no cache, no state carried from call to call, no batching engine,
and nothing imported from the program under test.  Weights stay in the type
they are served in and are widened where a product reads them, layer by layer
under ``jax.checkpoint``.

``forward`` also reports each position's smallest routing margin over the
sparse layers, taken over ``s + b``, the selection's own scores: how far the
k-th chosen expert lies above the best one left out.

``quant="int8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to eight bits
(a scale per row of the contraction), the precision next below the bfloat16
the configuration states.  ``quant="bfloat16"`` is the witness beside it: the
same plain mathematics in the precision the configuration states, so far as
the configuration's file says what that is (both operands of every matrix
product, q k v and each layer's output rounded to bfloat16, sums in float32).
It shares no code with the program either, so the tokens it puts first say how
far bfloat16 alone moves an answer, whatever engine computes it.

Departures from the published model are listed in the configuration file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: queries attended at a time
QUERY_BLOCK = 512
#: what ``forced_gaps`` calls the gap of the token a lower precision puts first
LOWER = {"int8": "control_gap", "bfloat16": "witness_gap"}
#: the floor of the gates' denominator (``fedml_tpu/llm/moe.py::route``'s)
GATE_FLOOR = 1e-20


def _fake_int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _as_bfloat16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def matmul(x, w, quant):
    """``x @ w`` over the last axis of ``x`` and the first of ``w``."""
    x, w = x.astype(F32), w.astype(F32)
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant == "bfloat16":
        x, w = _as_bfloat16(x), _as_bfloat16(w)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(x, w)


def rms_norm(x, scale, eps):
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


def _lora_of(lora, cfg):
    ls = float(cfg["lora"]["alpha"]) / float(cfg["lora"]["rank"])
    return ls, (lambda name: lora[name]) if lora is not None else (lambda name: None)


def short_conv(n, layer, lora, cfg, quant):
    """n (B, S, d) -> (B, S, d): the gated short convolution."""
    taps = int(cfg["conv_L_cache"])
    ls, lo = _lora_of(lora, cfg)
    s = n.shape[1]
    gate_in, gate_out, z = jnp.split(lora_dense(n, layer["in_proj"], lo("in_proj"), ls, quant), 3, axis=-1)
    u = gate_in * z
    w = layer["conv_weight"].astype(F32)                                   # (d, K)
    ext = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))                      # zero before the sequence
    c = sum(ext[:, j:j + s] * w[:, j] for j in range(taps))
    return lora_dense(gate_out * c, layer["out_proj"], lo("out_proj"), ls, quant)


def attention(n, layer, lora, cfg, quant):
    """n (B, S, d) -> (B, S, d): causal grouped-query attention, q and k
    normed a head at a time before the rotary embedding."""
    b, s, _ = n.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    ls, lo = _lora_of(lora, cfg)
    heads = lambda t, k: t.reshape(b, s, k, hd).transpose(0, 2, 1, 3)
    q = heads(lora_dense(n, layer["wq"], lo("wq"), ls, quant), h)         # (B, h, S, hd)
    k = heads(lora_dense(n, layer["wk"], lo("wk"), ls, quant), hkv)
    v = heads(lora_dense(n, layer["wv"], lo("wv"), ls, quant), hkv)
    q = rms_norm(q, layer["q_norm"]["scale"], cfg["norm_eps"])
    k = rms_norm(k, layer["k_norm"]["scale"], cfg["norm_eps"])
    pos = jnp.arange(s)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    if quant == "int8":
        q, k, v = (_fake_int8(t, -1) for t in (q, k, v))
    elif quant == "bfloat16":
        q, k, v = (_as_bfloat16(t) for t in (q, k, v))
    rep = h // hkv
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q.reshape(b, hkv, rep, s, hd), ((0, 0),) * 3 + ((0, pad), (0, 0)))
    qs = q.reshape(b, hkv, rep, (s + pad) // qb, qb, hd).transpose(3, 0, 1, 2, 4, 5)

    def query_block(args):
        qq, pp = args                            # (B, hkv, rep, qb, hd), (qb,)
        seen = pp[:, None] >= pos[None, :]
        scores = jnp.einsum("bgrqd,bgkd->bgrqk", qq, k) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bgkd->bgrqd", probs, v)

    out = jax.lax.map(query_block, (qs, jnp.pad(pos, (0, pad)).reshape(-1, qb)))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, s + pad, hd)[:, :, :s]
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return lora_dense(out, layer["wo"], lo("wo"), ls, quant)


def swiglu(x, w, quant):
    gate = matmul(x, w["w_gate"], quant)
    up = matmul(x, w["w_up"], quant)
    return matmul(jax.nn.silu(gate) * up, w["w_down"], quant)


def route(n, moe, cfg, quant):
    """n (N, d) -> gates (N, k), chosen experts (N, k), the margin (N,): how
    far, in the selection's own scores ``s + b``, the k-th chosen expert lies
    above the best one left out; and whether the bias changed the choice (N,):
    the k-th largest of ``s`` was not chosen."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul(n, moe["router"]["kernel"], quant))         # (N, E)
    chosen_by = s + moe["select_bias"].astype(F32) if cfg.get("use_expert_bias") else s
    top, idx = jax.lax.top_k(chosen_by, k + 1)
    gates = jnp.take_along_axis(s, idx[:, :k], axis=1)
    changed = jnp.min(gates, axis=-1) < jax.lax.top_k(s, k)[0][:, k - 1]
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), GATE_FLOOR)
    return gates * float(cfg.get("routed_scaling_factor", 1.0)), idx[:, :k], top[:, k - 1] - top[:, k], changed


def experts(n, moe, cfg, quant):
    """n (N, d) -> the routed sum over all experts, the margin, and where the
    bias changed the choice."""
    gates, idx, margin, changed = route(n, moe, cfg, quant)

    def one(y, args):
        e, w_gate, w_up, w_down = args
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)             # (N,)
        return y + g[:, None] * swiglu(n, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, quant), None

    count = moe["w_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (jnp.arange(count), moe["w_gate"], moe["w_up"], moe["w_down"]))
    return y, margin, changed


def block(x, layer, lora, cfg, kind, sparse, quant):
    """One layer, its routing margin (infinite for a dense one) and where its
    selection bias changed the choice."""
    eps = cfg["norm_eps"]
    kept = _as_bfloat16 if quant == "bfloat16" else (lambda t: t)       # the stream between the layers
    n = rms_norm(x, layer["attn_norm"]["scale"], eps)
    if kind == "conv":
        h = kept(x + short_conv(n, layer["conv"], lora, cfg, quant))
    elif kind == "full_attention":
        h = kept(x + attention(n, layer["attention"], lora, cfg, quant))
    else:
        raise ValueError(f"the reference has no layer of kind {kind!r}")
    n = rms_norm(h, layer["mlp_norm"]["scale"], eps)
    b, s, d = n.shape
    if not sparse:
        dense = {name: layer["mlp"][name]["kernel"] for name in ("w_gate", "w_up", "w_down")}
        return kept(h + swiglu(n, dense, quant)), jnp.full((b, s), jnp.inf, F32), jnp.zeros((b, s), bool)
    y, margin, changed = experts(n.reshape(b * s, d), layer["moe_mlp"], cfg, quant)
    return kept(h + y.reshape(b, s, d)), margin.reshape(b, s), changed.reshape(b, s)


def forward(base, lora, tokens, cfg, quant=None, tail=None, changed=None):
    """(B, S) token ids -> (B, S, V) float32 logits, and (B, S) the smallest
    routing margin over the layers.  ``tail = (start, T)``: the logits and
    margins of positions ``start .. start + T - 1`` only (``start`` may be
    traced).  ``changed``: a list that gets, for every sparse layer, the
    (B, S) positions at which the selection bias changed the choice."""
    embedding = base["tok_embed"]["embedding"]
    x = embedding[tokens].astype(F32)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    for i in range(cfg["num_hidden_layers"]):
        name, kind = f"layer_{i}", cfg["layer_types"][i]
        lo = None if lora is None else lora[name]["conv" if kind == "conv" else "attention"]
        sparse = i >= cfg["num_dense_layers"]
        x, m, moved = jax.checkpoint(functools.partial(
            block, cfg=cfg, kind=kind, sparse=sparse, quant=quant))(x, base[name], lo)
        margin = jnp.minimum(margin, m)
        if sparse and changed is not None:
            changed.append(moved)
    if tail is not None:
        x = jax.lax.dynamic_slice_in_dim(x, tail[0], tail[1], axis=1)
        margin = jax.lax.dynamic_slice_in_dim(margin, tail[0], tail[1], axis=1)
    x = rms_norm(x, base["final_norm"]["scale"], cfg["norm_eps"])
    return matmul(x, embedding.T, quant), margin


def nll(base, lora, tokens, cfg):
    """Mean next-token negative log-likelihood of (B, S) ``tokens``: the loss
    whose gradients the full-sequence test compares."""
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(base, lora, tokens[:, :-1], cfg)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def _freeze(cfg: dict) -> str:
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads", "rope_theta", "norm_eps",
            "num_hidden_layers", "num_dense_layers", "conv_L_cache", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "use_expert_bias")
    out = {k: cfg[k] for k in keep if k in cfg}
    out["layer_types"] = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    out["lora"] = {"rank": cfg["lora"]["rank"], "alpha": cfg["lora"]["alpha"]}
    return json.dumps(out, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _logits_jit(frozen: str, quant):
    cfg = json.loads(frozen)

    def f(base, lora, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(base, lora, tokens, cfg, quant)

    return jax.jit(f)


def logits(base, lora, tokens, cfg, quant=None):
    """Logits and routing margins, jitted once per configuration."""
    return _logits_jit(_freeze(cfg), quant)(base, lora, tokens)


@functools.lru_cache(maxsize=None)
def _changed_jit(frozen: str):
    cfg = json.loads(frozen)

    def f(base, lora, tokens):
        with jax.default_matmul_precision("highest"):
            moved = []
            forward(base, lora, tokens, cfg, changed=moved, tail=(0, 1))
            return jnp.stack(moved)

    return jax.jit(f)


def bias_changed(base, lora, tokens, cfg):
    """(sparse layers, B, S): where the selection bias changed which experts
    were chosen (the k largest of ``s + b`` are not the k largest of ``s``)."""
    return _changed_jit(_freeze(cfg))(base, lora, tokens)


# -- a served request, teacher-forced -------------------------------------------

@functools.lru_cache(maxsize=None)
def _forced_fn(frozen: str, tail: int):
    cfg = json.loads(frozen)

    def f(base, lora, tokens, start):
        """tokens (1, L).  For the ``tail`` positions p from ``start``: how far
        the logit of token p+1 lies below that position's best, the
        position's spread (best minus median), position p's own routing
        margin; and the logits themselves, for the control."""
        with jax.default_matmul_precision("highest"):
            ref, margin = forward(base, lora, tokens, cfg, None, (start, tail))
            ref = ref[0]
            best = jnp.max(ref, axis=-1)
            following = jax.lax.dynamic_slice_in_dim(tokens[0], start + 1, tail)
            nxt = jnp.take_along_axis(ref, following[:, None], axis=-1)[:, 0]
            return {"gap": best - nxt, "spread": best - jnp.median(ref, axis=-1),
                    "margin": margin[0]}, ref

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _first_fn(frozen: str, quant, tail: int):
    """The token a lower precision puts first at each of the ``tail``
    positions."""
    cfg = json.loads(frozen)

    def f(base, lora, tokens, start):
        with jax.default_matmul_precision("highest"):
            low, _ = forward(base, lora, tokens, cfg, quant, (start, tail))
            return jnp.argmax(low[0], axis=-1)

    return jax.jit(f)


def forced_gaps(base, lora, tokens, start, tail: int, cfg, quant=None):
    """The served tokens' gaps at positions ``start .. start + tail - 1`` of
    ``tokens`` (1, L), ``L >= start + tail + 1``.  ``quant``: one lower
    precision or several; for each also the same gap for the token that
    precision puts first: ``control_gap`` for ``"int8"``, ``witness_gap`` for
    ``"bfloat16"``."""
    frozen = _freeze(cfg)
    start = jnp.asarray(start, jnp.int32)
    out, ref = _forced_fn(frozen, int(tail))(base, lora, tokens, start)
    for low in (quant,) if isinstance(quant, str) else tuple(quant or ()):
        first = _first_fn(frozen, low, int(tail))(base, lora, tokens, start)
        out[LOWER[low]] = jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
    return out
