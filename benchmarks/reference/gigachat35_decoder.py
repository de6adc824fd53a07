"""The plain reference of a ``gigachat3_5`` decoder (GigaChat3.5-432B-A28B, as
its ``config.json`` gives it).  ``N(x) = x / sqrt(mean x^2 + rms_norm_eps) *
layernorm_gating_weight * sigmoid(w)`` is the zero-centred gated norm of every
block norm and of the final one; layer i is sequential with four of them
(``layernorm_type: pre_post``): ``h = x + N2(Op_i(N1 x))``,
``x' = h + N4(FF_i(N3 h))``.

- Layer i in ``full_attention_layers``: multi-head latent attention, expanded
  (every head's keys and values multiplied out of the latent ``c_kv``; the
  rotary key ``k_r`` shared), YaRN rotary frequencies, softmax scale
  ``(nope + rope) ** -0.5 * mscale(factor, mscale_all_dim) ** 2``
  (``use_mla_scaling_factor``); every head's output times ``sigmoid(W_gate n)``
  (``gated_attention``) before ``o``.
- Every other layer, the gated delta rule (``GigaChat35GatedDeltaNet``), **step
  by step**, the definition: ``q k v = W n``; ``[q; k; v]`` through a causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps (zeros before the
  sequence, no bias), SiLU; q and k L2-normalised a head at a time (eps 1e-6),
  q times ``d_k ** -0.5``; value head h reads key head ``h // (H_v / H_k)``;
  ``beta = sigmoid(W_b n)``, ``g = -exp(A_log) softplus(W_a n + dt_bias)``;
  ``S <- e^g S``, ``S <- S + k (beta (v - S^T k))^T``, ``o = S^T q`` with S
  (d_k x d_v) in float32 from zeros; ``y = W_o [RMS(o_h)(1 + w) *
  linear_sigmoid_gate_scale * sigmoid(z_h)]``, the norm's eps
  ``linear_attn_o_norm_eps``.
- ``FF_i``, ``i < first_k_dense_replace``: SwiGLU of ``intermediate_size``.
  Else ``s = sigmoid(W_r n)`` over all ``E`` routed experts (the router at its
  published width); the ``num_experts_per_tok`` largest of ``s + b`` are
  chosen (``noaux_tc`` in one group: ``b`` is not in the gates), ``g_e = s_e /
  sum_chosen s`` (``norm_topk_prob``) times ``routed_scaling_factor``; the sum
  over the chosen experts that are held here, plus the shared expert.  Every
  SwiGLU, dense, shared and routed, is clamped (``swiglu_limit``):
  ``silu(min(gate, L)) * clip(up, -L, L)``.
- a final norm and an untied head; LoRA on ``q_a q_b kv_a o`` of the latent
  layers and ``q k v o`` of the linear ones.

**The share.**  The configuration holds ``count`` consecutive experts from
``first`` of the router's ``E`` (``held``); ``held=None`` is the uncut layer.
The vocabulary is whatever the embedding and the head hold.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``; the
experts by a scan over the held experts with masks (one expert's weights
widened at a time); the attention a few heads
and a block of queries at a time, so that a request at the cell's length fits
on the chip beside the base; no kernel, no cache, no batching engine, and
nothing imported from the program under test.  Weights stay in the type they
are served in and are widened where a product reads them, layer by layer under
``jax.checkpoint``.

``forward`` also reports each position's smallest routing margin over the
sparse layers, in the selection's own scores ``s + b``: how far the k-th chosen
expert lies above the best one left out, where either of the two is held here
(no other choice changes what the held experts add).

``quant="int8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to eight bits
(a scale per row of the contraction), the precision next below the bfloat16
the configuration states.  ``quant="bfloat16"`` is the witness: both operands
of every matrix product and the stream between layers rounded to bfloat16.

Departures from the published model are listed in the configuration file.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from reference.lfm2_moe_decoder import LOWER, _as_bfloat16, _fake_int8, _lora_of, lora_dense, matmul
from reference.mla_moe_decoder import rope, yarn_mscale

F32 = jnp.float32
#: heads and queries of the latent attention computed at a time
HEAD_BLOCK = 8
QUERY_BLOCK = 1024
L2_EPS = 1e-6


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def gated_norm(x, norm, cfg):
    return rms(x, cfg["rms_norm_eps"]) * cfg["layernorm_gating_weight"] * jax.nn.sigmoid(norm["gate"])


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if cfg.get("use_mla_scaling_factor", True) and scaling and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


# -- the mixers -------------------------------------------------------------------

def mla(n, layer, lora, cfg, quant):
    """Multi-head latent attention, expanded, with every head's output gated:
    n (B, S, d) -> (B, S, d)."""
    b, s, _ = n.shape
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rp, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta, scaling = cfg["rms_norm_eps"], cfg["rope_theta"], cfg.get("rope_scaling")
    ls, lo = _lora_of(lora, cfg)
    pos = jnp.arange(s)
    c_q = rms(lora_dense(n, layer["q_a"], lo("q_a"), ls, quant), eps) * layer["q_a_norm"]["scale"]
    q = lora_dense(c_q, layer["q_b"], lo("q_b"), ls, quant).reshape(b, s, h, nope + rp)
    q = q.transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, theta, scaling)
    kv = lora_dense(n, layer["kv_a"], lo("kv_a"), ls, quant)
    c_kv = rms(kv[..., :rank], eps) * layer["kv_a_norm"]["scale"]
    k_r = rope(kv[..., rank:], pos, theta, scaling)                    # (B, S, rope)
    kv_h = matmul(c_kv, layer["kv_b"]["kernel"], quant).reshape(b, s, h, nope + dv)
    kv_h = kv_h.transpose(0, 2, 1, 3)
    k_nope, v = kv_h[..., :nope], kv_h[..., nope:]
    if quant == "int8":
        q_nope, q_rope, k_nope, v, k_r = (_fake_int8(t, -1) for t in (q_nope, q_rope, k_nope, v, k_r))
    elif quant == "bfloat16":
        q_nope, q_rope, k_nope, v, k_r = (_as_bfloat16(t) for t in (q_nope, q_rope, k_nope, v, k_r))
    scale = softmax_scale(cfg)
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb

    def heads(args):                                    # HEAD_BLOCK heads, every query block
        qn, qr, kn, vv = args                           # (hb, B, S, .)
        qn = jnp.pad(qn, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(qn.shape[:2] + (-1, qb, nope))
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(qr.shape[:2] + (-1, qb, rp))

        def block(args):
            qq, qqr, at = args                          # (hb, B, qb, .), first position
            seen = (at + jnp.arange(qb))[:, None] >= pos[None, :]
            scores = (jnp.einsum("hbqd,hbkd->hbqk", qq, kn)
                      + jnp.einsum("hbqd,bkd->hbqk", qqr, k_r)) * scale
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hbqk,hbkd->hbqd", probs, vv)

        out = jax.lax.map(block, (jnp.moveaxis(qn, 2, 0), jnp.moveaxis(qr, 2, 0),
                                  jnp.arange(qn.shape[2]) * qb))
        return jnp.moveaxis(out, 0, 2).reshape(out.shape[1:3] + (-1, dv))[:, :, :s]

    step = math.gcd(h, HEAD_BLOCK)
    blocks = lambda t: t.transpose(1, 0, 2, 3).reshape((h // step, step, b) + t.shape[2:])  # noqa: E731
    out = jax.lax.map(heads, (blocks(q_nope), blocks(q_rope), blocks(k_nope), blocks(v)))
    out = out.reshape(h, b, s, dv).transpose(1, 2, 0, 3)                 # (B, S, h, dv)
    out = out * jax.nn.sigmoid(matmul(n, layer["gate"]["kernel"], quant)).reshape(b, s, h, dv)
    return lora_dense(out.reshape(b, s, h * dv), layer["o"], lo("o"), ls, quant)


def delta_rule(q, k, v, beta, g):
    """The recurrence, one position after another: q, k (B, S, H, dk), v (B,
    S, H, dv), beta, g (B, S, H) -> o (B, S, H, dv) and the state after the
    last position (B, H, dk, dv); the state from zeros, in float32."""
    b, _, h, dk = k.shape

    def one(state, x):
        qt, kt, vt, bt, gt = x
        state = state * jnp.exp(gt)[..., None, None]
        kv = jnp.sum(state * kt[..., :, None], axis=-2)                  # S^T k
        state = state + kt[..., :, None] * ((vt - kv) * bt[..., None])[..., None, :]
        return state, jnp.sum(state * qt[..., :, None], axis=-2)         # S^T q

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, beta, g))
    state, o = jax.lax.scan(one, jnp.zeros((b, h, dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(o, 0, 1), state


def rule_inputs(mixed, b_in, a_in, layer, cfg):
    """What the rule reads, from the projections ``mixed = [q; k; v]`` (B, S,
    C) and ``b_in``, ``a_in`` (B, S, H_v): the convolution, SiLU, the unit q
    and k of every value head, ``beta`` and ``g``."""
    b, s, _ = mixed.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    w = layer["conv_weight"].astype(F32)                                   # (C, K)
    ext = jnp.pad(mixed.astype(F32), ((0, 0), (taps - 1, 0), (0, 0)))     # zeros before the sequence
    c = jax.nn.silu(sum(ext[:, j:j + s] * w[:, j] for j in range(taps)))
    q, k, v = jnp.split(c, [hk * dk, 2 * hk * dk], axis=-1)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + L2_EPS)  # noqa: E731
    rep = hv // hk
    q = jnp.repeat(unit(q.reshape(b, s, hk, dk)) * dk ** -0.5, rep, axis=2)
    k = jnp.repeat(unit(k.reshape(b, s, hk, dk)), rep, axis=2)
    beta = jax.nn.sigmoid(b_in.astype(F32))
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(a_in.astype(F32) + layer["dt_bias"])
    return q, k, v.reshape(b, s, hv, dv), beta, g


def linear(n, layer, lora, cfg, quant):
    """The gated delta rule: n (B, S, d) -> (B, S, d)."""
    b, s, _ = n.shape
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    ls, lo = _lora_of(lora, cfg)
    mixed = jnp.concatenate([lora_dense(n, layer[p], lo(p), ls, quant) for p in ("q", "k", "v")], -1)
    o, _ = delta_rule(*rule_inputs(mixed, matmul(n, layer["b"]["kernel"], quant),
                                   matmul(n, layer["a"]["kernel"], quant), layer, cfg))
    o = rms(o, cfg["linear_attn_o_norm_eps"]) * (1.0 + layer["o_norm"])
    z = matmul(n, layer["z"]["kernel"], quant).reshape(b, s, hv, dv)
    y = o * cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(z)
    return lora_dense(y.reshape(b, s, hv * dv), layer["o"], lo("o"), ls, quant)


# -- the feed-forward ---------------------------------------------------------------

def swiglu(x, w, cfg, quant):
    limit = float(cfg.get("swiglu_limit") or 0)
    gate = matmul(x, w["w_gate"], quant)
    up = matmul(x, w["w_up"], quant)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return matmul(jax.nn.silu(gate) * up, w["w_down"], quant)


def route(hn, moe, cfg, held, quant):
    """hn (N, d) -> gates (N, k), chosen experts (N, k), and the margin (N,)
    in ``s + b`` where the k-th or the (k+1)-th expert is held here."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul(hn, moe["router"]["kernel"], quant))       # (N, E)
    top, idx = jax.lax.top_k(s + moe["select_bias"].astype(F32), k + 1)
    gates = jnp.take_along_axis(s, idx[:, :k], axis=1)
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * float(cfg.get("routed_scaling_factor", 1.0))
    first, count = held if held is not None else (0, s.shape[-1])
    is_held = lambda i: (i >= first) & (i < first + count)              # noqa: E731
    margin = jnp.where(is_held(idx[:, k - 1]) | is_held(idx[:, k]), top[:, k - 1] - top[:, k], jnp.inf)
    return gates, idx[:, :k], margin


def experts(hn, layer, cfg, held, quant):
    """The held experts' part of the routed sum, the shared expert's, and the
    margin."""
    moe = layer["moe_mlp"]
    gates, idx, margin = route(hn, moe, cfg, held, quant)
    first = held[0] if held is not None else 0

    def one(y, args):                           # expert first + j, whose weights are row j
        j, w_gate, w_up, w_down = args
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), axis=-1)    # (N,)
        return y + g[:, None] * swiglu(hn, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, cfg, quant), None

    count = moe["w_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(hn), (jnp.arange(count), moe["w_gate"], moe["w_up"], moe["w_down"]))
    shared = {n: layer["shared_expert"][n]["kernel"] for n in ("w_gate", "w_up", "w_down")}
    return y + swiglu(hn, shared, cfg, quant), margin


def block(x, layer, lora, cfg, kind, sparse, held, quant):
    kept = _as_bfloat16 if quant == "bfloat16" else (lambda t: t)       # the stream between the layers
    mix = linear if kind == "linear_attention" else mla
    name = "linear_attn" if kind == "linear_attention" else "attention"
    h = kept(x + gated_norm(mix(gated_norm(x, layer["attn_norm"], cfg), layer[name], lora, cfg, quant),
                            layer["attn_post_norm"], cfg))
    hn = gated_norm(h, layer["mlp_norm"], cfg)
    b, s, d = hn.shape
    if not sparse:
        dense = {n: layer["mlp"][n]["kernel"] for n in ("w_gate", "w_up", "w_down")}
        return kept(h + gated_norm(swiglu(hn, dense, cfg, quant), layer["mlp_post_norm"], cfg)), \
            jnp.full((b, s), jnp.inf, F32)
    y, margin = experts(hn.reshape(b * s, d), layer, cfg, held, quant)
    return kept(h + gated_norm(y.reshape(b, s, d), layer["mlp_post_norm"], cfg)), margin.reshape(b, s)


def kinds(cfg):
    full = {int(i) for i in cfg["full_attention_layers"]}
    return ["full_attention" if i in full else "linear_attention" for i in range(cfg["num_hidden_layers"])]


def forward(base, lora, tokens, cfg, held=None, quant=None, tail=None):
    """(B, S) token ids -> (B, S, V) float32 logits and (B, S) the smallest
    routing margin over the sparse layers.  ``tail = (start, T)``: those of
    positions ``start .. start + T - 1`` only (``start`` may be traced)."""
    x = base["tok_embed"]["embedding"][tokens].astype(F32)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    for i, kind in enumerate(kinds(cfg)):
        name = f"layer_{i}"
        lo = None if lora is None else lora[name]["linear_attn" if kind == "linear_attention" else "attention"]
        x, m = jax.checkpoint(functools.partial(
            block, cfg=cfg, kind=kind, sparse=i >= cfg["first_k_dense_replace"], held=held,
            quant=quant))(x, base[name], lo)
        margin = jnp.minimum(margin, m)
    if tail is not None:
        x = jax.lax.dynamic_slice_in_dim(x, tail[0], tail[1], axis=1)
        margin = jax.lax.dynamic_slice_in_dim(margin, tail[0], tail[1], axis=1)
    x = gated_norm(x, base["final_norm"], cfg)
    return matmul(x, base["lm_head"]["kernel"], quant), margin


def nll(base, lora, tokens, cfg, held=None):
    """Mean next-token negative log-likelihood of (B, S) ``tokens``."""
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(base, lora, tokens[:, :-1], cfg, held)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def _freeze(cfg: dict) -> str:
    keep = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_scaling", "rms_norm_eps",
            "num_hidden_layers", "first_k_dense_replace", "full_attention_layers",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "use_mla_scaling_factor",
            "layernorm_gating_weight", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_attn_o_norm_eps", "linear_sigmoid_gate_scale", "swiglu_limit")
    out = {k: cfg[k] for k in keep if k in cfg}
    out["lora"] = {"rank": cfg["lora"]["rank"], "alpha": cfg["lora"]["alpha"]}
    return json.dumps(out, sort_keys=True)


def _held(held):
    return None if held is None else tuple(held)


@functools.lru_cache(maxsize=None)
def _logits_jit(frozen: str, held, quant):
    cfg = json.loads(frozen)

    def f(base, lora, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(base, lora, tokens, cfg, held, quant)

    return jax.jit(f)


def logits(base, lora, tokens, cfg, held=None, quant=None):
    """Logits and routing margins, jitted once per configuration."""
    return _logits_jit(_freeze(cfg), _held(held), quant)(base, lora, tokens)


# -- a served request, teacher-forced -------------------------------------------

@functools.lru_cache(maxsize=None)
def _forced_fn(frozen: str, held, tail: int):
    cfg = json.loads(frozen)

    def f(base, lora, tokens, start):
        """tokens (1, L).  For the ``tail`` positions p from ``start``: how far
        the logit of token p+1 lies below that position's best, the position's
        spread (best minus median), position p's own routing margin; and the
        logits themselves, for a lower precision's first token."""
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
    cfg = json.loads(frozen)

    def f(base, lora, tokens, start):
        with jax.default_matmul_precision("highest"):
            low, _ = forward(base, lora, tokens, cfg, held, quant, (start, tail))
            return jnp.argmax(low[0], axis=-1)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _state_fn(frozen: str):
    cfg = json.loads(frozen)

    def f(layer, mixed, b_in, a_in, n):
        with jax.default_matmul_precision("highest"):
            q, k, v, beta, g = rule_inputs(mixed, b_in, a_in, layer, cfg)
            real = (jnp.arange(mixed.shape[1]) < n)[None, :, None]
            _, state = delta_rule(q, k, v, jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0))
            return state

    return jax.jit(f)


def state_after(layer, mixed, b_in, a_in, n, cfg):
    """The matrix state of a linear layer after the first ``n`` positions of
    its projections ``mixed = [q; k; v]`` (B, S, C), ``b_in``, ``a_in`` (B, S,
    H_v), taken as given (a program's own, in its own type) and carried
    through the rule step by step in float32: (B, H_v, d_k, d_v).  Positions
    from ``n`` on get ``beta = g = 0`` and leave it alone."""
    return _state_fn(_freeze(cfg))(layer, mixed, b_in, a_in, jnp.asarray(n, jnp.int32))


def forced_gaps(base, lora, tokens, start, tail: int, cfg, held=None, quant=None):
    """The served tokens' gaps at positions ``start .. start + tail - 1`` of
    ``tokens`` (1, L), ``L >= start + tail + 1``.  ``quant``: one lower
    precision or several; for each also the same gap for the token that
    precision puts first: ``control_gap`` for ``"int8"``, ``witness_gap`` for
    ``"bfloat16"``."""
    frozen, held = _freeze(cfg), _held(held)
    start = jnp.asarray(start, jnp.int32)
    out, ref = _forced_fn(frozen, held, int(tail))(base, lora, tokens, start)
    for low in (quant,) if isinstance(quant, str) else tuple(quant or ()):
        first = _first_fn(frozen, held, low, int(tail))(base, lora, tokens, start)
        out[LOWER[low]] = jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
    return out
