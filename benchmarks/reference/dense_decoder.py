"""The plain reference of a dense decoder: RMSNorm, rotary embedding,
grouped-query causal attention, SwiGLU, LoRA on the four attention
projections, next-token loss, its gradient, AdamW steps and the weighted merge
of a federated round.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``.
No kernel, no cache, no batching engine, and nothing imported from the program
under test.  Layers are rematerialised one by one, and callers feed it a batch
row block or one request at a time, so that it fits on the chip beside
nothing else.

``quant="int8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to eight bits
(a scale per row of the contraction), the precision next below the bfloat16
the configurations state.

Departures from the published models are listed in the configuration files
(interleaved rotary pairs, separate q/k/v matrices, seeded weights).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _fake_int8(x, axis):
    """x rounded to eight bits with one scale per row along ``axis``; the
    gradient passes straight through, as in int8 training."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


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


def rope(x, positions, theta):
    """x: (B, H, S, D); positions: (S,).  Adjacent pairs rotate together."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def lora_dense(x, proj, lora, scale, quant):
    y = matmul(x, proj["base"]["kernel"], quant)
    if lora is not None:
        y = y + scale * matmul(matmul(x, lora["A"], quant), lora["B"], quant)
    return y


def attention(x, layer, lora, cfg, quant):
    b, s, _ = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    scale = float(cfg["lora"]["alpha"]) / float(cfg["lora"]["rank"])
    lo = (lambda n: lora[n]) if lora is not None else (lambda n: None)
    q = lora_dense(x, layer["wq"], lo("wq"), scale, quant)
    k = lora_dense(x, layer["wk"], lo("wk"), scale, quant)
    v = lora_dense(x, layer["wv"], lo("wv"), scale, quant)
    pos = jnp.arange(s)
    q = rope(q.reshape(b, s, h, hd).transpose(0, 2, 1, 3), pos, cfg["rope_theta"])
    k = rope(k.reshape(b, s, kv, hd).transpose(0, 2, 1, 3), pos, cfg["rope_theta"])
    v = v.reshape(b, s, kv, hd).transpose(0, 2, 1, 3)
    if quant == "int8":
        q, k, v = _fake_int8(q, -1), _fake_int8(k, -1), _fake_int8(v, -1)
    g = h // kv
    qg = q.reshape(b, kv, g, s, hd)
    scores = jnp.einsum("bkgqd,bkjd->bkgqj", qg, k) / (hd ** 0.5)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqj,bkjd->bkgqd", probs, v).reshape(b, h, s, hd)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return lora_dense(out, layer["wo"], lo("wo"), scale, quant)


def block(x, layer, lora, cfg, quant):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, layer["attn_norm"]["scale"], eps),
                      layer["attention"], lora, cfg, quant)
    hdn = rms_norm(x, layer["mlp_norm"]["scale"], eps)
    mlp = layer["mlp"]
    gate = matmul(hdn, mlp["w_gate"]["kernel"], quant)
    up = matmul(hdn, mlp["w_up"]["kernel"], quant)
    return x + matmul(jax.nn.silu(gate) * up, mlp["w_down"]["kernel"], quant)


def logits_fn(base, lora, tokens, cfg, quant=None):
    """(B, S) token ids -> (B, S, V) float32 logits."""
    x = base["tok_embed"]["embedding"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        name = f"layer_{i}"
        lo = None if lora is None else lora[name]["attention"]
        x = jax.checkpoint(
            functools.partial(block, cfg=cfg, quant=quant))(x, base[name], lo)
    x = rms_norm(x, base["final_norm"]["scale"], cfg["rms_norm_eps"])
    return matmul(x, base["lm_head"]["kernel"], quant)


def nll(lora, base, x, y, cfg, quant=None):
    """Mean next-token loss over every position of the batch."""
    logp = jax.nn.log_softmax(logits_fn(base, lora, x, cfg, quant), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def _frozen(cfg: dict) -> tuple:
    keys = ("hidden_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "vocab_size",
            "num_hidden_layers", "rope_theta", "rms_norm_eps")
    return tuple((k, cfg.get(k)) for k in keys) + (
        ("lora", (cfg["lora"]["rank"], cfg["lora"]["alpha"])),)


def _thaw(items: tuple) -> dict:
    cfg = dict(items)
    cfg["lora"] = {"rank": cfg["lora"][0], "alpha": cfg["lora"][1]}
    return cfg


@functools.lru_cache(maxsize=None)
def _grad_fn(items: tuple, quant):
    cfg = _thaw(items)

    def f(lora, base, x, y):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(nll)(lora, base, x, y, cfg, quant)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _logits_jit(items: tuple, quant):
    cfg = _thaw(items)

    def f(base, lora, tokens):
        with jax.default_matmul_precision("highest"):
            return logits_fn(base, lora, tokens, cfg, quant)

    return jax.jit(f)


def loss_and_grad(lora, base, x, y, cfg, quant=None):
    return _grad_fn(_frozen(cfg), quant)(lora, base, x, y)


def logits(base, lora, tokens, cfg, quant=None):
    return _logits_jit(_frozen(cfg), quant)(base, lora, tokens)


# -- the federated round -------------------------------------------------------

@jax.jit
def _adamw(lora, grads, mu, nu, count, lr):
    """One AdamW step with no weight decay (b1 0.9, b2 0.999, eps 1e-8)."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    count = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count.astype(F32)
    c2 = 1 - b2 ** count.astype(F32)
    new = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * (m / c1) / (jnp.sqrt(n / c2) + eps), lora, mu, nu)
    return new, mu, nu, count


@jax.jit
def _merge(loras, weights):
    w = weights / jnp.sum(weights)
    return jax.tree_util.tree_map(
        lambda *leaves: sum(wi * leaf for wi, leaf in zip(w, leaves)), *loras)


def federated_round(base, global_lora, x, y, weights, cfg, lr, quant=None,
                    rows=None):
    """One round as the configuration states it: every client starts from the
    global adapters, takes ``x.shape[1]`` AdamW steps on its own batches, and
    the adapters are averaged with the clients' weights.

    x, y: (clients, steps, batch, seq) token ids and targets; weights:
    (clients,).  ``rows`` keeps only the first ``rows`` rows of every batch
    (the planted fault "half of the batch left out").  Returns the merged
    adapters and the round's loss (weighted mean over clients of the mean
    over their steps)."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, global_lora)
    loras, losses = [], []
    for c in range(x.shape[0]):
        lora, mu, nu, count = global_lora, zeros, zeros, jnp.zeros((), jnp.int32)
        step_losses = []
        for s in range(x.shape[1]):
            xb, yb = x[c, s][:rows], y[c, s][:rows]
            loss, grads = loss_and_grad(lora, base, jnp.asarray(xb),
                                        jnp.asarray(yb), cfg, quant)
            lora, mu, nu, count = _adamw(lora, grads, mu, nu, count, F32(lr))
            step_losses.append(loss)
        loras.append(lora)
        losses.append(sum(step_losses) / len(step_losses))
    w = jnp.asarray(weights, F32)
    merged = _merge(loras, w)
    loss = sum(wi * li for wi, li in zip(w / jnp.sum(w), losses))
    return merged, float(loss)


# -- a served request, teacher-forced -------------------------------------------

@functools.lru_cache(maxsize=None)
def _forced_fn(items: tuple, quant):
    cfg = _thaw(items)

    def f(base, lora, tokens):
        """tokens (1, L).  For every position p < L-1: how far the logit of
        token p+1 lies below that position's best, the position's spread
        (best minus median), and, under ``quant``, the same gap for the
        token the lower precision puts first."""
        with jax.default_matmul_precision("highest"):
            ref = logits_fn(base, lora, tokens, cfg, None)[0, :-1]
            best = jnp.max(ref, axis=-1)
            nxt = jnp.take_along_axis(ref, tokens[0, 1:, None], axis=-1)[:, 0]
            spread = best - jnp.median(ref, axis=-1)
            out = {"gap": best - nxt, "spread": spread}
            if quant is not None:
                low = logits_fn(base, lora, tokens, cfg, quant)[0, :-1]
                first = jnp.argmax(low, axis=-1)
                out["control_gap"] = best - jnp.take_along_axis(
                    ref, first[:, None], axis=-1)[:, 0]
            return out

    return jax.jit(f)


def forced_gaps(base, lora, tokens, cfg, quant=None):
    return _forced_fn(_frozen(cfg), quant)(base, lora, tokens)
