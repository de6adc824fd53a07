"""The gated delta rule (Yang, Kautz and Hatamizadeh, arXiv:2412.06464) as a
mixer in place of attention (``layer_types[i] == "linear_attention"``), in the
form GigaChat 3.5's ``GigaChat35GatedDeltaNet`` and Qwen3-Next ship.  For each
token's normed input ``n``:

- ``q, k = W_q n, W_k n`` (``linear_key_heads`` heads of
  ``linear_key_head_dim``), ``v, z = W_v n, W_z n`` (``linear_value_heads``
  heads of ``linear_value_head_dim``), ``b, a = W_b n, W_a n`` (one number a
  value head);
- ``[q; k; v]`` through a causal depthwise convolution of
  ``linear_conv_kernel`` taps (no bias, zeros before the sequence), then SiLU;
- q and k L2-normalised a head at a time, q scaled by ``d_k ** -0.5``; value
  head h reads key head ``h // (value heads / key heads)``;
- ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``;
- per value head a matrix state ``S`` (d_k x d_v): ``S <- e^g S``, then
  ``S <- S + k (beta (v - S^T k))^T``, and the output ``o = S^T q``
  (``step`` computes it re-associated, reading ``S`` once for both sums);
- ``y = W_o [RMS(o_h) (1 + w) * linear_gate_scale * sigmoid(z_h)]_h``, the norm
  over a head's d_v numbers with eps ``linear_norm_eps`` and one weight ``w``
  of d_v (zero-centred).

A call of one position (the decode tick) takes that step.  A longer call (a
prefill chunk, the full sequence) runs the chunked form: blocks of ``BLOCK``
positions; within a block the WY representation (the UT transform, one unit
lower-triangular solve) gives every position's correction at once, and the
state is carried from block to block.  Positions past a lane's ``lens`` (a
final chunk's padding) get ``beta = 0`` and ``g = 0``: they leave the state as
the lane's last real position left it.

What a later call needs of the past, on the decode paths, is two leaves of the
``cache`` collection shaped by **rows of state** (``ShortConv``'s layout and
rules, ``model.py``): ``linear_state``, the matrix state in ``STATE_DTYPE``
(float32), ``(rows, value heads, d_k, d_v)``, and ``linear_conv``, the last
``taps - 1`` rows of ``[q; k; v]`` before the convolution, in the model's
dtype.  A lane whose first position is 0 starts from zeros (only where it has a
real position), and every call writes its lanes' rows back.  The decode tick
addresses state by slot: no ``state_rows`` (lane i is row i) and ``seq_lens``
1 or 0, so the first ``b`` rows are read where they lie and written back by one
in-place update at row 0, and a lane with ``seq_lens`` 0 gets back the rows it
read, bit for bit (``model.py::write_rows``).  A prefill chunk addresses its
one slot's row by index (``_rows_of``, which serves the chunk alone) and
scatters it back.

Everything between the projections runs under the scope ``core``
(``layer_*/linear_attn/core`` in a program's module map), so the device time of
the convolution, the rule and the state's reads and writes is told apart from
the projections'.  Products that touch the state run at
``Precision.HIGHEST``: float32 state, float32 sums.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .model import LlamaConfig, _projection, last_rows, starts_fresh, write_rows

#: positions a block of the chunked form holds
BLOCK = 64
#: the matrix state's type between steps and between calls
STATE_DTYPE = jnp.float32
#: what the L2 norm of q and k adds under the root
L2_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = L2_EPS):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rows_of(table, rows):
    """``table[rows]`` as a loop of row reads written here, so that each
    read carries this scope's name into the program's module map (the
    compiler's own loop for a gather of whole rows carries none).  The
    chunk's one row; the tick reads its rows where they lie."""
    return jax.lax.map(
        lambda r: jax.lax.dynamic_index_in_dim(table, r, keepdims=False), rows)


def step(state, q, k, v, beta, g):
    """One position: ``state`` (b, H, dk, dv) float32, ``q``/``k`` (b, H, dk),
    ``v`` (b, H, dv), ``beta``/``g`` (b, H) -> the next state and ``o`` (b, H,
    dv).  The rule re-associated so that the state is read once for both sums
    over d_k, ``kv = e^g S^T k`` and ``qs = e^g S^T q``, and once more for the
    update: ``delta = beta (v - kv)``, ``o = qs + (k^T q) delta`` (which is
    ``S_new^T q``) and ``S_new = e^g S + k delta^T``.  Products and sums
    elementwise, in float32: a product at ``HIGHEST`` would split the state
    into three bfloat16 copies (at a tick's 96 lanes, 600 MB a product and
    layer)."""
    decay = jnp.exp(g)[..., None]
    kv = decay * jnp.sum(state * k[..., :, None], axis=-2)             # e^g S^T k
    qs = decay * jnp.sum(state * q[..., :, None], axis=-2)             # e^g S^T q
    delta = (v - kv) * beta[..., None]
    o = qs + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    return state * decay[..., None] + k[..., :, None] * delta[..., None, :], o


def chunked(q, k, v, beta, g, state, block: int = BLOCK):
    """The rule over ``s`` positions in blocks: ``q``/``k`` (b, H, s, dk),
    ``v`` (b, H, s, dv), ``beta``/``g`` (b, H, s), ``state`` (b, H, dk, dv)
    float32 -> ``o`` (b, H, s, dv) and the state after the last position."""
    b, h, s, dk = k.shape
    dv = v.shape[-1]
    pad = -s % block
    if pad:     # beta = g = 0: the padding leaves the state alone
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) for t in (q, k, v))
        beta, g = (jnp.pad(t, ((0, 0), (0, 0), (0, pad))) for t in (beta, g))
    n = (s + pad) // block
    q, k, v = (t.reshape(b, h, n, block, t.shape[-1]) for t in (q, k, v))
    beta, g = (t.reshape(b, h, n, block) for t in (beta, g))
    gc = jnp.cumsum(g, axis=-1)                                 # log decay to i
    lower = jnp.tril(jnp.ones((block, block), bool))            # j <= i
    diff = jnp.where(lower, gc[..., :, None] - gc[..., None, :], 0.0)
    decay = jnp.where(lower, jnp.exp(diff), 0.0)                # (b, H, n, L, L)
    kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k, precision=HIGHEST)
    # the UT transform: (I + tril(diag(beta) K K^T * decay, -1)) [u | w] =
    # [beta v | beta e^gc k]
    strict = jnp.tril(jnp.ones((block, block), bool), -1)
    m = jnp.where(strict, kk * decay * beta[..., :, None], 0.0) + jnp.eye(block)
    rhs = jnp.concatenate([v * beta[..., None], k * (beta * jnp.exp(gc))[..., None]], -1)
    uw = jax.lax.linalg.triangular_solve(m, rhs, left_side=True, lower=True,
                                         unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k, precision=HIGHEST) * decay
    qg = q * jnp.exp(gc)[..., None]
    kd = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    last = jnp.exp(gc[..., -1])                                 # (b, H, n)

    def body(state, x):
        u_i, w_i, qk_i, qg_i, kd_i, last_i = x
        v_new = u_i - jnp.einsum("bhld,bhdv->bhlv", w_i, state, precision=HIGHEST)
        o = (jnp.einsum("bhld,bhdv->bhlv", qg_i, state, precision=HIGHEST)
             + jnp.einsum("bhlj,bhjv->bhlv", qk_i, v_new, precision=HIGHEST))
        state = state * last_i[..., None, None] + jnp.einsum(
            "bhld,bhlv->bhdv", kd_i, v_new, precision=HIGHEST)
        return state, o

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (u, w, qk, qg, kd, last))
    state, o = jax.lax.scan(body, state, xs)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * block, dv)[:, :, :s]
    return o, state


class GatedDeltaNet(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, state=None):
        cfg = self.cfg
        hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps = cfg.linear_conv_kernel
        dense = _projection(cfg)
        plain = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.store_dtype,
            name=name)
        b, s, _ = x.shape
        mixed = jnp.concatenate([dense(hk * dk, "q")(x), dense(hk * dk, "k")(x),
                                 dense(hv * dv, "v")(x)], axis=-1)
        z = plain(hv * dv, "z")(x)
        b_in, a_in = plain(hv, "b")(x), plain(hv, "a")(x)
        width = mixed.shape[-1]
        conv_w = self.param("conv_weight", nn.initializers.normal(taps ** -0.5),
                            (width, taps), cfg.store_dtype).astype(jnp.float32)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)),
            (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), jnp.float32)
        o_norm = self.param("o_norm", nn.initializers.zeros, (dv,), jnp.float32)
        with jax.named_scope("core"):
            rows, lens = state if state is not None else (None, None)
            if decode:
                n_rows = cfg.state_slots + 1 if cfg.state_slots else b
                conv_rows = self.variable("cache", "linear_conv", jnp.zeros,
                                          (n_rows, taps - 1, width), cfg.dtype)
                carried = self.variable("cache", "linear_state", jnp.zeros,
                                        (n_rows, hv, dk, dv), STATE_DTYPE)
                fresh = starts_fresh(positions, lens)
                if rows is None:    # lane i is row i: read where they lie
                    old_conv, old_state = conv_rows.value[:b], carried.value[:b]
                else:
                    old_conv = conv_rows.value[rows]
                    old_state = _rows_of(carried.value, rows)
                prev = jnp.where(fresh[:, None, None], 0, old_conv).astype(mixed.dtype)
                s0 = jnp.where(fresh[:, None, None, None], 0.0,
                               old_state.astype(jnp.float32))
            else:
                prev = jnp.zeros((b, taps - 1, width), mixed.dtype)
                s0 = jnp.zeros((b, hv, dk, dv), jnp.float32)
            ext = jnp.concatenate([prev, mixed], axis=1)         # (b, K-1+s, C)
            c = jax.nn.silu(sum(ext[:, j:j + s].astype(jnp.float32) * conv_w[:, j]
                                for j in range(taps)))
            q, k, v = jnp.split(c, [hk * dk, 2 * hk * dk], axis=-1)
            rep = hv // hk
            q = jnp.repeat(l2norm(q.reshape(b, s, hk, dk)) * dk ** -0.5, rep, axis=2)
            k = jnp.repeat(l2norm(k.reshape(b, s, hk, dk)), rep, axis=2)
            v = v.reshape(b, s, hv, dv)
            beta = jax.nn.sigmoid(b_in.astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(a_in.astype(jnp.float32) + dt_bias)
            if lens is not None:        # a final chunk's padding changes nothing
                real = jnp.arange(s)[None, :, None] < lens[:, None, None]
                beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
            heads = lambda t: jnp.moveaxis(t, 1, 2)             # noqa: E731
            if s == 1:
                s1, o = step(s0, q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0])
                o = o[:, :, None]
            else:
                o, s1 = chunked(heads(q), heads(k), heads(v), heads(beta), heads(g), s0,
                                BLOCK)
            if decode:
                conv_rows.value = write_rows(conv_rows.value, rows, lens, old_conv,
                                             last_rows(ext, s, lens).astype(cfg.dtype))
                carried.value = write_rows(carried.value, rows, lens, old_state,
                                           s1.astype(STATE_DTYPE))
            o = jnp.moveaxis(o, 1, 2)                           # (b, s, H, dv)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                                  + cfg.linear_norm_eps) * (1.0 + o_norm)
            gate = cfg.linear_gate_scale * jax.nn.sigmoid(
                z.astype(jnp.float32).reshape(b, s, hv, dv))
            y = (o * gate).reshape(b, s, hv * dv).astype(cfg.dtype)
        return dense(cfg.dim, "o")(y)


__all__ = ["GatedDeltaNet", "chunked", "step", "l2norm", "BLOCK", "STATE_DTYPE"]
