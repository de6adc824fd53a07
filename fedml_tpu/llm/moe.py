"""Mixture-of-Experts with expert parallelism — the EP entry in the
parallelism inventory (SURVEY §2.9 lists EP as absent from the reference;
it exists here because a TPU-native LLM stack should scale FFN capacity
without scaling per-token FLOPs).

Design (static shapes throughout, nothing dropped):

- **Router** (:func:`route`): softmax or sigmoid scores over ALL experts,
  group-limited top-k (the experts form ``n_group`` groups, a group scores
  the sum of its two best experts, the ``topk_group`` best groups stay and
  the k best experts among them are chosen; one group is plain top-k —
  the same code), optional renormalisation over the chosen k and a
  constant scale.  Under a selection bias (``select_bias``) groups and
  experts are chosen by ``scores + bias`` and the gates stay the chosen
  experts' scores.  The softmax router also sows the load-balancing
  auxiliary loss (mean(token-fraction · prob-fraction) · E², the standard
  switch loss).
- **Dispatch** (:func:`expert_ffn`): the ``N·k`` (token, expert) pairs are
  sorted by expert, the held experts' SwiGLU runs as grouped matmuls over
  the sorted rows (``ops/grouped_matmul.py::swiglu``), and every pair's
  output goes back to its token with its gate weight.  The shapes depend on
  ``N·k`` alone, so no skew drops a token.  Where the program is lowered for
  a TPU, the operands are bfloat16 and ``dim`` and ``ffn_dim`` are whole
  128-lane tiles, the grouped matmuls are two Pallas
  kernels (gate and up fused, then down) that read a hit expert's weights
  once, skip the experts no pair chose and leave the row tiles past the last
  held row untouched; everywhere else they are three
  ``jax.lax.ragged_dot``s.  Same rounding points either way, and the
  backward is always ``ragged_dot``'s.
- **Which experts live here**: ``held = (first, count)`` — the layer routes
  over all ``n_experts``, holds the weights of ``count`` consecutive ones
  and computes their part of the result (the gate keeps its denominator
  over all chosen experts).  What the absent experts would add is another
  holder's part; on one chip the layer runs without its exchange.
- **EP sharding**: under a mesh with a ``model`` axis the experts' weights
  are sharded over it (``with_sharding_constraint``), every shard computes
  the part of its own ``E / ep`` experts by the same function and the parts
  are summed over the axis.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.mesh import MODEL_AXIS
from ..ops.grouped_matmul import swiglu as grouped_swiglu

#: the collection a layer sows ``[pairs, experts_hit, load_max, tiles]``
#: into (int32): pairs computed by the held experts, held experts that got
#: at least one, the most any one got, and the (expert, row tile) visits of
#: the grouped-matmul kernels (0 where ``ragged_dot`` ran).  Mutable only
#: where a caller asks.
COUNTERS = "moe_counters"


def _active_mesh(explicit):
    """Explicit mesh if given, else the ambient ``with mesh:`` context (so
    EP engages through LlamaLM/Block without threading a mesh handle)."""
    if explicit is not None:
        return explicit
    from jax._src.mesh import thread_resources
    ctx = thread_resources.env.physical_mesh
    return None if ctx.empty else ctx


def _ep_mesh(explicit):
    """The mesh to run expert-parallel on, or None."""
    mesh = _active_mesh(explicit)
    if mesh is None or mesh.shape.get(MODEL_AXIS, 1) == 1:
        return None
    return mesh


def _ep_constraint(x, mesh):
    """Shard axis 0 (experts) over the model axis when a mesh is active."""
    mesh = _ep_mesh(mesh)
    if mesh is None:
        return x
    spec = P(*((MODEL_AXIS,) + (None,) * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def route(scores, top_k: int, n_group: int = 1, topk_group: int = 1,
          norm_topk: bool = True, scale: float = 1.0, bias=None):
    """``scores`` (N, E) float32, positive → ``(gates, experts)``, both
    (N, k): the k best experts of the ``topk_group`` best groups and their
    weights.  ``bias`` (E,) float32, or None for a zero one: the groups and
    the experts are chosen by ``scores + bias``, and the gates are the chosen
    experts' ``scores`` (the bias is not in them)."""
    n, e = scores.shape
    per = e // n_group
    chosen_by = scores if bias is None else scores + bias
    best2, _ = jax.lax.top_k(chosen_by.reshape(n, n_group, per), min(2, per))
    _, groups = jax.lax.top_k(best2.sum(-1), topk_group)        # (N, tg)
    kept = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), chosen_by, -jnp.inf)
    gates, experts = jax.lax.top_k(masked, top_k)
    if bias is not None:
        gates = jnp.take_along_axis(scores, experts, axis=1)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20)
    return gates * scale, experts


def expert_ffn(x, gates, experts, w_gate, w_up, w_down, first):
    """The held experts' part of ``sum_j gates[n, j] * E_experts[n, j](x[n])``.

    ``x`` (N, d); ``gates``, ``experts`` (N, k); ``w_gate``/``w_up``
    (count, d, f) and ``w_down`` (count, f, d) hold experts ``first`` ..
    ``first + count - 1`` (``first`` may be traced).  Returns the (N, d)
    float32 part, the (count,) int32 pairs each held expert computed and
    the row tiles the grouped-matmul kernels visited (0: ``ragged_dot``)."""
    n, k = experts.shape
    count = w_gate.shape[0]
    local = experts.reshape(-1) - first
    here = (local >= 0) & (local < count)
    key = jnp.where(here, local, count)                # absent experts last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    rows = x[order // k]                               # (N·k, d), by expert
    y, tiles = grouped_swiglu(rows, w_gate, w_up, w_down, sizes)
    # back to (token, choice) order; rows past the held groups count nothing
    # (a select: the kernels leave them unwritten)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    g = jnp.where(here, gates.reshape(-1), 0.0)
    y = jnp.where(here[:, None], y[back], 0.0) * g[:, None]
    return y.reshape(n, k, -1).sum(1), sizes, tiles


class MoEMLP(nn.Module):
    """Drop-in SwiGLU FFN replacement with E routed experts, top-k routing.
    (A shared expert is an ordinary ``MLP`` beside it, in ``Block``.)"""

    dim: int
    ffn_dim: int
    n_experts: int = 8
    top_k: int = 2
    scoring: str = "softmax"            # softmax | sigmoid
    n_group: int = 1
    topk_group: int = 1
    norm_topk: bool = True
    routed_scale: float = 1.0
    #: the experts are chosen by ``scores + select_bias``, a float32 buffer
    #: of (E,) among the parameters that no gradient reaches
    select_bias: bool = False
    #: (first, count): the experts whose weights this layer holds; None =
    #: all of them
    held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        b, s, dim = x.shape
        n_tok = b * s
        e, k = self.n_experts, self.top_k
        first, count = self.held or (0, e)

        xt = x.reshape(n_tok, dim)
        logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          name="router")(xt.astype(jnp.float32))
        if self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)             # (N, E)
        bias = jax.lax.stop_gradient(self.param(
            "select_bias", nn.initializers.zeros, (e,), jnp.float32)) \
            if self.select_bias else None
        gates, experts = route(scores, k, self.n_group, self.topk_group,
                               self.norm_topk, self.routed_scale, bias)

        if self.scoring == "softmax":
            # load-balancing aux loss (store for the trainer to read)
            me = scores.mean(0)                             # prob fraction
            ce = jnp.zeros((e,), jnp.float32).at[experts.reshape(-1)].add(
                1.0) / (n_tok * k)                          # token fraction
            self.sow("losses", "moe_aux", jnp.sum(me * ce) * e * e)

        init = nn.initializers.lecun_normal()
        w = [self.param(name, init, shape, self.param_dtype).astype(self.dtype)
             for name, shape in (("w_gate", (count, dim, self.ffn_dim)),
                                 ("w_up", (count, dim, self.ffn_dim)),
                                 ("w_down", (count, self.ffn_dim, dim)))]
        xt = xt.astype(self.dtype)
        mesh = _ep_mesh(self.mesh)
        if mesh is not None and count % mesh.shape[MODEL_AXIS] == 0:
            per = count // mesh.shape[MODEL_AXIS]

            def part(xt, gates, experts, *w):
                mine = first + jax.lax.axis_index(MODEL_AXIS) * per
                out, sizes, tiles = expert_ffn(xt, gates, experts, *w, mine)
                return (jax.lax.psum(out, MODEL_AXIS), sizes,
                        jax.lax.psum(tiles, MODEL_AXIS))

            out, sizes, tiles = jax.shard_map(
                part, mesh=mesh,
                in_specs=(P(), P(), P()) + (P(MODEL_AXIS),) * 3,
                out_specs=(P(), P(MODEL_AXIS), P()), check_vma=False)(
                    xt, gates, experts,
                    *[_ep_constraint(m, self.mesh) for m in w])
        else:
            out, sizes, tiles = expert_ffn(xt, gates, experts, *w, first)
        self.sow(COUNTERS, "layer", jnp.stack(
            [sizes.sum(), (sizes > 0).sum(), sizes.max(), tiles]
        ).astype(jnp.int32))
        return out.reshape(b, s, dim).astype(x.dtype)


__all__ = ["MoEMLP", "route", "expert_ffn", "COUNTERS"]
