"""Federated round algebra — DrJAX-style primitives + algorithm specs.

arXiv:2403.07128 (DrJAX) observes that a federated round is three
placement primitives composed around two pure callables:

    broadcast      server pytree -> every client        (placement marker)
    client_map     pure client fn mapped over a cohort  (vmap / scan / mesh)
    weighted_reduce  cohort-stacked pytree -> server    (weighted average)

Before this module each engine hand-rolled that composition — the SP
engine with ``stacked_weighted_average`` over a vmapped cohort, the mesh
engine with per-algorithm ``psum`` / ``psum_scatter`` branches inside its
``shard_map`` body — so adding an algorithm meant editing three merge
implementations.  Here the *shape* of every algorithm's round lives in one
declarative :class:`AlgorithmSpec` (which cross-client aggregates to
compute, from which client outputs, with which weights) and each engine
supplies only a :class:`Reducer` saying how a weighted average physically
executes on its layout.  q-FedAvg (:data:`QFEDAVG`) is the proof: a new
algorithm is ~20 lines of spec, not an engine fork.

Because the round is now one pure function of ``(ServerState, cohort,
HParams)``, ``jax.vmap`` over a stacked :class:`HParams` batch runs a whole
*population* of experiments — a server-lr / client-lr / regularizer / seed
sweep — as ONE compiled dispatch sharing one staging stream
(docs/PRIMITIVES.md).  :func:`parse_population` builds the stacked batch
from ``args.population`` / ``args.population_axes``;
:func:`population_member` extracts one member's state back out as a normal
single-experiment pytree (e.g. from an orbax checkpoint).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp

from . import tree as tree_util

Pytree = Any


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------

def broadcast(tree: Pytree) -> Pytree:
    """Server -> clients placement primitive.

    Under SPMD both placements are views of the same arrays, so this is the
    identity — kept as an explicit composition point so a round program
    reads as ``broadcast -> client_map -> weighted_reduce`` and future
    layouts (e.g. a host-paged client store) have a seam to hook."""
    return tree


def client_map(fn: Callable, mode: str = "vmap") -> Callable:
    """Map a pure per-client fn over cohort-stacked inputs.

    ``vmap`` batches clients into the MXU; ``scan`` runs them sequentially
    in constant memory.  The mesh engine uses ``vmap`` at the jit level and
    lets GSPMD partition the batch over the ``client`` mesh axis."""
    if mode == "vmap":
        return jax.vmap(fn)
    if mode != "scan":
        raise ValueError(f"client_map mode must be 'vmap'|'scan', got {mode!r}")

    def scanned(*args):
        def body(carry, inp):
            return carry, fn(*inp)
        _, outs = jax.lax.scan(body, 0, args)
        return outs

    return scanned


def weighted_reduce(stacked: Pytree, weights: jnp.ndarray,
                    axis_name: Optional[str] = None) -> Pytree:
    """Clients -> server placement primitive: weighted average over the
    leading client axis, optionally completed by a ``psum`` over a mesh
    axis when the cohort is sharded (each shard reduces its local clients,
    the collective reduces across shards)."""
    w = jnp.asarray(weights, jnp.float32)
    num = jax.tree_util.tree_map(
        lambda l: jnp.tensordot(w, l.astype(jnp.float32), axes=1), stacked)
    den = jnp.sum(w)
    if axis_name is not None:
        num = jax.tree_util.tree_map(
            lambda l: jax.lax.psum(l, axis_name), num)
        den = jax.lax.psum(den, axis_name)
    return jax.tree_util.tree_map(lambda l: l / den, num)


# --------------------------------------------------------------------------
# reducers — how one engine layout executes the reduce primitives
# --------------------------------------------------------------------------

class StackedReducer:
    """SP engine: the cohort is one stacked tree on this device."""

    def wavg(self, stacked: Pytree, w: jnp.ndarray) -> Pytree:
        return tree_util.stacked_weighted_average(stacked, w)

    def wavg_scalar(self, vec: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        p = w / jnp.sum(w)
        return jnp.sum(p * vec)

    def sum_scalar(self, vec: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(vec)


class PsumReducer:
    """Mesh replicated merge: local weighted partials + psum per leaf
    (runs inside ``shard_map``, manual over ``axis_name``)."""

    def __init__(self, axis_name: str):
        self.axis = axis_name

    def wavg(self, stacked: Pytree, w: jnp.ndarray) -> Pytree:
        from ..simulation.mesh import collectives as coll
        return coll.psum_wavg(stacked, w, self.axis)

    def wavg_scalar(self, vec, w):
        den = jax.lax.psum(jnp.sum(w), self.axis)
        return jax.lax.psum(jnp.sum(w * vec), self.axis) / den

    def sum_scalar(self, vec):
        return jax.lax.psum(jnp.sum(vec), self.axis)


class ScatterReducer:
    """Mesh scatter merge (arXiv:2004.13336): tree aggregates flatten into
    one padded vector and ``psum_scatter`` so each chip receives only its
    contiguous chunk; scalars still all-reduce."""

    def __init__(self, flat_spec, axis_name: str):
        self.flat = flat_spec
        self.axis = axis_name

    def wavg(self, stacked: Pytree, w: jnp.ndarray) -> jnp.ndarray:
        num = jax.tree_util.tree_map(
            lambda l: jnp.tensordot(w, l.astype(jnp.float32), axes=1),
            stacked)
        den = jax.lax.psum(jnp.sum(w), self.axis)
        return jax.lax.psum_scatter(self.flat.flatten(num), self.axis,
                                    scatter_dimension=0, tiled=True) / den

    def wavg_scalar(self, vec, w):
        den = jax.lax.psum(jnp.sum(w), self.axis)
        return jax.lax.psum(jnp.sum(w * vec), self.axis) / den

    def sum_scalar(self, vec):
        return jax.lax.psum(jnp.sum(vec), self.axis)


class PartialReducer:
    """Silo tier of the two-tier hierarchical aggregation
    (arXiv:2604.10859): every weighted reduction returns its *unfinished*
    ``{num, den}`` pair instead of the finished average, so S silo
    partials combine EXACTLY at the server —
    ``sum(nums) / sum(dens)`` is the flat cohort average up to float
    reassociation.  ``sum``-kind aggregates are already associative and
    stay plain.  Feed the result dicts to
    :func:`combine_partial_aggregates`."""

    def wavg(self, stacked: Pytree, w: jnp.ndarray) -> Dict[str, Any]:
        num = jax.tree_util.tree_map(
            lambda l: jnp.tensordot(jnp.asarray(w, jnp.float32),
                                    l.astype(jnp.float32), axes=1), stacked)
        return {"num": num, "den": jnp.sum(jnp.asarray(w, jnp.float32))}

    def wavg_scalar(self, vec: jnp.ndarray, w: jnp.ndarray
                    ) -> Dict[str, Any]:
        return {"num": jnp.sum(w * vec), "den": jnp.sum(w)}

    def sum_scalar(self, vec: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(vec)


def combine_partial_aggregates(spec: "AlgorithmSpec", partials
                               ) -> Dict[str, Any]:
    """Server tier: combine S per-silo partial-aggregate dicts (each built
    by :func:`build_aggregates` with a :class:`PartialReducer`) into the
    single finished aggregate dict
    ``ServerOptimizer.update_from_aggregates`` consumes.  Pure jnp math —
    safe to jit over a tuple of partials, or to run host-side on partials
    shipped over the cross-silo message path."""

    def finish(key):
        den = sum(p[key]["den"] for p in partials)
        num = jax.tree_util.tree_map(
            lambda *ls: sum(ls), *[p[key]["num"] for p in partials])
        return jax.tree_util.tree_map(lambda l: l / den, num)

    agg: Dict[str, Any] = {
        "n_sampled": sum(p["n_sampled"] for p in partials)}
    if spec.avg_params:
        agg["avg_params"] = finish("avg_params")
    for a in spec.aggregates:
        if a.kind in ("wavg", "scalar"):
            agg[a.name] = finish(a.name)
        else:  # sum — already associative
            agg[a.name] = sum(p[a.name] for p in partials)
    return agg


# --------------------------------------------------------------------------
# buffered-async aggregation (FedBuff-style, docs/ASYNC.md)
# --------------------------------------------------------------------------
#
# The synchronous round reduces one cohort in lockstep; the buffered-async
# engine (simulation/async_engine.py) instead lands each client's COMPLETED
# update in a size-K on-device row buffer and finishes the reduction the
# moment occupancy hits K, discounting stale rows by s(τ) = 1/(1+τ)^α
# (τ = server model versions elapsed since the client's dispatch).  The
# pieces live here because they are pure spec-driven algebra:
#
# - :func:`client_update_rows` evaluates every spec aggregate's per-client
#   SOURCE rows at dispatch time (against the dispatch-version state, which
#   is what the client actually trained from) without reducing them;
# - :func:`update_buffer_zeros` / :func:`update_buffer_add` maintain the
#   K-row buffer with occupancy, per-row staleness and discount as traced
#   DATA (scatter at a traced slot vector; slot K is the padding sentinel
#   XLA drops) — ONE compiled program serves every occupancy;
# - :func:`update_buffer_apply` finishes the buffer with the SAME stacked
#   reductions the sync engines run (StackedReducer math), so a K=cohort,
#   zero-latency apply reproduces the synchronous round BITWISE;
# - :func:`scale_partial` staleness-discounts a PartialReducer partial, so
#   the distributed async driver (simulation/async_driver.py) can ship
#   dispatch-time partials and combine them at the server through the
#   unchanged :func:`combine_partial_aggregates` path.

def staleness_discount(tau, alpha: float) -> jnp.ndarray:
    """FedBuff staleness discount ``s(τ) = 1/(1+τ)^α``.

    ``τ = 0`` gives exactly 1.0 (``1^x`` is exact in IEEE), which is what
    makes the bounded-staleness parity contract *bitwise*: a fresh update's
    discounted weight ``1.0 * w`` is ``w``."""
    return jnp.power(1.0 + jnp.asarray(tau, jnp.float32), -float(alpha))


def client_update_rows(spec: "AlgorithmSpec", opt, state, outs, w,
                       hp: Optional[HParams] = None) -> Dict[str, Any]:
    """Per-client UNREDUCED aggregate rows, evaluated at DISPATCH time.

    Every spec source runs against the state the clients were dispatched
    with (FedNova/q-FedAvg deltas reference ``state.global_params`` — the
    model version the client trained from, not whatever the server holds
    when the update finally lands).  Entries keep the stacked source and
    its per-client weight vector separate so the buffer can re-weight rows
    by staleness at apply time:

    - ``n_rows``: the real-client mask (``w > 0``),
    - wavg/scalar aggregates: ``{"src": stacked, "w": (C,)}``,
    - sum aggregates: ``{"src": src * ww}`` (pre-weighted, summed later).
    """
    rows: Dict[str, Any] = {"n_rows": _real(opt, outs, w)}
    if spec.avg_params:
        rows["avg_params"] = {"src": outs.params,
                              "w": jnp.asarray(w, jnp.float32)}
    for a in spec.aggregates:
        src = a.source(opt, state, outs, hp)
        ww = a.weights(opt, outs, w, hp)
        if a.kind in ("wavg", "scalar"):
            rows[a.name] = {"src": src, "w": ww}
        else:  # sum
            rows[a.name] = {"src": src * ww}
    return rows


def update_buffer_zeros(spec: "AlgorithmSpec", rows: Dict[str, Any],
                        k: int) -> Dict[str, Any]:
    """A zeroed size-``k`` row buffer shaped like ``rows`` with the
    leading client axis resized to ``k``, plus the per-row discount /
    staleness lanes and the traced occupancy counter."""
    def resize(l):
        return jnp.zeros((int(k),) + tuple(l.shape[1:]), l.dtype)

    return {
        "rows": jax.tree_util.tree_map(resize, rows),
        "s": jnp.zeros((int(k),), jnp.float32),      # discount per row
        "tau": jnp.zeros((int(k),), jnp.float32),    # staleness per row
        "occupancy": jnp.zeros((), jnp.float32),
        "version": jnp.zeros((), jnp.float32),       # server model version
    }


def update_buffer_add(buf: Dict[str, Any], rows: Dict[str, Any],
                      idx, slots, s, tau) -> Dict[str, Any]:
    """Land ≤K arrivals in the buffer — all-traced-data, ONE compiled
    program for every occupancy/batch size.

    ``idx``/``slots``/``s``/``tau`` are (K,)-padded lanes: lane j takes
    source row ``idx[j]`` of ``rows`` (a dispatch generation's stacked
    outputs) into buffer slot ``slots[j]`` with discount ``s[j]``.
    Padding lanes carry ``slots[j] = K`` — out-of-bounds scatter indices
    DROP under XLA's default mode, the same sentinel trick the cohort
    scatter and the adapter bank use, so occupancy never becomes a shape.
    """
    idx = jnp.asarray(idx, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    s = jnp.asarray(s, jnp.float32)
    tau = jnp.asarray(tau, jnp.float32)
    sel = jax.tree_util.tree_map(lambda l: l[idx], rows)
    new_rows = jax.tree_util.tree_map(
        lambda d, sl: d.at[slots].set(sl.astype(d.dtype)), buf["rows"], sel)
    k = buf["s"].shape[0]
    landed = jnp.sum((slots < k).astype(jnp.float32))
    return {
        "rows": new_rows,
        "s": buf["s"].at[slots].set(s),
        "tau": buf["tau"].at[slots].set(tau),
        "occupancy": buf["occupancy"] + landed,
        "version": buf["version"],
    }


def update_buffer_apply(spec: "AlgorithmSpec", opt, state, buf,
                        hp: Optional[HParams] = None):
    """Finish the buffer into one aggregate dict and run the unchanged
    server transition.

    The reductions are the synchronous engines' own stacked forms
    (:class:`StackedReducer` math) over the buffered rows with per-row
    staleness-discounted weights ``s_i · w_i`` — with every ``s_i = 1``
    and the buffer holding one cohort in dispatch order, this is
    *bitwise* the synchronous round's merge (the parity pin in
    tests/test_async_engine.py).  Returns ``(new_state, agg,
    reset_buffer)`` with the buffer re-zeroed and its version bumped, so
    the engine can donate the buffer through one jitted apply."""
    s = buf["s"]
    red = StackedReducer()
    agg: Dict[str, Any] = {"n_sampled": jnp.sum(s * buf["rows"]["n_rows"])}
    if spec.avg_params:
        e = buf["rows"]["avg_params"]
        agg["avg_params"] = red.wavg(e["src"], s * e["w"])
    for a in spec.aggregates:
        e = buf["rows"][a.name]
        if a.kind == "wavg":
            agg[a.name] = red.wavg(e["src"], s * e["w"])
        elif a.kind == "scalar":
            agg[a.name] = red.wavg_scalar(e["src"], s * e["w"])
        else:  # sum — rows arrived pre-weighted
            agg[a.name] = jnp.sum(s * e["src"])
    new_state = opt.update_from_aggregates(state, agg, hp)
    fresh = jax.tree_util.tree_map(jnp.zeros_like, buf)
    fresh["version"] = buf["version"] + 1.0
    return new_state, agg, fresh


def zero_like_partial(partial: Dict[str, Any]) -> Dict[str, Any]:
    """A partial aggregate that contributes NOTHING to
    :func:`combine_partial_aggregates`: every numerator, denominator,
    sum-kind entry, and ``n_sampled`` is zero, so ``sum(num)/sum(den)``
    over the padded tuple equals the average over the real partials
    alone.  Quorum rounds (docs/FAULT_TOLERANCE.md) pad the arrived set
    to the full silo count with these so the jitted combine keeps ONE
    compiled shape regardless of how many silos made the deadline —
    exact quorum math at zero steady-state recompiles.

    Zeros preserve each leaf's ARRAY KIND (numpy stays numpy, device
    stays device): the jit cache key sees identical argument signatures
    for a padded and a full tuple, so quorum-size changes never split
    the cache."""
    import numpy as np

    def zero(leaf):
        if isinstance(leaf, jax.Array):
            return jnp.zeros_like(leaf)
        return np.zeros_like(np.asarray(leaf))

    return jax.tree_util.tree_map(zero, partial)


def wire_roundtrip_partial(partial: Dict[str, Any], wire_link,
                           link: str) -> Dict[str, Any]:
    """Quantize/dequantize one partial aggregate through the fedwire
    codec WITH the link's error feedback (docs/WIRE.md) — exactly the
    transform the distributed tier applies when it ships the partial.

    The in-process :class:`~fedml_tpu.store.hierarchy.HierarchicalSiloAPI`
    runs this per silo so its numerics (including the EF trajectory on
    each ``partial:<i>`` link) MATCH the multi-rank wire — the parity
    tests compare the two drivers leaf-for-leaf.  Float leaves of at
    least a block ride the quantized vector; the ``{num, den}`` algebra's
    denominators and counters ride raw, so combine stays exact."""
    import flax.serialization as fser

    from .wire import WireCodec

    return fser.from_state_dict(partial, WireCodec.decode(
        wire_link.encode(fser.to_state_dict(partial), link=link)))


def scale_partial(spec: "AlgorithmSpec", partial: Dict[str, Any],
                  s) -> Dict[str, Any]:
    """Staleness-discount a :class:`PartialReducer` partial by ``s``:
    every numerator AND denominator scales, so ``combine_partial_
    aggregates`` over discounted partials is the staleness-weighted
    average — the FedBuff weight applied server-side against a partial
    computed at dispatch (the distributed async driver's wire path)."""
    s = jnp.asarray(s, jnp.float32)

    def scale_entry(v):
        if isinstance(v, dict) and set(v) == {"num", "den"}:
            return {"num": jax.tree_util.tree_map(lambda l: s * l,
                                                  v["num"]),
                    "den": s * v["den"]}
        return jax.tree_util.tree_map(lambda l: s * l, v)

    return {k: scale_entry(v) for k, v in partial.items()}


# --------------------------------------------------------------------------
# fedmon per-client health stats (docs/OBSERVABILITY.md, ISSUE 14)
# --------------------------------------------------------------------------

#: stat lanes of the in-trace per-client health rows (the async engine
#: appends a ``staleness`` lane at buffer-apply time)
HEALTH_STAT_FIELDS = ("update_norm", "cosine", "loss_delta", "weight")


def client_health_stats(old_params: Pytree, client_params: Pytree,
                        ref_delta: Pytree, loss, weights
                        ) -> Dict[str, jnp.ndarray]:
    """Fixed-shape per-client health stat rows, computed IN-TRACE.

    The fedmon contract (the PR 4 discipline extended): these are a few
    extra reductions over data the round already holds — the stacked
    per-client new params vs the broadcast ``old_params`` and a reference
    direction ``ref_delta`` (the server update ``new − old`` on the sync
    engines; the generation's weighted-mean delta on the async engine) —
    returned through the SAME metrics pytree the loss rides, so health on
    adds ZERO host syncs / explicit transfers / steady-state compiles.

    Returns ``(C,)`` f32 lanes: ``update_norm`` = ‖Δ_i‖₂, ``cosine`` =
    cos(Δ_i, ref_delta) (the label-flip signature is a strongly negative
    cosine), ``loss_delta`` = loss_i − cohort weighted-mean loss, and the
    real-client ``weight`` mask (mesh pad rows read 0 and are dropped by
    the host-side monitor).  Under the mesh the cohort axis is GSPMD-
    sharded over ``client`` and each lane reduces locally per client —
    no new collectives beyond the one scalar mean."""
    f32 = jnp.float32
    w = jnp.asarray(weights, f32)

    def leaf_stats(cp, op, rd):
        c = cp.shape[0]
        d = cp.astype(f32).reshape(c, -1) - op.astype(f32).reshape(1, -1)
        r = rd.astype(f32).reshape(-1)
        return jnp.sum(d * d, axis=1), d @ r, jnp.sum(r * r)

    per_leaf = list(map(leaf_stats,
                        jax.tree_util.tree_leaves(client_params),
                        jax.tree_util.tree_leaves(old_params),
                        jax.tree_util.tree_leaves(ref_delta)))
    sq = sum(p[0] for p in per_leaf)        # (C,) ‖Δ_i‖²
    dot = sum(p[1] for p in per_leaf)       # (C,) ⟨Δ_i, ref⟩
    ref_sq = sum(p[2] for p in per_leaf)    # scalar ‖ref‖²
    norm = jnp.sqrt(sq)
    cosine = dot / jnp.maximum(norm * jnp.sqrt(ref_sq), 1e-12)
    loss = jnp.asarray(loss, f32)
    mean_loss = jnp.sum(w * loss) / jnp.maximum(jnp.sum(w), 1e-12)
    return {"update_norm": norm, "cosine": cosine,
            "loss_delta": loss - mean_loss, "weight": w}


def cohort_robust_z(vals, weights, floor: float):
    """In-trace twin of ``obs.health.robust_z`` over the rows with weight:
    ``(x - median) / max(1.4826 * MAD, floor)``, 0 on pad rows.  The async
    engine standardises a generation's reference-relative lanes here, at
    dispatch, where the whole generation is one cohort: an apply's buffer
    mixes rows of several generations, each measured against its own
    generation's mean direction and mean loss, and a z-score over that
    mixture reads the smaller group as outliers."""
    real = jnp.asarray(weights, jnp.float32) > 0
    vals = jnp.asarray(vals, jnp.float32)
    med = jnp.nanmedian(jnp.where(real, vals, jnp.nan))
    mad = jnp.nanmedian(jnp.where(real, jnp.abs(vals - med), jnp.nan))
    z = (vals - med) / jnp.maximum(1.4826 * mad, floor)
    return jnp.where(real, z, 0.0)


def cohort_mean_delta(old_params: Pytree, client_params: Pytree,
                      weights) -> Pytree:
    """Weighted cohort-mean update direction ``Σ w_i Δ_i / Σ w_i`` — the
    reference direction when no post-update params exist yet (the async
    engine computes health rows at DISPATCH, before any apply)."""
    w = jnp.asarray(weights, jnp.float32)
    den = jnp.maximum(jnp.sum(w), 1e-12)
    return jax.tree_util.tree_map(
        lambda cp, op: jnp.tensordot(w, cp.astype(jnp.float32), axes=1)
        / den - op.astype(jnp.float32), client_params, old_params)


# --------------------------------------------------------------------------
# trace-time-dynamic hyperparameters
# --------------------------------------------------------------------------

#: HParams fields a population may sweep (YAML ``population_axes`` keys)
HPARAM_FIELDS = ("server_lr", "client_lr", "prox_mu", "feddyn_alpha",
                 "qfed_q", "seed")


@flax.struct.dataclass
class HParams:
    """Trace-time-dynamic knobs of one federated experiment.

    Every field is optional: ``None`` means "use the static value from
    args" and keeps the default path's numerics bitwise-identical (the
    static constant folds into the trace).  A *population* stacks each
    swept field to a ``(P,)`` leaf and ``vmap``s the round over it.

    ``seed`` folds into the round key (member-distinguishing — the
    rng-key-reuse fedlint rule flags vmapped bodies that consume a
    member-independent key)."""
    server_lr: Any = None
    client_lr: Any = None
    prox_mu: Any = None
    feddyn_alpha: Any = None
    qfed_q: Any = None
    seed: Any = None


def resolve(hp: Optional[HParams], name: str, static):
    """The swept value when ``hp`` carries one, else the static default.
    With ``hp=None`` (no population) this returns the Python float
    unchanged, so non-population traces are bitwise the historical ones."""
    if hp is None:
        return static
    v = getattr(hp, name, None)
    return static if v is None else v


def lr_ratio(hp: Optional[HParams], name: str, static_lr: float):
    """Multiplier turning an update computed at the STATIC learning rate
    into one at the swept rate.  Every optax chain this repo builds ends in
    ``scale(-lr)``, so updates are linear in lr and post-scaling by
    ``swept/static`` is exact up to one rounding; ``None`` (not swept)
    means "multiply by nothing" — the caller skips the scale entirely and
    the default path stays bitwise."""
    if hp is None:
        return None
    v = getattr(hp, name, None)
    if v is None:
        return None
    if static_lr == 0.0:
        raise ValueError(
            f"sweeping {name} requires a nonzero static {name} baseline "
            "(the swept rate applies as a ratio to the traced optimizer)")
    return v / static_lr


def fold_seed(key: jax.Array, hp: Optional[HParams]) -> jax.Array:
    """Member-distinguishing round key: fold the member's seed in when the
    population sweeps one (``fold_in(key, member_seed)`` — never the same
    key for every member)."""
    if hp is None or getattr(hp, "seed", None) is None:
        return key
    return jax.random.fold_in(key, jnp.asarray(hp.seed, jnp.uint32))


# --------------------------------------------------------------------------
# algorithm specs — the declarative layer over the primitives
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class AggSpec:
    """One cross-client aggregate of a round.

    ``source(opt, state, outs, hp)`` returns the per-client stacked pytree
    (``kind="wavg"``) or ``(C,)`` vector (scalar kinds); ``weights(opt,
    outs, w, hp)`` the per-client weight vector.  ``kind``:

    - ``wavg``   — weighted average of a stacked tree (the reducer may
      flatten + reduce-scatter it on the mesh),
    - ``scalar`` — weighted average of a scalar per client,
    - ``sum``    — sum of ``source * weights`` per client.
    """
    name: str
    source: Callable
    weights: Callable = lambda opt, outs, w, hp: w
    kind: str = "wavg"


def _real(opt, outs, w, hp=None):
    """Real-client mask: padded zero-weight cohort rows contribute nothing
    (the pad-dependent |S|/N drift fix of PR 1, now uniform)."""
    return (w > 0).astype(jnp.float32)


def _nova_deltas(opt, state, outs, hp):
    """FedNova normalized directions d_i = (x - y_i)/max(tau_i, 1)."""
    tau = outs.tau
    return jax.tree_util.tree_map(
        lambda yi, gx: (gx[None] - yi) / jnp.maximum(
            tau.reshape((-1,) + (1,) * (yi.ndim - 1)), 1.0),
        outs.params, state.global_params)


@dataclass(frozen=True)
class AlgorithmSpec:
    """Declarative round shape of one federated optimizer.

    ``aggregates`` lists the cross-client reductions beyond the universal
    ``avg_params`` / ``n_sampled`` pair; ``avg_params``/``client_state``
    toggle the universal pieces; ``update`` (optional) is a pure server
    transition ``(gvals, agg, hp, opt) -> (new_gvals, new_fields)`` applied
    identically to the replicated params pytree and to a flat scatter-mode
    shard — algorithms whose transition needs layout-specific state (optax
    moments) instead use the ``ServerOptimizer`` built-ins and leave this
    ``None``."""
    name: str
    aggregates: Tuple[AggSpec, ...] = ()
    avg_params: bool = True
    client_state: bool = False
    update: Optional[Callable] = None


_SPECS: Dict[str, AlgorithmSpec] = {}


def register_algorithm(spec: AlgorithmSpec) -> AlgorithmSpec:
    """Add an algorithm to the registry (``federated_optimizer: <name>`` in
    YAML then runs it on every engine).  Re-registering a name replaces the
    spec — deliberate, so notebooks can iterate."""
    _SPECS[spec.name] = spec
    return spec


def get_spec(name: str) -> AlgorithmSpec:
    try:
        return _SPECS[name.lower()]
    except KeyError:
        raise KeyError(
            f"no AlgorithmSpec registered for {name!r} "
            f"(known: {sorted(_SPECS)})") from None


def has_spec(name: str) -> bool:
    return name.lower() in _SPECS


# -- the built-in zoo as specs ----------------------------------------------

for _name in ("fedavg", "fedavg_seq", "fedprox", "fedopt", "fedopt_seq",
              "feddyn"):
    register_algorithm(AlgorithmSpec(_name, client_state=_name == "feddyn"))

register_algorithm(AlgorithmSpec(
    "scaffold",
    aggregates=(AggSpec("mean_delta_c",
                        source=lambda opt, state, outs, hp: outs.delta_c,
                        weights=_real),),
    client_state=True))

register_algorithm(AlgorithmSpec(
    "fednova",
    aggregates=(AggSpec("nova_d", source=_nova_deltas),
                AggSpec("tau_eff",
                        source=lambda opt, state, outs, hp: outs.tau,
                        kind="scalar"))))

for _name in ("mime", "fedsgd"):
    register_algorithm(AlgorithmSpec(
        _name,
        aggregates=(AggSpec("avg_grad",
                            source=lambda opt, state, outs, hp:
                            outs.grad_sum),)))

# fedbuff (docs/ASYNC.md): buffered-async FedAvg — the round SHAPE is plain
# FedAvg (one weighted params average), but the driver is the buffered-async
# engine: ``federated_optimizer: fedbuff`` selects
# simulation/async_engine.py::FedBuffAPI, which lands completed updates in
# a size-K buffer with staleness-discounted weights instead of waiting for
# a lockstep cohort.  ``args.async_base_optimizer`` swaps the underlying
# spec (any registered algorithm whose aggregates are spec-declared).
register_algorithm(AlgorithmSpec("fedbuff"))


# -- q-FedAvg (arXiv:1905.10497): fair aggregation as a pure spec -----------

def _qfed_q(opt, hp):
    return resolve(hp, "qfed_q", opt.qfed_q)


def _qfed_deltas(opt, state, outs, hp):
    L = 1.0 / opt.qfed_lr
    return jax.tree_util.tree_map(
        lambda yi, gx: (gx[None] - yi) * L, outs.params, state.global_params)


def _qfed_u(opt, state, outs, hp):      # F_k^q, padded rows zeroed
    return jnp.power(jnp.maximum(outs.loss, 1e-10), _qfed_q(opt, hp))


def _qfed_h(opt, state, outs, hp):      # q F^{q-1} ||Δ||^2 + L F^q
    L = 1.0 / opt.qfed_lr
    q = _qfed_q(opt, hp)
    F = jnp.maximum(outs.loss, 1e-10)
    sq = jax.tree_util.tree_map(
        lambda yi, gx: jnp.sum(
            ((gx[None] - yi) * L).astype(jnp.float32) ** 2,
            axis=tuple(range(1, yi.ndim))),
        outs.params, state.global_params)
    dn = sum(jax.tree_util.tree_leaves(sq))
    return q * jnp.power(F, q - 1.0) * dn + L * jnp.power(F, q)


def _qfed_update(gvals, agg, hp, opt):
    scale = agg["qfed_u"] / jnp.maximum(agg["qfed_h"], 1e-12)
    new = jax.tree_util.tree_map(lambda g, d: g - scale * d,
                                 gvals, agg["qfed_delta"])
    return new, {}


QFEDAVG = register_algorithm(AlgorithmSpec(
    "qfedavg", avg_params=False, update=_qfed_update,
    aggregates=(
        AggSpec("qfed_delta", source=_qfed_deltas,
                weights=lambda opt, outs, w, hp:
                _real(opt, outs, w) * _qfed_u(opt, None, outs, hp)),
        AggSpec("qfed_u", source=_qfed_u, weights=_real, kind="sum"),
        AggSpec("qfed_h", source=_qfed_h, weights=_real, kind="sum"),
    )))


# --------------------------------------------------------------------------
# spec-driven aggregate construction (shared by every engine)
# --------------------------------------------------------------------------

def build_aggregates(spec: AlgorithmSpec, red, opt, state, outs,
                     w: jnp.ndarray, hp: Optional[HParams] = None,
                     include_avg: bool = True) -> Dict[str, Any]:
    """The stage-1 cross-client reductions of one round, built from the
    algorithm's declarative spec with the engine's reducer.

    ``include_avg=False`` lets a quantized engine skip the plain
    ``avg_params`` reduction and substitute its EF-quantized collective
    (the auxiliary aggregates always stay full-precision, exactly as the
    hand-rolled merges did)."""
    agg: Dict[str, Any] = {"n_sampled": red.sum_scalar(_real(opt, outs, w))}
    if spec.avg_params and include_avg:
        agg["avg_params"] = red.wavg(outs.params, w)
    for a in spec.aggregates:
        src = a.source(opt, state, outs, hp)
        ww = a.weights(opt, outs, w, hp)
        if a.kind == "wavg":
            agg[a.name] = red.wavg(src, ww)
        elif a.kind == "scalar":
            agg[a.name] = red.wavg_scalar(src, ww)
        else:  # sum
            agg[a.name] = red.sum_scalar(src * ww)
    return agg


# --------------------------------------------------------------------------
# RoundProgram — broadcast ∘ client_map ∘ weighted_reduce ∘ server update
# --------------------------------------------------------------------------

@dataclass
class RoundProgram:
    """One federated round composed from the primitives.

    Built by the SP engine (``round_engine.make_round_fn``); the mesh
    engine uses the same spec/:func:`build_aggregates` layer but stages
    its client phase and merge differently around its ``shard_map``
    (simulation/mesh/engine.py).  Calling convention::

        new_state, outs, agg = program(state, x, y, mask, weights, rngs,
                                       c_clients, hp)
    """
    spec: AlgorithmSpec
    local_train: Callable          # pure per-client fn
    server_opt: Any                # ServerOptimizer
    mode: str = "vmap"             # client_map mode
    reducer: Any = field(default_factory=StackedReducer)

    def run_clients(self, state, x, y, mask, rngs, c_clients, hp=None):
        from ..ml.trainer.local_trainer import ServerCtx
        ctx = ServerCtx(global_params=state.global_params,
                        c_server=state.c_server,
                        server_momentum=state.momentum,
                        hparams=hp)
        g = broadcast(state.global_params)
        fn = lambda xb, yb, mb, rng, cc: self.local_train(
            g, xb, yb, mb, rng, ctx, cc)
        return client_map(fn, self.mode)(x, y, mask, rngs, c_clients)

    def __call__(self, state, x, y, mask, weights, rngs, c_clients=None,
                 hp=None):
        outs = self.run_clients(state, x, y, mask, rngs, c_clients, hp)
        agg = build_aggregates(self.spec, self.reducer, self.server_opt,
                               state, outs, weights, hp)
        new_state = self.server_opt.update_from_aggregates(state, agg, hp)
        return new_state, outs, agg


# --------------------------------------------------------------------------
# populations — vmapped experiment batches
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Population:
    """A stacked batch of P experiments sharing one compiled round."""
    size: int
    axes: Dict[str, tuple]
    members: Tuple[Dict[str, Any], ...]   # per-member hparam dicts (host)
    hparams: HParams                      # stacked (P,) leaves


def parse_population(args) -> Optional[Population]:
    """``args.population`` / ``args.population_axes`` -> :class:`Population`.

    ``population_axes`` maps hparam names (:data:`HPARAM_FIELDS`) to value
    lists; the population is their cartesian grid (first axis slowest).
    ``population: P`` alone sweeps ``seed: [0..P-1]`` — P repeats of the
    same config under member-distinct rng.  When both are given, P must
    equal the grid size (a cross-check for YAML edits)."""
    axes_in = getattr(args, "population_axes", None) or {}
    p_arg = int(getattr(args, "population", 0) or 0)
    if not axes_in and p_arg <= 1:
        return None
    bad = [k for k in axes_in if k not in HPARAM_FIELDS]
    if bad:
        raise ValueError(
            f"unknown population_axes {bad!r}; sweepable: {HPARAM_FIELDS}")
    axes = {k: tuple(v if isinstance(v, (list, tuple)) else [v])
            for k, v in axes_in.items()}
    if not axes:
        axes = {"seed": tuple(range(p_arg))}
    names = list(axes)
    grid = list(itertools.product(*[axes[n] for n in names]))
    if p_arg and p_arg != len(grid):
        raise ValueError(
            f"population={p_arg} but population_axes grid has {len(grid)} "
            "members")
    members = tuple(dict(zip(names, g)) for g in grid)
    stacked = {}
    for n in names:
        col = [m[n] for m in members]
        dtype = jnp.int32 if n == "seed" else jnp.float32
        stacked[n] = jnp.asarray(col, dtype)
    return Population(size=len(grid), axes=axes, members=members,
                      hparams=HParams(**stacked))


def stack_member_states(state: Pytree, p: int) -> Pytree:
    """P copies of one experiment state on a new leading member axis."""
    return jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * p), state)


def population_member(tree: Pytree, member: int) -> Pytree:
    """Extract member ``member`` of a population-stacked pytree as a normal
    single-experiment pytree (e.g. after an orbax restore of a stacked
    checkpoint)."""
    return jax.tree_util.tree_map(lambda x: x[member], tree)
