"""The federated round as ONE compiled program.

The reference runs a round as Python orchestration: sample → per-client eager
train loop → pickle/ship → per-key weighted sum (call stack in SURVEY §3.1).
Here the whole round — every sampled client's full local-SGD pass plus the
server merge — is a single jitted function over a *cohort tensor*:

    x:(C, S, B, ...)  y:(C, S, ...)  mask:(C, S)  weights:(C,)

- ``scan`` mode: clients run sequentially via ``lax.scan`` (constant memory —
  the single-process "sp" backend, reference ``simulation/sp``).
- ``vmap`` mode: clients run batched via ``jax.vmap`` (max MXU utilization on
  one chip for small models; the moral successor of the reference's
  ``SeqTrainScheduler`` many-clients-per-GPU packing, ``core/schedule/
  seq_train_scheduler.py:9`` — the schedule disappears into vectorization).
- the mesh engine (``simulation/mesh``) shard_maps this same per-client body
  over the ``client`` axis and merges with ``psum`` — the TPU-native form of
  the NCCL simulation's pre-scaled ``dist.reduce(SUM)``
  (``simulation/nccl/base_framework/common.py:196-228``).

Since ISSUE 7 the round is COMPOSED, not hand-rolled: the primitives and
per-algorithm aggregate specs live in ``core/federated.py``
(``broadcast ∘ client_map ∘ weighted_reduce`` + ``AlgorithmSpec``,
docs/PRIMITIVES.md), the round is a pure function of ``(state, cohort,
HParams)``, and :func:`make_population_round_fn` /
:func:`make_population_block_fn` vmap it over a stacked HParams batch so
a P-member hyperparameter sweep executes as ONE compiled dispatch.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..core import federated
from ..core import tree as tree_util
from ..core.compression import blockscale
from ..ml.aggregator.agg_operator import ServerOptimizer, ServerState
from ..ml.trainer.local_trainer import ClientOut, LocalTrainer, ServerCtx
from ..obs.carry import OPT_FLOPS, round_obs

#: fold_in tag deriving the per-round stochastic-rounding key stream of the
#: low-precision collective layer from the round key — disjoint from the
#: per-client streams (which come from jax.random.split of the same key)
QUANT_KEY_TAG = 0x5C41E


def make_server_ctx(trainer: LocalTrainer, state: ServerState,
                    hp=None) -> ServerCtx:
    return ServerCtx(
        global_params=state.global_params,
        c_server=state.c_server,
        server_momentum=state.momentum,
        hparams=hp,
    )


def make_run_clients(trainer: LocalTrainer, server_opt: ServerOptimizer,
                     mode: str = "scan") -> Callable:
    """Shared cohort executor: (state, x, y, mask, rngs, c_clients[, hp]) →
    stacked ClientOut — ``broadcast ∘ client_map`` over the client axis
    (core/federated.py primitives; vmap or scan)."""
    local_train = trainer.make_local_train()

    def run_clients(state, x, y, mask, rngs, c_clients, hp=None):
        ctx = make_server_ctx(trainer, state, hp)
        g = federated.broadcast(state.global_params)
        fn = lambda xb, yb, mb, rng, cc: local_train(g, xb, yb, mb, rng,
                                                     ctx, cc)
        return federated.client_map(fn, mode)(x, y, mask, rngs, c_clients)

    return run_clients


def make_round_fn(trainer: LocalTrainer, server_opt: ServerOptimizer,
                  mode: str = "scan", collective_precision: str = "fp32",
                  quant_block: int = blockscale.DEFAULT_BLOCK,
                  health: bool = False) -> Callable:
    """Build round_fn(state, x, y, mask, weights, key, c_clients, hp) ->
    (new_state, metrics, new_client_state).  All client-axis inputs are
    stacked; ``key`` is the single round key (split per client inside the
    jit); ``c_clients`` is None unless the algorithm keeps per-client state
    (SCAFFOLD/FedDyn).

    The round is the primitive composition of core/federated.py — one
    :class:`~fedml_tpu.core.federated.RoundProgram` instance: ``broadcast``
    the server params, ``client_map`` the local-SGD body, spec-declared
    ``weighted_reduce`` aggregates, then the server transition.  ``hp`` is
    an optional :class:`~fedml_tpu.core.federated.HParams`: swept fields
    become traced scalars and the WHOLE round is a pure function of
    ``(state, cohort, hp)`` — what lets a population ``vmap`` it
    (:func:`make_population_round_fn`, docs/PRIMITIVES.md).

    ``collective_precision != "fp32"`` applies the SAME quantize →
    accumulate-EF math the mesh engine's collective layer runs
    (docs/COLLECTIVE_PRECISION.md) — here the "collectives" are
    intra-process, so this is the single-shard reference the mesh parity
    tests compare against: the merge numerator is quantized against
    ``state.ef_num``, the server update transitions the fp32
    ``state.master_flat``, and ``state.global_params`` becomes the
    low-precision broadcast copy the next round's clients train from."""
    alg = server_opt.algorithm
    spec = server_opt.spec
    precision = collective_precision
    program = federated.RoundProgram(spec, trainer.make_local_train(),
                                     server_opt, mode)
    if precision != "fp32" and not spec.avg_params:
        raise ValueError(
            f"collective_precision={precision!r} quantizes the avg_params "
            f"merge numerator, which the {alg!r} spec does not use")

    def quantized_update(state: ServerState, outs: ClientOut, weights, qkey,
                         hp):
        # stage 1 with the EF-quantized numerator: avg_params is rebuilt
        # from the flat quantized contribution; auxiliary spec aggregates
        # (delta_c / nova_d / grad_sum) stay fp32, exactly as on the mesh
        agg = federated.build_aggregates(spec, program.reducer, server_opt,
                                         state, outs, weights, hp,
                                         include_avg=False)
        num = jax.tree_util.tree_map(
            lambda l: jnp.tensordot(weights, l.astype(jnp.float32),
                                    axes=1), outs.params)
        den = jnp.sum(weights)
        contrib = tree_util.tree_flatten_1d(num) / den
        v = state.ef_num[0] + contrib
        deq, err_sq = blockscale.collective_quantize(
            v, precision, jax.random.fold_in(qkey, 0), quant_block)
        new_ef_num = (v - deq)[None]
        agg["avg_params"] = tree_util.tree_unflatten_1d(
            deq, state.global_params)
        # stage 2 transitions the fp32 MASTER (global_params is the
        # broadcast copy the clients just trained from; deltas inside
        # the spec aggregates reference it, matching the mesh)
        master = tree_util.tree_unflatten_1d(state.master_flat,
                                             state.global_params)
        new_state = server_opt.update_from_aggregates(
            state.replace(global_params=master), agg, hp)
        new_master = tree_util.tree_flatten_1d(new_state.global_params)
        send, new_ef_bcast, berr_sq = blockscale.quantize_broadcast(
            new_master, state.ef_bcast, precision,
            jax.random.fold_in(qkey, 1), quant_block)
        new_state = new_state.replace(
            global_params=tree_util.tree_unflatten_1d(
                send, state.global_params),
            master_flat=new_master, ef_num=new_ef_num,
            ef_bcast=new_ef_bcast)
        return new_state, jnp.sqrt(err_sq + berr_sq)

    # modeled interconnect payload of merge + broadcast at this precision
    # (trace-time static; 0 would hide the fp32 baseline, so fp32 reports
    # its own dense payload and --comms ratios stay meaningful)
    def _bytes_model(n_flat: int) -> float:
        # static arithmetic on Python ints (the modeled byte count)
        # fedlint: disable-next-line=jit-host-sync -- not a tracer
        return float(
            blockscale.collective_payload_nbytes(n_flat, precision,
                                                 quant_block)
            + blockscale.collective_payload_nbytes(n_flat, precision,
                                                   quant_block))

    def round_fn(state: ServerState, x, y, mask, weights, key,
                 c_clients=None, hp=None):
        # member-distinct stream when a population sweeps seeds, then split
        # INSIDE the compiled round: a host-side split is a full device
        # roundtrip per round
        key = federated.fold_seed(key, hp)
        rngs = jax.random.split(key, mask.shape[0])
        outs: ClientOut = program.run_clients(state, x, y, mask, rngs,
                                              c_clients, hp)
        if precision == "fp32":
            agg = federated.build_aggregates(spec, program.reducer,
                                             server_opt, state, outs,
                                             weights, hp)
            new_state = server_opt.update_from_aggregates(state, agg, hp)
            quant_err = jnp.zeros((), jnp.float32)
        else:
            qkey = jax.random.fold_in(key, QUANT_KEY_TAG)
            new_state, quant_err = quantized_update(state, outs, weights,
                                                    qkey, hp)
        total_steps = jnp.sum(outs.num_steps)
        metrics = {
            "train_loss": jnp.sum(outs.loss * weights) / jnp.sum(weights),
            "total_steps": total_steps,
        }
        # device-carry telemetry (ISSUE 4): fixed-shape scalars computed
        # in-trace and returned through the metrics pytree — they ride the
        # same outputs the loss does (stacked (K,) under the block scan)
        # and materialize only at the driver's existing log-round flush
        feat = math.prod(x.shape[3:])
        metrics["obs"] = round_obs(
            state.global_params, new_state.global_params,
            real_steps=total_steps,
            real_clients=jnp.sum((weights > 0).astype(jnp.float32)),
            batch=int(x.shape[2]), feat=feat,
            opt_flops_per_param=OPT_FLOPS.get(alg, 4.0),
            collective_bytes=_bytes_model(
                tree_util.num_params(state.global_params)),
            quant_error=quant_err)
        if health:
            # fedmon (ISSUE 14): fixed-shape per-client stat rows ride the
            # metrics pytree under the same zero-sync contract as obs —
            # materialized only at the driver's existing log-round flush
            ref_delta = jax.tree_util.tree_map(
                lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
                new_state.global_params, state.global_params)
            metrics["health"] = federated.client_health_stats(
                state.global_params, outs.params, ref_delta, outs.loss,
                weights)
        # Return ONLY the per-client state (SCAFFOLD/FedDyn) — returning the
        # full stacked ``outs.params`` would force XLA to materialize a
        # C × |model| output buffer every round for data nothing consumes.
        return new_state, metrics, outs.new_client_state

    return round_fn


def make_gather_round_fn(trainer: LocalTrainer, server_opt: ServerOptimizer,
                         train_x, train_y, mode: str = "vmap",
                         collective_precision: str = "fp32",
                         quant_block: int = blockscale.DEFAULT_BLOCK,
                         health: bool = False) -> Callable:
    """Device-gather variant: the dataset lives on device once; the round
    takes only a (C, S, B) int32 index tensor from the host (KBs instead of
    the reference's per-round sample shipping).  The gather is HBM→HBM and
    fuses into the scanned step."""
    inner = make_round_fn(trainer, server_opt, mode,
                          collective_precision=collective_precision,
                          quant_block=quant_block, health=health)

    def round_fn(state: ServerState, idx, mask, weights, key,
                 c_clients=None, hp=None):
        x = jnp.take(train_x, idx, axis=0)   # (C, S, B, ...)
        y = jnp.take(train_y, idx, axis=0)
        return inner(state, x, y, mask, weights, key, c_clients, hp)

    return round_fn


def make_block_round_fn(trainer: LocalTrainer, server_opt: ServerOptimizer,
                        train_x, train_y, mode: str = "vmap",
                        collective_precision: str = "fp32",
                        quant_block: int = blockscale.DEFAULT_BLOCK,
                        health: bool = False) -> Callable:
    """Fused round-block: K federated rounds as ONE compiled program
    (``jit(lax.scan(round))`` — the DrJAX observation that rounds compose as
    pure JAX primitives, arXiv:2403.07128).

    ``block_fn(state, idx_blk, mask_blk, w_blk, keys_blk, cohort_blk,
    client_table) -> (new_state, metrics, new_client_table)`` where every
    cohort input gains a leading round axis of length K (``idx_blk``:
    ``(K, C, S, B)`` int32 — gather mode only, so pre-staging a whole block
    ships kilobytes of indices, not data), ``keys_blk`` stacks the K
    per-round keys (identical to the unfused path's, so parity is exact),
    and ``cohort_blk`` is the ``(K, C)`` sampled-client ids indexing the
    device-resident per-client state table (SCAFFOLD/FedDyn; ``None``
    otherwise).  The ServerState and the table thread through the scan
    carry; per-round metrics stack into ``(K,)`` outputs so the host syncs
    once per block instead of once per round.
    """
    inner = make_gather_round_fn(trainer, server_opt, train_x, train_y, mode,
                                 collective_precision=collective_precision,
                                 quant_block=quant_block, health=health)
    has_table = server_opt.spec.client_state

    def block_fn(state: ServerState, idx_blk, mask_blk, w_blk, keys_blk,
                 cohort_blk, client_table=None, hp=None):
        def step(carry, inp):
            st, table = carry
            idx, mask, w, key, cohort = inp
            c = tree_util.cohort_gather(table, cohort) if has_table else None
            st, metrics, new_c = inner(st, idx, mask, w, key, c, hp)
            if has_table:
                table = tree_util.cohort_scatter(table, cohort, new_c)
            return (st, table), metrics

        (state, client_table), metrics = jax.lax.scan(
            step, (state, client_table),
            (idx_blk, mask_blk, w_blk, keys_blk, cohort_blk))
        return state, metrics, client_table

    return block_fn


# -- vmapped experiment populations (ISSUE 7 tentpole) -----------------------
# Because the round is a pure function of (state, cohort, hp), vmap over a
# stacked HParams batch executes P experiments as ONE dispatch: members
# share the cohort tensors / round keys (in_axes=None — the sweep isolates
# the hparam effect; sweep ``seed`` for member-distinct rng, folded inside
# the round), while ServerState, the per-client state table, and HParams
# stack on a leading (P,) member axis.  Metrics leaves come back (P,)
# ((P, K) under the fused block scan).  See docs/PRIMITIVES.md.

def make_population_round_fn(trainer: LocalTrainer,
                             server_opt: ServerOptimizer,
                             train_x, train_y, mode: str = "vmap",
                             collective_precision: str = "fp32",
                             quant_block: int = blockscale.DEFAULT_BLOCK,
                             health: bool = False) -> Callable:
    """``pop_fn(states, idx, mask, w, key, c_stacked, hps)`` — the gather
    round vmapped over the member axis of ``states`` / ``c_stacked`` /
    ``hps``; cohort inputs broadcast.  ``health`` is accepted for builder
    uniformity but rejected upstream (``validate_args``): per-client stat
    rows are single-experiment."""
    inner = make_gather_round_fn(trainer, server_opt, train_x, train_y, mode,
                                 collective_precision=collective_precision,
                                 quant_block=quant_block, health=health)
    has_table = server_opt.spec.client_state
    table_ax = 0 if has_table else None
    return jax.vmap(inner, in_axes=(0, None, None, None, None, table_ax, 0))


def make_population_block_fn(trainer: LocalTrainer,
                             server_opt: ServerOptimizer,
                             train_x, train_y, mode: str = "vmap",
                             collective_precision: str = "fp32",
                             quant_block: int = blockscale.DEFAULT_BLOCK,
                             health: bool = False) -> Callable:
    """The fused K-round block vmapped over the member axis: P experiments
    × K rounds in ONE compiled dispatch (``vmap`` over ``jit(lax.scan)``'s
    body composes — metrics stack to ``(P, K)``)."""
    inner = make_block_round_fn(trainer, server_opt, train_x, train_y, mode,
                                collective_precision=collective_precision,
                                quant_block=quant_block, health=health)
    has_table = server_opt.spec.client_state
    table_ax = 0 if has_table else None
    return jax.vmap(inner,
                    in_axes=(0, None, None, None, None, None, table_ax, 0))


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


#: server-optimizer families whose round aggregates are plain weighted
#: averages and carry no per-client state, so bucket partials merge exactly
#: (SCAFFOLD/FedDyn keep per-client trees, FedNova/Mime aux terms don't
#: merge across padded buckets — those stay on the single-cohort path)
BUCKETABLE_ALGS = ("fedavg", "fedavg_seq", "fedprox", "fedopt", "fedopt_seq")


def make_bucket_agg_fn(trainer: LocalTrainer, server_opt: ServerOptimizer,
                       mode: str = "vmap") -> Callable:
    """Partial-round program for BUCKETED cohorts (ragged client sizes).

    The single-cohort round pads every client to the cohort's max step
    count, so under a skewed Dirichlet split most of the cohort burns
    masked compute.  Bucketing groups clients by pow2 step class and runs
    this program once per bucket; because ``compute_aggregates`` is a
    weighted average, bucket partials merge EXACTLY
    (``ServerOptimizer.merge_aggregates``) before one
    ``update_from_aggregates`` — same math, less padding.

    Returns ``bucket_fn(state, x, y, mask, weights, rngs) ->
    (agg, total_w, loss_w, total_steps)``.  Padded client rows must carry
    weight 0 (excluded from every average).
    """
    if server_opt.algorithm not in BUCKETABLE_ALGS:
        raise ValueError(
            f"cohort bucketing supports {BUCKETABLE_ALGS}; "
            f"{server_opt.algorithm!r} keeps aux state whose aggregates "
            "don't merge across padded buckets")
    run_clients = make_run_clients(trainer, server_opt, mode)

    def bucket_fn(state: ServerState, x, y, mask, weights, rngs):
        outs: ClientOut = run_clients(state, x, y, mask, rngs, None)
        agg = server_opt.compute_aggregates(state, outs.params, weights, {})
        total_w = jnp.sum(weights)
        loss_w = jnp.sum(outs.loss * weights)
        return agg, total_w, loss_w, jnp.sum(outs.num_steps)

    return bucket_fn
