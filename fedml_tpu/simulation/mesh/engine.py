"""Mesh-sharded federated simulation — the north-star engine.

Round/block program builders + the ``MeshFedAvgAPI`` driver, split out of
the 720-line ``mesh_simulator.py`` together with ``layout.py`` (sharding
rules) and ``collectives.py`` (quantized reductions) — see MIGRATION.md.

Clients shard over the ``client`` axis of a ``jax.sharding.Mesh``; each
device group runs its cohort shard through the SAME compiled per-client
body the SP engine uses (``vmap`` across its local clients, ``lax.scan``
within each client's batches).  The whole round — local SGD for all
clients on all chips + global merge + server optimizer step — is ONE
``jit(shard_map(...))`` dispatch.

WHICH aggregates the merge computes is no longer written here: both
merge bodies build them from the algorithm's declarative spec
(``core/federated.py`` ``AlgorithmSpec`` + ``build_aggregates``) with
this engine's reducers — ``PsumReducer`` for the replicated layout,
``ScatterReducer`` for the reduce-scatter layout — so the SP engine and
both mesh layouts share one definition of every algorithm
(docs/PRIMITIVES.md; registered specs like q-FedAvg run here unchanged).

The FedAvg merge + server update runs in one of two layouts
(``args.update_sharding``):

- ``replicated`` — the weighted numerator is ``psum``-all-reduced per leaf
  and every chip runs the full-model server update redundantly.
- ``scatter`` (default on multi-shard meshes) — the cross-replica layout of
  arXiv:2004.13336: client-weighted partial sums flatten into one padded
  vector (``core.flatmodel.FlatSpec``) and ``psum_scatter`` so each chip
  receives only its contiguous chunk; ``ServerOptimizer.update_shard``
  transitions ONLY that chunk (FedOpt moments, SCAFFOLD ``c_server``,
  FedDyn ``h``, Mime momentum are permanently shard-resident) and the new
  params reassemble through the ``P(client)`` out-spec for the next
  round's broadcast.  See docs/UPDATE_SHARDING.md.

With ``mesh_shape=(n_client_shards, n_model_shards)`` and
``n_model_shards > 1`` the same program runs the 2-D ``client × model``
layout (docs/MESH_2D.md): ``shard_map`` goes manual over ``client`` and
*auto* over ``model`` — client train steps run model-parallel with params
sharded per ``layout.param_spec`` (GSPMD partitions the matmuls, the
arXiv:2204.06514 pjit pattern), while the merge keeps its explicit
``psum_scatter`` along ``client`` and the flat server state (opt moments,
EF rows, fp32 master) shards along BOTH axes.  One client's model no
longer has to fit in one chip's HBM (core/memory_estimate.py prices the
difference).
"""

from __future__ import annotations

import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core import federated
from ...core import rng as rng_util
from ...core import tree as tree_util
from ...core.compression import blockscale
from ...core.mesh import CLIENT_AXIS
from ...ml.aggregator.agg_operator import ServerOptimizer, ServerState
from ...ml.trainer.local_trainer import LocalTrainer
from ...obs.carry import OPT_FLOPS, round_obs
from ...obs.jaxhooks import count_put
from ..round_engine import QUANT_KEY_TAG, next_pow2
from ..sp.fedavg_api import FedAvgAPI
from ..staging import AsyncCohortStager  # noqa: F401  (re-export: the
# stager predates ISSUE 3's fused blocks and callers import it from here)
from . import collectives as coll
from .layout import MeshLayout

log = logging.getLogger(__name__)


def _stage_leaves(trainer) -> tuple:
    """Staged-leaf names of the trainer's model (empty when the model
    carries no PipelineDef) — what MeshLayout needs to shard a nontrivial
    ``stage`` factor (docs/PIPELINE.md)."""
    pipe = getattr(getattr(trainer, "model", None), "pipeline", None)
    return tuple(getattr(pipe, "stage_leaves", ()) or ())


def make_mesh_round_fn(trainer: LocalTrainer, server_opt: ServerOptimizer,
                       mesh: Mesh, gather: bool = False,
                       sharded_data: bool = False,
                       update_sharding: str = "replicated",
                       state_template: ServerState = None,
                       donate: bool = False,
                       collective_precision: str = "fp32",
                       quant_block: int = blockscale.DEFAULT_BLOCK,
                       health: bool = False):
    """round_fn(state, x|idx, y|·, mask, weights, key, c_clients) with the
    client axis sharded over the mesh.  In gather mode the first data arg is
    the (C, S, B) index tensor and ``y`` is the device-resident dataset pair
    (train_x, train_y):

    - ``sharded_data=False`` — dataset replicated per device; the gather is
      a local ``jnp.take`` inside the shard.
    - ``sharded_data=True`` — dataset ROWS sharded over the client axis;
      the cohort gather runs as a jitted global ``jnp.take`` over the
      sharded table BEFORE ``shard_map``.

    ``update_sharding="scatter"`` selects the reduce-scatter / shard-update
    merge (module docstring); it needs ``state_template`` — a state from
    ``ServerOptimizer.init_sharded``.  ``donate=True`` donates the state
    argument so XLA reuses the old ServerState buffers in place.

    ``collective_precision`` (docs/COLLECTIVE_PRECISION.md) quantizes the
    two hot-path collectives INSIDE the compiled round against per-shard
    on-device error feedback, with the server update transitioning the
    shard-resident fp32 master (``ServerState.master_flat``)."""
    round_fn = _make_mesh_round_core(trainer, server_opt, mesh, gather,
                                     sharded_data, update_sharding,
                                     state_template, collective_precision,
                                     quant_block, health)
    return jax.jit(round_fn, donate_argnums=(0,) if donate else ())


def _make_mesh_round_core(trainer: LocalTrainer, server_opt: ServerOptimizer,
                          mesh: Mesh, gather: bool, sharded_data: bool,
                          update_sharding: str,
                          state_template: ServerState,
                          collective_precision: str = "fp32",
                          quant_block: int = blockscale.DEFAULT_BLOCK,
                          health: bool = False):
    """Unjitted round body shared by the per-round jit
    (:func:`make_mesh_round_fn`) and the fused round-block scan
    (:func:`make_mesh_block_fn`)."""
    local_train = trainer.make_local_train()
    alg = server_opt.algorithm
    spec = server_opt.spec
    layout = MeshLayout(mesh, stage_leaves=_stage_leaves(trainer))
    n_shards = layout.n_client_shards
    scatter = update_sharding == "scatter"
    precision = collective_precision
    quantized = precision != "fp32"
    if scatter and state_template is None:
        raise ValueError("scatter mode needs a state_template from "
                         "ServerOptimizer.init_sharded")
    if quantized and state_template is None:
        raise ValueError("collective_precision needs a state_template "
                         "carrying the EF buffers (ServerOptimizer.init/"
                         "init_sharded with collective_precision set)")
    if quantized and not spec.avg_params:
        raise ValueError(
            f"collective_precision={precision!r} quantizes the avg_params "
            f"merge numerator, which the {alg!r} spec does not use")
    from ..round_engine import make_server_ctx

    use_ingather = gather and not sharded_data
    flat = (layout.flat_spec_of(state_template.global_params)
            if state_template is not None else None)

    pipe_cohort = None
    if layout.pipeline:
        # 3-D layout (docs/PIPELINE.md): the train phase is the fully-
        # manual microbatched pipeline shard_map, NOT the GSPMD vmap below
        from .pipeline import PipelineTrainer, make_pipeline_cohort
        if not isinstance(trainer, PipelineTrainer):
            raise TypeError(
                "a mesh with n_stage_shards > 1 needs a PipelineTrainer "
                "(MeshFedAvgAPI builds one when the mesh has a stage "
                "factor; direct make_mesh_round_fn callers must too)")
        pipe_cohort = make_pipeline_cohort(trainer, layout)
    # trace-time statics for the stage byte model (hoisted so the jit-
    # reachable _bytes_model below stays int()-free — fedlint)
    pipe_hidden = int(trainer.pipe.hidden) if layout.pipeline else 0
    pipe_micro = int(trainer.n_micro) if layout.pipeline else 1

    def run_cohort(state: ServerState, x, y, mask, rngs, c_clients):
        # Client train phase — runs at the JIT level (GSPMD), NOT inside
        # the merge shard_map: cohort arrays are client-sharded, params
        # model-sharded per layout.param_spec, and XLA partitions the
        # vmapped per-client scan over both axes (the pjit pattern of
        # arXiv:2204.06514).  The scanned local-SGD body cannot live
        # inside a partial-auto shard_map on this toolchain (the SPMD
        # partitioner rejects scan under manual subgroups), and the merge
        # cannot live outside one (its psum_scatter/EF semantics are
        # per-client-shard by construction) — so the round is staged:
        # GSPMD train, then the manual-over-client merge body below.
        if use_ingather:
            idx, (train_x, train_y) = x, y
            x = jnp.take(train_x, idx, axis=0)
            y = jnp.take(train_y, idx, axis=0)
        if pipe_cohort is not None:
            return pipe_cohort(state.global_params, state.c_server,
                               state.momentum, x, y, mask, rngs, c_clients)
        ctx = make_server_ctx(trainer, state)
        fn = lambda xb, yb, mb, rng, cc: local_train(
            state.global_params, xb, yb, mb, rng, ctx, cc)
        return jax.vmap(fn)(x, y, mask, rngs, c_clients)

    def _cohort_dims(x, y):
        """Trace-time statics for the ObsCarry phase weights: examples per
        step (B), elements per example (feat), local steps per client."""
        batch = int(x.shape[2])
        src_shape = y[0].shape[1:] if use_ingather else x.shape[3:]
        return batch, math.prod(src_shape), int(x.shape[1])

    def _bytes_model(params, batch: int, steps: int) -> tuple:
        """Trace-time statics: modeled interconnect payload bytes/round,
        split per mesh axis (ObsCarry; consumed by ``fedtrace summarize``
        and ``bench.py --comms/--mesh2d/--pipeline``)."""
        if scatter:
            n_flat = flat.padded_size
        else:
            n_flat = tree_util.num_params(params)
        mode = "scatter" if scatter else "replicated"
        m = layout.n_model_shards
        s = layout.n_stage_shards
        # replicated merge of model-sharded leaves: each chip's psum
        # payload is its 1/m shard, not the full flat length (the
        # fedverify census pinned the 2-D drift — ISSUE 10)
        n_payload = n_flat if scatter else -(-n_flat // (m * s))
        cbytes = coll.client_axis_bytes(n_payload, n_shards, precision,
                                        quant_block, mode)
        mbytes = coll.model_axis_bytes(n_flat, m, mode=mode)
        if layout.pipeline:
            sbytes = coll.stage_axis_bytes(
                n_flat, s, mode=mode, hidden=pipe_hidden,
                microbatch=batch // pipe_micro, n_micro=pipe_micro,
                steps=steps)
        else:
            sbytes = 0.0
        return cbytes, sbytes, mbytes

    def raw_metrics(outs, w, quant_err_sq=None):
        """Per-shard psums of the round scalars; the ObsCarry itself is
        assembled OUTSIDE the shard_map (round_fn) where old/new params
        coexist on both layouts."""
        wsum = jax.lax.psum(jnp.sum(w), CLIENT_AXIS)
        m = {
            "train_loss": jax.lax.psum(jnp.sum(outs.loss * w),
                                       CLIENT_AXIS) / wsum,
            "total_steps": jax.lax.psum(jnp.sum(outs.num_steps),
                                        CLIENT_AXIS),
            "clients": jax.lax.psum(jnp.sum((w > 0).astype(jnp.float32)),
                                    CLIENT_AXIS),
        }
        if quantized:
            # per-shard residual energies sum into one replicated scalar
            m["quant_err_sq"] = (jax.lax.psum(quant_err_sq, CLIENT_AXIS)
                                 if quant_err_sq is not None
                                 else jnp.zeros((), jnp.float32))
        return m

    def merge_replicated(state: ServerState, outs, w, qrow):
        # merge + server update on this client shard's slice of the cohort
        # outputs (outs leaves arrive (c_local, ...) per the P(client)
        # in-spec); runs manual over ``client``, auto over ``model``.
        # Which aggregates exist is the algorithm's declarative spec
        # (core/federated.py); HOW each reduces here is the PsumReducer
        # (local weighted partials + psum per leaf).
        qrow = qrow[0]  # (1, key) in-spec slice -> this shard's base key
        red = federated.PsumReducer(CLIENT_AXIS)
        quant_err_sq = None
        if quantized:
            # EF-quantized merge numerator: each shard adds its residual
            # row, quantizes its LOCAL flat contribution to the average,
            # and the all-reduce moves the low-precision payload; the
            # residual goes back into this shard's ef_num row.  Auxiliary
            # spec aggregates stay full-precision.
            agg = federated.build_aggregates(spec, red, server_opt, state,
                                             outs, w, include_avg=False)
            num = jax.tree_util.tree_map(
                lambda l: jnp.tensordot(w, l.astype(jnp.float32), axes=1),
                outs.params)
            den = jax.lax.psum(jnp.sum(w), CLIENT_AXIS)
            v = state.ef_num[0] + tree_util.tree_flatten_1d(num) / den
            deq, quant_err_sq = coll.quantize_ef(
                v, precision, coll.slot_key(qrow, 0), quant_block)
            new_ef_num = (v - deq)[None]
            summed = jax.lax.psum(coll.wire_cast(deq, precision),
                                  CLIENT_AXIS).astype(jnp.float32)
            agg["avg_params"] = tree_util.tree_unflatten_1d(
                summed, state.global_params)
        else:
            agg = federated.build_aggregates(spec, red, server_opt, state,
                                             outs, w)

        new_state = server_opt.update_from_aggregates(state, agg)
        if quantized:
            new_state = new_state.replace(ef_num=new_ef_num)
        return new_state, raw_metrics(outs, w, quant_err_sq)

    def merge_scatter(state: ServerState, outs, w, qrow, gchunk):
        # spec-declared aggregates through the ScatterReducer: tree
        # aggregates flatten into ONE padded vector and reduce-scatter so
        # each chip receives only its contiguous chunk of the cohort-summed
        # numerator instead of the full all-reduced model
        qrow = qrow[0]  # (1, key) in-spec slice -> this shard's base key
        red = federated.ScatterReducer(flat, CLIENT_AXIS)
        quant_err_sq = None
        if quantized:
            # EF-quantized reduce-scatter of the FedAvg numerator: the
            # shard's flat contribution to the AVERAGE (divide by the
            # psummed weight first — EF residuals then live in stable
            # param-delta units across rounds) plus this shard's residual
            # row, block-scaled/stochastically rounded, reduce-scattered
            # at the wire precision
            agg = federated.build_aggregates(spec, red, server_opt, state,
                                             outs, w, include_avg=False)
            den = jax.lax.psum(jnp.sum(w), CLIENT_AXIS)
            num = jax.tree_util.tree_map(
                lambda l: jnp.tensordot(w, l.astype(jnp.float32), axes=1),
                outs.params)
            v = state.ef_num[0] + flat.flatten(num) / den
            deq, quant_err_sq = coll.quantize_ef(
                v, precision, coll.slot_key(qrow, 0), quant_block)
            new_ef_num = (v - deq)[None]
            agg["avg_params"] = jax.lax.psum_scatter(
                coll.wire_cast(deq, precision), CLIENT_AXIS,
                scatter_dimension=0, tiled=True).astype(jnp.float32)
        else:
            agg = federated.build_aggregates(spec, red, server_opt, state,
                                             outs, w)

        # this chip's chunk of the current global params, then the sharded
        # stage-2 transition on 1/n_shards of the model.  With quantized
        # collectives the chunk comes from the shard-resident fp32 MASTER
        # (state.global_params is the low-precision broadcast copy the
        # clients trained from — transitioning it would compound the
        # broadcast rounding into the model state every round); at fp32 it
        # is the pre-flattened params sliced in by the P(client) in-spec.
        gshard = state.master_flat if quantized else gchunk
        new_gshard, new_fields = server_opt.update_shard(state, gshard, agg)
        # the new params leave as this shard's chunk through the P(client)
        # out-spec (the historical in-body all_gather, inverted);
        # opt_state/c_server/h/momentum stay shard-resident forever
        if quantized:
            # broadcast at the collective precision: the gathered chunk is
            # the quantized one; the fp32 master never crosses the wire
            send, new_ef_bcast, berr_sq = coll.quantize_broadcast(
                new_gshard, state.ef_bcast, precision,
                coll.slot_key(qrow, 1), quant_block)
            new_fields["master_flat"] = new_gshard
            new_fields["ef_num"] = new_ef_num
            if state.ef_bcast is not None:
                new_fields["ef_bcast"] = new_ef_bcast
            quant_err_sq = quant_err_sq + berr_sq
            out_chunk = coll.wire_cast(send, precision)
        else:
            out_chunk = new_gshard
        # round_fn swaps the assembled new params in; the passthrough keeps
        # the ServerState structure (and the donated buffer) intact
        new_state = state.replace(round_idx=state.round_idx + 1,
                                  **new_fields)
        return new_state, out_chunk, raw_metrics(outs, w, quant_err_sq)

    shard = layout.client_spec
    state_spec = layout.state_partition_specs(state_template, scatter,
                                              quantized)
    # merge phase: manual over ``client`` (explicit psum_scatter / psum +
    # per-shard EF), auto over ``model`` (GSPMD carries the model factor
    # of params/outs/flat state straight through the elementwise body)
    if scatter:
        sharded_merge = jax.shard_map(
            merge_scatter, mesh=mesh,
            in_specs=(state_spec, shard, shard, shard, shard),
            out_specs=(state_spec, shard, P()),
            check_vma=False, axis_names=layout.manual_axes,
        )
    else:
        sharded_merge = jax.shard_map(
            merge_replicated, mesh=mesh,
            in_specs=(state_spec, shard, shard, shard),
            out_specs=(state_spec, P()),
            check_vma=False, axis_names=layout.manual_axes,
        )

    def assemble_metrics(mraw, old_params, new_params, x, y):
        batch, feat, steps = _cohort_dims(x, y)
        cbytes, sbytes, mbytes = _bytes_model(old_params, batch, steps)
        qerr = (jnp.sqrt(mraw.pop("quant_err_sq")) if quantized else None)
        metrics = {"train_loss": mraw["train_loss"],
                   "total_steps": mraw["total_steps"]}
        # device-carry telemetry (ISSUE 4): psummed globals + static shape
        # products, assembled at the jit level so both merge layouts share
        # one code path; rides the metrics pytree exactly like the loss
        metrics["obs"] = round_obs(
            old_params, new_params, real_steps=mraw["total_steps"],
            real_clients=mraw["clients"], batch=batch, feat=feat,
            opt_flops_per_param=OPT_FLOPS.get(alg, 4.0),
            collective_bytes=cbytes + sbytes + mbytes,
            collective_bytes_client=cbytes, collective_bytes_stage=sbytes,
            collective_bytes_model=mbytes, quant_error=qerr)
        return metrics

    def round_fn(state, x, y, mask, w, key, c_clients):
        # split inside the compiled program (host-side split costs a device
        # roundtrip per round); GSPMD shards the keys per the cohort arrays
        rngs = jax.random.split(key, mask.shape[0])
        # stochastic-rounding streams of the collective layer: one base key
        # per client shard, precomputed here and sliced in by the P(client)
        # in-spec (bitwise the historical in-body axis_index fold_in)
        qkey = jax.random.fold_in(key, QUANT_KEY_TAG)
        qrows = coll.shard_qkeys(qkey, n_shards)
        if gather and sharded_data:
            # cohort gather over the ROW-SHARDED dataset: XLA lowers the
            # take into cross-chip collectives; pin the result onto the
            # client axis so only the cohort is resident per shard
            idx, (train_x, train_y) = x, y
            cohort_spec = NamedSharding(mesh, P(CLIENT_AXIS))
            x = jax.lax.with_sharding_constraint(
                jnp.take(train_x, idx, axis=0), cohort_spec)
            y = jax.lax.with_sharding_constraint(
                jnp.take(train_y, idx, axis=0), cohort_spec)
        old_params = state.global_params
        if scatter:
            # client-VISIBLE server state (SCAFFOLD's c_server in the
            # corrected gradient, Mime's momentum in the client step) is
            # flat shard-resident; unflatten it HERE for the train phase
            # (GSPMD inserts the gathers — the historical in-body
            # all_gather is unavailable under the 2-D partial-auto merge).
            # Server-side-only state (FedOpt moments, FedDyn h) never
            # leaves its shard.
            gathered = {
                f: flat.unflatten(getattr(state, f))
                for f in ("c_server", "momentum")
                if getattr(state, f) is not None}
            ctx_state = state.replace(**gathered) if gathered else state
            outs = run_cohort(ctx_state, x, y, mask, rngs, c_clients)
            # fp32 path: pre-flattened params, sliced per shard by the
            # in-spec (the quantized path reads the master instead, so it
            # gets a free zeros placeholder).  Leaves pin replicated before
            # the concat — see layout.replicate_leaves.
            gflat = (jnp.zeros((flat.padded_size,), jnp.float32) if quantized
                     else flat.flatten(layout.replicate_leaves(old_params)))
            new_state, out_chunk, mraw = sharded_merge(state, outs, w,
                                                       qrows, gflat)
            new_params = layout.constrain_params(
                flat.unflatten(out_chunk.astype(jnp.float32)))
            new_state = new_state.replace(global_params=new_params)
        else:
            outs = run_cohort(state, x, y, mask, rngs, c_clients)
            new_state, mraw = sharded_merge(state, outs, w, qrows)
            new_state = new_state.replace(
                global_params=layout.constrain_params(
                    new_state.global_params))
        # resting placement for the next round's input (and the donated
        # buffer reuse): flat aux state back onto BOTH axes — the merge
        # out-specs only fix the manual ``client`` factor
        new_state = layout.constrain_state(new_state, scatter, quantized)
        metrics = assemble_metrics(mraw, old_params,
                                   new_state.global_params, x, y)
        if health:
            # fedmon (ISSUE 14): per-client stat rows assembled at the JIT
            # level where old/new params coexist on both merge layouts —
            # the cohort axis stays GSPMD-sharded over ``client``, each
            # lane reduces per client, and the rows ride the metrics
            # pytree under the PR 4 zero-sync contract
            ref_delta = jax.tree_util.tree_map(
                lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
                new_state.global_params, old_params)
            metrics["health"] = federated.client_health_stats(
                old_params, outs.params, ref_delta, outs.loss, w)
        return new_state, metrics, outs.new_client_state

    return round_fn


def make_mesh_block_fn(trainer: LocalTrainer, server_opt: ServerOptimizer,
                       mesh: Mesh, gather: bool = False,
                       sharded_data: bool = False,
                       update_sharding: str = "replicated",
                       state_template: ServerState = None,
                       donate: bool = False,
                       collective_precision: str = "fp32",
                       quant_block: int = blockscale.DEFAULT_BLOCK,
                       health: bool = False):
    """Fused mesh round-block: K rounds as ONE ``jit(lax.scan(round))``
    dispatch (ISSUE 3 tentpole; same composition DrJAX builds from,
    arXiv:2403.07128).

    ``block_fn(state, x_blk, dev_data, mask_blk, w_blk, keys_blk,
    cohort_blk, client_table)``: cohort inputs carry a leading round axis
    (``x_blk`` is the ``(K, C, S, B)`` index tensor in gather mode —
    fusion requires device-resident data so a staged block is indices
    only); ``dev_data`` is the device-resident ``(train_x, train_y)`` pair
    passed once per call, not per round.  ServerState and the
    client-axis-sharded per-client state table thread through the scan
    carry (both donated), the table gathered/scattered by ``cohort_blk``
    ids INSIDE the compiled program, and per-round metrics stack into
    ``(K,)`` outputs so the host syncs once per block."""
    core = _make_mesh_round_core(trainer, server_opt, mesh, gather,
                                 sharded_data, update_sharding,
                                 state_template, collective_precision,
                                 quant_block, health)
    has_table = server_opt.algorithm in ("scaffold", "feddyn")
    layout = MeshLayout(mesh, stage_leaves=_stage_leaves(trainer))
    row_sharding = NamedSharding(mesh, P(CLIENT_AXIS))

    def block_fn(state: ServerState, x_blk, dev_data, mask_blk, w_blk,
                 keys_blk, cohort_blk, client_table=None):
        def step(carry, inp):
            st, table = carry
            x, mask, w, key, cohort = inp
            c = None
            if has_table:
                # rows of the client-axis-sharded table -> cohort stack,
                # pinned back onto the client axis for the shard_map body
                c = jax.lax.with_sharding_constraint(
                    tree_util.cohort_gather(table, cohort), row_sharding)
            st, metrics, new_c = core(st, x, dev_data, mask, w, key, c)
            if has_table:
                table = layout.constrain_table(
                    tree_util.cohort_scatter(table, cohort, new_c))
            return (st, table), metrics

        (state, client_table), metrics = jax.lax.scan(
            step, (state, client_table),
            (x_blk, mask_blk, w_blk, keys_blk, cohort_blk))
        return state, metrics, client_table

    return jax.jit(block_fn, donate_argnums=(0, 7) if donate else ())


class MeshFedAvgAPI(FedAvgAPI):
    """Same driver surface as the SP engine; rounds dispatch onto the mesh.

    The accuracy curve is bitwise-comparable to the SP engine under the same
    seed (same per-client keys, same batch schedule) — the §7 exit criterion.

    ``args.mesh_shape``: ``(n_client_shards, n_model_shards)`` — the 2-D
    ``client × model`` layout when the model factor exceeds 1
    (docs/MESH_2D.md); wins over the per-axis ``mesh_*`` knobs when set.
    ``args.update_sharding``: "replicated" | "scatter" | "auto" (default:
    scatter whenever the mesh has more than one client shard).
    ``args.async_staging`` (default True): double-buffer the host→device
    cohort staging so round r+1's transfer overlaps round r's compute.
    """

    def __init__(self, args, device, dataset, model, mesh: Mesh = None):
        self.layout = MeshLayout.from_args(args, mesh, model=model)
        self.mesh = self.layout.mesh
        self.n_shards = self.layout.n_client_shards
        self.n_stage_shards = self.layout.n_stage_shards
        self.n_model_shards = self.layout.n_model_shards
        mode = str(getattr(args, "update_sharding", "auto") or "auto").lower()
        if mode == "auto":
            mode = "scatter" if self.n_shards > 1 else "replicated"
        if mode not in ("replicated", "scatter"):
            raise ValueError(
                f"update_sharding must be 'replicated', 'scatter' or "
                f"'auto', got {mode!r}")
        self.update_sharding = mode
        super().__init__(args, device, dataset, model, client_mode="vmap")
        self._data_sharding = NamedSharding(self.mesh, P(CLIENT_AXIS))
        self._repl_sharding = NamedSharding(self.mesh, P())
        # mixed placement (layout.state_sharding): flat aux state over the
        # client axis (× model on the 2-D layout), params replicated on 1-D
        # or per-param model-sharded on 2-D, scalars replicated
        self.state = jax.device_put(self.state, self.layout.state_sharding(
            self.state, scatter=self.update_sharding == "scatter",
            quantized=self.collective_precision != "fp32"))
        self._stager = AsyncCohortStager(
            self._stage_cohort,
            enabled=bool(getattr(args, "async_staging", True)),
            depth=int(getattr(args, "staging_depth", 1) or 1),
            limit=self.comm_rounds)

    def _make_trainer(self, model, args):
        """3-D layout (docs/PIPELINE.md): the microbatched pipeline trainer
        — ``loss_fn`` replaced, every optimizer/SCAFFOLD step inherited."""
        if not self.layout.pipeline:
            return LocalTrainer(model, args)
        from .pipeline import (PipelineTrainer, check_pipeline_shapes)
        micro = int(getattr(args, "microbatches", 1) or 1)
        check_pipeline_shapes(model, self.layout,
                              int(getattr(args, "batch_size", 10)), micro)
        return PipelineTrainer(model, args,
                               n_stages=self.layout.n_stage_shards,
                               microbatches=micro)

    def _build_round_fn(self, client_mode: str):
        # device_data: True/"replicated" | "sharded" | False ("host")
        mode = getattr(self.args, "device_data", True)
        if isinstance(mode, str):
            mode = mode.lower()
        self._gather = mode not in (False, "host", "off")
        self._sharded_data = mode == "sharded"
        if self._gather:
            if self._sharded_data:
                # row-shard the dataset over the client axis: resident HBM
                # per chip group = |dataset|/n_client_shards
                n = self.n_shards
                spec = NamedSharding(self.mesh, P(CLIENT_AXIS))
                tx, ty = self.dataset.train_x, self.dataset.train_y
                pad = (-len(tx)) % n
                if pad:  # row count must divide evenly; padded rows are
                    # never indexed (cohort indices < len(tx))
                    tx = np.concatenate([tx, np.zeros_like(tx[:pad])])
                    ty = np.concatenate([ty, np.zeros_like(ty[:pad])])
                self._dev_data = (
                    jax.device_put(jnp.asarray(tx), spec),
                    jax.device_put(jnp.asarray(ty), spec))
            else:
                repl = NamedSharding(self.mesh, P())
                self._dev_data = (
                    jax.device_put(jnp.asarray(self.dataset.train_x), repl),
                    jax.device_put(jnp.asarray(self.dataset.train_y), repl))
        if self.update_sharding == "scatter":
            # re-init server aux state into its permanent shard-resident
            # flat layout (FedAvgAPI.__init__ built the replicated one);
            # the flat vector pads to n_client_shards * n_model_shards so
            # each client chunk subdivides over the model axis
            self.state = self.server_opt.init_sharded(
                self.state.global_params, self.n_shards,
                collective_precision=self.collective_precision,
                flat_multiple=self.layout.flat_multiple)
        return make_mesh_round_fn(self.trainer, self.server_opt, self.mesh,
                                  gather=self._gather,
                                  sharded_data=self._sharded_data,
                                  update_sharding=self.update_sharding,
                                  state_template=self.state,
                                  donate=self.DONATE_STATE,
                                  collective_precision=self.collective_precision,
                                  quant_block=self.quant_block,
                                  health=self._health)

    def _init_server_state(self, params):
        """Replicated-layout init for the mesh: one EF residual row PER
        SHARD (each chip quantizes its own local numerator), and no
        master/broadcast split — the replicated merge mode has no
        post-update gather, so global_params stay fp32 and only the
        numerator all-reduce is quantized.  Scatter mode replaces this
        state wholesale in ``_build_round_fn`` via ``init_sharded``."""
        return self.server_opt.init(
            params, collective_precision=self.collective_precision,
            ef_shards=self.n_shards, quantized_broadcast=False)

    def _init_client_table(self):
        """Client-state table rows padded to a multiple of the shard count
        and sharded over the client axis (rows) and, on the 2-D layout,
        the model axis (row contents): each chip permanently owns its
        slice of the SCAFFOLD/FedDyn state; cohort rows move by
        gather/scatter collectives inside the compiled round."""
        self._table_rows = -(-self.registered_clients
                             // self.n_shards) * self.n_shards
        table = tree_util.client_table_init(self.state.global_params,
                                            self._table_rows)
        return jax.device_put(table, self.layout.table_sharding(table))

    def _put_rows(self, rows):
        """Host cohort-row stack from the paged store -> device with the
        leading cohort axis sharded over ``client`` (the same resting
        placement the dense table's jitted gather produced)."""
        return jax.device_put(rows, NamedSharding(self.mesh, P(CLIENT_AXIS)))

    def _put_table(self, table):
        """Fused-block store path: the block's mini-table takes the dense
        table's sharding (rows over ``client``, contents over ``model`` on
        2-D layouts)."""
        return jax.device_put(table, self.layout.table_sharding(table))

    def _build_block_fn(self):
        if not self._gather:
            raise ValueError(
                "round_block fusion on the mesh engine needs "
                "device-resident data (device_data=True or 'sharded'): "
                "staging a block must ship index tensors, not cohorts")
        inner = make_mesh_block_fn(self.trainer, self.server_opt, self.mesh,
                                   gather=self._gather,
                                   sharded_data=self._sharded_data,
                                   update_sharding=self.update_sharding,
                                   state_template=self.state,
                                   donate=self.DONATE_STATE,
                                   collective_precision=self.collective_precision,
                                   quant_block=self.quant_block,
                                   health=self._health)
        # the jitted block program itself (the dev_data closure below is
        # plain Python): what fedverify AOT-lowers (block_program hook)
        self._block_inner = inner
        dev_data = self._dev_data

        def call(state, idx, mask, w, keys, cohort, table):
            return inner(state, idx, dev_data, mask, w, keys, cohort, table)

        return call

    def _stage_block(self, start_round: int):
        """Mesh block staging: stacked index/mask/weight tensors sharded
        over the client axis (leading round axis replicated), cohort ids
        padded with the out-of-range sentinel so pad rows never touch the
        client-state table.  Pure function of ``start_round``."""
        k = min(self._round_block, self.comm_rounds - start_round)
        rounds = range(start_round, start_round + k)
        per = []
        for r in rounds:
            clients = self._client_sampling(r)
            idx, mask, w = self.dataset.cohort_indices(
                self._data_ids(clients), self.batch_size, self.seed, r,
                self.epochs)
            per.append((clients, idx, mask, w))
        n = per[0][1].shape[0]
        n_padded = -(-n // self.n_shards) * self.n_shards
        steps = next_pow2(max(p[1].shape[1] for p in per))
        sentinel = getattr(self, "_table_rows", self.registered_clients)
        idx_blk = np.zeros((k, n_padded, steps, self.batch_size), np.int32)
        mask_blk = np.zeros((k, n_padded, steps), np.float32)
        w_blk = np.zeros((k, n_padded), np.float32)
        cohort_blk = np.full((k, n_padded), sentinel, np.int32)
        for i, (clients, idx, mask, w) in enumerate(per):
            s = idx.shape[1]
            idx_blk[i, :n, :s] = idx
            mask_blk[i, :n, :s] = mask
            w_blk[i, :n] = w
            cohort_blk[i, :n] = clients
        root = rng_util.root_key(self.seed)
        keys_blk = np.stack([np.asarray(rng_util.round_key(root, r))
                             for r in rounds])
        shard = NamedSharding(self.mesh, P(None, CLIENT_AXIS))
        put = lambda a: jax.device_put(jnp.asarray(a), shard)
        repl = lambda a: jax.device_put(jnp.asarray(a), self._repl_sharding)
        count_put(self._tracer,
                  (idx_blk, mask_blk, w_blk, keys_blk, cohort_blk))
        return (k, steps, put(idx_blk), put(mask_blk), put(w_blk),
                repl(keys_blk), repl(cohort_blk))

    def _stage_cohort(self, round_idx: int):
        """Build + device_put one round's cohort tensors.  Pure function of
        the round index (sampling and batching are seed-derived), so the
        stager may run it ahead of time on a worker thread."""
        clients = self._client_sampling(round_idx)
        n = len(clients)
        n_padded = -(-n // self.n_shards) * self.n_shards
        pad_c = n_padded - n
        if self._gather:
            idx, mask, w = self.dataset.cohort_indices(
                self._data_ids(clients), self.batch_size, self.seed,
                round_idx, self.epochs)
            steps = next_pow2(idx.shape[1])
            pad_s = steps - idx.shape[1]
            if pad_s or pad_c:
                idx = np.pad(idx, [(0, pad_c), (0, pad_s), (0, 0)])
                mask = np.pad(mask, [(0, pad_c), (0, pad_s)])
                w = np.pad(w, (0, pad_c))
            data_x, data_y = idx, self._dev_data
        else:
            x, y, mask, w = self.dataset.cohort_batches(
                self._data_ids(clients), self.batch_size, self.seed,
                round_idx, self.epochs)
            steps = next_pow2(x.shape[1])
            pad_s = steps - x.shape[1]
            if pad_s or pad_c:
                x = np.pad(x, [(0, pad_c), (0, pad_s)] + [(0, 0)] * (x.ndim - 2))
                y = np.pad(y, [(0, pad_c), (0, pad_s)] + [(0, 0)] * (y.ndim - 2))
                mask = np.pad(mask, [(0, pad_c), (0, pad_s)])
                w = np.pad(w, (0, pad_c))
            data_x, data_y = x, y
        put = lambda a: jax.device_put(jnp.asarray(a), self._data_sharding)
        # the resident dataset of gather mode is not staged here
        count_put(self._tracer, (data_x, mask, w) if self._gather
                  else (data_x, data_y, mask, w))
        dy = data_y if self._gather else put(data_y)
        return clients, pad_c, put(data_x), dy, put(mask), put(w)

    # -- fedverify hooks (ISSUE 10, docs/FEDVERIFY.md) ---------------------
    def round_program(self, round_idx: int = 0):
        """The exact jitted mesh round + one round's staged (sharded)
        arguments + donated argnums, for AOT lowering by
        ``analysis/fedverify.py``.  Staging device_puts the cohort
        tensors (cheap, kilobytes) but runs NO round."""
        clients, pad_c, data_x, data_y, mask, w = self._stage_cohort(
            round_idx)
        key = rng_util.round_key(rng_util.root_key(self.seed), round_idx)
        c_stacked = None
        if self.client_table is not None or self._pager is not None:
            cohort = np.concatenate(
                [np.asarray(clients, np.int32),
                 np.full(pad_c, self._table_rows, np.int32)])
            c_stacked = self._gather_c(cohort, round_idx=round_idx)
        args = (self.state, data_x, data_y, mask, w, key, c_stacked)
        return self.round_fn, args, (0,) if self.DONATE_STATE else ()

    def round_signature(self, round_idx: int) -> str:
        """Shard-padded staged-input signature of one mesh round (see
        ``FedAvgAPI.round_signature``)."""
        _, _, data_x, data_y, mask, w = self._stage_cohort(round_idx)
        leaves = jax.tree_util.tree_leaves((data_x, data_y, mask, w))
        return repr([(tuple(a.shape), str(a.dtype)) for a in leaves])

    def block_program(self, start_round: int = 0):
        """:meth:`round_program` for the fused mesh ``round_block`` scan
        (the dev_data pair becomes an explicit argument — the driver's
        ``call`` closure is sugar over the same jitted program)."""
        if self._block_fn is None:
            self._block_fn = self._build_block_fn()
        k, steps, idx, mask, w, keys, cohort = self._stage_block(
            start_round)
        args = (self.state, idx, self._dev_data, mask, w, keys, cohort,
                self.client_table)
        return (self._block_inner, args,
                (0, 7) if self.DONATE_STATE else ())

    def block_signature(self, start_round: int) -> str:
        k, steps, idx, mask, w, keys, cohort = self._stage_block(
            start_round)
        return repr([(tuple(a.shape), str(a.dtype))
                     for a in (idx, mask, w, keys, cohort)])

    def train_one_round(self, round_idx: int):
        nxt = round_idx + 1 if round_idx + 1 < self.comm_rounds else None
        clients, pad_c, data_x, data_y, mask, w = self._stager.get(
            round_idx, prefetch=nxt)
        key = rng_util.round_key(rng_util.root_key(self.seed), round_idx)
        # per-client state rows gather/scatter on DEVICE against the
        # client-axis-sharded table (the host-dict era device_got the whole
        # stacked cohort state back every round); pad rows use the
        # out-of-range sentinel so their writes drop
        cohort = None
        c_stacked = None
        if self.client_table is not None or self._pager is not None:
            cohort = np.concatenate(
                [np.asarray(clients, np.int32),
                 np.full(pad_c, self._table_rows, np.int32)])
            c_stacked = self._gather_c(cohort, round_idx=round_idx)
        self.state, metrics, new_c = self.round_fn(
            self.state, data_x, data_y, mask, w, key, c_stacked)
        self._scatter_c(cohort, new_c, round_idx=round_idx)
        return metrics
