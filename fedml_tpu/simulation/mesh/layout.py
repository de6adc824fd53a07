"""Mesh layout rules — who owns which bytes on the ``(client, model)`` /
``(client, stage, model)`` mesh.

Split out of the 720-line ``mesh_simulator.py`` (ISSUE 6 enabling refactor;
see docs/MESH_2D.md and MIGRATION.md).  Everything here is *static* layout
policy: axis names, per-parameter PartitionSpecs, the ServerState sharding
maps, and the flat-model pad multiple.  The collectives live in
``collectives.py``; the round/block programs in ``engine.py`` and the
microbatched pipeline train phase in ``pipeline.py``.

Three layouts share one code path:

- 1-D (``n_model_shards == 1``): the engine's historical layout — clients
  sharded over ``client``, params replicated, flat aux state chunked over
  ``client``.  ``shard_map`` runs fully manual.
- 2-D (``n_model_shards > 1``): the GSPMD ``("batch", "model")`` pattern of
  arXiv:2204.06514 on top of the arXiv:2004.13336 scatter merge — client
  train steps run model-parallel (params sharded per :meth:`param_spec`,
  XLA partitioning the matmuls over ``model``), the FedAvg numerator keeps
  its ``psum_scatter`` along ``client``, and flat server state (opt
  moments, EF rows, fp32 master) shards along BOTH axes so each chip owns
  ``1/(c*m)`` of it.  ``shard_map`` runs manual over ``client`` and *auto*
  over ``model``: collectives along ``client`` stay explicit while GSPMD
  propagates the ``model`` factor through the per-client bodies.
- 3-D (``n_stage_shards > 1``, docs/PIPELINE.md): the staged leaves the
  model names (``FlaxModel.pipeline.stage_leaves`` — layer-stacked params)
  additionally partition their LAYER axis over ``stage``; the client train
  step becomes the microbatched pipeline (``pipeline.py``, fully-manual
  ``shard_map`` — this toolchain's SPMD partitioner aborts on ``lax.scan``
  under a manual subgroup, so the train phase cannot be partial-auto),
  while the merge keeps the 2-D partial-auto pattern with ``stage`` as a
  second auto axis and the flat server state shards over ALL THREE axes —
  each chip owns ``1/(c*s*m)``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core.flatmodel import FlatSpec
from ...core.mesh import CLIENT_AXIS, MODEL_AXIS, STAGE_AXIS, make_mesh
from ...ml.aggregator.agg_operator import (ServerState,
                                           replicated_ef_state_map,
                                           sharded_state_map)


class MeshLayout:
    """Static sharding policy for one mesh.

    ``flat_multiple`` is ``n_client_shards * n_stage_shards *
    n_model_shards``: the flat model vector pads so the per-client-shard
    chunk (``psum_scatter`` granularity) still divides evenly into
    ``stage``/``model``-axis subchunks.  With ``s == m == 1`` this is
    exactly the historical pad-to-``n_shards``.

    ``stage_leaves`` names the top-level params whose dim 0 is a layer
    axis (``FlaxModel.pipeline.stage_leaves``) — required whenever the
    mesh has a nontrivial stage factor.
    """

    def __init__(self, mesh: Mesh, stage_leaves: Sequence[str] = ()):
        self.mesh = mesh
        self.n_client_shards = int(mesh.shape[CLIENT_AXIS])
        self.n_stage_shards = int(mesh.shape.get(STAGE_AXIS, 1))
        self.n_model_shards = int(mesh.shape.get(MODEL_AXIS, 1))
        self.two_d = self.n_model_shards > 1
        self.pipeline = self.n_stage_shards > 1
        self.stage_leaves = tuple(stage_leaves)
        if self.pipeline and not self.stage_leaves:
            raise ValueError(
                "a mesh with n_stage_shards > 1 needs a staged model: "
                "stage_leaves is empty (use model='pipe_mlp' or any "
                "FlaxModel carrying a PipelineDef — docs/PIPELINE.md)")
        #: axes the MERGE program's ``shard_map`` is manual over
        #: (``axis_names=``); the rest — ``model`` on the 2-D layout,
        #: ``stage`` too on the pipeline layout — GSPMD partitions
        #: automatically (docs/MESH_2D.md).  On the 1-D layout this is every
        #: mesh axis: the fully-manual program.  The train phase on the
        #: pipeline layout does NOT consult this — it runs fully manual
        #: (module docstring).
        auto = set()
        if self.two_d:
            auto.add(MODEL_AXIS)
        if self.pipeline:
            auto.add(STAGE_AXIS)
        self.manual_axes = frozenset(mesh.axis_names) - auto
        self.flat_multiple = (self.n_client_shards * self.n_stage_shards
                              * self.n_model_shards)
        # -- shard_map PartitionSpecs (manual axes only) -------------------
        self.client_spec = P(CLIENT_AXIS)
        self.repl_spec = P()
        # -- device_put placements (full sharding incl. stage/model) ------
        self.repl_sharding = NamedSharding(mesh, P())
        self.client_sharding = NamedSharding(mesh, P(CLIENT_AXIS))
        #: flat server-state vectors: one contiguous chunk per chip across
        #: EVERY nontrivial axis — per-chip HBM = padded_flat / (c*s*m)
        flat_axes = (CLIENT_AXIS,)
        if self.pipeline:
            flat_axes += (STAGE_AXIS,)
        if self.two_d:
            flat_axes += (MODEL_AXIS,)
        self.flat_sharding = NamedSharding(
            mesh, P(flat_axes) if len(flat_axes) > 1 else P(CLIENT_AXIS))
        #: per-shard EF residual rows (n_client_shards, flat_len): rows over
        #: ``client``, columns over ``stage``/``model``
        cols = flat_axes[1:]
        self.ef_rows_sharding = NamedSharding(
            mesh, P(CLIENT_AXIS, cols if len(cols) > 1 else cols[0])
            if cols else P(CLIENT_AXIS))

    @classmethod
    def from_args(cls, args, mesh: Optional[Mesh] = None,
                  model=None) -> "MeshLayout":
        """Build the mesh from ``args.mesh_shape`` (2-D ``(client, model)``
        or 3-D ``(client, stage, model)`` form, which wins when set) or the
        per-axis ``mesh_*`` knobs.  ``model`` (a FlaxModel) supplies the
        staged-leaf names on pipeline layouts."""
        if mesh is None:
            from ...core.mesh import parse_mesh_shape
            shape = parse_mesh_shape(getattr(args, "mesh_shape", None))
            if shape is not None and len(shape) == 3:
                mesh = make_mesh(client=shape[0], stage=shape[1],
                                 model=shape[2])
            elif shape is not None:
                mesh = make_mesh(client=shape[0], model=shape[1])
            else:
                mesh = make_mesh(
                    client=int(getattr(args, "mesh_client", -1)),
                    stage=int(getattr(args, "mesh_stage", 1)),
                    data=int(getattr(args, "mesh_data", 1)),
                    model=int(getattr(args, "mesh_model", 1)),
                    seq=int(getattr(args, "mesh_seq", 1)))
        pipe = getattr(model, "pipeline", None)
        leaves = tuple(getattr(pipe, "stage_leaves", ()) or ())
        return cls(mesh, stage_leaves=leaves)

    # -- per-parameter partition rules ------------------------------------
    def _is_staged(self, path) -> bool:
        for k in path:
            name = getattr(k, "key", getattr(k, "name", None))
            if name in self.stage_leaves:
                return True
        return False

    def param_spec(self, leaf, staged: bool = False) -> P:
        """Model-axis PartitionSpec of one parameter leaf: matrices
        (ndim >= 2 — LoRA A/B, attention q/k/v/o, MLP gate/up/down,
        embeddings) shard their largest ``model``-divisible dim; vectors
        and scalars (biases, norm scales) replicate.

        On the pipeline layout ``staged`` leaves shard dim 0 (the layer
        axis) over ``stage`` and, when ndim >= 3, dim 1 (the per-layer
        input dim — row-parallel) over ``model``; NON-staged leaves
        replicate over both (the manual pipeline body computes embed/head
        redundantly per stage group and psums their grads over the ring —
        docs/PIPELINE.md prices the trade)."""
        shape = tuple(np.shape(leaf) if not hasattr(leaf, "shape")
                      else leaf.shape)
        if self.pipeline:
            if not staged:
                return P()
            spec = [None] * len(shape)
            spec[0] = STAGE_AXIS
            if (self.two_d and len(shape) >= 3
                    and shape[1] % self.n_model_shards == 0
                    and shape[1] >= self.n_model_shards):
                spec[1] = MODEL_AXIS
            return P(*spec)
        if not self.two_d:
            return P()
        if len(shape) < 2:
            return P()
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        for d in dims:
            if shape[d] % self.n_model_shards == 0 and shape[d] >= \
                    self.n_model_shards:
                spec = [None] * len(shape)
                spec[d] = MODEL_AXIS
                return P(*spec)
        return P()

    def params_pspec(self, params: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda p, l: self.param_spec(l, self._is_staged(p)), params)

    def params_sharding(self, params: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda p, l: NamedSharding(
                self.mesh, self.param_spec(l, self._is_staged(p))), params)

    def constrain_params(self, params: Any) -> Any:
        """Pin a params pytree onto its resting layout — replicated on 1-D
        (the historical broadcast copy), the model-axis rules on 2-D, the
        staged rules on 3-D.  Keeps the round's output layout stable
        across rounds so donation reuses buffers and steady-state rounds
        never recompile."""
        return jax.tree_util.tree_map(
            lambda l, s: jax.lax.with_sharding_constraint(l, s),
            params, self.params_sharding(params))

    # -- per-client state table (SCAFFOLD c_i / FedDyn residuals) ----------
    def table_spec(self, leaf, staged: bool = False) -> P:
        """Rows over ``client``; each row (param-shaped) follows the
        stage/model-axis rule shifted past the leading row dim."""
        row = jax.ShapeDtypeStruct(tuple(leaf.shape)[1:], leaf.dtype)
        return P(CLIENT_AXIS, *self.param_spec(row, staged))

    def table_sharding(self, table: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda p, l: NamedSharding(
                self.mesh, self.table_spec(l, self._is_staged(p))), table)

    def constrain_table(self, table: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda l, s: jax.lax.with_sharding_constraint(l, s),
            table, self.table_sharding(table))

    # -- ServerState maps ---------------------------------------------------
    def state_partition_specs(self, state: ServerState, scatter: bool,
                              quantized: bool) -> ServerState:
        """shard_map in/out specs for the ServerState pytree — manual axes
        only; the ``stage``/``model`` factor of every leaf rides the auto
        axes."""
        if scatter:
            return sharded_state_map(state, self.repl_spec, self.client_spec)
        if quantized:
            return replicated_ef_state_map(state, self.repl_spec,
                                           self.client_spec)
        return self.repl_spec

    def state_sharding(self, state: ServerState, scatter: bool,
                       quantized: bool) -> Any:
        """``jax.device_put`` placement of the persistent ServerState:
        like :meth:`state_partition_specs` but with the stage/model axes
        made explicit — flat aux vectors over EVERY axis,
        ``global_params`` per the :meth:`param_spec` rules."""
        def shard_leaf(x):
            # flat (L,) vectors chunk over all axes; the (n_shards, L) EF
            # rows keep rows on ``client`` and columns on ``stage``/``model``
            if np.ndim(x) >= 2:
                return self.ef_rows_sharding
            return self.flat_sharding

        if scatter:
            marked = sharded_state_map(state, self.repl_sharding, shard_leaf)
        elif quantized:
            marked = replicated_ef_state_map(state, self.repl_sharding,
                                             self.ef_rows_sharding)
        else:
            marked = jax.tree_util.tree_map(lambda _: self.repl_sharding,
                                            state)
        if (self.two_d or self.pipeline) and state.global_params is not None:
            marked = marked.replace(
                global_params=self.params_sharding(state.global_params))
        return marked

    def constrain_state(self, state: ServerState, scatter: bool,
                        quantized: bool) -> ServerState:
        """Pin the post-merge ServerState back onto its resting placement
        (:meth:`state_sharding`).  The merge shard_map's out-specs only fix
        the manual ``client`` factor; along the auto ``stage``/``model``
        axes GSPMD would otherwise replicate the flat aux state on round
        exit, silently forfeiting the 1/(c*s*m) per-chip ownership.
        Identity on the 1-D layout (the historical program is already
        resting)."""
        if not (self.two_d or self.pipeline):
            return state
        return jax.tree_util.tree_map(
            lambda l, s: jax.lax.with_sharding_constraint(l, s),
            state, self.state_sharding(state, scatter, quantized))

    def replicate_leaves(self, tree: Any) -> Any:
        """Pin every leaf replicated.  Needed before a jit-level
        ``FlatSpec.flatten`` of model-sharded params: this toolchain's
        SPMD partitioner miscompiles ``concatenate`` over mixed-sharded
        operands (values scale by an axis size), so the leaves must agree
        on a sharding before they concat (docs/MESH_2D.md, Known limits)."""
        return jax.tree_util.tree_map(
            lambda l: jax.lax.with_sharding_constraint(l,
                                                       self.repl_sharding),
            tree)

    # -- flat-model view ----------------------------------------------------
    def flat_spec_of(self, params: Any) -> FlatSpec:
        return FlatSpec.of(params, self.flat_multiple)
