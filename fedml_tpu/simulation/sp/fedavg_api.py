"""Single-process federated simulation — parity with
``FedAvgAPI`` (reference ``python/fedml/simulation/sp/fedavg/fedavg_api.py``),
generalized over every federated optimizer the zoo supports.

Structure parity: per-round client sampling seeded by round
(``_client_sampling``, reference ``:127-137``), local training of each sampled
client, weighted aggregation (``_aggregate``, ``:144``), periodic evaluation
(``_local_test_on_all_clients``, ``:176``).

TPU-native difference: the whole round executes as one jitted program (see
``simulation/round_engine.py``); per-client work is a ``lax.scan``/``vmap``
over the cohort tensor, so wall-clock per round is one XLA dispatch.
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import federated
from ...core import rng as rng_util
from ...core import tree as tree_util
from ...core.compression.blockscale import DEFAULT_BLOCK
from ...core.state import resolve_collective_precision
from ...data.federated_dataset import FederatedDataset
from ...ml.aggregator.agg_operator import ServerOptimizer
from ...ml.trainer.local_trainer import LocalTrainer
from ...mlops import event, log_round_info
from ...obs import get_tracer
from ...obs.carry import obs_host, obs_host_rows, obs_population_rows
from ...obs.jaxhooks import count_put
from ..round_engine import make_round_fn, next_pow2
from ..staging import AsyncCohortStager

log = logging.getLogger(__name__)


class FedAvgAPI:
    """Runs any FedAvg-family optimizer single-host.

    ``client_mode``: "scan" (sequential clients — constant memory) or "vmap"
    (clients batched into the MXU — fastest for small models).
    """

    def __init__(self, args, device, dataset: FederatedDataset, model,
                 client_mode: str = "vmap"):
        self.args = args
        self.device = device
        self.dataset = dataset
        self.model = model
        self.seed = int(getattr(args, "random_seed", 0))
        self.batch_size = int(getattr(args, "batch_size", 10))
        self.epochs = int(getattr(args, "epochs", 1))
        self.comm_rounds = int(getattr(args, "comm_round", 10))
        self.clients_per_round = int(getattr(args, "client_num_per_round", 10))
        self.eval_freq = int(getattr(args, "frequency_of_the_test", 5))

        # fedtrace (ISSUE 4): args.trace turns the global tracer on (file
        # path via args.trace_path); when off every tracer call site below
        # costs a single attribute check
        if bool(getattr(args, "trace", False)):
            from ...obs import configure as _obs_configure
            _obs_configure(enabled=True,
                           path=getattr(args, "trace_path", None))
        self._tracer = get_tracer()
        # fedmon (ISSUE 14, docs/OBSERVABILITY.md): args.health turns on
        # the in-trace per-client stat rows (computed inside the compiled
        # round, flushed at the existing log-round sync) + the host-side
        # anomaly/drift monitor; args.metrics_port serves the live
        # /metrics · /healthz · /debug/health endpoint over it
        self._health = bool(getattr(args, "health", False))
        self.health_monitor = None
        self.metrics_server = None
        if self._health:
            if federated.parse_population(args) is not None:
                raise ValueError(
                    "incompatible flags: health + population — per-client "
                    "health rows are single-experiment (the stat stream "
                    "is keyed by client id, not member)")
            from ...obs.health import HealthMonitor
            self.health_monitor = HealthMonitor.from_args(args)
        if getattr(args, "metrics_port", None) is not None:
            from ...obs.metricsd import start_from_args
            self.metrics_server = start_from_args(
                args, monitor=self.health_monitor)

        self.trainer = self._make_trainer(model, args)
        self.server_opt = ServerOptimizer(args)
        # vmapped experiment population (ISSUE 7, docs/PRIMITIVES.md):
        # args.population / population_axes turn the round into a batch of
        # P hparam variants sharing one dispatch and one staging stream
        self.population = federated.parse_population(args)
        if self.population and \
                type(self).train_one_round is not FedAvgAPI.train_one_round:
            # a subclass with its own round loop would silently mis-handle
            # the (P,)-stacked state/metrics
            raise NotImplementedError(
                f"{type(self).__name__} does not support population vmap "
                "(SP engine only for now — docs/PRIMITIVES.md)")
        # low-precision collective layer (docs/COLLECTIVE_PRECISION.md):
        # resolved against the engine's shard count (the mesh subclass sets
        # n_shards before super().__init__, so "auto" sees the real mesh)
        self.collective_precision = resolve_collective_precision(
            args, getattr(self, "n_shards", 1))
        self.quant_block = int(getattr(args, "quant_block", 0)
                               or DEFAULT_BLOCK)
        # ragged-cohort bucketing (stateless wavg algorithms only)
        from ..round_engine import BUCKETABLE_ALGS
        self._bucketing = bool(getattr(args, "cohort_bucketing", False))
        if self._bucketing and self.server_opt.algorithm not in \
                BUCKETABLE_ALGS:
            raise ValueError(
                f"cohort_bucketing supports {BUCKETABLE_ALGS}, not "
                f"{self.server_opt.algorithm!r}")
        if self._bucketing and self.collective_precision != "fp32":
            # bucket partials merge on host; there is no single in-program
            # merge collective to quantize against one EF buffer
            raise ValueError(
                "collective_precision requires the unbucketed cohort path")
        if self._bucketing and self.population:
            raise ValueError(
                "population vmap needs the unbucketed cohort path (bucket "
                "shapes are data-dependent per member)")
        if self._bucketing and \
                type(self).train_one_round is not FedAvgAPI.train_one_round:
            # a subclass with its own round loop would silently ignore the
            # flag and report unbucketed numbers as bucketed
            raise ValueError(
                f"{type(self).__name__} does not implement cohort_bucketing")
        self._bucket_fn = None
        self._update_from_agg = None
        # round-block fusion (ISSUE 3): K rounds per compiled dispatch
        self._round_block = int(getattr(args, "round_block", 1) or 1)
        if self._round_block > 1:
            if self._bucketing:
                raise ValueError(
                    "round_block fusion needs the unbucketed cohort path "
                    "(bucket partials are data-dependent per round)")
            if type(self).train_one_round is not FedAvgAPI.train_one_round \
                    and type(self)._build_block_fn is FedAvgAPI._build_block_fn:
                # a subclass with its own round loop would silently run the
                # base engine's fused block and skip its logic
                raise ValueError(
                    f"{type(self).__name__} does not implement round_block "
                    "fusion")
        self._client_mode = client_mode
        self._block_fn = None
        self._block_stager: Optional[AsyncCohortStager] = None
        self._ct_ops = None
        key = rng_util.root_key(self.seed)
        params = model.init(rng_util.purpose_key(key, "init"))
        self.state = self._init_server_state(params)
        if self.population:
            # every member starts from the SAME model init; states diverge
            # per member inside the vmapped round as hparams differ
            self.state = federated.stack_member_states(
                self.state, self.population.size)
        # Registered-population sampling (fedstore, docs/CLIENT_STORE.md):
        # the client ID SPACE may exceed the dataset's client count —
        # cohorts sample from ``registered_clients`` ids, per-client STATE
        # is keyed by the full id, and data/weights come from the dataset
        # client ``id % num_clients``.  Default (0) = the historical
        # one-id-per-dataset-client behavior, bitwise unchanged.
        self.registered_clients = (
            int(getattr(args, "registered_clients", 0) or 0)
            or self.dataset.num_clients)
        if self.registered_clients < self.dataset.num_clients:
            raise ValueError(
                f"registered_clients={self.registered_clients} < dataset "
                f"client count {self.dataset.num_clients}")
        self.round_fn = self._build_round_fn(client_mode)
        # Per-client algorithm state (SCAFFOLD control variates c_i / FedDyn
        # lagrangian residuals ∇̂_i) lives DEVICE-resident between rounds as
        # a dense (num_clients, ...) table gathered/scattered by cohort ids
        # inside the compiled program — the old host dict forced a
        # device_get + tree_stack every round (ISSUE 3 tentpole).  With
        # ``args.client_store`` the dense table is replaced by the paged
        # host-side sparse store (fedml_tpu/store): only the active
        # cohort's rows are ever device-resident, page-in overlaps compute
        # through the AsyncCohortStager double buffer, and updated rows
        # write back asynchronously after each round/block.
        self._store = None
        self._pager = None
        self.client_table = None
        if self.server_opt.spec.client_state:
            if bool(getattr(args, "client_store", False)):
                if self.population:
                    raise ValueError(
                        "incompatible flags: client_store pages ONE "
                        "experiment's rows; population/population_axes "
                        "needs the dense member-stacked table")
                self._init_client_store()
            else:
                self.client_table = self._init_client_table()
        if self.population and self.client_table is not None:
            self.client_table = federated.stack_member_states(
                self.client_table, self.population.size)
        # fedstore DATA plane (docs/WIRE.md): with ``args.data_paging`` the
        # cohort EXAMPLE tensors stream through the same LRU+spill pager as
        # client state — host RSS is bounded by the resident page cap, not
        # the dataset, so a 1M-registered multi-host-shaped run pages data
        # as well as state.
        self._data_store = None
        self._data_pager = None
        if bool(getattr(args, "data_paging", False)):
            self._init_data_pager()
        self.metrics_history = []

    #: donate the ServerState buffers into the round (in-place update on
    #: device). Subclasses that call round_fn with states sharing buffers
    #: (hierarchical group loop) must turn this off.
    DONATE_STATE = True

    def _make_trainer(self, model, args) -> LocalTrainer:
        """Trainer factory hook: the mesh subclass swaps in the
        :class:`~..mesh.pipeline.PipelineTrainer` when the mesh carries a
        nontrivial ``stage`` factor (docs/PIPELINE.md)."""
        return LocalTrainer(model, args)

    def _init_server_state(self, params):
        """Initial ServerState; with a quantized collective layer it also
        carries the EF residual row, the fp32 flat master copy, and (int8)
        the broadcast residual.  The mesh subclass overrides the layout."""
        return self.server_opt.init(
            params, collective_precision=self.collective_precision)

    def _build_round_fn(self, client_mode: str):
        donate = (0,) if self.DONATE_STATE else ()
        if self._bucketing:
            # the bucketed round host-stages per-bucket cohorts; don't
            # upload a device-resident dataset copy nothing will read
            return None
        if bool(getattr(self.args, "device_data", True)) \
                and not bool(getattr(self.args, "data_paging", False)):
            # dataset device-resident once; rounds ship only index tensors
            # (data_paging forces the host-staged path — a paged dataset
            # must never be uploaded whole)
            self._dev_x = jnp.asarray(self.dataset.train_x)
            self._dev_y = jnp.asarray(self.dataset.train_y)
            if self.population:
                # P experiments, ONE dispatch: the gather round vmapped
                # over the member axis of (state, table, hparams); cohort
                # tensors broadcast (docs/PRIMITIVES.md)
                from ..round_engine import make_population_round_fn
                return jax.jit(make_population_round_fn(
                    self.trainer, self.server_opt, self._dev_x, self._dev_y,
                    mode=client_mode,
                    collective_precision=self.collective_precision,
                    quant_block=self.quant_block), donate_argnums=donate)
            from ..round_engine import make_gather_round_fn
            return jax.jit(make_gather_round_fn(
                self.trainer, self.server_opt, self._dev_x, self._dev_y,
                mode=client_mode,
                collective_precision=self.collective_precision,
                quant_block=self.quant_block, health=self._health),
                donate_argnums=donate)
        if self.population:
            raise ValueError(
                "population vmap needs the device-gather cohort path "
                "(device_data=True): members share one staged cohort")
        return jax.jit(make_round_fn(
            self.trainer, self.server_opt, mode=client_mode,
            collective_precision=self.collective_precision,
            quant_block=self.quant_block, health=self._health),
            donate_argnums=donate)

    # -- round pieces ------------------------------------------------------
    def _client_sampling(self, round_idx: int) -> np.ndarray:
        return rng_util.sample_clients(self.seed, round_idx,
                                       self.registered_clients,
                                       self.clients_per_round)

    def _data_ids(self, clients) -> np.ndarray:
        """Dataset client ids backing a cohort of REGISTERED ids: identity
        in the historical case, modulo fold when the registered population
        exceeds the dataset's client count (docs/CLIENT_STORE.md)."""
        clients = np.asarray(clients)
        if self.registered_clients == self.dataset.num_clients:
            return clients
        return clients % self.dataset.num_clients

    def _init_client_table(self):
        """Dense per-client state table: row ``c`` is client ``c``'s
        SCAFFOLD c_i / FedDyn ∇̂_i, zero-initialized (the dict semantics'
        ``get(c, zeros)`` default).  The mesh engine overrides this to pad
        the row count and shard the rows over the client axis."""
        self._table_rows = self.registered_clients
        params = self.state.global_params
        if self.population:
            # rows are shaped like ONE member's params; the driver stacks
            # the finished table onto the member axis afterwards
            params = federated.population_member(params, 0)
        return tree_util.client_table_init(params, self._table_rows)

    def _init_client_store(self):
        """Paged sparse host store replacing the dense table
        (fedml_tpu/store, docs/CLIENT_STORE.md): host RSS scales with the
        TOUCHED id set (LRU-capped with spill), not the registered
        population, and the traced round is unchanged — the pager hands
        the round the same cohort-stacked rows the dense gather did."""
        from ...store import ClientStateStore, CohortStatePager
        args = self.args
        self._table_rows = self.registered_clients  # mesh pad sentinel
        row_t = jax.tree_util.tree_map(
            lambda p: np.zeros(p.shape, p.dtype), self.state.global_params)
        self._store = ClientStateStore(
            row_t, self.registered_clients,
            page_size=int(getattr(args, "store_page_size", 256) or 256),
            max_resident_pages=int(getattr(args, "store_max_pages", 0)
                                   or 0),
            spill_dir=getattr(args, "store_spill_dir", None))
        self._pager = CohortStatePager(
            self._store, self._cohort_ids_for,
            depth=int(getattr(args, "staging_depth", 1) or 1),
            stride=self._round_block, limit=self.comm_rounds,
            enabled=bool(getattr(args, "async_staging", True)))

    def _cohort_ids_for(self, round_idx: int) -> np.ndarray:
        """State ids round (or fused block starting at) ``round_idx``
        touches — pure in the round index, so the pager's worker thread
        may page them in ahead of time."""
        if self._round_block > 1:
            k = min(self._round_block, self.comm_rounds - round_idx)
            return np.unique(np.concatenate(
                [self._client_sampling(r)
                 for r in range(round_idx, round_idx + k)]))
        return self._client_sampling(round_idx)

    # -- fedstore data paging (docs/WIRE.md) -------------------------------
    def _init_data_pager(self):
        """Page cohort EXAMPLE tensors through the LRU+spill pager: rows
        are single ``{"x", "y"}`` examples in a read-only
        :class:`~fedml_tpu.store.ClientStateStore` keyed by train index,
        gathered per round by the same :class:`CohortStatePager` that
        pages client state (page-in overlaps compute on its worker
        thread; no write-backs — data is immutable)."""
        from ...store import ClientStateStore, CohortStatePager
        args = self.args
        ds = self.dataset
        row_t = {"x": np.zeros(ds.train_x.shape[1:], ds.train_x.dtype),
                 "y": np.zeros(ds.train_y.shape[1:], ds.train_y.dtype)}
        page = int(getattr(args, "data_page_size", 0) or 0) or \
            int(getattr(args, "store_page_size", 256) or 256)
        self._data_store = ClientStateStore(
            row_t, ds.train_data_num, page_size=page,
            max_resident_pages=int(getattr(args, "data_max_pages", 0)
                                   or 0),
            spill_dir=getattr(args, "data_spill_dir", None))
        # one-time fill in page-sized slices: with a resident-page cap the
        # LRU spills as we go, so peak RSS never holds a second dense copy
        for lo in range(0, ds.train_data_num, page):
            ids = np.arange(lo, min(lo + page, ds.train_data_num),
                            dtype=np.int64)
            self._data_store.scatter(
                ids, {"x": ds.train_x[ids], "y": ds.train_y[ids]})
        self._data_pager = CohortStatePager(
            self._data_store, self._example_ids_for,
            depth=int(getattr(args, "staging_depth", 1) or 1),
            limit=self.comm_rounds,
            enabled=bool(getattr(args, "async_staging", True)))

    def _example_ids_for(self, round_idx: int) -> np.ndarray:
        """Example rows round ``round_idx`` touches — pure in the round
        index (sampling and batch schedules are), so the pager's worker
        thread may page them in ahead of the round."""
        clients = self._client_sampling(round_idx)
        idx, _m, _w = self.dataset.cohort_indices(
            self._data_ids(clients), self.batch_size, self.seed,
            round_idx, self.epochs)
        return np.unique(idx.ravel())

    def _paged_cohort_batches(self, clients, round_idx: int):
        """``dataset.cohort_batches`` values via the example pager: gather
        the round's unique rows once (prefetched pages resident), then fan
        them out to the ``(cohort, steps, batch, ...)`` layout by
        position.  Padding steps carry row-0 values under a zero mask —
        the device-gather path's padding convention."""
        ds = self.dataset
        idx, mask, w = ds.cohort_indices(
            self._data_ids(clients), self.batch_size, self.seed,
            round_idx, self.epochs)
        uniq = np.unique(idx.ravel())
        nxt = round_idx + 1
        rows = self._data_pager.gather(
            round_idx, uniq,
            prefetch=nxt if nxt < self.comm_rounds else None)
        pos = np.searchsorted(uniq, idx.ravel())
        x = np.asarray(rows["x"])[pos].reshape(
            idx.shape + ds.train_x.shape[1:])
        y = np.asarray(rows["y"])[pos].reshape(
            idx.shape + ds.train_y.shape[1:])
        return x, y, mask, w

    def _put_rows(self, rows):
        """Host cohort-row stack -> device (the mesh engine shards the
        leading cohort axis)."""
        return jax.tree_util.tree_map(jnp.asarray, rows)

    def _put_table(self, table):
        """Host mini-table -> device, for the fused-block store path (the
        mesh engine applies its table sharding)."""
        return jax.tree_util.tree_map(jnp.asarray, table)

    def _table_ops(self):
        """Jitted cohort gather/scatter over the client-state table, built
        once per API instance; the scatter donates the old table buffers so
        the update is in-place on device."""
        if self._ct_ops is None:
            gather, scatter = tree_util.cohort_gather, tree_util.cohort_scatter
            if self.population:
                # member-stacked table: one shared cohort id vector indexes
                # every member's rows
                gather = jax.vmap(gather, in_axes=(0, None))
                scatter = jax.vmap(scatter, in_axes=(0, None, 0))
            self._ct_ops = (
                jax.jit(gather),
                jax.jit(scatter, donate_argnums=(0,)))
        return self._ct_ops

    def _gather_c(self, cohort, round_idx=None):
        """Stack the cohort's per-client state rows — an HBM→HBM gather on
        the device table (no host dict, no per-round tree_stack), or a
        host-store page-in + gather when the paged store is enabled (the
        pager prefetches the NEXT round's pages on its worker thread)."""
        if self._pager is not None:
            r = int(round_idx or 0)
            nxt = r + self._round_block
            rows = self._pager.gather(
                r, cohort,
                prefetch=nxt if nxt < self.comm_rounds else None)
            return self._put_rows(rows)
        if self.client_table is None:
            return None
        return self._table_ops()[0](self.client_table, cohort)

    def _scatter_c(self, cohort, new_state_stacked, round_idx=None):
        if new_state_stacked is None:
            return
        if self._pager is not None:
            # asynchronous write-back: the device→host materialization and
            # store scatter run on the pager's writer thread; the next
            # gather drains it before reading
            self._pager.write_back(int(round_idx or 0), cohort,
                                   new_state_stacked)
            return
        if self.client_table is None:
            return
        self.client_table = self._table_ops()[1](self.client_table, cohort,
                                                 new_state_stacked)

    def _train_one_round_bucketed(self, round_idx: int):
        """Ragged-cohort round: clients grouped into pow2 step-count
        buckets, one partial program per bucket, aggregates merged exactly
        (``round_engine.make_bucket_agg_fn``).  Cuts the masked-padding
        compute a single max-steps cohort burns under skewed Dirichlet
        splits; gated to the stateless weighted-average algorithms."""
        from ..round_engine import make_bucket_agg_fn

        clients = self._data_ids(self._client_sampling(round_idx))
        key = rng_util.round_key(rng_util.root_key(self.seed), round_idx)
        per = [self.dataset.client_batches(int(c), self.batch_size, self.seed,
                                           round_idx, self.epochs)
               for c in clients]
        if self._bucket_fn is None:
            self._bucket_fn = jax.jit(make_bucket_agg_fn(
                self.trainer, self.server_opt, mode="vmap"))
            self._update_from_agg = jax.jit(
                self.server_opt.update_from_aggregates)
        # same per-position rng stream as the unbucketed round; one host
        # materialization (per-position np.asarray would be ~C tiny
        # blocking transfers per round)
        rngs_all = np.asarray(jax.random.split(key, len(clients)))
        weights_all = self.dataset.client_sample_counts()[clients].astype(
            np.float32)

        buckets = {}
        for pos, (xb, _) in enumerate(per):
            buckets.setdefault(next_pow2(xb.shape[0]), []).append(pos)

        partials, total_ws, loss_ws, step_sums = [], [], [], []
        for steps, positions in sorted(buckets.items()):
            cb = next_pow2(len(positions))
            x = np.zeros((cb, steps) + per[0][0].shape[1:],
                         self.dataset.train_x.dtype)
            y = np.zeros((cb, steps) + per[0][1].shape[1:],
                         self.dataset.train_y.dtype)
            mask = np.zeros((cb, steps), np.float32)
            w = np.zeros((cb,), np.float32)
            rngs = np.zeros((cb,) + rngs_all[0].shape, rngs_all.dtype)
            for i, pos in enumerate(positions):
                xb, yb = per[pos]
                s = xb.shape[0]
                x[i, :s], y[i, :s], mask[i, :s] = xb, yb, 1.0
                w[i] = weights_all[pos]
                rngs[i] = rngs_all[pos]
            agg, tw, lw, ts = self._bucket_fn(
                self.state, jnp.asarray(x), jnp.asarray(y),
                jnp.asarray(mask), jnp.asarray(w), jnp.asarray(rngs))
            partials.append(agg)
            total_ws.append(tw)
            loss_ws.append(lw)
            step_sums.append(ts)

        merged = self.server_opt.merge_aggregates(partials, total_ws)
        self.state = self._update_from_agg(self.state, merged)
        tw = sum(jnp.asarray(t) for t in total_ws)
        allocated = sum(next_pow2(len(p)) * s for s, p in buckets.items())
        return {"train_loss": sum(loss_ws) / tw,
                "total_steps": sum(step_sums),
                # compiled client-lane slots this round actually allocated
                # (the padding-waste metric bucketing exists to shrink)
                "allocated_steps": allocated}

    def _stage_round_arrays(self, round_idx: int):
        """Gather-mode staged cohort arrays for one round — the index
        tensor, step mask and client weights with steps padded to the
        pow2 class (the PR 2 bounded-recompile contract).  Pure function
        of ``round_idx``; shared by the round loop and the fedverify
        lowering/signature hooks (docs/FEDVERIFY.md)."""
        clients = self._client_sampling(round_idx)
        idx, mask, w = self.dataset.cohort_indices(
            self._data_ids(clients), self.batch_size, self.seed,
            round_idx, self.epochs)
        # pad steps to pow2 buckets → bounded recompile count
        steps = next_pow2(idx.shape[1])
        if steps != idx.shape[1]:
            pad = steps - idx.shape[1]
            idx = np.pad(idx, [(0, 0), (0, pad), (0, 0)])
            mask = np.pad(mask, [(0, 0), (0, pad)])
        return clients, idx, mask, w, steps

    def train_one_round(self, round_idx: int):
        if self._bucketing:
            return self._train_one_round_bucketed(round_idx)
        if hasattr(self, "_dev_x"):
            with self._tracer.span("staging", cat="staging",
                                   round=round_idx):
                clients, idx, mask, w, steps = self._stage_round_arrays(
                    round_idx)
                count_put(self._tracer, (idx, mask, w))
                idx, mask, w = (jnp.asarray(idx), jnp.asarray(mask),
                                jnp.asarray(w))
            key = rng_util.round_key(rng_util.root_key(self.seed),
                                     round_idx)
            cohort = np.asarray(clients, dtype=np.int32)
            c_stacked = self._gather_c(cohort, round_idx=round_idx)
            if self.population:
                self.state, metrics, new_c = self.round_fn(
                    self.state, idx, mask, w, key, c_stacked,
                    self.population.hparams)
            else:
                self.state, metrics, new_c = self.round_fn(
                    self.state, idx, mask, w, key, c_stacked)
        else:
            clients = self._client_sampling(round_idx)
            key = rng_util.round_key(rng_util.root_key(self.seed),
                                     round_idx)
            cohort = np.asarray(clients, dtype=np.int32)
            c_stacked = self._gather_c(cohort, round_idx=round_idx)
            with self._tracer.span("staging", cat="staging",
                                   round=round_idx):
                if self._data_pager is not None:
                    x, y, mask, w = self._paged_cohort_batches(clients,
                                                               round_idx)
                else:
                    x, y, mask, w = self.dataset.cohort_batches(
                        self._data_ids(clients), self.batch_size,
                        self.seed, round_idx, self.epochs)
                steps = next_pow2(x.shape[1])
                if steps != x.shape[1]:
                    pad = steps - x.shape[1]
                    x = np.pad(x,
                               [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                    y = np.pad(y,
                               [(0, 0), (0, pad)] + [(0, 0)] * (y.ndim - 2))
                    mask = np.pad(mask, [(0, 0), (0, pad)])
                count_put(self._tracer, (x, y, mask, w))
                x, y, mask, w = (jnp.asarray(x), jnp.asarray(y),
                                 jnp.asarray(mask), jnp.asarray(w))
            self.state, metrics, new_c = self.round_fn(
                self.state, x, y, mask, w, key, c_stacked)
        self._scatter_c(cohort, new_c, round_idx=round_idx)
        metrics = dict(metrics)
        metrics["allocated_steps"] = len(clients) * steps
        return metrics

    # -- fused round blocks (ISSUE 3 tentpole) -----------------------------
    def _build_block_fn(self):
        """jit of ``round_engine.make_block_round_fn`` over the
        device-resident dataset; ServerState (arg 0) and the client-state
        table (arg 6) are donated so the scan carry updates in place."""
        if not hasattr(self, "_dev_x"):
            raise ValueError(
                "round_block fusion needs the device-gather cohort path "
                "(device_data=True): pre-staging a block is cheap only "
                "when rounds ship index tensors, not data")
        donate = (0, 6) if self.DONATE_STATE else ()
        if self.population:
            # P members × K rounds as ONE dispatch: vmap over the member
            # axis of the fused block scan (metrics stack to (P, K))
            from ..round_engine import make_population_block_fn
            return jax.jit(make_population_block_fn(
                self.trainer, self.server_opt, self._dev_x, self._dev_y,
                mode=self._client_mode,
                collective_precision=self.collective_precision,
                quant_block=self.quant_block), donate_argnums=donate)
        from ..round_engine import make_block_round_fn
        return jax.jit(make_block_round_fn(
            self.trainer, self.server_opt, self._dev_x, self._dev_y,
            mode=self._client_mode,
            collective_precision=self.collective_precision,
            quant_block=self.quant_block, health=self._health),
            donate_argnums=donate)

    def _stage_block(self, start_round: int):
        """Build one block's stacked cohort tensors: every per-round input
        gains a leading round axis of length ``k = min(round_block,
        comm_rounds - start_round)`` (the ragged tail reuses the same
        traced fn as a smaller final block).  Steps pad to the BLOCK-max
        pow2 class so homogeneous blocks hit one compiled program (the
        PR 2 bounded-recompile contract).  Pure function of
        ``start_round`` — safe for the async stager's worker thread."""
        k = min(self._round_block, self.comm_rounds - start_round)
        rounds = range(start_round, start_round + k)
        per = []
        for r in rounds:
            clients = self._client_sampling(r)
            idx, mask, w = self.dataset.cohort_indices(
                self._data_ids(clients), self.batch_size, self.seed, r,
                self.epochs)
            per.append((clients, idx, mask, w))
        steps = next_pow2(max(p[1].shape[1] for p in per))
        n = per[0][1].shape[0]
        idx_blk = np.zeros((k, n, steps, self.batch_size), np.int32)
        mask_blk = np.zeros((k, n, steps), np.float32)
        w_blk = np.zeros((k, n), np.float32)
        cohort_blk = np.zeros((k, n), np.int32)
        for i, (clients, idx, mask, w) in enumerate(per):
            s = idx.shape[1]
            idx_blk[i, :, :s] = idx
            mask_blk[i, :, :s] = mask
            w_blk[i] = w
            cohort_blk[i] = clients
        root = rng_util.root_key(self.seed)
        keys_blk = np.stack([np.asarray(rng_util.round_key(root, r))
                             for r in rounds])
        count_put(self._tracer,
                  (idx_blk, mask_blk, w_blk, keys_blk, cohort_blk))
        return (k, steps, jnp.asarray(idx_blk), jnp.asarray(mask_blk),
                jnp.asarray(w_blk), jnp.asarray(keys_blk),
                jnp.asarray(cohort_blk))

    def train_block(self, start_round: int):
        """Run ``min(round_block, comm_rounds - start_round)`` rounds as
        ONE compiled dispatch.  Returns ``(k, metrics)`` with each metrics
        leaf a stacked ``(k,)`` device array — the caller syncs the whole
        block at once (or not at all)."""
        if self._block_fn is None:
            self._block_fn = self._build_block_fn()
        if self._block_stager is None:
            self._block_stager = AsyncCohortStager(
                self._stage_block,
                enabled=bool(getattr(self.args, "async_staging", True)),
                depth=int(getattr(self.args, "staging_depth", 1) or 1),
                stride=self._round_block, limit=self.comm_rounds)
        nxt = start_round + self._round_block
        k, steps, idx, mask, w, keys, cohort = self._block_stager.get(
            start_round, prefetch=nxt if nxt < self.comm_rounds else None)
        if self.population:
            self.state, metrics, self.client_table = self._block_fn(
                self.state, idx, mask, w, keys, cohort, self.client_table,
                self.population.hparams)
        elif self._pager is not None:
            metrics = self._train_block_store(start_round, idx, mask, w,
                                              keys, cohort)
        else:
            self.state, metrics, self.client_table = self._block_fn(
                self.state, idx, mask, w, keys, cohort, self.client_table)
        metrics = dict(metrics)
        metrics["allocated_steps"] = np.full(
            k, idx.shape[1] * steps, np.int64)
        return k, metrics

    def _train_block_store(self, start_round: int, idx, mask, w, keys,
                           cohort):
        """Fused K-round block against the paged store: the block's
        TOUCHED rows page into a device mini-table whose slot count is the
        block's cohort capacity (a trace-time static, so steady-state
        blocks reuse one compiled program), cohort ids remap to slots, and
        the whole mini-table writes back asynchronously after the ONE
        dispatch — same compiled block the dense table runs, different
        backing plane."""
        cohort_np = np.asarray(cohort)
        sentinel = self._table_rows
        real = np.unique(cohort_np)
        real = real[real < sentinel]
        shards = int(getattr(self, "n_shards", 1))
        n_slots = -(-cohort_np.size // shards) * shards
        local = np.searchsorted(real, cohort_np)
        local = np.where(cohort_np < sentinel, local, n_slots).astype(
            np.int32).reshape(cohort_np.shape)
        nxt = start_round + self._round_block
        rows = self._pager.gather(
            start_round, real,
            prefetch=nxt if nxt < self.comm_rounds else None)
        mini = jax.tree_util.tree_map(
            lambda r: np.concatenate(
                [r, np.zeros((n_slots - r.shape[0],) + r.shape[1:],
                             r.dtype)]), rows)
        self.state, metrics, table = self._block_fn(
            self.state, idx, mask, w, keys, jnp.asarray(local),
            self._put_table(mini))
        # padded id vector (fixed length, sentinel-dropped writes) so the
        # write-back path never shape-specializes on the touched-row count
        ids = np.full(n_slots, self.registered_clients, np.int64)
        ids[:len(real)] = real
        self._pager.write_back(start_round, ids, table)
        return metrics

    # -- fedverify hooks (ISSUE 10, docs/FEDVERIFY.md) ---------------------
    def lowerable_programs(self):
        """Every ``(kind, fn, args, donate)`` this engine can stage at
        its current config — the Program registry's engine surface
        (``analysis/programs.py``, ISSUE 18).  Callers iterate THIS one
        list; the per-kind hooks below are its implementation."""
        from ...analysis import programs as program_registry
        return program_registry.lowerable(self)

    def round_program(self, round_idx: int = 0):
        """Expose the exact jitted round program + one round's staged
        arguments + the donated argnums, so ``analysis/fedverify.py`` can
        AOT-lower it on abstract shapes (no step runs).  Gather-mode
        (device-resident data) only — the same precondition the fused
        block has."""
        if self._bucketing or not hasattr(self, "_dev_x"):
            raise NotImplementedError(
                "fedverify lowers the device-gather round program "
                "(device_data=True, cohort_bucketing off)")
        clients, idx, mask, w, _ = self._stage_round_arrays(round_idx)
        key = rng_util.round_key(rng_util.root_key(self.seed), round_idx)
        cohort = np.asarray(clients, dtype=np.int32)
        c_stacked = self._gather_c(cohort, round_idx=round_idx)
        args = (self.state, jnp.asarray(idx), jnp.asarray(mask),
                jnp.asarray(w), key, c_stacked)
        if self.population:
            args = args + (self.population.hparams,)
        return self.round_fn, args, (0,) if self.DONATE_STATE else ()

    def round_signature(self, round_idx: int) -> str:
        """jit-cache signature of one round's staged cohort inputs —
        the jit keys on (shape, dtype) per leaf, so the distinct set of
        these strings over a run IS the program's recompile surface
        (fedverify contract 5; PR 2 pinned it dynamically, this pins it
        statically)."""
        _, idx, mask, w, steps = self._stage_round_arrays(round_idx)
        return repr([(a.shape, str(a.dtype)) for a in (idx, mask, w)])

    def block_program(self, start_round: int = 0):
        """:meth:`round_program` for the fused ``round_block`` scan."""
        if self._block_fn is None:
            self._block_fn = self._build_block_fn()
        k, steps, idx, mask, w, keys, cohort = self._stage_block(
            start_round)
        args = (self.state, idx, mask, w, keys, cohort, self.client_table)
        if self.population:
            args = args + (self.population.hparams,)
        return self._block_fn, args, (0, 6) if self.DONATE_STATE else ()

    def block_signature(self, start_round: int) -> str:
        k, steps, idx, mask, w, keys, cohort = self._stage_block(
            start_round)
        return repr([(a.shape, str(a.dtype))
                     for a in (idx, mask, w, keys, cohort)])

    def evaluate(self):
        with self._tracer.span("eval", cat="eval"):
            xb, yb, mb = self.dataset.test_batches()
            if self.population:
                # one vmapped dispatch scores every member; the scalar
                # return keeps the driver/record surface unchanged while
                # the per-member arrays land on ``member_eval``
                losses, accs = self.trainer.evaluate_members(
                    self.state.global_params, xb, yb, mb)
                self.member_eval = {"loss": losses, "acc": accs}
                return float(losses.mean()), float(accs.mean())
            return self.trainer.evaluate(self.state.global_params, xb, yb,
                                         mb)

    def _per_client_eval_fn(self):
        """Compiled all-clients eval program, built once per API instance
        (a per-call ``@jax.jit`` closure would re-trace every call — the
        jit cache is keyed on the function object)."""
        if getattr(self, "_pc_eval", None) is not None:
            return self._pc_eval
        eval_step = self.trainer.make_eval_step()

        @jax.jit
        def run(params, X, Y, M):
            def per_client(_, batches):
                xb, yb, mb = batches

                def body(carry, b):
                    l, c, n = eval_step(params, *b)
                    return (carry[0] + l, carry[1] + c, carry[2] + n), None

                (l, c, n), _ = jax.lax.scan(
                    body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())),
                    (xb, yb, mb))
                n = jnp.maximum(n, 1.0)
                return None, (l / n, c / n)

            _, (losses, accs) = jax.lax.scan(per_client, None, (X, Y, M))
            return losses, accs

        self._pc_eval = run
        return run

    def evaluate_per_client(self, split: str = "train", batch_size: int = 64):
        """Reference ``_local_test_on_all_clients`` (``fedavg_api.py:176``):
        the global model scored on every client's LOCAL data.  One compiled
        program evaluates all clients (padded to a common shape and scanned),
        instead of the reference's per-client eager loops.  Returns per-client
        accuracy plus the fairness aggregates the FL literature reports
        (mean / std / min / 10th percentile).

        ``split="test"`` uses the natural per-client test partition when the
        dataset has one (LEAF), else falls back to the train split."""
        clients, X, Y, M = self.dataset.pack_per_client(batch_size, split)
        run = self._per_client_eval_fn()
        losses, accs = run(self.state.global_params, jnp.asarray(X),
                           jnp.asarray(Y), jnp.asarray(M))
        accs = np.asarray(accs)
        return {
            "per_client_acc": accs,
            "per_client_loss": np.asarray(losses),
            "acc_mean": float(accs.mean()),
            "acc_std": float(accs.std()),
            "acc_min": float(accs.min()),
            "acc_p10": float(np.percentile(accs, 10)),
        }

    # -- checkpoint / resume (core capability the reference lacks; §5) -----
    def _checkpointer(self):
        ckpt_dir = getattr(self.args, "checkpoint_dir", None)
        if not ckpt_dir:
            return None
        if not hasattr(self, "_ckpt"):
            codec = str(getattr(self.args, "checkpoint_codec", "orbax")
                        or "orbax").lower()
            keep = int(getattr(self.args, "checkpoint_keep", 3))
            if codec == "wire":
                # fedwire-unified checkpoints (docs/WIRE.md): the same
                # codec that frames wire messages writes the round files
                from ...core.checkpoint import WireCheckpointer
                self._ckpt = WireCheckpointer(ckpt_dir, keep)
            else:
                from ...core.checkpoint import RoundCheckpointer
                self._ckpt = RoundCheckpointer(ckpt_dir, keep)
        return self._ckpt

    def maybe_resume(self) -> int:
        """Restore latest checkpoint if present; returns start round."""
        ckpt = self._checkpointer()
        if ckpt is None or ckpt.latest_round() is None:
            return 0
        state, client_state = ckpt.restore(
            template=(self.state,
                      self._store if self._store is not None
                      else self.client_table))
        self.state = state
        if self.client_table is not None and client_state is not None \
                and client_state is not self._store:
            self.client_table = client_state
        return int(ckpt.latest_round()) + 1

    def maybe_checkpoint(self, round_idx: int, window: int = 1):
        """Checkpoint when any round in ``[round_idx - window + 1,
        round_idx]`` hits the frequency (fused blocks checkpoint at block
        granularity: the state only exists at block boundaries)."""
        ckpt = self._checkpointer()
        if ckpt is None:
            return
        freq = int(getattr(self.args, "checkpoint_freq", 10))
        due = (round_idx == self.comm_rounds - 1
               or any((round_idx - j) % freq == 0 for j in range(window)))
        if due:
            if self._pager is not None:
                # a checkpoint must capture every completed round's rows
                self._pager.drain_writebacks()
            ckpt.save(round_idx, self.state,
                      self._store if self._store is not None
                      else self.client_table)

    def _observe_health(self, round_idx: int, metrics: dict, dt: float):
        """Feed one round's materialized per-client stat rows to the
        fedmon monitor (docs/OBSERVABILITY.md).  ``health_clients`` (the
        async engine's slot→client map) wins over the round sampling;
        stats arrays may be cohort-padded — the monitor trims to the id
        list and drops weight-0 rows."""
        ids = metrics.get("health_clients")
        if ids is None:
            ids = self._client_sampling(round_idx)
        self.health_monitor.observe_round(
            round_idx, np.asarray(ids),
            {f: np.asarray(v) for f, v in metrics["health"].items()},
            round_time_s=dt)

    # -- main loop (reference fedavg_api.py:66 train) ----------------------
    def _is_log_round(self, round_idx: int) -> bool:
        return (round_idx % self.eval_freq == 0
                or round_idx == self.comm_rounds - 1)

    def _flush_round_records(self, pending):
        """Materialize deferred per-round metrics into host records.  The
        ``float()`` here is the ONE device→host sync point for every round
        since the last flush — between flushes the device queue stays full
        (the old loop's per-round blocking ``float(train_loss)`` serialized
        host and device; ISSUE 3 satellite)."""
        while pending:
            round_idx, metrics, dt = pending.pop(0)
            member_losses = None
            if self.population:
                # (P,) member losses: ONE materialization, then host math
                member_losses = np.asarray(metrics["train_loss"])
                train_loss = float(member_losses.mean())
            else:
                train_loss = float(metrics["train_loss"])
            if self._tracer.enabled and isinstance(metrics, dict) \
                    and metrics.get("obs") is not None:
                # piggyback the existing sync: the float() above already
                # blocked on this round's program, so materializing the
                # device-carry scalars here adds no new sync point
                if self.population:
                    self._tracer.round_obs(round_idx, dt, obs_population_rows(
                        metrics["obs"], member_losses)[0])
                else:
                    self._tracer.round_obs(round_idx, dt,
                                           obs_host(metrics["obs"]))
            if self.health_monitor is not None and isinstance(metrics, dict) \
                    and metrics.get("health") is not None:
                # fedmon: the float() above already synced this round's
                # program, so materializing the per-client stat rows here
                # adds no new sync point; the sampled ids are a pure
                # function of the round index (or the async engine's
                # explicit slot→client map)
                self._observe_health(round_idx, metrics, dt)
            record = {"round": round_idx, "train_loss": train_loss,
                      "round_time": dt,
                      "dataset_provenance": getattr(self.dataset,
                                                    "provenance", "unknown")}
            if member_losses is not None:
                record.update(
                    members=self.population.size,
                    member_train_loss_best=float(member_losses.min()),
                    member_train_loss_worst=float(member_losses.max()))
            if self._is_log_round(round_idx):
                # flush is called AT the log round, so self.state is this
                # round's state and the eval matches the old cadence
                test_loss, test_acc = self.evaluate()
                record.update(test_loss=test_loss, test_acc=test_acc)
                log.info("round %d: train_loss=%.4f test_acc=%.4f (%.2fs)",
                         round_idx, train_loss, test_acc,
                         record["round_time"])
            log_round_info(round_idx, record)
            self.metrics_history.append(record)

    def _train_fused(self, start_round: int):
        """Fused driver: ``round_block`` rounds per dispatch, one host sync
        per block (the stacked ``(k,)`` metrics), cohorts for block ``b+1``
        staged on the worker thread while block ``b`` runs."""
        r = start_round
        while r < self.comm_rounds:
            event("train", started=True, round_idx=r)
            t0 = time.time()
            with self._tracer.span("block", cat="round", start_round=r):
                k, ms = self.train_block(r)
                # ONE sync per block: materializing the stacked losses
                # waits for the whole block's compiled program
                losses = np.asarray(ms["train_loss"])
            block_dt = time.time() - t0
            event("train", started=False, round_idx=r)
            member_losses = None
            if self.population:
                member_losses = losses          # (P, k)
                losses = member_losses.mean(axis=0)
            if self._tracer.enabled and ms.get("obs") is not None:
                # stacked (k,) device-carry rows ride the block's ONE sync
                rows = (obs_population_rows(ms["obs"], member_losses)
                        if self.population else obs_host_rows(ms["obs"]))
                for j, row in enumerate(rows):
                    self._tracer.round_obs(r + j, block_dt / k, row)
            if self.health_monitor is not None and \
                    ms.get("health") is not None:
                # fedmon: the (K, C) stat rows ride the block's one sync;
                # one observe per round, ids re-derived from the sampling
                h_np = {f: np.asarray(v) for f, v in ms["health"].items()}
                for j in range(k):
                    self.health_monitor.observe_round(
                        r + j, self._client_sampling(r + j),
                        {f: v[j] for f, v in h_np.items()},
                        round_time_s=block_dt / k)
            eval_due = any(self._is_log_round(ri) for ri in range(r, r + k))
            for j in range(k):
                ri = r + j
                record = {"round": ri, "train_loss": float(losses[j]),
                          "round_time": block_dt / k,
                          "dataset_provenance": getattr(
                              self.dataset, "provenance", "unknown")}
                if member_losses is not None:
                    record.update(
                        members=self.population.size,
                        member_train_loss_best=float(
                            member_losses[:, j].min()),
                        member_train_loss_worst=float(
                            member_losses[:, j].max()))
                if j == k - 1 and eval_due:
                    test_loss, test_acc = self.evaluate()
                    record.update(test_loss=test_loss, test_acc=test_acc)
                    log.info(
                        "round %d: train_loss=%.4f test_acc=%.4f "
                        "(block of %d, %.2fs)", ri, record["train_loss"],
                        test_acc, k, block_dt)
                log_round_info(ri, record)
                self.metrics_history.append(record)
            self.maybe_checkpoint(r + k - 1, window=k)
            r += k

    def train(self):
        t_start = time.time()
        start_round = self.maybe_resume()
        if self._tracer.enabled and \
                bool(getattr(self.args, "trace_device", False)):
            # fedscope measured device time (docs/OBSERVABILITY.md): one
            # out-of-band per-phase probe BEFORE the round loop — its own
            # compiles/syncs never touch the steady-state path, and its
            # device.<phase>_s counters replace the FLOP proxy downstream
            from ...obs.devicetime import measure_device_phases
            try:
                measure_device_phases(
                    self, round_idx=start_round,
                    profile_dir=getattr(self.args, "trace_profile_dir",
                                        None))
            except Exception:
                log.warning("trace_device probe failed; keeping the "
                            "FLOP-proxy attribution", exc_info=True)
        if self._round_block > 1:
            self._train_fused(start_round)
        else:
            pending = []
            for round_idx in range(start_round, self.comm_rounds):
                event("train", started=True, round_idx=round_idx)
                t0 = time.time()
                with self._tracer.span("round", cat="round",
                                       round=round_idx):
                    metrics = self.train_one_round(round_idx)
                event("train", started=False, round_idx=round_idx)
                pending.append((round_idx, metrics, time.time() - t0))
                if self._is_log_round(round_idx):
                    self._flush_round_records(pending)
                self.maybe_checkpoint(round_idx)
            self._flush_round_records(pending)
        total = time.time() - t_start
        if self._pager is not None:
            # the training loop is done: make the store consistent with the
            # final round before anyone reads/checkpoints it
            self._pager.drain_writebacks()
            log.info("fedstore: %s", self._pager.stats())
        if self._data_pager is not None:
            log.info("fedstore data plane: %s", self._data_pager.stats())
        log.info("finished %d rounds in %.1fs (%.3fs/round)",
                 self.comm_rounds, total, total / max(self.comm_rounds, 1))
        if self._tracer.enabled and self._tracer.path:
            # args.trace_path contract: the YAML user gets the Chrome
            # trace on disk without touching the tracer API
            self._tracer.export_chrome()
            log.info("fedtrace: wrote %s (analyze with tools/fedtrace.py)",
                     self._tracer.path)
        return self.state.global_params
