"""FedBuffAPI — buffered-async federated aggregation (docs/ASYNC.md).

Every other engine in this repo is synchronous: one straggler gates the
round.  This driver implements FedBuff-style buffered asynchrony (Nguyen
et al., "Federated Learning with Buffered Asynchronous Aggregation") on
the PR 7 round algebra:

- clients launch in **dispatch generations** (one staged cohort per
  generation — bitwise the sync engine's staging) against a
  **versioned** ``ServerState``; client compute runs lazily against the
  generation's dispatch-version state snapshot, so a dropped client
  costs nothing;
- each completed update lands, at its simulated arrival time, in a
  size-K on-device row buffer with staleness-discounted weight
  ``s(τ) = 1/(1+τ)^α`` (τ = server versions elapsed since dispatch;
  ``core/federated.py`` buffer algebra);
- the moment occupancy hits K the server finishes the buffer with the
  spec's own stacked reductions and runs the unchanged
  ``ServerOptimizer`` transition — one apply == one logical "round" of
  the inherited driver loop, so eval cadence / checkpointing / metrics
  history all work untouched.

**Atomic-cohort fast path.**  When an entire fresh generation is about
to land in an empty buffer with zero staleness and K == cohort size (the
zero-latency regime, and the common case under light tails), the buffer
degenerates to exactly one synchronous round — so the driver detects it
host-side and runs the inherited sync ``round_fn`` on the generation's
staged cohort: one dispatch instead of K buffer adds, and the
bounded-staleness parity contract becomes BITWISE by construction (the
async engine literally executes the sync engine's compiled program).

Zero-recompile contract: buffer occupancy, per-row staleness, discount
weights and the model-version tag are all traced DATA (the adapter-bank
trick — scatter at a traced slot vector with the out-of-bounds padding
sentinel), so steady state runs a fixed program set (dispatch /
buffer-add / buffer-apply / fast-path round) no matter how arrivals
interleave (pinned by tests/test_async_engine.py).

Client arrivals come from the event-driven virtual-clock simulator
(``simulation/async_sim.py``): heavy-tailed latency, persistent
stragglers, dropout.  The virtual clock is the wall-clock the bench's
to-target-accuracy rows compare (``bench.py --async``).

Per-client algorithm state (SCAFFOLD c_i / FedDyn residuals) gathers at
DISPATCH (the rows the client actually trained from) and writes back at
ARRIVAL — with ``args.client_store`` both sides run through the paged
``ClientStateStore``/pager in arrival order, so million-registered async
runs page state exactly like the sync engine does.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import federated
from ..core import rng as rng_util
from .async_sim import ArrivalSimulator
from .round_engine import make_run_clients
from .sp.fedavg_api import FedAvgAPI

log = logging.getLogger(__name__)


class _Generation:
    """One in-flight dispatch generation.

    Holds the staged cohort call (host/device inputs + the dispatch-time
    ``ServerState`` reference) and, once the first arrival needs it, the
    lazily computed per-client update rows.  Kept until every arrival has
    been consumed or dropped."""

    __slots__ = ("state", "args", "cohort", "rows", "new_c", "remaining",
                 "version")

    def __init__(self, state, args, cohort, remaining, version):
        self.state = state          # dispatch-version ServerState
        self.args = args            # (idx, mask, w, key, c_stacked)
        self.cohort = cohort
        self.rows = None            # lazily computed update rows
        self.new_c = None
        self.remaining = remaining
        self.version = version


class FedBuffAPI(FedAvgAPI):
    """Buffered-async driver over any registered AlgorithmSpec.

    ``federated_optimizer: fedbuff`` selects this engine;
    ``args.async_base_optimizer`` (default ``fedavg``) picks the
    underlying spec + server transition.  One logical round of the
    inherited loop == one buffer apply.
    """

    #: generations may reference older ServerStates (the dispatch
    #: snapshot a straggler trained from), so no program may donate them
    DONATE_STATE = False

    #: dispatches allowed without completing one apply before the driver
    #: declares the configuration unable to make progress (dropout ~ 1)
    MAX_DISPATCHES_PER_APPLY = 64

    def __init__(self, args, device, dataset, model,
                 client_mode: str = "vmap"):
        base = str(getattr(args, "async_base_optimizer", "") or "fedavg")
        if str(getattr(args, "federated_optimizer",
                       "fedbuff")).lower() == "fedbuff":
            args.federated_optimizer = base
        if int(getattr(args, "round_block", 1) or 1) > 1:
            raise ValueError(
                "incompatible flags: fedbuff + round_block — applies are "
                "event-driven, there is no K-round lockstep scan to fuse")
        if bool(getattr(args, "cohort_bucketing", False)):
            raise ValueError(
                "incompatible flags: fedbuff + cohort_bucketing (the "
                "buffer is one fixed-shape virtual cohort)")
        super().__init__(args, device, dataset, model, client_mode)
        if self.collective_precision != "fp32":
            raise ValueError(
                "fedbuff buffers fp32 update rows; collective_precision "
                "must stay 'fp32'")
        if not hasattr(self, "_dev_x"):
            raise ValueError(
                "fedbuff needs the device-gather cohort path "
                "(device_data=True): generations ship index tensors")
        self.buffer_k = (int(getattr(args, "async_buffer_k", 0) or 0)
                         or self.clients_per_round)
        self.async_alpha = float(getattr(args, "async_alpha", 0.5))
        self.max_staleness = int(getattr(args, "async_max_staleness", 0)
                                 or 0)
        self.inflight_gens = max(1, int(
            getattr(args, "async_inflight_gens", 1) or 1))
        self.fastpath = bool(getattr(args, "async_fastpath", True))
        self.sim = ArrivalSimulator(
            seed=self.seed,
            latency_median_s=float(
                getattr(args, "async_latency_median_s", 0.0) or 0.0),
            latency_sigma=float(
                getattr(args, "async_latency_sigma", 1.5) or 1.5),
            dropout=float(getattr(args, "async_dropout", 0.0) or 0.0),
            speed_sigma=float(
                getattr(args, "async_speed_sigma", 0.0) or 0.0),
            unavailable_p=float(
                getattr(args, "async_unavailable_p", 0.0) or 0.0),
            unavailable_mean_s=float(
                getattr(args, "async_unavailable_mean_s", 0.0) or 0.0))
        self._dispatch_fn = self._build_dispatch_fn()
        self._add_fn = jax.jit(federated.update_buffer_add,
                               donate_argnums=(0,))
        self._apply_fn = self._build_apply_fn()
        self._row_fn = None          # traced single-row client-state pick
        self.buffer = None           # built lazily from the rows template
        self._gens: Dict[int, _Generation] = {}
        self._next_gen = 0
        self._version = 0
        self._occ_host = 0           # host mirror of traced occupancy
        # fedmon: host mirror of which client landed in each buffer slot
        # (the apply's per-slot health lanes pair with these ids)
        self._slot_clients = np.zeros(self.buffer_k, np.int64)
        self._staleness_window: list = []
        self.updates_dropped = 0
        self.clients_dispatched = 0
        self.updates_buffered = 0
        self.fastpath_applies = 0

    # -- compiled programs --------------------------------------------------
    def _build_dispatch_fn(self):
        """One generation's client phase: gather the cohort from the
        device-resident dataset, run every client's local pass from the
        generation's dispatch-version params, and return the spec's
        per-client UNREDUCED aggregate rows + loss/steps lanes."""
        spec = self.server_opt.spec
        server_opt = self.server_opt
        run_clients = make_run_clients(self.trainer, server_opt,
                                       self._client_mode)
        dev_x, dev_y = self._dev_x, self._dev_y

        health = self._health
        floors = self.health_monitor.config if health else None

        def dispatch_fn(state, idx, mask, w, key, c_stacked):
            x = jnp.take(dev_x, idx, axis=0)
            y = jnp.take(dev_y, idx, axis=0)
            rngs = jax.random.split(key, mask.shape[0])
            outs = run_clients(state, x, y, mask, rngs, c_stacked)
            rows = federated.client_update_rows(spec, server_opt, state,
                                                outs, w)
            # metrics lanes ride the same buffer: the apply's train_loss
            # is the staleness-weighted mean of the K landed updates
            rows["__loss"] = {"src": outs.loss,
                              "w": jnp.asarray(w, jnp.float32)}
            rows["__steps"] = {"src": jnp.asarray(outs.num_steps,
                                                  jnp.float32)}
            if health:
                # fedmon (ISSUE 14): per-client stat rows evaluated at
                # DISPATCH against the generation's own cohort — the
                # reference direction is the generation's weighted-mean
                # delta (no post-apply params exist yet); rows land in
                # the buffer like every other lane, staleness joins at
                # apply from the buffer's tau lane
                h = federated.client_health_stats(
                    state.global_params, outs.params,
                    federated.cohort_mean_delta(state.global_params,
                                                outs.params, w),
                    outs.loss, w)
                # cosine and loss_delta are relative to THIS generation's
                # mean, so they are standardised against this generation
                # too; the monitor takes the z lanes as they come
                h["z_cosine"] = federated.cohort_robust_z(
                    h["cosine"], w, floors.cosine_floor)
                h["z_loss_delta"] = federated.cohort_robust_z(
                    h["loss_delta"], w, floors.loss_floor)
                rows["__health"] = h
            return rows, outs.new_client_state

        return jax.jit(dispatch_fn)

    def _build_apply_fn(self):
        spec = self.server_opt.spec
        server_opt = self.server_opt
        health = self._health

        def apply_fn(state, buf):
            new_state, agg, fresh = federated.update_buffer_apply(
                spec, server_opt, state, buf)
            e = buf["rows"]["__loss"]
            eff = buf["s"] * e["w"]
            metrics = {
                "train_loss": jnp.sum(e["src"] * eff)
                / jnp.maximum(jnp.sum(eff), 1e-12),
                "total_steps": jnp.sum(buf["rows"]["__steps"]["src"]),
                "staleness_mean": jnp.sum(buf["tau"])
                / jnp.maximum(buf["occupancy"], 1.0),
                "staleness_max": jnp.max(buf["tau"]),
                "buffer_occupancy": buf["occupancy"],
                "model_version": buf["version"],
            }
            if health:
                # per-slot stat lanes landed at arrival + the buffer's own
                # staleness lane; the driver pairs them with its host-side
                # slot→client map
                h = buf["rows"]["__health"]
                metrics["health"] = dict(h, staleness=buf["tau"])
            return new_state, metrics, fresh

        # the buffer is donated (reset in place every apply); the state is
        # NOT — in-flight generations may still reference it
        return jax.jit(apply_fn, donate_argnums=(1,))

    def _pick_row_fn(self):
        """Traced single-row pick from a generation's stacked client-state
        outputs (slot is DATA — one compiled program for every lane)."""
        if self._row_fn is None:
            def pick(tree, slot):
                return jax.tree_util.tree_map(
                    lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1,
                                                           axis=0), tree)
            self._row_fn = jax.jit(pick)
        return self._row_fn

    # -- dispatch / arrival machinery ---------------------------------------
    def _dispatch_generation(self):
        g = self._next_gen
        self._next_gen += 1
        with self._tracer.span("async.dispatch", cat="round", gen=g,
                               version=self._version):
            clients, idx, mask, w, _steps = self._stage_round_arrays(g)
            key = rng_util.round_key(rng_util.root_key(self.seed), g)
            cohort = np.asarray(clients, dtype=np.int32)
            # per-client algorithm state as of DISPATCH (what the client
            # trains from); pages in through the store pager when enabled
            c_stacked = self._gather_c(cohort, round_idx=g)
            args = (jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(w),
                    key, c_stacked)
        self._gens[g] = _Generation(self.state, args, cohort, len(cohort),
                                    self._version)
        self.sim.dispatch(g, self._version, clients)
        self.clients_dispatched += len(cohort)
        return g

    def _maybe_dispatch(self):
        while len(self._gens) < self.inflight_gens:
            self._dispatch_generation()

    def _ensure_rows(self, gen: _Generation):
        """Run the generation's client phase (once) against its dispatch
        snapshot — lazy, so a fully-dropped generation never computes."""
        if gen.rows is None:
            idx, mask, w, key, c_stacked = gen.args
            gen.rows, gen.new_c = self._dispatch_fn(gen.state, idx, mask,
                                                    w, key, c_stacked)
            if self.buffer is None:
                self.buffer = federated.update_buffer_zeros(
                    self.server_opt.spec, gen.rows, self.buffer_k)
                self.buffer["version"] = jnp.asarray(
                    float(self._version), jnp.float32)
        return gen.rows

    def _writeback_arrival(self, gen: _Generation, ev):
        """Arrival-order write-back of one client's new algorithm state —
        through the paged store when enabled, else the dense table."""
        if gen.new_c is None:
            return
        row = self._pick_row_fn()(gen.new_c, jnp.asarray(ev.slot,
                                                         jnp.int32))
        ids = np.asarray([ev.client], np.int64)
        if self._pager is not None:
            self._pager.write_back(self._version, ids, row)
        elif self.client_table is not None:
            self.client_table = self._table_ops()[1](
                self.client_table, np.asarray(ids, np.int32), row)

    def _process_arrival(self, ev) -> bool:
        """Land one arrival in the buffer (or drop it).  Returns True when
        a row actually landed."""
        gen = self._gens[ev.gen]
        gen.remaining -= 1
        try:
            tau = self._version - ev.version
            if ev.dropped or (self.max_staleness
                              and tau > self.max_staleness):
                self.updates_dropped += 1
                return False
            self._ensure_rows(gen)
            k = self.buffer_k
            idx = np.zeros(k, np.int32)
            slots = np.full(k, k, np.int32)      # padding sentinel
            s = np.zeros(k, np.float32)
            taus = np.zeros(k, np.float32)
            idx[0] = ev.slot
            slots[0] = self._occ_host
            s[0] = float((1.0 + tau) ** (-self.async_alpha))
            taus[0] = float(tau)
            with self._tracer.span("async.arrival", cat="round",
                                   client=ev.client, staleness=tau,
                                   latency_s=round(ev.latency_s, 6)):
                self.buffer = self._add_fn(self.buffer, gen.rows, idx,
                                           slots, s, taus)
            self._slot_clients[slots[0]] = ev.client
            self._occ_host += 1
            self.updates_buffered += 1
            self._staleness_window.append(tau)
            self._writeback_arrival(gen, ev)
            return True
        finally:
            if gen.remaining <= 0:
                del self._gens[ev.gen]   # frees the generation's buffers

    # -- the atomic-cohort fast path ----------------------------------------
    def _atomic_cohort(self, ev) -> Optional[_Generation]:
        """Detect the degenerate-buffer case: the popped arrival plus the
        next K-1 queued events are exactly one untouched, zero-staleness
        generation filling the empty buffer.  Then the apply == one
        synchronous round over that generation's staged cohort, and the
        driver runs the inherited sync ``round_fn`` instead of K buffer
        adds (bitwise the sync engine, and one dispatch instead of K)."""
        if not self.fastpath or self._occ_host != 0:
            return None
        gen = self._gens.get(ev.gen)
        if gen is None or gen.rows is not None:
            return None
        k = self.buffer_k
        if gen.version != self._version or len(gen.cohort) != k:
            return None
        if ev.dropped or ev.slot != 0 or gen.remaining != k:
            return None
        nxt = self.sim.peek_next(k - 1)
        if len(nxt) != k - 1:
            return None
        slots = sorted(e.slot for e in nxt)
        if any(e.gen != ev.gen or e.dropped for e in nxt) \
                or slots != list(range(1, k)):
            return None
        return gen

    def _apply_fastpath(self, gen: _Generation, ev):
        """Consume the whole generation's arrivals and run the sync round
        program on its staged cohort."""
        for _ in range(self.buffer_k - 1):
            e2 = self.sim.next_arrival()
            assert e2 is not None and e2.gen == ev.gen
        idx, mask, w, key, c_stacked = gen.args
        self.state, metrics, new_c = self.round_fn(self.state, idx, mask,
                                                   w, key, c_stacked)
        self._scatter_c(gen.cohort, new_c, round_idx=self._version)
        del self._gens[ev.gen]
        self.updates_buffered += self.buffer_k
        self._staleness_window.extend([0] * self.buffer_k)
        self.fastpath_applies += 1
        metrics = dict(metrics)
        metrics.update(
            staleness_mean=0.0, staleness_max=0.0,
            buffer_occupancy=float(self.buffer_k),
            model_version=float(self._version))
        if self._health and metrics.get("health") is not None:
            # the sync round's stat rows are in cohort order with zero
            # staleness by construction
            metrics["health"] = dict(
                metrics["health"],
                staleness=np.zeros(self.buffer_k, np.float32))
            metrics["health_clients"] = np.asarray(gen.cohort, np.int64)
        return metrics

    # -- the driver round ---------------------------------------------------
    def train_one_round(self, round_idx: int):
        """Advance the event loop until ONE buffer apply happens.  The
        inherited ``train()`` loop, eval cadence, metrics flush and
        checkpointing drive this exactly like a synchronous round."""
        dispatches_at_entry = self._next_gen
        metrics = None
        while metrics is None:
            self._maybe_dispatch()
            ev = self.sim.next_arrival()
            if ev is None:
                if self._next_gen - dispatches_at_entry > \
                        self.MAX_DISPATCHES_PER_APPLY:
                    raise RuntimeError(
                        "fedbuff cannot fill its buffer (every arrival "
                        "dropped?); check async_dropout/async_max_"
                        "staleness")
                continue
            gen = self._atomic_cohort(ev)
            if gen is not None:
                metrics = self._apply_fastpath(gen, ev)
                break
            self._process_arrival(ev)
            if self._occ_host >= self.buffer_k:
                self.state, metrics, self.buffer = self._apply_fn(
                    self.state, self.buffer)
                self._occ_host = 0
                if self._health:
                    metrics = dict(metrics)
                    metrics["health_clients"] = self._slot_clients.copy()
        self._version += 1
        metrics = dict(metrics)
        window = self._staleness_window
        self._staleness_window = []
        p50 = float(np.percentile(window, 50)) if window else 0.0
        p99 = float(np.percentile(window, 99)) if window else 0.0
        if self._tracer.enabled:
            self._tracer.counter("async.buffer_occupancy", self.buffer_k)
            self._tracer.counter("async.staleness_p50", p50)
            self._tracer.counter("async.staleness_p99", p99)
            self._tracer.counter("async.updates_dropped",
                                 self.updates_dropped)
            self._tracer.counter("async.sim_time_s",
                                 round(self.sim.now, 6))
        metrics.update(
            allocated_steps=self.buffer_k,
            staleness_p50=p50, staleness_p99=p99,
            sim_time_s=self.sim.now,
            updates_dropped=self.updates_dropped,
            clients_dispatched=self.clients_dispatched)
        return metrics

    def maybe_resume(self) -> int:
        """Checkpoint resume restarts the async plane at the restored
        version with an empty buffer and no in-flight work (in-flight
        updates are not checkpointable state — they re-dispatch)."""
        start = super().maybe_resume()
        if start:
            self._version = start
            self._next_gen = start
            if self.buffer is not None:
                self.buffer = jax.tree_util.tree_map(jnp.zeros_like,
                                                     self.buffer)
                self.buffer["version"] = jnp.asarray(float(start),
                                                     jnp.float32)
            self._occ_host = 0
            self._gens.clear()
        return start

    # -- fedverify hooks (docs/FEDVERIFY.md) --------------------------------
    def dispatch_program(self, gen: int = 0):
        """The generation dispatch program + one staged call, for AOT
        lowering under the five contract families."""
        clients, idx, mask, w, _steps = self._stage_round_arrays(gen)
        key = rng_util.round_key(rng_util.root_key(self.seed), gen)
        cohort = np.asarray(clients, dtype=np.int32)
        c_stacked = self._gather_c(cohort, round_idx=gen)
        args = (self.state, jnp.asarray(idx), jnp.asarray(mask),
                jnp.asarray(w), key, c_stacked)
        return self._dispatch_fn, args, ()

    def dispatch_signature(self, gen: int) -> str:
        _clients, idx, mask, w, _steps = self._stage_round_arrays(gen)
        return repr([(a.shape, str(a.dtype)) for a in (idx, mask, w)])

    def buffer_program(self):
        """The buffer-apply program + a template-shaped call.  The buffer
        template comes from ``eval_shape`` of the dispatch program — no
        step runs."""
        _clients, idx, mask, w, _steps = self._stage_round_arrays(0)
        key = rng_util.round_key(rng_util.root_key(self.seed), 0)
        cohort = np.asarray(_clients, dtype=np.int32)
        c_stacked = self._gather_c(cohort, round_idx=0)
        rows_tpl, _ = jax.eval_shape(
            self._dispatch_fn, self.state, jnp.asarray(idx),
            jnp.asarray(mask), jnp.asarray(w), key, c_stacked)
        buf = federated.update_buffer_zeros(self.server_opt.spec,
                                            rows_tpl, self.buffer_k)
        return self._apply_fn, (self.state, buf), (1,)
