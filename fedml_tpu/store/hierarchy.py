"""Two-tier silo→server aggregation — the in-process simulation driver.

arXiv:2604.10859 ("Understanding Communication Backends in Cross-Silo
FL") motivates the topology: a flat server ingesting every client update
saturates long before the population does, while a silo tier that
pre-reduces its own cohort slice ships S partial aggregates upward
instead of C client updates.  PR 7's round algebra makes the silo tier
nearly free to express: each silo runs the SAME spec-driven
``build_aggregates`` the flat engines use, just with a
:class:`~fedml_tpu.core.federated.PartialReducer` so its reductions stay
unfinished ``{num, den}`` pairs; the server combines S partials with
:func:`~fedml_tpu.core.federated.combine_partial_aggregates` and applies
the unchanged ``ServerOptimizer`` transition.  Because weighted averages
are associative in their numerators, the hierarchical round matches flat
aggregation to float-reassociation error (pinned to 2e-5 in
``tests/test_client_store.py``) for EVERY registered AlgorithmSpec —
q-FedAvg included.

The distributed twin of this driver is the partial-aggregate path on
``cross_silo/server/fedml_aggregator.py`` (silos ship partials over the
existing message plane); this class is the same math in one process, S
compiled silo dispatches + 1 combine dispatch per round.
"""

from __future__ import annotations

import logging
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import federated
from ..core import rng as rng_util
from ..core import wire
from ..core.distributed.communication.fault_injection import (
    maybe_crash_at_round)
from ..core.distributed.reliability import (KEY_UNRELIABLE,
                                             ReliableEndpoint, RoundWAL)
from ..obs import get_tracer
from ..obs.jaxhooks import count_put
from ..simulation.round_engine import make_run_clients, next_pow2
from ..simulation.sp.fedavg_api import FedAvgAPI

log = logging.getLogger(__name__)


class HierarchicalSiloAPI(FedAvgAPI):
    """FedAvgAPI with the round split across ``args.num_silos`` silos.

    Each round: the cohort is sliced into S equal contiguous silo cohorts;
    one jitted silo program (shared — same shapes per slice, so ONE
    compile) reduces each slice to a partial aggregate; one jitted combine
    program finishes the averages and runs the server transition.  Client
    sampling, per-client rng streams, batch schedules and weights are
    bitwise the flat engine's, so the only divergence from flat
    aggregation is float reassociation in the summed numerators.
    """

    # the silo loop reuses state buffers across S dispatches per round
    DONATE_STATE = False

    def __init__(self, args, device, dataset, model,
                 client_mode: str = "vmap"):
        super().__init__(args, device, dataset, model, client_mode)
        self.num_silos = int(getattr(args, "num_silos", 0) or 2)
        if self.clients_per_round % self.num_silos:
            raise ValueError(
                f"client_num_per_round={self.clients_per_round} must "
                f"divide evenly into num_silos={self.num_silos} silo "
                "slices")
        if self.collective_precision != "fp32":
            raise ValueError(
                "hierarchical silo aggregation combines fp32 partial "
                "aggregates; collective_precision must stay 'fp32' — "
                "quantize the silo→server tier with wire_precision "
                "instead (fedwire, docs/WIRE.md)")
        self._silo_fn = None
        self._combine_fn = None
        # fedwire (docs/WIRE.md): with wire_precision set, the in-process
        # round passes every silo partial through the SAME encode→decode
        # the distributed tier ships — so wire numerics (including the
        # stateful algorithms the multi-process driver rejects) are
        # testable without processes
        codec = wire.codec_from_args(args)
        self._wire = wire.WireLink(codec) if codec is not None else None
        # one-round staging cache: the distributed driver calls
        # silo_partial() for a single slice, but staging is a pure
        # function of round_idx — stage the full cohort once per round
        self._staged_round = None
        self._staged = None

    def _build_silo_fns(self):
        server_opt = self.server_opt
        spec = server_opt.spec
        run_clients = make_run_clients(self.trainer, server_opt,
                                       self._client_mode)
        red = federated.PartialReducer()
        gather = hasattr(self, "_dev_x")
        dev = (self._dev_x, self._dev_y) if gather else None

        def silo_fn(state, x, y, mask, w, rngs, c):
            if gather:
                x, y = jnp.take(dev[0], x, axis=0), jnp.take(dev[1], x,
                                                             axis=0)
            outs = run_clients(state, x, y, mask, rngs, c)
            partial = federated.build_aggregates(spec, red, server_opt,
                                                 state, outs, w)
            return (partial, jnp.sum(outs.loss * w),
                    jnp.sum(outs.num_steps), outs.new_client_state)

        def combine_fn(state, partials):
            agg = federated.combine_partial_aggregates(spec, partials)
            return server_opt.update_from_aggregates(state, agg)

        self._silo_fn = jax.jit(silo_fn)
        self._combine_fn = jax.jit(combine_fn)

    def _stage_round(self, round_idx: int):
        """Stage the FULL cohort for one round (host arrays) — pure
        function of ``round_idx``, cached so the distributed driver's
        per-silo :meth:`silo_partial` calls pay one staging per round.
        Returns ``(clients, cohort, idx, x, y, mask, w, rngs, steps,
        c_stacked)``."""
        if self._staged_round == round_idx:
            return self._staged
        clients = self._client_sampling(round_idx)
        cohort = np.asarray(clients, np.int32)
        key = rng_util.round_key(rng_util.root_key(self.seed), round_idx)
        with self._tracer.span("staging", cat="staging", round=round_idx):
            if hasattr(self, "_dev_x"):
                idx, mask, w = self.dataset.cohort_indices(
                    self._data_ids(clients), self.batch_size, self.seed,
                    round_idx, self.epochs)
                steps = next_pow2(idx.shape[1])
                if steps != idx.shape[1]:
                    pad = steps - idx.shape[1]
                    idx = np.pad(idx, [(0, 0), (0, pad), (0, 0)])
                    mask = np.pad(mask, [(0, 0), (0, pad)])
                x = y = None
            else:
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
                    x = np.pad(x, [(0, 0), (0, pad)]
                               + [(0, 0)] * (x.ndim - 2))
                    y = np.pad(y, [(0, 0), (0, pad)]
                               + [(0, 0)] * (y.ndim - 2))
                    mask = np.pad(mask, [(0, 0), (0, pad)])
                idx = None
            # what silo_partial puts on the device, slice by slice
            count_put(self._tracer, (idx, x, y, mask, w))
        # identical per-client streams to the flat round: ONE split of the
        # round key over the whole cohort, then sliced per silo
        rngs = np.asarray(jax.random.split(key, len(clients)))
        c_stacked = self._gather_c(cohort, round_idx=round_idx)
        self._staged = (clients, cohort, idx, x, y, mask, w, rngs, steps,
                        c_stacked)
        self._staged_round = round_idx
        return self._staged

    def silo_partial(self, round_idx: int, silo_idx: int):
        """Run ONE silo's slice of the round: reduce its cohort slice to
        an unfinished partial aggregate.  Returns ``(partial, silo_w,
        loss_w, steps, new_c)`` — everything a silo process ships (or the
        in-process loop consumes directly).  Math is identical to the
        flat engine's slice, so S of these combine exactly."""
        (clients, _cohort, idx, x, y, mask, w, rngs, _steps,
         c_stacked) = self._stage_round(round_idx)
        if self._silo_fn is None:
            self._build_silo_fns()
        per = len(clients) // self.num_silos
        sl = slice(silo_idx * per, (silo_idx + 1) * per)
        xs = jnp.asarray(idx[sl] if idx is not None else x[sl])
        ys = None if y is None else jnp.asarray(y[sl])
        c_s = (None if c_stacked is None else
               jax.tree_util.tree_map(lambda t: t[sl], c_stacked))
        partial, lw, ts, new_c = self._silo_fn(
            self.state, xs, ys, jnp.asarray(mask[sl]),
            jnp.asarray(w[sl]), jnp.asarray(rngs[sl]), c_s)
        return partial, float(np.sum(w[sl])), lw, ts, new_c

    def apply_partials(self, partials):
        """Server tier: combine S partial aggregates (device trees OR
        decoded wire dicts — ``combine_partial_aggregates`` is pure jnp
        math over either) and run the unchanged server transition."""
        if self._combine_fn is None:
            self._build_silo_fns()
        self.state = self._combine_fn(self.state, tuple(partials))
        return self.state

    def train_one_round(self, round_idx: int):
        s = self.num_silos
        partials, new_cs = [], []
        loss_w = steps_total = 0.0
        for i in range(s):
            partial, _sw, lw, ts, new_c = self.silo_partial(round_idx, i)
            if self._wire is not None:
                partial = federated.wire_roundtrip_partial(
                    partial, self._wire, link=f"partial:{i}")
            partials.append(partial)
            new_cs.append(new_c)
            loss_w = loss_w + lw
            steps_total = steps_total + ts
        (clients, cohort, _idx, _x, _y, _mask, w, _rngs, steps,
         _c) = self._stage_round(round_idx)
        self.apply_partials(partials)
        if new_cs and new_cs[0] is not None:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs), *new_cs)
            self._scatter_c(cohort, stacked, round_idx=round_idx)
        metrics = {
            "train_loss": loss_w / float(np.sum(w)),
            "total_steps": steps_total,
            "silos": s,
            "allocated_steps": len(clients) * steps,
        }
        return metrics


# ---------------------------------------------------------------------------
# multi-process two-tier federation (fedscope + fedguard,
# docs/OBSERVABILITY.md, docs/FAULT_TOLERANCE.md)
# ---------------------------------------------------------------------------
#
# The in-process HierarchicalSiloAPI above proves the MATH of two-tier
# aggregation; this driver proves the TOPOLOGY: rank 0 (combine tier) and
# ranks 1..S (one process per silo) exchange partial aggregates and state
# syncs over any real comm backend (filestore / GRPC / MQTT_S3).  Every
# message rides the FedMLCommManager path, so fedscope's comm.send /
# comm.recv spans + injected trace context land on the measured path and
# ``tools/fedtrace.py merge`` can stitch the per-process captures into one
# timeline whose ``critical-path`` names the gating silo.
#
# The protocol is DISPATCH-DRIVEN (fedguard): rank 0 opens round r by
# fanning the current state out as STATE_SYNC(r); silos are purely
# reactive — whatever round is dispatched, they compute and upload.
# That makes both crash directions resumable: a restarted rank 0
# re-dispatches from its WAL round, and a restarted silo simply answers
# the next dispatch (the state rides every sync, so rejoin IS the sync
# path).  With ``reliable_delivery`` the payload types below get
# ack/retransmit + dedupe; ``quorum``/``quorum_deadline_s`` let rank 0
# close a round with a subset of silos (exact — the partial algebra
# carries its own denominators, and the arrived set is padded with
# zero partials so the combine keeps one compiled shape).

#: protocol message types (disjoint from cross_silo MyMessage's range)
MSG_TYPE_SILO_PARTIAL = 601
MSG_TYPE_STATE_SYNC = 602
MSG_TYPE_FINISH = 603


class _SiloEndpoint(ReliableEndpoint):
    """Queue-backed endpoint over the real FedMLCommManager receive path
    (handlers run on the comm loop thread and enqueue; the driver's round
    loop consumes from the queue).  ``recv`` raises :class:`TimeoutError`
    naming rank/expected/elapsed — never a bare ``queue.Empty``."""

    def __init__(self, args, rank: int, size: int, backend: str):
        from ..core.distributed.fedml_comm_manager import FedMLCommManager

        inbox: "queue.Queue" = queue.Queue()

        class _Mgr(FedMLCommManager):
            def register_message_receive_handlers(self):
                for t in (MSG_TYPE_SILO_PARTIAL, MSG_TYPE_STATE_SYNC,
                          MSG_TYPE_FINISH):
                    self.register_message_receive_handler(
                        t, lambda m: inbox.put(m))

        super().__init__(_Mgr(args, rank=rank, size=size, backend=backend),
                         inbox, rank)


def run_silo_federation(args, device, dataset, model):
    """Drive ONE process of the multi-process two-tier topology.

    ``args.rank`` 0 is the combine tier (server); ranks ``1..num_silos``
    each own one silo slice of every round's cohort.  All processes share
    ``random_seed``, so cohort sampling / rng streams / batch schedules
    are bitwise the in-process :class:`HierarchicalSiloAPI`'s; the only
    divergence from the flat round is float reassociation in the combined
    numerators (same contract as the in-process driver) — plus, under a
    quorum close, the missing silos' cohort slices.

    Fault tolerance (docs/FAULT_TOLERANCE.md): ``reliable_delivery``
    adds ack/retransmit + heartbeat leases; ``quorum`` /
    ``quorum_deadline_s`` close rounds without stragglers/dead silos;
    ``checkpoint_dir`` arms per-round checkpoints plus the applied-round
    WAL so a killed-and-restarted rank 0 resumes without double-applying.

    Straggler injection for the fedscope acceptance run:
    ``args.silo_slow_rank`` / ``args.silo_slow_s`` hold one silo's round
    open by a fixed sleep INSIDE its ``silo.round`` span, so ``fedtrace
    critical-path`` on the merged timeline must name that silo as the
    round-gating chain.

    Returns the server's per-round metrics list on rank 0, None on silos.
    """
    rank = int(getattr(args, "rank", 0))
    num_silos = int(getattr(args, "num_silos", 0) or 2)
    rounds = int(getattr(args, "comm_round", 1))
    backend = str(getattr(args, "backend", "filestore"))
    if bool(getattr(args, "reliable_delivery", False)):
        # the payload types below get ack/retransmit; heartbeat/lease
        # defaults are driver-scoped (a silo round is sub-second here)
        if not getattr(args, "reliable_types", None):
            args.reliable_types = [MSG_TYPE_SILO_PARTIAL,
                                   MSG_TYPE_STATE_SYNC, MSG_TYPE_FINISH]
        if not getattr(args, "heartbeat_interval_s", 0.0):
            args.heartbeat_interval_s = 0.5
        if not getattr(args, "lease_s", 0.0):
            args.lease_s = 5.0
    tracer = get_tracer()
    if bool(getattr(args, "trace", False)) or tracer.enabled:
        from ..obs import configure
        configure(label="server" if rank == 0 else f"silo{rank}")
        tracer = get_tracer()

    api = HierarchicalSiloAPI(args, device, dataset, model)
    if api.client_table is not None or getattr(api, "_store", None) \
            is not None:
        raise ValueError(
            "distributed silo federation supports stateless-client "
            "algorithms for now (SCAFFOLD/FedDyn rows would go stale "
            "across silo processes; run those in-process)")

    if api.metrics_server is not None:
        # fedmon: each rank serves its own /metrics + /healthz (nonzero
        # base ports offset by rank in obs/metricsd.start_from_args)
        log.info("fedmon: rank %d metrics endpoint on %s", rank,
                 api.metrics_server.url)

    ep = _SiloEndpoint(args, rank, num_silos + 1, backend)
    try:
        if rank == 0:
            return _run_combine_tier(api, ep, num_silos, rounds, args,
                                     tracer)
        _run_silo_tier(api, ep, rank, args, tracer)
        return None
    finally:
        # rank 0 grants in-flight reliable FINISHes a short ack window
        ep.close(flush_s=2.0 if rank == 0 else 0.0)
        if api.metrics_server is not None:
            api.metrics_server.close()
        tracer.close()   # flush this process's mergeable trace


def _collect_quorum(ep, guard, round_idx, expected, quorum, deadline_s,
                    recv_timeout_s, tracer):
    """Collect SILO_PARTIAL uploads for ``round_idx`` until every live
    expected silo arrived, or — once ``deadline_s`` has elapsed since the
    round's FIRST partial arrived — until at least ``quorum`` have.  The
    deadline bounds how far a straggler may trail the fastest silo, not
    how long a round may take: what every silo pays alike (round 0
    compiles the silo program on each rank) is not straggling, and from
    the round's open it raced the compiler on a slow or loaded host.
    Lease-dead ranks leave the expected set
    mid-wait (and re-enter next round if they heal).  Returns
    ``(got, live)``; raises ``RuntimeError`` when the quorum can never
    be met and ``TimeoutError`` when nothing arrives for
    ``recv_timeout_s``."""
    got = {}
    live = set(expected)
    t_first = None      # arrival of the round's first partial
    last_arrival = time.monotonic()
    while True:
        if guard is not None:
            live = set(expected) - guard.dead_ranks()
        if len(live | set(got)) < quorum:
            raise RuntimeError(
                f"round {round_idx}: quorum {quorum} unreachable — "
                f"arrived={sorted(got)}, live={sorted(live)}, "
                f"dead={sorted(set(expected) - live)}")
        waiting = live - set(got)
        if not waiting:
            break
        if deadline_s > 0 and len(got) >= quorum \
                and time.monotonic() - t_first >= deadline_s:
            log.warning(
                "round %d: quorum close at deadline with %d/%d silos "
                "(missing %s)", round_idx, len(got), len(expected),
                sorted(waiting))
            break
        msg = ep.poll(timeout_s=0.05)
        if msg is None:
            if time.monotonic() - last_arrival > recv_timeout_s:
                raise TimeoutError(
                    f"rank 0: no MSG_TYPE_SILO_PARTIAL for round "
                    f"{round_idx} from ranks {sorted(waiting)} within "
                    f"{time.monotonic() - last_arrival:.1f}s "
                    f"(comm_recv_timeout_s={recv_timeout_s:g})")
            continue
        last_arrival = time.monotonic()
        if msg.get_type() != MSG_TYPE_SILO_PARTIAL:
            continue
        if int(msg.get("round_idx")) != round_idx:
            # round binding: late partials for a closed round drop here
            log.warning("server: dropping stale round-%s partial",
                        msg.get("round_idx"))
            tracer.counter("comm.stale_partials", 1.0)
            continue
        got.setdefault(int(msg.get("silo")), msg)
        if t_first is None:
            t_first = last_arrival
    return got, live


def _run_combine_tier(api, ep, num_silos, rounds, args, tracer):
    import zlib

    import flax.serialization as fser

    from ..core.distributed.communication.message import (Message,
                                                          encode_tree)
    from ..obs import context as obs_context

    # fedwire (docs/WIRE.md): quantize the state-sync fan-out on ONE link
    # — every silo receives the same bytes (bitwise-identical replicas),
    # and the int8 EF residual advances once per round, the host-side
    # quantize_broadcast algebra
    codec = wire.codec_from_args(args)
    wire_link = wire.WireLink(codec) if codec is not None else None

    guard = ep.guard
    expected = list(range(1, num_silos + 1))
    if guard is not None:
        guard.start_heartbeats(expected_ranks=expected)
    quorum = int(getattr(args, "quorum", 0) or 0) or num_silos
    deadline_s = float(getattr(args, "quorum_deadline_s", 0.0) or 0.0)
    recv_timeout_s = float(getattr(args, "comm_recv_timeout_s", 120.0)
                           or 120.0)

    # crash-resume: per-round orbax checkpoint + applied-round WAL —
    # restart restores round c, backfills a torn journal entry, and
    # resumes dispatch at c + 1 (reliability.RoundWAL write protocol)
    wal = None
    start_round = 0
    if getattr(args, "checkpoint_dir", None):
        args.checkpoint_freq = 1
        start_round = api.maybe_resume()
        wal = RoundWAL(str(args.checkpoint_dir))
        wal.ensure(start_round - 1 if start_round else None)
        if start_round:
            log.info("server: resumed from checkpoint+WAL at round %d",
                     start_round)

    history = []
    for r in range(start_round, rounds):
        t0 = time.time()
        # kill-rank-0 chaos hook: fires BETWEEN rounds — the previous
        # round is fully applied+journaled, exactly the crash window
        # the WAL resume contract covers
        maybe_crash_at_round(args, 0, r)
        with tracer.span("round", cat="round", round=r):
            live = set(expected) - (guard.dead_ranks() if guard
                                    else set())
            state_dict = fser.to_state_dict(api.state)
            state_digest = None
            if wire_link is not None:
                with tracer.span("wire.encode", cat="comm", round=r,
                                 link="state_sync"):
                    state_dict = wire_link.encode(state_dict,
                                                  link="state_sync")
                if wal is not None:
                    # the digest of the ENCODED payload — the exact bytes
                    # the wire ships and the wire checkpoint would write
                    state_digest = (
                        f"{zlib.crc32(encode_tree(state_dict)):08x}")
            for s in expected:
                sync = Message(MSG_TYPE_STATE_SYNC, 0, s)
                sync.add_params("round_idx", r)
                sync.add_params("state", state_dict)
                if s not in live:
                    # lease-dead rank: still PROBE it with the dispatch
                    # (the state sync IS the rejoin path for a restarted
                    # or healed silo) but fire-and-forget — no
                    # retransmit obligations toward a peer that may
                    # never come back, and no quorum wait on it below
                    sync.add_params(KEY_UNRELIABLE, True)
                ep.send(sync)
            got, live = _collect_quorum(ep, guard, r, expected, quorum,
                                        deadline_s, recv_timeout_s,
                                        tracer)
            with tracer.span("combine", cat="round", round=r,
                             quorum=len(got)):
                partials = [wire.maybe_decode(got[s].get("partial"))
                            for s in sorted(got)]
                # pad the arrived set to S with zero partials: the
                # combine keeps ONE compiled shape at every quorum size
                # and the algebra stays exact (zero num, zero den)
                if len(partials) < num_silos:
                    pad = federated.zero_like_partial(partials[0])
                    partials += [pad] * (num_silos - len(partials))
                api.apply_partials(partials)
                jax.block_until_ready(api.state.global_params)
            if wal is not None:
                api.maybe_checkpoint(r)
                wal.record(
                    r, msg_ids=[str(m.get(obs_context.KEY_MSG_ID))
                                for m in got.values()
                                if m.get(obs_context.KEY_MSG_ID)],
                    quorum=len(got), state_digest=state_digest)
        dead = sorted(set(expected) - live)
        tracer.counter("comm.quorum_size", float(len(got)), round=r)
        tracer.counter("comm.quorum_missing_ranks",
                       float(num_silos - len(got)), round=r)
        tracer.counter("comm.quorum_deficit",
                       float(max(quorum - len(got), 0)), round=r)
        tracer.counter("comm.dead_ranks", float(len(dead)), round=r)
        loss_w = sum(float(np.asarray(m.get("loss_w")))
                     for m in got.values())
        w_total = sum(float(m.get("silo_w")) for m in got.values())
        history.append({"round": r,
                        "train_loss": loss_w / max(w_total, 1e-9),
                        "round_time": time.time() - t0,
                        "silos": num_silos, "quorum": len(got),
                        "dead_ranks": dead})
        log.info("server round %d: train_loss=%.4f (%.2fs, %d/%d silos)",
                 r, history[-1]["train_loss"], history[-1]["round_time"],
                 len(got), num_silos)
    for s in expected:
        ep.send(Message(MSG_TYPE_FINISH, 0, s))
    return history


def _run_silo_tier(api, ep, rank, args, tracer):
    """Reactive silo loop: whatever round rank 0 dispatches (a
    STATE_SYNC carrying the current state), compute that round's slice
    and upload the partial.  A restarted silo rejoins by simply
    answering the next dispatch — the state rides every sync.

    fedwire compute/DCN overlap (``args.wire_overlap``, docs/WIRE.md):
    the round-r partial's device→host materialization, wire encode and
    send run on a single writer thread (the AsyncCohortStager /
    CohortStatePager write-back pattern), so this loop is already
    blocked on round r+1's dispatch — and, once it arrives, decoding
    state and staging the next cohort — while round r's bytes are still
    leaving.  One upload in flight at a time: the next submit first
    surfaces the previous one's failure."""
    import flax.serialization as fser
    from concurrent.futures import ThreadPoolExecutor

    from ..core.distributed.communication.message import Message

    guard = ep.guard
    if guard is not None:
        guard.start_heartbeats()
    recv_timeout_s = float(getattr(args, "comm_recv_timeout_s", 120.0)
                           or 120.0)
    slow_rank = int(getattr(args, "silo_slow_rank", 0) or 0)
    slow_s = float(getattr(args, "silo_slow_s", 0.0) or 0.0)
    codec = wire.codec_from_args(args)
    wire_link = wire.WireLink(codec) if codec is not None else None
    writer = (ThreadPoolExecutor(max_workers=1)
              if bool(getattr(args, "wire_overlap", False)) else None)
    pending = None

    def upload(r, partial, silo_w, loss_w):
        sd = fser.to_state_dict(partial)
        if wire_link is not None:
            with tracer.span("wire.encode", cat="comm", round=r,
                             link="partial"):
                sd = wire_link.encode(sd, link="partial")
        up = Message(MSG_TYPE_SILO_PARTIAL, rank, 0)
        up.add_params("round_idx", r)
        up.add_params("silo", rank)
        up.add_params("partial", sd)
        up.add_params("silo_w", silo_w)
        up.add_params("loss_w", np.asarray(loss_w))
        ep.send(up)

    try:
        while True:
            msg = ep.recv(timeout_s=recv_timeout_s,
                          expect="MSG_TYPE_STATE_SYNC/MSG_TYPE_FINISH "
                                 "from rank 0")
            if msg.get_type() == MSG_TYPE_FINISH:
                return
            if msg.get_type() != MSG_TYPE_STATE_SYNC:
                continue
            # NOTE: a re-dispatched round (same round_idx, new msg_id — a
            # restarted rank 0 whose collect window died with it) is
            # recomputed and re-uploaded; retransmits of ONE dispatch share
            # a msg_id and are deduped below us, and the server keys arrived
            # partials by silo, so answering again is always safe
            r = int(msg.get("round_idx"))
            api.state = fser.from_state_dict(
                api.state, wire.maybe_decode(msg.get("state")))
            # crash-at-round chaos: dies on receipt of round r's dispatch,
            # BEFORE computing — the round must close at quorum without us
            maybe_crash_at_round(args, rank, r)
            with tracer.span("silo.round", cat="round", round=r,
                             silo=rank):
                partial, silo_w, loss_w, _steps, _new_c = api.silo_partial(
                    r, rank - 1)
                # materialize before the span closes so the span covers the
                # silo's real device compute, not just the dispatch
                jax.block_until_ready(partial)
                if slow_rank == rank and slow_s > 0:
                    time.sleep(slow_s)   # injected straggler
            if writer is not None:
                if pending is not None:
                    pending.result()   # surface round r-1 upload failures
                pending = writer.submit(upload, r, partial, silo_w,
                                        loss_w)
            else:
                upload(r, partial, silo_w, loss_w)
    finally:
        if writer is not None:
            if pending is not None:
                pending.result()
            writer.shutdown(wait=True)
